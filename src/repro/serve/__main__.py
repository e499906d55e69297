"""``python -m repro.serve`` — the replicated serving tier's entrypoint.

Two modes:

  * ``--worker`` (what :class:`repro.serve.StencilRouter` spawns): run
    one replica — a :class:`StencilServer` over the shared persistent
    store plus a continuous-batching :class:`StencilScheduler` — and
    speak the router's length-prefixed pickle protocol on stdin/stdout.
    File descriptor 1 is re-pointed at stderr before jax ever runs, so
    stray prints can never corrupt the protocol stream.

  * default: a self-contained demo — spawn a small router fleet over a
    store directory, register a Jacobi kernel, push a mixed trace
    through it, and print per-replica stats.  Mostly documentation you
    can run.
"""
from __future__ import annotations

import argparse
import os
import sys
import threading


def device_report() -> dict:
    """What this process runs on: JAX's platform, device kind and ids
    of its local devices, and the TPU chip it was bound to (``None``
    when unbound), which the router checks for sharing."""
    import jax

    devs = jax.local_devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "ids": [d.id for d in devs],
        "chip": os.environ.get("TPU_VISIBLE_CHIPS"),
    }


def _worker(args) -> int:
    # Claim fd 1 for the protocol BEFORE importing jax: anything that
    # prints to stdout afterwards lands on stderr instead of the wire.
    proto_out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    from repro.compat import use_compile_cache
    from repro.serve.engine import StencilRequest, StencilServer
    from repro.serve.router import read_frame, write_frame
    from repro.serve.scheduler import StencilScheduler

    use_compile_cache()
    server = StencilServer(
        max_batch=args.max_batch,
        max_inflight=args.max_inflight,
        bucketing=args.bucketing,
        warmup=args.warmup,
        store_dir=args.store,
    )
    scheduler = StencilScheduler(server)
    out_lock = threading.Lock()
    stdin = sys.stdin.buffer

    def reply(msg_id, ok, result=None, error=None):
        write_frame(
            proto_out,
            {"id": msg_id, "ok": ok, "result": result, "error": error},
            out_lock,
        )

    def handle_submit(msg):
        try:
            ticket = scheduler.submit(
                StencilRequest(msg["design"], msg["arrays"]),
                lane=msg.get("lane"),
                tenant=msg.get("tenant") or "default",
            )
        except Exception as e:
            reply(msg["id"], False, error=e)
            return

        def wait():
            try:
                reply(msg["id"], True, result=ticket.result(timeout=600.0))
            except Exception as e:
                reply(msg["id"], False, error=e)

        # replies are per-ticket and out-of-order by design: the router
        # matches them by id, so a slow batch never blocks a fast one
        threading.Thread(target=wait, daemon=True).start()

    while True:
        msg = read_frame(stdin)
        if msg is None:                   # router hung up
            break
        op = msg.get("op")
        try:
            if op == "submit":
                handle_submit(msg)
            elif op == "register":
                reg = server.register(
                    msg["name"], msg["spec"], iterations=msg["iterations"],
                )
                reply(msg["id"], True, result={
                    "cache_hit": reg.counters.cache_hit,
                    "bucketed": reg.bucketed,
                })
            elif op == "ping":
                reply(msg["id"], True, result={
                    "pid": os.getpid(),
                    "device": device_report(),
                    "scheduler": scheduler.stats(),
                })
            elif op == "drain":
                scheduler.drain()
                reply(msg["id"], True)
            elif op == "exit":
                scheduler.close()
                reply(msg["id"], True)
                break
            else:
                reply(msg["id"], False, error=ValueError(f"bad op {op!r}"))
        except Exception as e:
            reply(msg["id"], False, error=e)
    scheduler.close()
    return 0


def _demo(args) -> int:
    import tempfile

    import numpy as np

    from repro.configs import stencils
    from repro.serve.engine import StencilRequest
    from repro.serve.router import StencilRouter, tpu_host_chips

    replicas = args.replicas or tpu_host_chips() or 2
    rng = np.random.default_rng(0)
    spec = stencils.jacobi2d(shape=(32, 16), iterations=2)
    store = args.store or tempfile.mkdtemp(prefix="sasa-store-")
    print(f"router: {replicas} replicas over store {store}")
    with StencilRouter(
        store, replicas=replicas, max_batch=args.max_batch,
    ) as router:
        router.register("jacobi", spec)
        reqs = [
            StencilRequest("jacobi", {
                n: rng.standard_normal(shape).astype(dt)
                for n, (dt, shape) in spec.inputs.items()
            })
            for _ in range(8)
        ]
        outs = router.serve(reqs)
        print(f"served {len(outs)} grids, first checksum "
              f"{float(np.sum(outs[0])):.6f}")
        for name, info in router.ping().items():
            sched = info.get("scheduler", {})
            print(f"  {name}: healthy={info.get('healthy')} "
                  f"device={info.get('device')} "
                  f"completed={sched.get('completed')}")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="replicated stencil-serving tier "
                    "(worker protocol or demo fleet)",
    )
    parser.add_argument("--worker", action="store_true",
                        help="run one router-spawned replica on stdio")
    parser.add_argument("--store", default=None,
                        help="shared DesignStore directory")
    parser.add_argument("--max-batch", type=int, default=4)
    parser.add_argument("--max-inflight", type=int, default=2)
    parser.add_argument("--bucketing", action="store_true")
    parser.add_argument("--warmup", action="store_true")
    parser.add_argument("--replicas", type=int, default=None,
                        help="demo mode: fleet size (default: one per "
                             "TPU chip, or 2 off the TPU)")
    args = parser.parse_args(argv)
    if args.worker:
        if not args.store:
            parser.error("--worker requires --store")
        return _worker(args)
    return _demo(args)


if __name__ == "__main__":
    sys.exit(main())
