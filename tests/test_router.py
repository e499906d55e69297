"""Replicated serving tier: worker protocol, routing, health, handoff.

Every test here spawns ``python -m repro.serve --worker`` subprocesses
(each imports jax), so the whole module is slow-marked: tier-1
(``scripts/ci.sh fast``) skips it, the full suite runs it.
"""
import subprocess
import sys
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro.configs import stencils
from repro.kernels import ref
from repro.serve import StencilRequest
from repro.serve import router as router_mod
from repro.serve.router import (
    ReplicaDied,
    StencilRouter,
    check_fleet,
    read_frame,
    write_frame,
)

pytestmark = pytest.mark.slow

RNG = np.random.default_rng(31)
ITERS = 2


def spec_16x8():
    return stencils.jacobi2d(shape=(16, 8), iterations=ITERS)


def grid_request(design, spec):
    return StencilRequest(design, {
        n: RNG.standard_normal(shape).astype(dt)
        for n, (dt, shape) in spec.inputs.items()
    })


def oracle(spec, req):
    one = {n: jnp.asarray(a) for n, a in req.arrays.items()}
    return np.asarray(ref.stencil_iterations_ref(spec, one, ITERS))


def wait_until(predicate, timeout_s=30.0, what="condition"):
    deadline = time.monotonic() + timeout_s
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what} not reached in {timeout_s}s")
        time.sleep(0.05)


def test_worker_protocol_roundtrip(tmp_path):
    """Speak the framed pickle protocol to one bare worker: ping,
    register, submit, exit — replies matched by id, grid correct."""
    import os

    import repro

    src_dir = str(
        __import__("pathlib").Path(next(iter(repro.__path__))).parent
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve", "--worker",
         "--store", str(tmp_path / "store"), "--max-batch", "2"],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
    )
    try:
        spec = spec_16x8()
        write_frame(proc.stdin, {"id": 0, "op": "ping"})
        pong = read_frame(proc.stdout)
        assert pong["id"] == 0 and pong["ok"]
        assert pong["result"]["pid"] == proc.pid
        assert pong["result"]["device"] == {
            "platform": "cpu", "kind": "cpu", "ids": [0], "chip": None,
        }

        write_frame(proc.stdin, {
            "id": 1, "op": "register", "name": "jac", "spec": spec,
            "iterations": None,
        })
        reg = read_frame(proc.stdout)
        assert reg["id"] == 1 and reg["ok"]

        req = grid_request("jac", spec)
        write_frame(proc.stdin, {
            "id": 2, "op": "submit", "design": "jac",
            "arrays": req.arrays, "lane": None, "tenant": "default",
        })
        out = read_frame(proc.stdout)
        assert out["id"] == 2 and out["ok"]
        np.testing.assert_allclose(
            out["result"], oracle(spec, req), rtol=2e-4, atol=2e-4
        )

        write_frame(proc.stdin, {"id": 3, "op": "exit"})
        ack = read_frame(proc.stdout)
        assert ack["id"] == 3 and ack["ok"]
        assert proc.wait(timeout=30) == 0
    finally:
        proc.kill()
        proc.wait(timeout=30)


def test_router_fleet_serves_and_health_checks(tmp_path):
    spec = spec_16x8()
    with StencilRouter(tmp_path / "store", replicas=2,
                       max_batch=2) as router:
        router.register("jac", spec)
        reqs = [grid_request("jac", spec) for _ in range(5)]
        outs = router.serve(reqs)
        for req, out in zip(reqs, outs):
            np.testing.assert_allclose(
                out, oracle(spec, req), rtol=2e-4, atol=2e-4
            )
        health = router.ping()
        assert set(health) == {"replica-0", "replica-1"}
        assert set(router.devices) == set(health)
        assert all(d["platform"] == "cpu" for d in router.devices.values())
        assert all(info["healthy"] for info in health.values())
        served = sum(
            info["scheduler"]["completed"] for info in health.values()
        )
        assert served == 5
    # close() reaps every worker
    assert all(r.proc.poll() is not None for r in router._replicas)


def test_router_reroutes_after_replica_death(tmp_path):
    """Kill the replica that owns the design: routing skips the corpse
    and requests keep resolving on the survivor."""
    spec = spec_16x8()
    with StencilRouter(tmp_path / "store", replicas=2,
                       max_batch=2) as router:
        router.register("jac", spec)
        owner = router._route("jac")
        router.serve([grid_request("jac", spec)])

        owner.proc.kill()
        wait_until(lambda: not owner.healthy, what="death detection")

        reqs = [grid_request("jac", spec) for _ in range(3)]
        outs = router.serve(reqs)
        for req, out in zip(reqs, outs):
            np.testing.assert_allclose(
                out, oracle(spec, req), rtol=2e-4, atol=2e-4
            )
        survivor = router._route("jac")
        assert survivor is not owner and survivor.healthy
        health = router.ping()
        assert health[owner.name] == {"healthy": False}
        assert health[survivor.name]["healthy"]


def test_router_hands_off_inflight_requests_on_death(tmp_path):
    """Requests in flight on a replica when it dies are re-routed whole
    to a survivor (registration replayed first) — the client's futures
    resolve without resubmission."""
    spec = spec_16x8()
    with StencilRouter(tmp_path / "store", replicas=2,
                       max_batch=2) as router:
        router.register("jac", spec)
        owner = router._route("jac")
        reqs = [grid_request("jac", spec) for _ in range(4)]
        futures = [router.submit(r) for r in reqs]
        owner.proc.kill()
        for req, fut in zip(reqs, futures):
            np.testing.assert_allclose(
                fut.result(timeout=120.0), oracle(spec, req),
                rtol=2e-4, atol=2e-4,
            )


def test_router_fails_cleanly_with_no_survivors(tmp_path):
    spec = spec_16x8()
    router = StencilRouter(tmp_path / "store", replicas=1, max_batch=2)
    try:
        router.register("jac", spec)
        only = router._route("jac")
        future = router.submit(grid_request("jac", spec))
        only.proc.kill()
        wait_until(lambda: not only.healthy, what="death detection")
        # the one in-flight future either resolved before the kill or
        # fails with ReplicaDied — it must not hang
        try:
            future.result(timeout=60.0)
        except ReplicaDied:
            pass
        with pytest.raises(ReplicaDied):
            router.submit(grid_request("jac", spec))
    finally:
        router.close()


def _dev(platform="tpu", ids=(0,), chip="0"):
    return {"platform": platform, "kind": "TPU v5 lite" if platform == "tpu"
            else "cpu", "ids": list(ids), "chip": chip}


@pytest.mark.parametrize("devices, tpu_host, refusal", [
    ({"a": _dev(chip="0"), "b": _dev(chip="1")}, True, None),
    ({"a": _dev("cpu", chip=None), "b": _dev("cpu", chip=None)}, False, None),
    ({"a": _dev(chip="0"), "b": _dev(chip="0")}, True, "share TPU chip"),
    ({"a": _dev(chip=None), "b": _dev(chip=None)}, True, "share TPU chip"),
    ({"a": _dev(chip="0"), "b": _dev("cpu", chip="1")}, True,
     "runs on 'cpu' on a TPU host"),
    ({"a": _dev(ids=(0, 1, 2, 3), chip=None)}, True, "holds 4 TPU devices"),
])
def test_check_fleet(devices, tpu_host, refusal):
    if refusal is None:
        check_fleet(devices, tpu_host)
    else:
        with pytest.raises(ValueError, match=refusal):
            check_fleet(devices, tpu_host)


def test_router_binds_one_chip_per_worker_and_refuses_extra(
    monkeypatch, tmp_path
):
    """More replicas than chips is refused before any worker starts;
    otherwise worker i is spawned bound to chip i."""
    spawned = []

    def fake_popen(argv, env=None, **kw):
        spawned.append(env)
        raise OSError("not spawning in this test")

    monkeypatch.setattr(router_mod, "tpu_host_chips", lambda: 2)
    monkeypatch.setattr(router_mod.subprocess, "Popen", fake_popen)
    with pytest.raises(ValueError, match="chip of its own"):
        StencilRouter(tmp_path / "store", replicas=3)
    assert spawned == []
    with pytest.raises(OSError):
        StencilRouter(tmp_path / "store", replicas=2)
    assert spawned[0]["TPU_VISIBLE_CHIPS"] == "0"
    assert spawned[0]["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"


def test_cpu_hosts_bind_no_chip(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert router_mod.tpu_host_chips() == 0
