"""batch_ms.serve: mean host-clock time of one batch in the engine, from
staging (stack, pad, device_put) through dispatch to readback, from the
engine's counters over the window."""


def read(ctx):
    c = ctx.counters
    if not c.get("exec_count"):
        return None
    return 1e3 * c["exec_total_s"] / c["exec_count"]
