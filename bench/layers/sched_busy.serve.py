"""sched_busy.serve: summed host time of the serving path's five phase
spans (``sasa.prepare``, ``sasa.stage``, ``sasa.dispatch``,
``sasa.finalize``, ``sasa.resolve``) in the traced window, over the
window.  Thread-seconds per second: 100% is one thread busy all the time.
A sum and not a union, so phases on other threads add to it.  A trace
with no device in it reads nothing: a time comes from a chip run only."""
SPANS = ("sasa.prepare", "sasa.stage", "sasa.dispatch", "sasa.finalize",
         "sasa.resolve")


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    spans = [e for e in t.host if e.name in SPANS]
    if not spans:
        return None
    return 100.0 * sum(e.end - e.start for e in spans) / 1e9 / t.window_s
