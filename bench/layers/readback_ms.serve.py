"""readback_ms.serve: host time to read one batch back to numpy
(``sasa.finalize``), summed over the traced window and divided by the
number of those spans.  A trace with no device in it reads nothing: a
time comes from a chip run only."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    spans = [e for e in t.host if e.name == "sasa.finalize"]
    if not spans:
        return None
    return sum(e.end - e.start for e in spans) / 1e6 / len(spans)
