"""stage_ms.serve: host time to stage one batch, the host stack and pad
(``sasa.prepare``) and the ``device_put`` (``sasa.stage``), summed over
the traced window and divided by the number of ``sasa.stage`` spans.  A
trace with no device in it reads nothing: a time comes from a chip run
only."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices:
        return None
    stage = [e for e in t.host if e.name == "sasa.stage"]
    if not stage:
        return None
    prepare = [e for e in t.host if e.name == "sasa.prepare"]
    return sum(e.end - e.start for e in stage + prepare) / 1e6 / len(stage)
