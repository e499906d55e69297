"""p95_ms.serve: the 95th percentile of request latency over the window,
scheduled arrival to answer in the client's hands, over the same samples
as the end-to-end ``p50_ms``.  Most of it above the median is time spent
queued in the scheduler behind busy batches; a host stall of a second or
more adds its backlog to it, so it is read here, beside ``p50_ms``."""


def read(ctx):
    return ctx.end_to_end.get("p95_ms")
