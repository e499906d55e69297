"""batch_occupancy.serve: requests over the batch slots dispatched for
them (batches x max_batch), from the engine's counters over the window;
the rest of each batch is padding."""


def read(ctx):
    c = ctx.counters
    if not c.get("batches"):
        return None
    return 100.0 * c["requests"] / (c["batches"] * c["max_batch"])
