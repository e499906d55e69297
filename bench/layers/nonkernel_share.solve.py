"""nonkernel_share.solve: device busy time outside the stencil kernel (the
round loop's boundary re-pad, output slice and re-wrap around it), as a
share of all device busy time in the window."""
KERNEL = "stencil_tile_batched"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.busy_s
    kernel_s = ctx.trace.op_seconds(KERNEL)
    if busy <= 0 or kernel_s <= 0:
        return None
    return 100.0 * max(busy - kernel_s, 0.0) / busy
