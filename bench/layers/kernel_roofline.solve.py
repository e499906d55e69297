"""kernel_roofline.solve: the stencil kernel's share of its roofline.

The least time the chip could take for the window's work is the larger of
its operations over the f32 vector peak and its bytes over the HBM peak
(``bench/peaks.json``); the share is that least time over the summed
device time of the kernel (``stencil_tile_batched``) in the trace.  The
work is the published stencil's, counted by ``sasabench.work``.
"""
KERNEL = "stencil_tile_batched"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.op_seconds(KERNEL)
    if kernel_s <= 0:
        return None
    compute_s = ctx.work["ops"] / ctx.peaks["vpu_f32_op_s"]
    memory_s = ctx.work["bytes"] / ctx.peaks["hbm_bytes_s"]
    bound = "compute (VPU f32)" if compute_s >= memory_s else "memory (HBM)"
    ctx.log(f"kernel_roofline.solve: {bound} bound; least time compute "
            f"{compute_s!r} s, memory {memory_s!r} s; kernel {kernel_s!r} s")
    return 100.0 * max(compute_s, memory_s) / kernel_s
