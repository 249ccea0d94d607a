"""moe_gmm_ms_per_step: self time of the grouped-matmul kernel's device
ops (``moe_roofline.KERNEL_OP``) per traced step; none where no such op
ran."""


def read(ctx):
    from benchmarks.chip import moe_roofline

    seconds = moe_roofline.kernel_s(ctx.reduced.op_s)
    if seconds <= 0 or ctx.steps <= 0:
        return None
    return 1e3 * seconds / ctx.steps
