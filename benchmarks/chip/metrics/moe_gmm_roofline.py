"""moe_gmm_roofline: the grouped products' roofline time per step
(``moe_roofline.py``, counted from the configuration and traffic of the
cell this metric's entry lists) over the kernel's self time per traced
step; none where no kernel op ran."""


def read(ctx):
    from benchmarks.chip import moe_roofline

    seconds = moe_roofline.kernel_s(ctx.reduced.op_s)
    if seconds <= 0 or ctx.steps <= 0:
        return None
    cell = moe_roofline.cell_of("moe_gmm_roofline")
    ideal = moe_roofline.roofline_s(cell.config, cell.traffic, ctx.peak, ctx.chips)
    return 100.0 * ideal / (seconds / ctx.steps)
