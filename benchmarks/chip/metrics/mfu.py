"""mfu: the traced windows' model FLOPs (``flops.py``) over the time the
device was busy in them, as a share of the bf16 peak of the chips used
(``peaks.json``).  Over busy time, not the window, so that it reads the
device's work apart from the host's gaps, which ``device_idle_share``
reads."""


def read(ctx):
    if ctx.reduced.busy_s <= 0:
        return None
    achieved = ctx.flops_per_token * ctx.tokens / ctx.reduced.busy_s
    return 100.0 * achieved / (ctx.chips * ctx.peak["bf16_flops_per_s"])
