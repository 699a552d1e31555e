"""mfu: the window's required operations (``counts.step_flops`` of each
step's real batch) over the traced window times the chip's bf16 peak, in %."""
from bench import counts


def read(ctx):
    if not ctx.trace.device_ops:
        return None
    flops = sum(counts.step_flops(ctx.config, b) for b in ctx.step_sizes)
    return 100.0 * flops / (ctx.window_s * ctx.peaks()["bf16_flops"])
