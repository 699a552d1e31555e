"""lmc_compensate_roofline: least time for the compensation calls the
window's steps require (``counts.compensate_calls``: the real halo rows
gathered and blended, memory bound) over the summed device time of the
compensation kernel's events, in %. Nothing to read where the trace holds no
such kernel."""
from bench import counts

# part of the kernel's HLO instruction name (``lmc_compensate_kernel.<n>``)
KERNEL = "lmc_compensate"


def read(ctx):
    from bench.trace import op_seconds
    spent = op_seconds(ctx.trace, ctx.lo, ctx.hi,
                       lambda text: KERNEL in text)
    if spent <= 0.0:
        return None
    p = ctx.peaks()
    least = sum(counts.roofline_seconds(counts.compensate_calls(ctx.config, b),
                                        p["bf16_flops"],
                                        p["hbm_bytes_per_s"])
                for b in ctx.step_sizes)
    return 100.0 * least / spent
