"""ell_spmm_roofline: least time for the aggregation calls the window's steps
require (``counts.spmm_calls``: real pairs and rows, memory bound) over the
summed device time of the ELL SpMM kernel's events, forward and transposed,
in %. Nothing to read where the trace holds no such kernel."""
from bench import counts

# part of the kernel's HLO instruction name: ``ell_spmm.<n>`` forward,
# ``transpose_jvp_jit_ell_spmm___.<n>`` transposed
KERNEL = "ell_spmm"


def read(ctx):
    from bench.trace import op_seconds
    spent = op_seconds(ctx.trace, ctx.lo, ctx.hi,
                       lambda text: KERNEL in text)
    if spent <= 0.0:
        return None
    p = ctx.peaks()
    least = sum(counts.roofline_seconds(counts.spmm_calls(ctx.config, b),
                                        p["bf16_flops"],
                                        p["hbm_bytes_per_s"])
                for b in ctx.step_sizes)
    return 100.0 * least / spent
