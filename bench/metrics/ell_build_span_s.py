"""ell_build_span_s: mean duration in seconds of the program's
``pipeline.ell`` spans that start inside the window: the ELL bucketing of a
batch's edge list, inside its ``pipeline.build``, on the threads that build
the batches. One batch a step at ``recycle`` 1. Nothing to read without that
span (a backend with no ELL, or a program without the span)."""
from bench import scopes


def read(ctx):
    return scopes.mean_span(ctx.trace, "pipeline.ell", ctx.lo, ctx.hi)
