"""build_span_s: mean duration in seconds of the program's ``pipeline.build``
spans that start inside the window, on the threads that build the batches
(sampling, subgraph and host ``Batch``). Nothing to read without that
span."""
from bench import scopes


def read(ctx):
    return scopes.mean_span(ctx.trace, "pipeline.build", ctx.lo, ctx.hi)
