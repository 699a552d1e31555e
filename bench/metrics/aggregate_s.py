"""aggregate_s: device seconds a step in operations under the program's
``lmc.agg`` scope that are not transposed: the forward aggregations (segment
SpMM or ELL SpMM). Nothing to read where no operation carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.per_step_part(ctx, "aggregate", __file__)
