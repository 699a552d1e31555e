"""aggregate_t_s: device seconds a step in operations whose path reads
``transpose(jvp(lmc.agg``: the transposed aggregations of the backward
message passing. Nothing to read where no operation carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.per_step_part(ctx, "aggregate_t", __file__)
