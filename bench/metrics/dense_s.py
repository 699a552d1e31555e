"""dense_s: device seconds a step in operations under the program's
``lmc.dense`` scope: feature gather and embed, the layers' transforms, head,
loss and gradient glue, forward and transposed. Nothing to read where no
operation carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.per_step_part(ctx, "dense", __file__)
