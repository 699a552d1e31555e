"""compensate_s: device seconds a step in operations under the program's
``lmc.halo`` scope: halo compensation, the store gather and blend or the
``lmc_compensate`` kernel, forward and backward. Nothing to read where no
operation carries a scope."""
from bench import scopes


def read(ctx):
    return scopes.per_step_part(ctx, "compensate", __file__)
