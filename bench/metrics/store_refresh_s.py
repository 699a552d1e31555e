"""store_refresh_s: device seconds a step in operations under the program's
``lmc.store`` scope: the scatter of the batch rows into the historical
stores, forward and backward. Nothing to read where no operation carries a
scope."""
from bench import scopes


def read(ctx):
    return scopes.per_step_part(ctx, "store_refresh", __file__)
