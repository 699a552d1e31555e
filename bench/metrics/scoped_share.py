"""scoped_share: share of the device's busy time in the window spent in
operations that carry one of the program's four step scopes (``lmc.agg``,
``lmc.halo``, ``lmc.store``, ``lmc.dense``), in %. Nothing to read where
none does."""
from bench import scopes


def read(ctx):
    return scopes.scoped_share(ctx.trace, ctx.lo, ctx.hi,
                               scopes.op_paths(__file__))
