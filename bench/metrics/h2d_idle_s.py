"""h2d_idle_s: device idle seconds a step while the host is inside the
program's ``pipeline.h2d`` span (the synchronous part of ``jax.device_put``
of a batch, the staged next one included). Nothing to read without device
operations or without that span."""
from bench import scopes


def read(ctx):
    return scopes.per_step_idle(ctx, "pipeline.h2d")
