"""wait_idle_s: device idle seconds a step while the host is inside the
program's ``pipeline.wait`` span (the trainer blocked on a batch the
workers have not built yet). Nothing to read without device operations or
without that span."""
from bench import scopes


def read(ctx):
    return scopes.per_step_idle(ctx, "pipeline.wait")
