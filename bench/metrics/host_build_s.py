"""host_build_s: seconds the host takes to build one of the window's batches
(``ClusterSampler.build_batch`` and ``core.lmc.host_batch``), timed by the
benchmark on direct calls after the window, the mean over those batches."""


def read(ctx):
    if not ctx.host_build_s:
        return None
    return sum(ctx.host_build_s) / len(ctx.host_build_s)
