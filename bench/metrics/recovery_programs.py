"""Programs launched on the device (``XLA Modules`` events) that start
inside a failure boundary, per failure: the eager programs a recovery
dispatches."""
from bench.devscope import per_failure


def read(ctx):
    return per_failure(ctx, "programs")
