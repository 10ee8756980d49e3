"""Device time of the operations under the program's ``adam`` scope
(clipping included) inside the dispatched windows, per training step
(device trace, mapped through the recorder's anchors, averaged over the
chips)."""
from bench.devscope import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "adam")
