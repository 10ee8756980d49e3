"""Device time of the operations under the program's ``ssd_scan`` scope
(the chunked state-space scan and its backward) inside the dispatched
windows, per training step (device trace, averaged over the chips)."""
from bench.devscope import scope_ms_per_step


def read(ctx):
    return scope_ms_per_step(ctx, "ssd_scan")
