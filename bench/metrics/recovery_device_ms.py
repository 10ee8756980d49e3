"""Device busy time inside the failure boundaries (from the end of the
window drain before a failure to the start of the next window dispatch,
mapped onto the device trace through the recorder's anchors), per
failure."""
from bench.devscope import per_failure


def read(ctx):
    ms = per_failure(ctx, "device_s")
    return None if ms is None else 1e3 * ms
