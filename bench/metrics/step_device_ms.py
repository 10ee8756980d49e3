"""Device busy time inside the dispatched windows, per training step
(device trace)."""


def read(ctx):
    if ctx.trace is None or not ctx.steps:
        return None
    return 1e3 * ctx.trace["window_busy_s"] / ctx.steps
