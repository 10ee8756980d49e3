"""Mean duration of the program's ``recovery`` events inside the window:
the strategy's own handling of one failure, device work included (its
recovery error is drained before the event ends)."""


def read(ctx):
    if not ctx.recoveries:
        return None
    return 1e3 * sum(ctx.recoveries) / len(ctx.recoveries)
