"""Mean host time between one window's drain and the next dispatch, at
boundaries where no stage failed (trainer loop, program spans)."""


def read(ctx):
    gaps = [g for g, failed in ctx.boundaries if not failed]
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
