"""Device: share of the traced window in which no operation ran on the
chip (1 - busy / window, busy being the union of the device's op
intervals)."""


def read(ctx):
    w = ctx.trace["window_s"]
    return 100.0 * (1.0 - ctx.trace["busy_s"] / w) if w > 0 else None
