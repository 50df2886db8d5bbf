"""Device: share of the traced window in which no op ran on a chip,
averaged over the chips (%)."""


def read(ctx):
    t = ctx.table
    if not t.ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
