"""Share of the traced window in which no op ran on the device,
averaged over the chips used."""


def read(ctx):
    if not ctx.ops or ctx.window_s <= 0:
        return None
    return 1.0 - ctx.busy_s / ctx.window_s
