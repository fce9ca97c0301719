"""device_idle (%): share of the traced window in which no XLA op ran on
a chip (1 - busy / window), busy being the union of the op intervals on
the device's ``XLA Ops`` line, averaged over the chips."""


def read(ctx):
    busy = ctx.trace.mean_busy_s()
    if not busy:
        return None
    return 100.0 * (1.0 - busy / ctx.window_s)
