"""device_idle.router: the share of the traced window in which the chip
ran no operation (1 - the union of op intervals over the window), in
percent, averaged over the cell's chips."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_share())
