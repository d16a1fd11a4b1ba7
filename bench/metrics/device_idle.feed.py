"""device_idle.feed: device_idle.batch in the cells that report
``slates_per_s.feed``."""


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_share())
