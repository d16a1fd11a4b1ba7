"""greedy_roofline.feed: greedy_roofline.batch in the cells that report
``slates_per_s.feed``."""
from bench.metrics_common import greedy_share


def read(ctx):
    return greedy_share(ctx)
