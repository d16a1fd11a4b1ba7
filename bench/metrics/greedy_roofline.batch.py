"""greedy_roofline.batch: the greedy's least time on the chip
(``bench.roofline``, from the shapes of the calls served) over the
device time of the greedy kernels in the traced window, in percent."""
from bench.metrics_common import greedy_share


def read(ctx):
    return greedy_share(ctx)
