"""greedy_roofline.router: the greedy's least time on the chip
(``bench.roofline``, from the shapes of the selections served) over the
device time of the greedy kernels in the traced window, in percent."""
from bench.metrics_common import greedy_share


def read(ctx):
    return greedy_share(ctx)
