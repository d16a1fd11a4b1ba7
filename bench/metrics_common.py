"""What the greedy roofline readers share: the names of the greedy
kernels' events in a TPU trace, and the share itself."""

# The greedy kernels in a TPU trace: on every path the cells drive, the
# only Pallas kernels are the greedy's (resident ``dpp_greedy_kernel``,
# router ``fused_chunk_exact``/``_windowed``, and the tiled step, which
# the trace names ``closed_call.N`` inside the step loop), and each
# Pallas op's HLO text names its target.
GREEDY_KERNELS = r'custom_call_target="tpu_custom_call"'


def greedy_share(ctx):
    """100 x least greedy time / device time of the greedy kernels, or
    None where the window holds no greedy kernel (or no peaks)."""
    if ctx.least_s is None:
        return None
    t = ctx.kernel_s(GREEDY_KERNELS)
    if t <= 0:
        return None
    return 100.0 * ctx.least_s / t
