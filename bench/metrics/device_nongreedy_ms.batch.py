"""device_nongreedy_ms.batch: device milliseconds per ``Reranker.rerank``
call spent outside the greedy's Pallas kernels: the busy time of the
traced window (the union of op intervals) less the time of the ops the
program names as a greedy kernel family, over the calls the program
counted in the window (``serving_rerank_calls_total``), averaged over
the cell's chips."""

# the greedy's kernel families, by the name each pallas_call gives its
# op: "%dpp_step_exact.7 = ... custom-call(...)"
GREEDY_FAMILIES = r"^%?dpp_(resident|step|chunk)_(exact|windowed)\b"


def read(ctx):
    calls = ctx.counter("serving_rerank_calls_total")
    kernel_s = ctx.kernel_s(GREEDY_FAMILIES)
    if not calls or kernel_s <= 0:
        return None
    busy_s = ctx.busy_share() * ctx.trace.window_ns / 1e9
    return 1e3 * (busy_s - kernel_s) / calls
