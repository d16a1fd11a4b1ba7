"""rerank.greedy_ms.feed: host milliseconds of the greedy phase of one
``Reranker.rerank`` call: the summed ``serving.rerank.greedy`` spans of
the traced window (the greedy's dispatch and the mapping of its picks
to global ids) over the calls the program counted in it
(``serving_rerank_calls_total``)."""


def read(ctx):
    calls = ctx.counter("serving_rerank_calls_total")
    spans = ctx.span_s("serving.rerank.greedy")
    if not calls or not spans:
        return None
    return 1e3 * sum(spans) / calls
