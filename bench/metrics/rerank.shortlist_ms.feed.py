"""rerank.shortlist_ms.feed: host milliseconds of the shortlist phase of
one ``Reranker.rerank`` call: the summed ``serving.rerank.shortlist``
spans of the traced window (the top-k, the gather, the relevance map
and the building of V) over the calls the program counted in it
(``serving_rerank_calls_total``)."""


def read(ctx):
    calls = ctx.counter("serving_rerank_calls_total")
    spans = ctx.span_s("serving.rerank.shortlist")
    if not calls or not spans:
        return None
    return 1e3 * sum(spans) / calls
