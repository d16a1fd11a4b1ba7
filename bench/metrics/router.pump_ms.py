"""router.pump_ms: mean host milliseconds of one ``router.pump`` span
(sync, evict, admit, launch, materialize) in the traced window."""


def read(ctx):
    spans = ctx.span_s("router.pump")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
