"""router.admit_ms: host milliseconds the router spends admitting one
request: the summed ``router.pump.admit`` spans of the traced window
over the requests the router admitted in it
(``router_requests_total{event=admitted}``)."""


def read(ctx):
    admitted = ctx.counter("router_requests_total", event="admitted")
    spans = ctx.span_s("router.pump.admit")
    if not admitted or not spans:
        return None
    return 1e3 * sum(spans) / admitted
