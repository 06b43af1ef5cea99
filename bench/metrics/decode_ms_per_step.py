"""Device milliseconds per decode step of the oracle: busy device time under
the harness's ``bench.oracle.decode`` spans inside the traced window, over
their count."""


def read(ctx):
    spans = ctx.trace.spans_named("bench.oracle.decode")
    if not spans or not ctx.trace.busy:
        return None
    return 1e3 * ctx.trace.device_s_in(spans) / len(spans)
