"""Device milliseconds of the oracle's scoring calls per row they scored:
busy device time under the harness's ``bench.oracle.score`` spans inside the
traced window, over the rows those spans carried."""


def read(ctx):
    spans = ctx.trace.spans_named("bench.oracle.score")
    rows = sum(int(s.stats.get("rows", 0)) for s in spans)
    if not rows or not ctx.trace.busy:
        return None
    return 1e3 * ctx.trace.device_s_in(spans) / rows
