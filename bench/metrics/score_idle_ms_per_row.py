"""Milliseconds per scored row in which the device sat idle during a scoring
call: chip 0's idle time inside the program's ``repro.engine.score`` spans
(oracle and proxy) that start in the traced window, over the rows they
scored."""
from bench import program_spans


def read(ctx):
    spans = program_spans.named(ctx, "repro.engine.score")
    rows = sum(int(e.stats.get("rows", 0)) for e in spans)
    idle = program_spans.idle_s_in(ctx, spans)
    if not rows or idle is None:
        return None
    return 1e3 * idle / rows
