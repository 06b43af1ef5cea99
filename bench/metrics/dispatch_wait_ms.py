"""Milliseconds a model call waits in the dispatcher before its fused batch
starts: over the program's ``repro.dispatch.oracle.*`` and
``repro.dispatch.proxy.*`` spans that start in the traced window, the sum of
their ``wait_ms_sum`` over the sum of their ``fused_calls``."""
from bench import program_spans

ROLES = ("repro.dispatch.oracle.", "repro.dispatch.proxy.")


def read(ctx):
    spans = [e for e in program_spans.events(ctx) if e.name.startswith(ROLES)]
    calls = sum(int(e.stats.get("fused_calls", 0)) for e in spans)
    if not calls:
        return None
    return sum(float(e.stats.get("wait_ms_sum", 0.0)) for e in spans) / calls
