"""Share of the decode slots that served a live request, in percent: over
the program's ``repro.sched.decode`` spans that start in the traced window,
the sum of their ``live`` over the sum of their ``slots``."""
from bench import program_spans


def read(ctx):
    spans = program_spans.named(ctx, "repro.sched.decode")
    slots = sum(int(e.stats.get("slots", 0)) for e in spans)
    if not slots:
        return None
    return 100.0 * sum(int(e.stats.get("live", 0)) for e in spans) / slots
