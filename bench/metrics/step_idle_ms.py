"""Milliseconds per scheduler step in which the device sat idle while the
engine generated: chip 0's idle time inside the program's
``repro.engine.generate`` spans that start in the traced window, over the
``repro.sched.prefill`` and ``repro.sched.decode`` spans that start inside
them."""
from bench import program_spans

STEPS = ("repro.sched.prefill", "repro.sched.decode")


def read(ctx):
    gens = program_spans.named(ctx, "repro.engine.generate")
    steps = program_spans.starting_in(program_spans.named(ctx, *STEPS), gens)
    idle = program_spans.idle_s_in(ctx, gens)
    if not steps or idle is None:
        return None
    return 1e3 * idle / len(steps)
