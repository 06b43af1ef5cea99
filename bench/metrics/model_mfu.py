"""The whole model step's share of the chip's peak bf16 rate, in percent:
the operations the served tokens need (``bench.flops``: real tokens, causal
attention, the unembedding of read positions), carried on the harness's
scoring, prefill and decode spans, over the traced window's length times the
peak.  A span that crosses an edge of the window counts for the share of its
length inside it (one scoring span covers a whole fused batch, seconds
long)."""

CALLS = (".score", ".prefill", ".decode")


def read(ctx):
    w0, w1 = ctx.trace.window
    if w1 <= w0 or not ctx.trace.busy:
        return None
    ops = 0.0
    for s in ctx.trace.spans:
        if s.name.endswith(CALLS) and s.t1 > w0 and s.t0 < w1 and s.t1 > s.t0:
            inside = min(s.t1, w1) - max(s.t0, w0)
            ops += float(s.stats.get("flops", 0.0)) * inside / (s.t1 - s.t0)
    if not ops:
        return None
    return 100.0 * ops / ((w1 - w0) * float(ctx.peak["bf16_flops_per_s"]))
