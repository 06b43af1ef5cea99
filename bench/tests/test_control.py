"""The comparison's control at test size: the reference computed one
precision step below the configuration's (float8 weights and activations)
must read at least three times what the program reads, for the scoring step
and for prefill-then-decode, and, put in the program's place in a whole
run, must come out not correct at the configuration's limits.  On the chip
the same control is read at the cells' own sizes by
``bench/calibrate.py``."""
import numpy as np
import pytest

from bench import check, harness, weights
from bench.configs import dense_reference as ref
from bench.tests.tiny import run_cell, tiny_cell, tiny_config

PROMPTS = [f"Claim: item #{i} " + "word " * (4 * i) + "\nAnswer:" for i in range(1, 9)]


@pytest.fixture(scope="module")
def setup():
    from repro.engine.engine import InferenceEngine
    cfg = tiny_config()
    w = weights.draw(cfg, 2**33 + 21)
    eng = InferenceEngine(harness.program_config(cfg), params=w, max_seq=256)
    return cfg, w, eng


def _ref_logprobs(cfg, w, seqs, control):
    pos = [[len(s) - 1] for s in seqs]
    lg = np.stack([x[0] for x in check._logits(ref, w, cfg, seqs, pos, 256, control=control)])
    return check._logsoftmax(lg)


def test_scoring_control_reads_three_times_the_program(setup):
    cfg, w, eng = setup
    served = eng._last_logits(PROMPTS)
    seqs = [check.encode(p) for p in PROMPTS]
    exact = _ref_logprobs(cfg, w, seqs, control=False)
    low = _ref_logprobs(cfg, w, seqs, control=True)
    cols = [weights.TRUE_ID, weights.FALSE_ID]
    program = np.abs(served[:, cols] - exact[:, cols]).max()
    control = np.abs(low[:, cols] - exact[:, cols]).max()
    assert control >= 3 * program, (program, control)


def test_decode_control_reads_three_times_the_program(setup):
    cfg, w, eng = setup
    from repro.engine.scheduler import ContinuousBatchScheduler, Request
    sched = ContinuousBatchScheduler(eng.runner, sampler=eng.sampler)
    for i, p in enumerate(PROMPTS):
        sched.submit(Request(rid=i, tokens=np.asarray(check.encode(p), np.int32),
                             max_new_tokens=12))
    done = sorted(sched.run_to_completion(), key=lambda r: r.rid)
    seqs, pos = [], []
    for r in done:
        seqs.append(list(r.tokens) + r.out_tokens[:-1])
        pos.append([len(r.tokens) - 1 + k for k in range(len(r.out_tokens))])
    exact = check._logits(ref, w, cfg, seqs, pos, 256, control=False)
    low = check._logits(ref, w, cfg, seqs, pos, 256, control=True)
    program = max(float(lg[k].max() - lg[k, t]) for lg, r in zip(exact, done)
                  for k, t in enumerate(r.out_tokens))
    control = max(float(lg[k].max() - lg[k, int(np.argmax(lo[k]))])
                  for lg, lo, r in zip(exact, low, done) for k in range(len(r.out_tokens)))
    assert control >= 3 * max(program, 1e-3), (program, control)


@pytest.mark.parametrize("mix, over", [("cascade", {"records_per_query": 64, "clients": 2}),
                                       ("gist", {"records_per_query": 4, "clients": 2})])
def test_control_in_the_programs_place_is_not_correct(capsys, mix, over):
    res = run_cell(tiny_cell(mix, **over), capsys, control=True)
    assert res["correct"] is True, res["checks"]
    assert res["control_correct"] is False, res["control"]
