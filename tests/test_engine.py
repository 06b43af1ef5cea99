"""Serving engine: continuous batching equivalence, paged cache, fault
tolerance / straggler re-queue, predicate scoring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data.tokenizer import TOKENIZER
from repro.engine import paged as paged_mod
from repro.engine.engine import InferenceEngine, _score_chunks
from repro.engine.runner import ModelRunner, _bucket
from repro.engine.sampler import Sampler
from repro.engine.scheduler import ContinuousBatchScheduler, Request
from repro.models import registry
from repro.obs import trace


@pytest.fixture(scope="module")
def small_engine():
    cfg = get_smoke("llama3.2-3b").with_(vocab_size=TOKENIZER.vocab_size)
    return InferenceEngine(cfg, max_slots=3, max_seq=128)


def _seq_generate(cfg, params, prompt_tokens, n, max_seq=128):
    r = ModelRunner(cfg, params, max_slots=1, max_seq=max_seq)
    logits = r.prefill_into_slot(prompt_tokens, 0)
    out = [int(np.argmax(logits))]
    lens = np.asarray([len(prompt_tokens)], np.int32)
    for _ in range(n - 1):
        logits = r.decode(np.asarray([out[-1]], np.int32), lens)
        out.append(int(np.argmax(logits[0])))
        lens = lens + 1  # fresh array: async dispatch may still read the old one
    return out


def test_continuous_batching_matches_sequential(small_engine):
    eng = small_engine
    prompts = [f"request number {i} about topic {i % 3}" for i in range(5)]
    refs = []
    for p in prompts:
        toks = np.asarray(TOKENIZER.encode(p), np.int32)
        refs.append(_seq_generate(eng.cfg, eng.runner.params, toks, 6))
    sched = ContinuousBatchScheduler(eng.runner)
    for i, p in enumerate(prompts):
        sched.submit(Request(rid=i, tokens=np.asarray(TOKENIZER.encode(p), np.int32),
                             max_new_tokens=6))
    done = {r.rid: r.out_tokens for r in sched.run_to_completion()}
    for i in range(5):
        assert done[i][:6] == refs[i][:6], f"request {i} diverged"


def test_scheduler_fault_injection_requeues(small_engine):
    eng = small_engine
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] in (2, 5):     # two injected worker failures
            raise RuntimeError("injected worker fault")

    sched = ContinuousBatchScheduler(eng.runner, fault_hook=flaky, max_retries=3)
    for i in range(4):
        sched.submit(Request(rid=i, tokens=np.asarray(TOKENIZER.encode(f"p{i}"), np.int32),
                             max_new_tokens=4))
    done = sched.run_to_completion()
    assert len(done) == 4
    assert all(r.done and not r.failed for r in done)
    assert any(r.retries > 0 for r in done)  # at least one recovered


def test_predicate_and_compare_shapes(small_engine):
    eng = small_engine
    passed, score = eng.predicate(["is water wet?"] * 4)
    assert passed.shape == (4,) and score.shape == (4,)
    assert np.all((score >= 0) & (score <= 1))
    pref = eng.compare(["A or B?"] * 3)
    assert pref.shape == (3,)


def test_paged_decode_matches_contiguous():
    cfg = get_smoke("llama3.2-3b").with_(vocab_size=TOKENIZER.vocab_size)
    params = registry.init_params(cfg, jax.random.PRNGKey(1))
    B, T = 2, 12
    toks = np.random.default_rng(0).integers(0, 256, (B, T)).astype(np.int32)
    cache = registry.init_cache(cfg, B, 32)
    for t in range(T):
        logits_ref, cache = registry.decode_step(cfg, params, jnp.asarray(toks[:, t:t+1]),
                                                 cache, jnp.int32(t))
    alloc = paged_mod.PageAllocator(num_pages=16, page_size=4, max_slots=B,
                                    max_pages_per_slot=8)
    pages = paged_mod.init_pages(cfg, 16, 4)
    lens = np.zeros(B, np.int32)
    step = jax.jit(lambda p, tk, pg, tb, ln: paged_mod.paged_decode_step(cfg, p, tk, pg, tb, ln))
    for t in range(T):
        for s in range(B):
            alloc.ensure(s, t + 1)
        logits, pages = step(params, jnp.asarray(toks[:, t:t+1]), pages,
                             jnp.asarray(alloc.table), jnp.asarray(lens))
        lens = lens + 1  # fresh array: async dispatch may still read the old one
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref), atol=1e-4)


def test_page_allocator_release_reuse():
    alloc = paged_mod.PageAllocator(num_pages=4, page_size=8, max_slots=2,
                                    max_pages_per_slot=4)
    alloc.ensure(0, 30)      # 4 pages
    with pytest.raises(MemoryError):
        alloc.ensure(1, 1)
    alloc.release(0)
    alloc.ensure(1, 8)       # reuse freed pages
    assert len(alloc.free) == 3


def test_sampler_modes():
    logits = np.asarray([[0.0, 5.0, 1.0]])
    assert Sampler(temperature=0.0)(logits)[0] == 1
    s = Sampler(temperature=1.0, top_k=2, seed=0)
    draws = {int(s(logits)[0]) for _ in range(20)}
    assert draws <= {1, 2}  # top-2 only


def test_scoring_path_matches_full_forward_at_last_token(small_engine):
    """The scoring step gathers each row's last real position on device and
    pads rows/widths to buckets; its log-probs equal the full teacher-forced
    forward's at that position, for prompts of mixed lengths."""
    eng = small_engine
    prompts = [("claim " * (i + 1)) + "is true?" for i in range(11)]
    got = eng._last_logits(prompts)
    assert got.shape == (len(prompts), eng.cfg.vocab_size)
    for i, p in enumerate(prompts):
        toks = jnp.asarray([TOKENIZER.encode(p)], jnp.int32)
        logits, _ = registry.forward(eng.cfg, eng.runner.params, toks)
        want = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        np.testing.assert_allclose(got[i], np.asarray(want), atol=1e-4)
    # batches of 3, 5 and 8 prompts share the 8-row bucket, 9 takes the
    # 16-row one: two compiles at most, whatever the batch sizes
    n0 = eng.runner._score._cache_size()
    same_len = [f"claim {i:03d} is true?" for i in range(9)]
    for n in (3, 5, 8, 9):
        eng._last_logits(same_len[:n])
    assert eng.runner._score._cache_size() - n0 <= 2


def _straddling_prompts(n_short: int, n_long: int, seed: int) -> list[str]:
    """Prompts of 20-32 tokens (the 32-token width bucket) and of 33-60 tokens
    (the 64-token one), shuffled."""
    rng = np.random.default_rng(seed)
    lens = [int(rng.integers(20, 33)) for _ in range(n_short)] \
        + [int(rng.integers(33, 61)) for _ in range(n_long)]
    prompts = []
    for i, n in enumerate(lens):
        p = f"row {i} claim"
        p += " x" * ((n - len(TOKENIZER.encode(p))) // 2)
        prompts.append(p + "?" * (n - len(TOKENIZER.encode(p))))
    assert [len(TOKENIZER.encode(p)) for p in prompts] == lens
    return [prompts[i] for i in rng.permutation(len(prompts))]


def test_scoring_sorts_rows_by_length_and_keeps_the_callers_order(small_engine):
    """A shuffled call of mixed lengths across a width boundary is scored in
    length-sorted chunks of one width each: short rows run at the narrow
    width, fewer tokens are padded than at the widest width, and every row
    still equals the full teacher-forced forward at its last token, in the
    caller's order."""
    eng = small_engine
    prompts = _straddling_prompts(36, 12, seed=3)
    seqs = [TOKENIZER.encode(p) for p in prompts]
    tr = trace.Tracer()
    with trace.activate(tr):
        got = eng._last_logits(prompts)
    assert got.shape == (len(prompts), eng.cfg.vocab_size)
    by_len: dict[int, list[int]] = {}
    for i, s in enumerate(seqs):
        by_len.setdefault(len(s), []).append(i)
    for rows in by_len.values():
        toks = jnp.asarray([seqs[i] for i in rows], jnp.int32)
        logits, _ = registry.forward(eng.cfg, eng.runner.params, toks)
        want = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32), axis=-1)
        np.testing.assert_allclose(got[rows], np.asarray(want), atol=1e-4)
    (score,) = [s for s in tr.spans() if s.name == "engine/score"]
    widest = _bucket(max(len(s) for s in seqs))
    assert widest == 64
    assert score.attrs["rows"] == 48 and score.attrs["chunks"] == 3
    assert score.attrs["tokens"] == sum(len(s) for s in seqs)
    assert score.attrs["padded_tokens"] == 32 * 32 + 8 * 32 + 16 * 64 < len(prompts) * widest
    assert score.attrs["narrowed_rows"] == 36
    preps = [s for s in tr.spans() if s.name == "engine/score.prep"]
    assert [(s.attrs["rows"], s.attrs["width"]) for s in preps] == [(32, 32), (4, 32), (12, 64)]


def _arrival_padded(lens: list[int], max_seq: int) -> int:
    """Tokens padded when the call is cut every 32 rows in arrival order."""
    return sum(min(_bucket(len(c), 8), 32) * min(_bucket(max(c)), max_seq)
               for c in (lens[i:i + 32] for i in range(0, len(lens), 32)))


def test_small_call_across_a_width_pads_no_more_than_arrival_order(small_engine):
    """Two rows on either side of a width boundary stay one chunk at the
    wider width: splitting them by width would pad 8x32 + 8x64 tokens
    against arrival order's 8x64, and dispatch twice."""
    eng = small_engine
    prompts = _straddling_prompts(1, 1, seed=7)
    seqs = [TOKENIZER.encode(p) for p in prompts]
    tr = trace.Tracer()
    with trace.activate(tr):
        got = eng._last_logits(prompts)
    for i, s in enumerate(seqs):
        logits, _ = registry.forward(eng.cfg, eng.runner.params, jnp.asarray([s], jnp.int32))
        want = jax.nn.log_softmax(logits[0, -1].astype(jnp.float32))
        np.testing.assert_allclose(got[i], np.asarray(want), atol=1e-4)
    (score,) = [s for s in tr.spans() if s.name == "engine/score"]
    assert score.attrs["chunks"] == 1 and score.attrs["narrowed_rows"] == 0
    assert score.attrs["padded_tokens"] == 8 * 64 == _arrival_padded(
        [len(s) for s in seqs], eng.runner.max_seq)


@pytest.mark.parametrize("seed", range(4))
def test_score_chunks_never_pad_more_than_arrival_order(seed):
    """Over random calls of 1-140 rows with lengths across several width
    buckets, the chunks hold every row once, at most 32 rows each, narrowest
    first at the bucket of their longest row, and pad no more tokens than
    cutting the call every 32 rows unsorted."""
    rng = np.random.default_rng(seed)
    for _ in range(300):
        lens = [int(n) for n in rng.integers(1, 600, size=int(rng.integers(1, 141)))]
        chunks = _score_chunks(lens, 512)
        rows = np.concatenate([idx for _, idx in chunks])
        assert sorted(rows.tolist()) == list(range(len(lens)))
        assert all(1 <= len(idx) <= 32 for _, idx in chunks)
        assert [w for w, _ in chunks] == sorted(w for w, _ in chunks)
        assert all(w == min(_bucket(max(lens[j] for j in idx)), 512) for w, idx in chunks)
        padded = sum(min(_bucket(len(idx), 8), 32) * w for w, idx in chunks)
        assert padded <= _arrival_padded(lens, 512)


def test_warm_up_widths_cover_length_sorted_calls():
    """Compiling the scoring step as the benchmark's warm-up does (each
    width bucket the prompts reach, at 8, 16 and 32 rows) covers every
    shuffled mixed-length call: sorting rows into chunks adds no program."""
    cfg = get_smoke("llama3.2-3b").with_(vocab_size=TOKENIZER.vocab_size, num_layers=1,
                                         d_model=32, d_ff=64)
    eng = InferenceEngine(cfg, max_slots=2, max_seq=128)
    probes = _straddling_prompts(8, 8, seed=5)
    by_width: dict[int, list[str]] = {}
    for p in probes:
        by_width.setdefault(_bucket(len(TOKENIZER.encode(p))), []).append(p)
    assert sorted(by_width) == [32, 64]
    for base in by_width.values():
        for rows in (8, 16, 32):
            eng._last_logits([base[i % len(base)] for i in range(rows)])
    n0 = eng.runner._score._cache_size()
    assert n0 == 6
    for seed, (n_short, n_long) in enumerate([(36, 12), (5, 60), (40, 3), (1, 1), (70, 30)]):
        eng._last_logits(_straddling_prompts(n_short, n_long, seed=seed))
    assert eng.runner._score._cache_size() == n0


class _BrokenRunner:
    """A runner whose device step fails (as an XLA runtime error would)."""
    max_slots, max_seq = 2, 64

    def prefill_into_slot(self, tokens, slot, extra=None):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    def decode(self, tokens, lens):  # pragma: no cover - never reached
        raise AssertionError


def test_generate_propagates_runner_errors(small_engine):
    eng = InferenceEngine.__new__(InferenceEngine)
    eng.runner, eng.sampler = _BrokenRunner(), Sampler()
    eng.stats = type(small_engine.stats)()
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        eng.generate(["a prompt"], max_new_tokens=2)


def test_generate_raises_when_requests_run_out_of_retries(small_engine):
    def always_fail():
        raise RuntimeError("injected worker fault")

    before = small_engine.stats.failed_requests
    with pytest.raises(RuntimeError, match="2 of 2 generate requests failed"):
        small_engine.generate(["p0", "p1"], max_new_tokens=2,
                              fault_hook=always_fail)
    assert small_engine.stats.failed_requests == before + 2
