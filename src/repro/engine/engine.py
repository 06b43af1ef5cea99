"""InferenceEngine: the vLLM-analogue facade the semantic operators consume.

Four primitives (mirroring the paper's model-access patterns):
  generate(prompts)          -> free-text generations            (sem_map/agg)
  predicate(prompts)         -> bool + True-token log-prob       (sem_filter/join;
                                the log-prob is the cascade proxy score)
  compare(prompts)           -> A/B choice + log-prob            (sem_topk)
  classify(prompt, n_opts)   -> argmax over first n option ids   (sem_group_by)

Predicate/compare/classify need exactly one output token, so they are served
by a single teacher-forced forward pass over a padded batch (cheap decoding —
the effect the paper credits for sem_filter's 3.6x win over generic AI UDF
maps); generate() runs through the continuous-batching scheduler.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.tokenizer import TOKENIZER
from repro.engine.runner import ModelRunner, _bucket
from repro.engine.sampler import Sampler
from repro.engine.scheduler import ContinuousBatchScheduler, Request
from repro.models import registry
from repro.obs import trace as _trace


_SCORE_ROWS = 32   # most rows in one scoring step


def _score_chunks(lens: list[int], max_seq: int) -> list[tuple[int, np.ndarray]]:
    """The rows of one scoring call, by their token lengths, as (width, row
    indices) chunks of at most `_SCORE_ROWS` rows, narrowest first.

    Rows are sorted by length (stable) and cut one of two ways: wherever the
    width bucket changes and every 32 rows within a width, or every 32 rows
    with the short remainder first.  The call takes the cut that pads fewer
    tokens (row bucket x width bucket), the width cut on a tie.  The 32-row
    cut has the chunk sizes of arrival order and never pads more than it, so
    no call pads more than unsorted; the width cut pads less where a width's
    rows fill their own row buckets."""
    order = np.argsort(lens, kind="stable")
    width = [min(_bucket(lens[j]), max_seq) for j in order]
    grouped: list[int] = []
    for i, w in enumerate(width):
        if not grouped or w != width[i - 1] or i - grouped[-1] == _SCORE_ROWS:
            grouped.append(i)
    r = len(order) % _SCORE_ROWS
    cut = [0] * (r > 0) + list(range(r, len(order), _SCORE_ROWS))

    def spans(starts: list[int]) -> list[tuple[int, int]]:
        return list(zip(starts, starts[1:] + [len(order)]))

    def padded(starts: list[int]) -> int:
        return sum(min(_bucket(e - s, 8), _SCORE_ROWS) * width[e - 1] for s, e in spans(starts))

    return [(width[e - 1], order[s:e]) for s, e in spans(min(grouped, cut, key=padded))]


@dataclasses.dataclass
class EngineStats:
    lm_calls: int = 0
    generated_tokens: int = 0
    prompt_tokens: int = 0
    failed_requests: int = 0       # generate requests out of retries

    def add(self, calls: int, prompt: int, gen: int) -> None:
        self.lm_calls += calls
        self.prompt_tokens += prompt
        self.generated_tokens += gen


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params=None, *, seed: int = 0,
                 max_slots: int = 8, max_seq: int = 512, temperature: float = 0.0):
        self.cfg = cfg
        if params is None:
            params = registry.init_params(cfg, jax.random.PRNGKey(seed))
        self.runner = ModelRunner(cfg, params, max_slots=max_slots, max_seq=max_seq)
        self.sampler = Sampler(temperature=temperature, seed=seed)
        self.stats = EngineStats()

    # ------------------------------------------------------------------
    def generate(self, prompts: list[str], *, max_new_tokens: int = 48,
                 fault_hook=None) -> list[str]:
        with _trace.span("engine/generate", "engine", event="repro.engine.generate",
                         requests=len(prompts)):
            sched = ContinuousBatchScheduler(self.runner, sampler=self.sampler,
                                             fault_hook=fault_hook)
            for i, p in enumerate(prompts):
                toks = np.asarray(TOKENIZER.encode(p)[: self.runner.max_seq - max_new_tokens - 1],
                                  np.int32)
                sched.submit(Request(rid=i, tokens=toks, max_new_tokens=max_new_tokens,
                                     stop_id=TOKENIZER.eos_id))
            done = sched.run_to_completion()
            self.stats.add(len(prompts), sum(len(r.tokens) for r in done),
                           sum(len(r.out_tokens) for r in done))
            by_id = {r.rid: r for r in done if not r.failed}
            missing = len(prompts) - len(by_id)
            if missing:  # never answer a failed request with an empty string
                self.stats.failed_requests += missing
                raise RuntimeError(f"{missing} of {len(prompts)} generate requests "
                                   "failed after retries")
            return [TOKENIZER.decode([t for t in by_id[i].out_tokens if t != TOKENIZER.eos_id])
                    for i in range(len(prompts))]

    # ------------------------------------------------------------------
    def _last_logits(self, prompts: list[str]) -> np.ndarray:
        """Per-row log-probs after the last real token, in the caller's row
        order. [B, V].

        Rows are sorted by token length and scored in chunks of at most 32
        (`_score_chunks`), so a short prompt is not padded to a long
        neighbour's width.  Rows and widths are padded to power-of-two
        buckets (at least 8 rows, 16 tokens), so the scoring step compiles
        once per (rows, width) bucket, not once per batch."""
        with _trace.span("engine/score", "engine", event="repro.engine.score",
                         rows=len(prompts)) as sp:
            seqs = [TOKENIZER.encode(p)[: self.runner.max_seq] for p in prompts]
            chunks = _score_chunks([len(s) for s in seqs], self.runner.max_seq)
            out = np.empty((len(prompts), self.cfg.vocab_size), np.float32)
            tokens = padded = 0
            for width, idx in chunks:
                with _trace.span("engine/score.prep", "engine",
                                 event="repro.engine.score.prep") as prep:
                    chunk = [seqs[j] for j in idx]
                    rows = min(_bucket(len(chunk), 8), _SCORE_ROWS)
                    toks = TOKENIZER.pad_batch(chunk + [[]] * (rows - len(chunk)), width)
                    last = np.asarray([max(len(s), 1) - 1 for s in chunk]
                                      + [0] * (rows - len(chunk)), np.int32)
                    prep.set(rows=len(chunk), width=width)
                out[idx] = self.runner.logprobs(toks, last)[: len(chunk)]
                n = sum(len(s) for s in chunk)
                self.stats.add(len(chunk), n, len(chunk))
                tokens += n
                padded += rows * width
            widest = max((w for w, _ in chunks), default=0)
            narrowed = sum(len(idx) for w, idx in chunks if w < widest)
            sp.set(tokens=tokens, padded_tokens=padded, chunks=len(chunks),
                   narrowed_rows=narrowed)
            return out

    def predicate(self, prompts: list[str]) -> tuple[np.ndarray, np.ndarray]:
        """Returns (passes [B] bool, score [B]: p(True | {True,False}))."""
        if not prompts:
            return np.zeros(0, bool), np.zeros(0, np.float32)
        logp = self._last_logits(prompts)
        lt, lf = logp[:, TOKENIZER.true_id], logp[:, TOKENIZER.false_id]
        score = 1.0 / (1.0 + np.exp(-(lt - lf)))  # calibrated True-vs-False prob
        return lt > lf, score.astype(np.float32)

    def compare(self, prompts: list[str]) -> np.ndarray:
        """Returns [B] bool: True if option A preferred over option B."""
        if not prompts:
            return np.zeros(0, bool)
        logp = self._last_logits(prompts)
        return logp[:, TOKENIZER.a_id] > logp[:, TOKENIZER.b_id]

    def choose(self, prompts: list[str], n_options: int) -> np.ndarray:
        """Returns [B] int in [0, n_options): argmax over the option labels.

        Matches the ``GenerativeModel`` protocol (operators pass the option
        *count*; sem_group_by prompts number the categories "0.", "1.", ...):
        options map to their single-token digit ids internally.  Beyond 10
        options the leading digit is shared, so ties collapse to the first
        option of each decade — callers wanting exact >10-way classification
        should bucket (sem_group_by keeps C small).
        """
        logp = self._last_logits(prompts)
        option_token_ids = [TOKENIZER.encode(str(min(i, 9)), bos=False)[0]
                            for i in range(n_options)]
        return np.argmax(logp[:, option_token_ids], axis=-1)
