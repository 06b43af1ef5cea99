"""The one traffic generator: every mix is a JSON file of parameters under
``bench/mixes`` that this module reads.

A mix names its closed loop (``clients`` callers that each wait for their
answer before they send again), the semantic operator each query runs, the
langex ``template``, the records per query, and for each text field its
length distribution (log-normal in bytes: ``median``, ``sigma``, clipped to
``min``..``max``).  A filter mix also gives its ``predicate``: the share of
rows the oracle says true to and the spread of its label margin
(``bench.weights.label_direction``).

Every seed gets the same work in another order: the lengths of one query's
fields are the distribution's stratified quantiles, shuffled by the seed.  Words, and so the prompts, are drawn from the seed.  Every record
carries a unique ``#<n>`` tag, so no two prompts of a run are equal, and
warm-up records come from their own stream and tag range."""
from __future__ import annotations

import dataclasses
import json
import statistics
from pathlib import Path

import numpy as np

MIX_DIR = Path(__file__).resolve().parent / "mixes"
WORDS = ("fast", "slow", "storage", "network", "compute", "module", "unit",
         "service", "cache", "latency", "reliable", "cheap", "component",
         "replica", "index", "stream", "batch", "vector", "query", "table",
         "cluster", "node", "shard", "log", "the", "a", "of", "with", "for",
         "and", "under", "load", "sensor", "river", "market", "policy",
         "protein", "study", "report", "claim", "evidence", "measured",
         "increase", "decrease", "city", "energy", "model", "result")
WARM_TAG0 = 10_000_000          # warm-up tags start here; window tags at 0


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


@dataclasses.dataclass
class Query:
    qid: int
    records: list[dict]


def _quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the field's clipped log-normal."""
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(int)


class Traffic:
    """Seeded queries of one mix.  ``warm=True`` draws from a disjoint
    stream (other words, other tags) for the warm-up; ``stream`` names
    another stream with the window's tags (rows that are never served)."""

    def __init__(self, mix: dict, seed: int, *, warm: bool = False, stream: int | None = None):
        self.mix = mix
        stream = (1 if warm else 0) if stream is None else stream
        self.rng = np.random.default_rng([abs(int(seed)), stream])
        self.tag = WARM_TAG0 if warm else 0
        self.qid = 0
        self.n = int(mix["records_per_query"])

    def _text(self, n_bytes: int) -> str:
        self.tag += 1
        out = f"#{self.tag}"
        while len(out) < n_bytes:
            out += " " + WORDS[int(self.rng.integers(len(WORDS)))]
        return out[:n_bytes]

    def query(self, lengths: dict | None = None) -> Query:
        """One fresh query; ``lengths`` (field -> [n] bytes) overrides the
        shuffled quantiles (the warm-up uses it to reach each width)."""
        recs: list[dict] = [{} for _ in range(self.n)]
        for field, spec in self.mix["fields"].items():
            if lengths is not None and field in lengths:
                lens = np.asarray(lengths[field])
            else:
                lens = self.rng.permutation(_quantile_lengths(spec, self.n))
            for r, n_bytes in zip(recs, lens):
                r[field] = self._text(int(n_bytes))
        own = next(iter(self.mix["fields"]))
        for r in recs:          # a record's id is its first field's tag
            r["id"] = int(r[own].split()[0][1:])
        self.qid += 1
        return Query(self.qid, recs)


def build_pipeline(mix: dict, query: Query, session):
    """The lazy pipeline one query submits to the gateway."""
    from repro.core.frame import SemFrame
    lz = SemFrame(query.records, session).lazy()
    op = mix["operator"]
    if op == "filter_cascade":
        return lz.sem_filter(mix["template"], recall_target=mix["recall_target"],
                             precision_target=mix["precision_target"])
    if op == "map":
        return lz.sem_map(mix["template"], out_column="answer")
    raise ValueError(f"unknown operator {op!r} in mix")
