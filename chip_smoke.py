#!/usr/bin/env python3
"""Bring-up run of the semantic-query gateway on the real engine, on a TPU.

    python chip_smoke.py [--seed 0]      # one chip: the main path
    python chip_smoke.py --chips 4       # device-sharded retrieval only

One chip.  ``make_session`` builds the oracle at the published Llama-3.2-3B
widths (28 layers, d_model 3072, 24/8 heads, d_ff 8192, vocab 128256, bf16),
a proxy of the same widths cut to 2 layers, and the 12-layer E5-small
embedder, all with random weights from ``--seed``.  A ``Gateway`` then runs
4 sessions from 2 tenants over 20k generated records: ``sem_search`` on an
IVF index (int8 in two sessions, fp32 in the other two) -> ``sem_filter``
with recall and precision targets (proxy and oracle scoring) -> ``sem_map``
(oracle generation).  Two cross-checks run on the chip: the Pallas retrieval
kernels against the jnp reference, and batched predicate scoring against the
prefill path's last-token logits.

Four chips.  ``VectorIndex`` and ``IVFIndex`` (fp32 and int8) with
``shards=4`` against the single-device scan of the same corpus, and a check
that each device holds an equal share of the corpus.

The run fails, printing no result line, when JAX finds no TPU.  The last
line of a passing run is ``{"ok": true, "device": {...}}``.  Compiled
programs persist in JAX's compilation cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
SCORE_TOL = 1e-2        # |kernel - ref| on unit-vector scores (bf16 passes)
LOGPROB_TOL = 0.125     # |scoring - prefill| log-prob, nats (bf16 model)
N_RECORDS = 20_000
DOC_BYTES = 96          # one width for every embedder batch
SEARCH_K = 32
QUERIES = ("fast storage component", "module with low latency",
           "reliable network service", "cheap compute unit")
WORDS = ("fast", "slow", "storage", "network", "compute", "module", "unit",
         "service", "cache", "latency", "reliable", "cheap", "component",
         "replica", "index", "stream", "batch", "vector", "query", "table",
         "cluster", "node", "shard", "log")


class PhaseClock:
    """Per-phase wall seconds, split into compile seconds (tracing, lowering
    and backend compilation or persistent-cache reads, from JAX's monitoring
    events) and the rest.  Compile seconds are summed over threads, so in a
    phase whose sessions compile concurrently they can exceed its wall
    seconds; ``run_s`` is wall minus compile, floored at 0."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.phases: list[dict] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            with self._lock:
                self.compile_s += duration
                self.compiles += event == COMPILE_EVENTS[-1]

    def _on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, n0, h0 = self.compile_s, self.compiles, self.cache_hits
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        row = {"phase": name, "wall_s": wall, "compile_s": comp,
               "run_s": max(wall - comp, 0.0), "compiles": self.compiles - n0,
               "persistent_cache_hits": self.cache_hits - h0,
               "peak_bytes_in_use_so_far": peak_bytes()}
        self.phases.append(row)
        print("phase", json.dumps(row), flush=True)


def peak_bytes() -> int | None:
    """Device 0's peak bytes in use since the process started."""
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def make_records(n: int, seed: int) -> list[dict]:
    import numpy as np
    rng = np.random.default_rng(seed)
    picks = rng.integers(0, len(WORDS), size=(n, 24))
    out = []
    for i in range(n):
        text = f"record {i:05d}: " + " ".join(WORDS[j] for j in picks[i])
        out.append({"id": i, "doc": text.ljust(DOC_BYTES)[:DOC_BYTES]})
    return out


def mixture(n: int, d: int, seed: int, n_queries: int = 64):
    """Seeded Gaussian-mixture corpus [n, d] and queries near corpus rows."""
    import numpy as np
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(128, d)).astype(np.float32)
    corpus = centers[rng.integers(0, 128, n)] + \
        0.5 * rng.normal(size=(n, d)).astype(np.float32)
    rows = rng.choice(n, n_queries, replace=False)
    queries = corpus[rows] + 0.1 * rng.normal(size=(n_queries, d)).astype(np.float32)
    return corpus.astype(np.float32), queries.astype(np.float32)


def compare_planes(ref, got, k: int, *, masked: float) -> dict:
    """Top-k agreement of two score planes [nq, m].  Masked lanes must match
    exactly; scores agree within SCORE_TOL; a top-k position may differ only
    at a near-tie, where the reference's two scores are closer than twice
    the largest score difference."""
    import numpy as np
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    mask_ok = bool(np.array_equal(ref == masked, got == masked))
    live = ref != masked
    max_diff = float(np.abs(ref - got)[live].max()) if live.any() else 0.0
    r_idx = np.argsort(-ref, axis=1, kind="stable")[:, :k]
    g_idx = np.argsort(-got, axis=1, kind="stable")[:, :k]
    rows = np.arange(len(ref))[:, None]
    differ = r_idx != g_idx
    gap = np.abs(ref[rows, r_idx] - ref[rows, g_idx])
    ties_ok = bool(np.all(gap[differ] <= 2 * max_diff))
    return {"ok": mask_ok and max_diff <= SCORE_TOL and ties_ok,
            "rows_identical": int((~differ.any(axis=1)).sum()),
            "rows": len(ref), "tie_swaps": int(differ.sum()),
            "max_abs_diff": max_diff, "masked_lanes_match": mask_ok}


def compare_ids(ref_s, ref_i, got_i, max_gap: float) -> dict:
    """Top-k ids of two searches: identical, or swapped only where the
    reference's scores are within ``max_gap`` of each other."""
    import numpy as np
    ref_s, ref_i, got_i = (np.asarray(a) for a in (ref_s, ref_i, got_i))
    differ = ref_i != got_i
    ok = True
    for r, c in zip(*np.nonzero(differ)):
        pos = np.flatnonzero(ref_i[r] == got_i[r, c])
        ok &= pos.size == 1 and abs(ref_s[r, pos[0]] - ref_s[r, c]) <= max_gap
    return {"ok": bool(ok), "rows_identical": int((~differ.any(axis=1)).sum()),
            "rows": len(ref_i), "tie_swaps": int(differ.sum())}


def retrieval_check(seed: int, n: int, d: int, *, k: int = 10,
                    n_clusters: int = 128, nprobe: int = 8) -> dict:
    """Pallas kernels (the default dispatch) against ``impl="ref"`` on the
    same inputs: ``similarity``, ``ivf_search`` and ``ivf_search_q``."""
    import numpy as np
    from repro.index.backend import MASKED_SCORE
    from repro.index.ivf_index import IVFIndex
    from repro.kernels import ops

    corpus, queries = mixture(n, d, seed)
    out = {"impl": ops._resolve(None)}
    out["similarity"] = compare_planes(
        ops.similarity(queries, corpus, impl="ref"),
        ops.similarity(queries, corpus), k, masked=MASKED_SCORE)
    for quant in ("none", "int8"):
        ix = IVFIndex(corpus, n_clusters=n_clusters, seed=seed, quantize=quant)
        if quant == "none":
            args = (queries, ix.centroids, ix.store, ix.store_mask)
            run = ops.ivf_search
        else:
            args = (queries, ix.centroids, ix.store_q, ix.store_scales,
                    ix.store_mask)
            run = ops.ivf_search_q
        s_ref, p_ref = run(*args, nprobe=nprobe, impl="ref")
        s_got, p_got = run(*args, nprobe=nprobe)
        name = "ivf_search" if quant == "none" else "ivf_search_q"
        out[name] = compare_planes(s_ref, s_got, k, masked=MASKED_SCORE)
        out[name]["probes_match"] = bool(np.array_equal(p_ref, p_got))
        out[name]["ok"] &= out[name]["probes_match"]
        out[name]["tile_len"] = int(ix.store_mask.shape[1])
    out["ok"] = all(v["ok"] for v in out.values() if isinstance(v, dict))
    return out


def scoring_check(engine, records: list[dict], n_prompts: int = 8) -> dict:
    """True/False log-probs from the batched scoring path (what
    ``engine.predicate`` reads) against the log-softmax of the prefill
    path's last-token logits, prompt by prompt.  Prompt lengths differ so
    padding and the last-position gather are exercised."""
    import numpy as np
    from repro.data.tokenizer import TOKENIZER

    prompts = [f"Claim: {r['doc'][:40 + 14 * i]}\nIs the claim true? Answer:"
               for i, r in enumerate(records[:n_prompts])]
    scored = engine._last_logits(prompts)
    passes, _ = engine.predicate(prompts)
    labels = [TOKENIZER.true_id, TOKENIZER.false_id]
    diffs, agree = [], 0
    for i, p in enumerate(prompts):
        toks = np.asarray(TOKENIZER.encode(p), np.int32)
        logits = engine.runner.prefill_into_slot(toks, 0).astype(np.float64)
        lse = np.log(np.sum(np.exp(logits - logits.max()))) + logits.max()
        pre = logits[labels] - lse
        diffs.append(np.abs(pre - scored[i, labels]).max())
        agree += bool(passes[i]) == bool(pre[0] > pre[1])
    max_diff = float(max(diffs))
    return {"ok": max_diff <= LOGPROB_TOL, "prompts": len(prompts),
            "max_abs_diff_logprob": max_diff, "tolerance": LOGPROB_TOL,
            "verdicts_agree": agree,
            "prompt_tokens": [len(TOKENIZER.encode(p)) for p in prompts]}


def hbm_plan(oracle_cfg, proxy_cfg, embed_cfg, max_seq: int, slots: int) -> dict:
    from repro.common import param_bytes
    from repro.embed import encoder
    from repro.models import registry

    def cache_bytes(cfg):
        return param_bytes(registry.cache_specs(cfg, slots, max_seq))

    return {"oracle_weights_gb": param_bytes(registry.param_specs(oracle_cfg)) / 1e9,
            "proxy_weights_gb": param_bytes(registry.param_specs(proxy_cfg)) / 1e9,
            "embedder_weights_gb": param_bytes(encoder.param_specs(embed_cfg)) / 1e9,
            "oracle_kv_gb": cache_bytes(oracle_cfg) / 1e9,
            "proxy_kv_gb": cache_bytes(proxy_cfg) / 1e9,
            # compile for one described v5e chip of the 28-layer scoring
            # step at 32 rows x 512 tokens: 1.01 GB of temporaries
            "scoring_temporaries_gb": 1.01}


def run_one_chip(oracle_cfg, proxy_cfg, embed_cfg, *, seed: int,
                 n_records: int, max_seq: int, clock: PhaseClock,
                 retrieval_dim: int) -> dict:
    from repro.core.backends.jax_engine import make_session
    from repro.core.frame import SemFrame
    from repro.serve import Gateway
    from repro.serve.session import DONE

    print("hbm_plan", json.dumps(hbm_plan(oracle_cfg, proxy_cfg, embed_cfg,
                                          max_seq, slots=8)), flush=True)
    result: dict = {}
    with clock.phase("retrieval_check"):
        result["retrieval"] = retrieval_check(seed, n_records, retrieval_dim)
    print("retrieval_check", json.dumps(result["retrieval"]), flush=True)

    with clock.phase("build_session"):
        sess = make_session(oracle_cfg, proxy_cfg, embed_cfg,
                            max_seq=max_seq, seed=seed)
        records = make_records(n_records, seed)
    oracle, proxy = sess.oracle._m.engine, sess.proxy._m.engine

    with clock.phase("gateway"):
        gw = Gateway(sess, max_inflight=4)
        try:
            handles = []
            for i, query in enumerate(QUERIES):
                sf = (SemFrame(records, gw.session).lazy()
                      .sem_search("doc", query, k=SEARCH_K, index_kind="ivf",
                                  quantize="int8" if i % 2 == 0 else "none")
                      .sem_filter("the {doc} describes a fast component",
                                  recall_target=0.9, precision_target=0.9)
                      .sem_map("give a short gist of {doc}", out_column="gist"))
                handles.append(gw.submit(sf, tenant=f"tenant{i % 2}"))
            gw.wait_all()
            snap = gw.snapshot()
        finally:
            gw.close()
    sessions = [{"sid": h.sid, "tenant": h.tenant, "status": h.status,
                 "rows": len(h.records or []),
                 "error": None if h.error is None else repr(h.error)[:300]}
                for h in handles]
    done = sum(s["status"] == DONE for s in sessions)
    result["gateway"] = {
        "sessions_done": done, "sessions_submitted": len(handles),
        "sessions": sessions,
        "failed_requests": {"oracle": oracle.stats.failed_requests,
                            "proxy": proxy.stats.failed_requests},
        "lm_calls": {"oracle": oracle.stats.lm_calls, "proxy": proxy.stats.lm_calls},
        "generated_tokens": oracle.stats.generated_tokens,
        "indexes": snap.get("index_builds"),
        "ok": done == len(handles) and oracle.stats.failed_requests == 0
        and proxy.stats.failed_requests == 0}
    print("gateway", json.dumps(result["gateway"], default=str), flush=True)

    with clock.phase("scoring_check"):
        result["scoring"] = scoring_check(oracle, records)
    result["scoring"]["scoring_shapes_compiled"] = {
        "oracle": oracle.runner._score._cache_size(),
        "proxy": proxy.runner._score._cache_size()}
    print("scoring_check", json.dumps(result["scoring"]), flush=True)
    result["ok"] = all(result[k]["ok"] for k in ("retrieval", "gateway", "scoring"))
    return result


def run_four_chips(*, seed: int, n: int, d: int, k: int = 10,
                   n_clusters: int = 128, nprobe: int = 8,
                   clock: PhaseClock) -> dict:
    """Device-sharded retrieval on 4 devices against the single-device scan."""
    import jax
    import numpy as np
    from repro.index.backend import MASKED_SCORE
    from repro.index.ivf_index import IVFIndex
    from repro.index.vector_index import VectorIndex
    from repro.kernels import ops
    from repro.kernels.similarity import place_shards, shard_mesh

    corpus, queries = mixture(n, d, seed)
    result: dict = {"devices": len(jax.devices())}
    with clock.phase("sharded_exact"):
        s1, i1 = VectorIndex(corpus).search(queries, k)
        four = VectorIndex(corpus, shards=4)
        _, i4 = four.search(queries, k)
        result["exact"] = compare_ids(s1, i1, i4, max_gap=SCORE_TOL)
        result["exact"]["shards"] = four.last_stats.get("shards")
        result["exact"]["ok"] &= result["exact"]["shards"] == 4
    for quant in ("none", "int8"):
        name = "ivf" if quant == "none" else "ivf_int8"
        with clock.phase(f"sharded_{name}"):
            one = IVFIndex(corpus, n_clusters=n_clusters, seed=seed, quantize=quant)
            four = IVFIndex(corpus, n_clusters=n_clusters, seed=seed,
                            quantize=quant, shards=4)
            same_index = bool(np.array_equal(one.centroids, four.centroids))
            if quant == "none":
                args = (queries, one.centroids, one.store, one.store_mask)
                s_one, p_one = ops.ivf_search(*args, nprobe=nprobe)
                s_four, p_four = ops.sharded_ivf_search(*args, nprobe=nprobe, shards=4)
            else:
                args = (queries, one.centroids, one.store_q, one.store_scales,
                        one.store_mask)
                s_one, p_one = ops.ivf_search_q(*args, nprobe=nprobe)
                s_four, p_four = ops.sharded_ivf_search_q(*args, nprobe=nprobe,
                                                          shards=4)
            plane = compare_planes(s_one, s_four, k, masked=MASKED_SCORE)
            plane["probes_match"] = bool(np.array_equal(p_one, p_four))
            so, io = one.search(queries, k, nprobe=nprobe)
            _, i4 = four.search(queries, k, nprobe=nprobe)
            ids = compare_ids(so, io, i4, max_gap=SCORE_TOL)
            result[name] = {"plane": plane, "ids": ids, "same_index": same_index,
                            "shards": four.last_stats.get("shards"),
                            "ok": plane["ok"] and plane["probes_match"]
                            and ids["ok"] and same_index
                            and four.last_stats.get("shards") == 4}
    # each device must hold its own equal share, not everything on device 0
    mesh = shard_mesh(4)
    shares = {}
    for name, arr in (("corpus_rows", corpus), ("ivf_tiles", one.store_q)):
        placed = place_shards(arr, mesh)
        shares[name] = sorted((s.device.id, int(s.data.shape[0]))
                              for s in placed.addressable_shards)
    even = all(len({dev for dev, _ in v}) == 4 and len({m for _, m in v}) == 1
               and sum(m for _, m in v) == len(arr)
               for v, arr in ((shares["corpus_rows"], corpus),
                              (shares["ivf_tiles"], one.store_q)))
    result["shares"] = {"per_device": shares, "ok": even}
    result["ok"] = all(result[k]["ok"] for k in ("exact", "ivf", "ivf_int8", "shares"))
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print("device", json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                                "count": len(devices), "compile_cache": cache_dir}),
          flush=True)
    clock = PhaseClock()
    if args.chips == 4:
        result = run_four_chips(seed=args.seed, n=N_RECORDS, d=384, clock=clock)
        print("sharded_retrieval", json.dumps(result), flush=True)
    else:
        from repro.configs.llama3_2_3b import CONFIG as LLAMA
        from repro.embed.encoder import E5_SMALL
        proxy = LLAMA.with_(num_layers=2)
        print("reduced", json.dumps({"proxy": {"num_layers": [LLAMA.num_layers, 2]}}),
              flush=True)
        result = run_one_chip(LLAMA, proxy, E5_SMALL, seed=args.seed,
                              n_records=N_RECORDS, max_seq=512, clock=clock,
                              retrieval_dim=E5_SMALL.d_model)
    print("memory", json.dumps({"peak_bytes_in_use": peak_bytes(),
                                "bytes_limit": (dev.memory_stats() or {}).get("bytes_limit")}),
          flush=True)
    print("totals", json.dumps({
        "compile_s": sum(p["compile_s"] for p in clock.phases),
        "run_s": sum(p["run_s"] for p in clock.phases),
        "compiles": clock.compiles,
        "persistent_cache_hits": clock.cache_hits}), flush=True)
    if not result["ok"]:
        print("chip_smoke: a check failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
