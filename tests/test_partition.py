"""Partitioned parallel execution: guarantee preservation across the stack.

The contract under test is the tentpole's: fragmentation redistributes
*work*, never *results*.  Partitioned filter cascades learn the same
thresholds and pass-set as the unpartitioned run (one global importance
sample); partitioned top-k / agg / join are record-identical; sharded
similarity retrieval (jnp contract on one device, shard_map in a forced
multi-device subprocess) matches the exact scan; and the comparator's
in-batch dedup never re-prompts a repeated or mirrored pair.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core.backends import synth
from repro.core.frame import SemFrame, Session
from repro.core.operators.topk import _Comparator, sem_topk_partitioned
from repro.core.plan import nodes as N
from repro.core.plan import parallel
from repro.core.plan.optimize import PlanOptimizer, explain_plan
from repro.kernels import ops

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _session(world, *, with_proxy=False, log=None, sample_size=40):
    return Session(
        oracle=synth.SimulatedModel(world, "oracle"),
        proxy=synth.SimulatedModel(world, "proxy") if with_proxy else None,
        embedder=synth.SimulatedEmbedder(world), sample_size=sample_size)


PART_KW = dict(n_partitions=4, partition_min_rows=8)


# ---------------------------------------------------------------------------
# splitters
# ---------------------------------------------------------------------------


def test_contiguous_partitions_cover_in_order():
    parts = parallel.contiguous_partitions(10, 4)
    assert [len(p) for p in parts] == [2, 3, 2, 3]
    assert np.concatenate(parts).tolist() == list(range(10))


def test_hash_partitions_keep_groups_whole():
    records = [{"g": f"k{i % 5}"} for i in range(40)]
    parts = parallel.hash_partitions(records, 3, "g")
    assert sorted(i for p in parts for i in p) == list(range(40))
    for p in parts:
        keys = {records[i]["g"] for i in p}
        for q in parts:
            if p is not q:
                assert not keys & {records[i]["g"] for i in q}
    # equality classes match the unpartitioned group dict: 1 and 1.0 are ONE
    # group, so they must land in one partition
    mixed = [{"g": 1}, {"g": 1.0}, {"g": 2}, {"g": True}]
    mparts = parallel.hash_partitions(mixed, 3, "g")
    home = {pi for pi, p in enumerate(mparts) for i in p
            if mixed[i]["g"] in (1, 1.0, True)}
    assert len(home) == 1


def test_range_partitions_are_key_ordered():
    records = [{"v": f"{(i * 7) % 20:03d}"} for i in range(20)]
    parts = parallel.range_partitions(records, 4, "v")
    flat = [records[i]["v"] for p in parts for i in p]
    assert flat == sorted(flat)
    # numeric keys order numerically, not lexicographically ("10" < "2")
    nums = [{"v": (i * 7) % 20} for i in range(20)]
    nparts = parallel.range_partitions(nums, 4, "v")
    nflat = [nums[i]["v"] for p in nparts for i in p]
    assert nflat == sorted(nflat)


def test_subtree_partitions_align_to_reduce_tree():
    # 100 leaves, fanout 8 -> depth 3, chunks of 64: partitions [64, 36]
    parts = parallel.subtree_partitions(100, 8, 4)
    assert [len(p) for p in parts] == [64, 36]
    # n <= fanout: the whole reduce is one root prompt, one partition
    assert [len(p) for p in parallel.subtree_partitions(6, 8, 4)] == [6]


# ---------------------------------------------------------------------------
# filter: thresholds + pass-set preserved
# ---------------------------------------------------------------------------


def test_partitioned_gold_filter_identical():
    records, world, *_ = synth.make_filter_world(90, seed=31)
    synth.add_phrase_predicate(world, records, "is rare", 0.3, seed=31)
    base = (SemFrame(records, _session(world)).lazy()
            .sem_filter("the {claim} is rare").collect())
    lz = (SemFrame(records, _session(world)).lazy()
          .sem_filter("the {claim} is rare"))
    part = lz.collect(**PART_KW, fragment_workers=4)
    assert part.records == base.records
    assert any(r.rule == "plan_partitions" for r in lz.last_rewrites)


def test_partitioned_cascade_same_thresholds_and_pass_set():
    """The acceptance contract: identical tau_plus/tau_minus (the cascade
    calibrates on ONE global importance sample regardless of partitioning),
    identical pass-set, identical oracle bill, for the same seed."""
    records, world, *_ = synth.make_filter_world(120, seed=32)
    synth.add_phrase_predicate(world, records, "is checkable", 0.4, seed=32)

    log_base, log_part = [], []
    base = (SemFrame(records, _session(world, with_proxy=True), log_base)
            .lazy().sem_filter("the {claim} is checkable",
                               recall_target=0.9, precision_target=0.85)
            .collect())
    part = (SemFrame(records, _session(world, with_proxy=True), log_part)
            .lazy().sem_filter("the {claim} is checkable",
                               recall_target=0.9, precision_target=0.85)
            .collect(**PART_KW, fragment_workers=4))
    assert part.records == base.records
    st_b = next(s for s in log_base if s["operator"] == "sem_filter")
    st_p = next(s for s in log_part if s["operator"] == "sem_filter")
    assert st_p["tau_plus"] == st_b["tau_plus"]
    assert st_p["tau_minus"] == st_b["tau_minus"]
    assert st_p["oracle_region"] == st_b["oracle_region"]
    assert st_p["oracle_calls"] == st_b["oracle_calls"]
    assert st_p["proxy_calls"] == st_b["proxy_calls"]
    assert st_p["n_partitions"] == 4


# ---------------------------------------------------------------------------
# topk / agg: record-identical
# ---------------------------------------------------------------------------


def test_partitioned_topk_record_identical():
    records, world, model, emb, piv = synth.make_rank_world(
        64, compare_noise=0.0, seed=33)
    base = (SemFrame(records, _session(world)).lazy()
            .sem_topk("most accurate {abstract}", 6).collect())
    part = (SemFrame(records, _session(world)).lazy()
            .sem_topk("most accurate {abstract}", 6)
            .collect(**PART_KW, fragment_workers=4))
    # noiseless comparator -> both recover the true top-6, in rank order
    assert part.records == base.records


def test_partitioned_topk_merge_reuses_comparator_cache():
    records, world, model, emb, piv = synth.make_rank_world(
        40, compare_noise=0.0, seed=34)
    idx, st = sem_topk_partitioned(records, "most accurate {abstract}", 5,
                                   model, [list(range(0, 20)),
                                           list(range(20, 40))], seed=0)
    truth = sorted(range(40), key=lambda i: -world.rank_value[f"doc{i}"])[:5]
    assert idx == truth
    assert st["n_partitions"] == 2 and st["merge_candidates"] == 10


@pytest.mark.parametrize("n", [30, 64, 100, 130])
def test_partitioned_agg_record_identical(n):
    """Record-identical AND prompt-count-identical: the count catches a
    level-misaligned tree (e.g. a small trailing subtree skipping the
    unpartitioned run's singleton re-prompt at n=130) that an idempotent
    simulated backend would otherwise mask."""
    records, world, model, emb = synth.make_topic_world(n, 3, seed=35)
    log_b, log_p = [], []
    base = (SemFrame(records, _session(world), log_b).lazy()
            .sem_agg("summarize {paper}").collect())
    part = (SemFrame(records, _session(world), log_p).lazy()
            .sem_agg("summarize {paper}")
            .collect(**PART_KW, fragment_workers=4))
    assert part.records == base.records  # subtree-aligned => same prompts
    calls = lambda log: sum(st.get("generate_calls", 0) for st in log)
    assert calls(log_p) == calls(log_b)


def test_partitioned_groupby_agg_identical_rows_and_order():
    records, world, model, emb = synth.make_topic_world(60, 4, seed=36)
    for i, t in enumerate(records):
        # mixed-type keys for one bucket (1 vs 1.0 are ONE group under dict
        # equality): the hash partitioner must keep them together
        t["bucket"] = (1 if i % 8 == 0 else 1.0 if i % 8 == 4
                       else f"b{i % 4}")
    base = (SemFrame(records, _session(world)).lazy()
            .sem_agg("summarize {paper}", group_by="bucket").collect())
    part = (SemFrame(records, _session(world)).lazy()
            .sem_agg("summarize {paper}", group_by="bucket")
            .collect(**PART_KW, fragment_workers=4))
    assert part.records == base.records  # same answers, same key order


# ---------------------------------------------------------------------------
# join / sim-join: record-identical under both exchange strategies
# ---------------------------------------------------------------------------


def test_partitioned_join_broadcast_and_grid_identical():
    left, right, world, *_ = synth.make_join_world(36, 9, seed=37)
    base = (SemFrame(left, _session(world)).lazy()
            .sem_join(right, "the {abstract} reports the {reaction:right}")
            .collect())
    bcast = (SemFrame(left, _session(world)).lazy()
             .sem_join(right, "the {abstract} reports the {reaction:right}")
             .collect(**PART_KW, fragment_workers=4))
    grid_lz = (SemFrame(left, _session(world)).lazy()
               .sem_join(right, "the {abstract} reports the {reaction:right}"))
    grid = grid_lz.collect(**PART_KW, broadcast_max_rows=4, fragment_workers=4)
    assert bcast.records == base.records
    assert grid.records == base.records
    assert any("fragment grid" in r.detail for r in grid_lz.last_rewrites)


def test_partitioned_simjoin_identical():
    left, right, world, *_ = synth.make_join_world(30, 8, seed=38)
    base = (SemFrame(left, _session(world)).lazy()
            .sem_sim_join(right, "abstract", "reaction", k=2,
                          index_kind="exact").collect())
    part = (SemFrame(left, _session(world)).lazy()
            .sem_sim_join(right, "abstract", "reaction", k=2,
                          index_kind="exact")
            .collect(**PART_KW, fragment_workers=4))
    assert part.records == base.records


# ---------------------------------------------------------------------------
# sharded retrieval: exactness (jnp contract path on one device)
# ---------------------------------------------------------------------------


def test_sharded_search_matches_exact_scan(rng):
    corpus = rng.normal(size=(600, 24)).astype(np.float32)
    queries = rng.normal(size=(9, 24)).astype(np.float32)
    sims = ops.similarity(queries, corpus)
    exact_idx = np.argsort(-sims, axis=1)[:, :7]
    scores, idx = ops.sharded_search(queries, corpus, 7, shards=4)
    np.testing.assert_array_equal(idx, exact_idx)
    np.testing.assert_allclose(
        scores, np.take_along_axis(sims, exact_idx, axis=1), rtol=1e-5)


def test_sharded_ivf_scores_identical_to_unsharded(rng):
    from repro.index.ivf_index import IVFIndex
    corpus = rng.normal(size=(900, 16)).astype(np.float32)
    queries = rng.normal(size=(5, 16)).astype(np.float32)
    ivf = IVFIndex(corpus, n_clusters=24, seed=2)
    s_u, p_u = ops.ivf_search(queries, ivf.centroids, ivf.store,
                              ivf.store_mask, nprobe=6)
    s_s, p_s = ops.sharded_ivf_search(queries, ivf.centroids, ivf.store,
                                      ivf.store_mask, nprobe=6, shards=4)
    np.testing.assert_array_equal(p_u, p_s)
    np.testing.assert_allclose(s_u, s_s, rtol=1e-6)


def test_sharded_ops_one_device_kernel_path(rng):
    """On a one-device process an explicit kernel impl runs the single-device
    kernel (interpreted here; Pallas on a one-chip TPU host), not the jnp
    shard simulation; "auto" off-TPU keeps the simulation.  Both agree with
    the reference."""
    from repro.index.ivf_index import IVFIndex
    assert ops._resolve_sharded("interpret", 4) == ("interpret", 1)
    assert ops._resolve_sharded(None, 4) == ("ref", 4)
    corpus = rng.normal(size=(600, 16)).astype(np.float32)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    s_r, i_r = ops.sharded_search(q, corpus, 7, shards=4, impl="ref")
    s_k, i_k = ops.sharded_search(q, corpus, 7, shards=4, impl="interpret")
    np.testing.assert_array_equal(i_k, i_r)
    np.testing.assert_allclose(s_k, s_r, atol=1e-5)
    for quant in ("none", "int8"):
        ivf = IVFIndex(corpus, n_clusters=12, seed=1, quantize=quant)
        if quant == "none":
            args, run = (q, ivf.centroids, ivf.store, ivf.store_mask), \
                ops.sharded_ivf_search
        else:
            args, run = (q, ivf.centroids, ivf.store_q, ivf.store_scales,
                         ivf.store_mask), ops.sharded_ivf_search_q
        s1, p1 = run(*args, nprobe=4, shards=4, impl="ref")
        s2, p2 = run(*args, nprobe=4, shards=4, impl="interpret")
        np.testing.assert_array_equal(p1, p2)
        np.testing.assert_allclose(s1, s2, atol=1e-5)


def test_sharded_index_degenerate_equals_exact(rng):
    """Acceptance: sharded search at nprobe=n_clusters == ops.similarity
    exact scan, and the sharded exact index == the unsharded one."""
    from repro.index.ivf_index import IVFIndex
    from repro.index.vector_index import VectorIndex
    corpus = rng.normal(size=(800, 16)).astype(np.float32)
    queries = rng.normal(size=(6, 16)).astype(np.float32)
    _, base_idx = VectorIndex(corpus).search(queries, 10)
    sharded_exact = VectorIndex(corpus, shards=4)
    _, se_idx = sharded_exact.search(queries, 10)
    np.testing.assert_array_equal(se_idx, base_idx)
    st = sharded_exact.last_stats
    assert st["shards"] == 4
    assert st["scored_vectors_per_shard"] == 6 * 200

    deg = IVFIndex(corpus, n_clusters=16, seed=3, shards=4)
    _, dv = deg.search(queries, 10, nprobe=deg.n_clusters)
    np.testing.assert_array_equal(dv, base_idx)
    assert deg.last_stats["shards"] == 4


def test_sharded_index_save_load_roundtrip(tmp_path, rng):
    from repro.index.backend import load_index
    from repro.index.vector_index import VectorIndex
    corpus = rng.normal(size=(300, 8)).astype(np.float32)
    VectorIndex(corpus, shards=4).save(str(tmp_path / "ix"))
    back = load_index(str(tmp_path / "ix"))
    assert back.shards == 4


# ---------------------------------------------------------------------------
# comparator dedup (satellite regression)
# ---------------------------------------------------------------------------


class _CountingCompareModel:
    def __init__(self, model):
        self._m = model
        self.prompts: list[str] = []

    def compare(self, prompts):
        self.prompts.extend(prompts)
        return self._m.compare(prompts)


def test_comparator_batch_dedupes_repeats_and_mirrors():
    records, world, model, emb, piv = synth.make_rank_world(6, seed=40)
    counting = _CountingCompareModel(model)
    cmp = _Comparator(records, "most accurate {abstract}", counting)
    out = cmp.batch([(0, 1), (0, 1), (1, 0), (2, 3), (3, 2), (2, 3)])
    # one prompt per *unordered* pair: {0,1} and {2,3}
    assert len(counting.prompts) == 2
    # mirrors are consistent by construction (no independent re-sampling)
    assert bool(out[0]) == bool(out[1])
    assert bool(out[2]) != bool(out[0])
    assert bool(out[4]) != bool(out[3])
    assert bool(out[5]) == bool(out[3])
    # cached pairs never re-prompt
    cmp.batch([(1, 0), (3, 2)])
    assert len(counting.prompts) == 2


# ---------------------------------------------------------------------------
# explain / gateway surface
# ---------------------------------------------------------------------------


def test_explain_surfaces_partition_stats():
    records, world, *_ = synth.make_filter_world(80, seed=41)
    synth.add_phrase_predicate(world, records, "is rare", 0.2, seed=41)
    lz = (SemFrame(records, _session(world)).lazy()
          .sem_filter("the {claim} is rare"))
    txt = lz.explain(**PART_KW)
    assert "Exchange[gather, P=4]" in txt
    assert "Partition[contiguous, P=4]" in txt
    assert "frag_oracle~" in txt


def test_agg_partition_count_matches_subtree_alignment():
    """The Exchange/Partition metadata for an Agg reflects the subtree-
    aligned fragment count (fixed by n and fanout), not the configured
    n_partitions — 100 leaves at fanout 8 -> chunks of 64 -> 2 fragments."""
    records, world, model, emb = synth.make_topic_world(100, 3, seed=45)
    opt = PlanOptimizer(_session(world), n_partitions=4, partition_min_rows=8)
    plan = opt.optimize(N.Agg(N.Scan(records), "summarize {paper}", fanout=8))
    assert isinstance(plan, N.Exchange) and plan.n_partitions == 2
    assert plan.child.child.n_partitions == 2
    assert any("2 subtree partitions" in r.detail for r in opt.applied)


def test_optimizer_skips_small_inputs_and_cascade_joins():
    left, right, world, *_ = synth.make_join_world(20, 6, seed=42)
    sess = _session(world, with_proxy=True)
    opt = PlanOptimizer(sess, n_partitions=4, partition_min_rows=64)
    plan = opt.optimize(N.Filter(N.Scan(left), "the {abstract} holds"))
    assert isinstance(plan, N.Filter)  # 20 rows < min: untouched
    opt2 = PlanOptimizer(sess, n_partitions=4, partition_min_rows=8)
    cascade = N.Join(N.Scan(left), N.Scan(right),
                     "the {abstract} reports the {reaction:right}",
                     recall_target=0.9)
    plan2 = opt2.optimize(cascade)
    assert isinstance(plan2, N.Join)   # cascade join: global sample stays

    wrapped = opt2.optimize(N.Filter(N.Scan(left), "the {abstract} holds"))
    assert isinstance(wrapped, N.Exchange)
    assert "Exchange" in explain_plan(wrapped)


def test_gateway_runs_fragments_and_preserves_records():
    records, world, *_ = synth.make_filter_world(100, seed=43)
    synth.add_phrase_predicate(world, records, "is rare", 0.25, seed=43)
    from repro.serve import Gateway
    sess = _session(world, with_proxy=True)
    sf = SemFrame(records, sess)
    base = sf.lazy().sem_filter("the {claim} is rare").collect()
    with Gateway(sess, max_inflight=2, n_partitions=4, fragment_workers=3,
                 optimizer_kw={"partition_min_rows": 16}) as gw:
        handles = [gw.submit(sf.lazy().sem_filter("the {claim} is rare"),
                             tenant=f"t{i}") for i in range(2)]
        outs = [h.result(timeout=120) for h in handles]
        snap = gw.snapshot()
    for out in outs:
        assert [t["id"] for t in out] == [t["id"] for t in base.records]
    assert snap["fragments_run"] >= 8       # 4 fragments x 2 sessions
    assert snap["partitioned_ops"] >= 2
    # fragment traffic still rolls up into each session's scope (the shared
    # semantic cache may hand the slower session its answers for free, so
    # assert activity — oracle calls or cross-session cache hits — per scope)
    assert any(h.stats.oracle_calls > 0 for h in handles)
    assert all(h.stats.oracle_calls + h.stats.cache_hits > 0 for h in handles)


def test_base_executor_treats_markers_as_transparent():
    records, world, *_ = synth.make_filter_world(40, seed=44)
    synth.add_phrase_predicate(world, records, "is rare", 0.3, seed=44)
    from repro.core.plan.execute import PlanExecutor
    sess = _session(world)
    plan = N.Exchange(N.Filter(N.Partition(N.Scan(records), 4),
                               "the {claim} is rare"), "gather", 4)
    out = PlanExecutor(sess).run(plan)
    gold = (SemFrame(records, _session(world))
            .sem_filter("the {claim} is rare"))
    assert out == gold.records


# ---------------------------------------------------------------------------
# multi-device shard_map path (forced 4-device CPU topology, subprocess —
# device count locks at first jax init, so it cannot share this process)
# ---------------------------------------------------------------------------


def test_shard_map_paths_match_ref_on_four_devices():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 4
        from repro.kernels import ops
        from repro.index.vector_index import VectorIndex
        rng = np.random.default_rng(0)
        corpus = rng.normal(size=(1030, 16)).astype(np.float32)
        q = rng.normal(size=(7, 16)).astype(np.float32)
        s_r, i_r = ops.sharded_search(q, corpus, 5, shards=4, impl="ref")
        s_m, i_m = ops.sharded_search(q, corpus, 5, shards=4,
                                      impl="shard_map")
        assert np.array_equal(i_r, i_m) and np.allclose(s_r, s_m)
        # auto dispatch takes the shard_map path on a multi-device host and
        # the index surfaces per-shard accounting
        ix = VectorIndex(corpus, shards=4)
        _, idx = ix.search(q, 5)
        assert np.array_equal(idx, i_r)
        assert ix.last_stats["shards"] == 4
        from repro.index.ivf_index import IVFIndex
        ivf = IVFIndex(corpus, n_clusters=18, seed=1)
        s1, p1 = ops.sharded_ivf_search(q, ivf.centroids, ivf.store,
                                        ivf.store_mask, nprobe=5, shards=4,
                                        impl="ref")
        s2, p2 = ops.sharded_ivf_search(q, ivf.centroids, ivf.store,
                                        ivf.store_mask, nprobe=5, shards=4,
                                        impl="shard_map")
        assert np.array_equal(p1, p2) and np.allclose(s1, s2)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr
    assert "OK" in r.stdout
