"""One run of one cell: build the session, warm up, drive the gateway for
the window, drain, check the answers against the plain reference, print.

Everything that belongs to one cell is found by name: the configuration in
``bench/configs/<config>.json`` (with its reference module beside it), the
traffic mix in ``bench/mixes/<mix>.json``, each per-layer metric's reader in
``bench/metrics/<metric>.py`` (or the file of the part of its name before
the first dot).  Adding a cell, a mix or a metric adds files and entries;
it edits none of the harness."""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import statistics
import sys
import threading
import time
from pathlib import Path

import numpy as np

from bench import check, traffic
from bench import weights as W

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
POLL_S = 0.002            # completion polling of the driver loop
DRAIN_S = 60.0            # how long past the close an answer may still come
TRACE_S = 60.0            # the longest traced part of a --trace 1 window
LABEL_ROWS = 64           # prompts that place the oracle's label margin ...
LABEL_QUERIES = 8         # ... from this many queries' worth of tags
LABEL_STREAM = 2          # the traffic stream they come from
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  COMPILE_EVENT)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = load_json(root / "BENCHMARK.json")
    w = next((w for w in bm["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bm["configs"] if c["name"] == w["config"])

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, config=load_json(root / cfg_entry["file"]),
                mix=traffic.load_mix(w["traffic"]), chips=int(w["chips"]),
                end_to_end=[m for m in bm["end_to_end"] if mine(m)],
                per_layer=[m for m in bm["per_layer"] if mine(m)])


def reader(metric: str):
    """The per-layer metric's reader module, found by its name."""
    for stem in (metric, metric.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(f"bench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    raise FileNotFoundError(f"no reader for per-layer metric {metric!r}")


def program_config(cfg: dict):
    """The system's ``ModelConfig`` for a configuration file's sizes."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=cfg.get("head_dim", 0),
        qkv_bias=bool(cfg["qkv_bias"]), rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype="bfloat16")


class CompileClock:
    """Counts backend compiles and compile seconds from JAX's events."""

    def __init__(self):
        import jax
        self.lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self.lock:
                self.compile_s += duration
                self.compiles += event == COMPILE_EVENT

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self.lock:
                self.cache_hits += 1


# ---------------------------------------------------------------------------
# Recording what the timed path produces
# ---------------------------------------------------------------------------


def _annotate(on: bool, name: str, **stats):
    if not on:
        return _NULL
    import jax
    return jax.profiler.TraceAnnotation(name, **stats)


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class Recorder:
    """Wraps one engine's scoring, prefill, decode, sampling and generate
    calls: records what each produced (label log-probs per prompt; the
    tokens each request was served) and, while tracing, opens a
    ``bench.<role>.<call>`` annotation around each with its rows, tokens and
    operations.  The wrapped calls return exactly what they returned."""

    def __init__(self, role: str, engine, cfg: dict, *, max_new: int):
        from bench import flops
        self.role, self.engine, self.cfg, self.max_new = role, engine, cfg, max_new
        self.annotate = False
        self.flops = flops
        self.scored: list[tuple[str, float, float]] = []
        self.generated: list[dict] = []
        self._slots: dict[int, dict] = {}
        self._started: list[dict] = []
        self._pending = None
        r, e = engine.runner, engine
        self._orig = (e._last_logits, r.prefill_into_slot, r.decode, e.generate, e.sampler)
        e._last_logits = self._last_logits
        r.prefill_into_slot = self._prefill
        r.decode = self._decode
        e.generate = self._generate
        e.sampler = self._sample

    def reset(self):
        self.scored.clear()
        self.generated.clear()

    def _last_logits(self, prompts):
        lens = [min(len(p.encode()) + 1, self.engine.runner.max_seq) for p in prompts]
        fl = sum(self.flops.sequence_flops(self.cfg, n) for n in lens)
        with _annotate(self.annotate, f"bench.{self.role}.score", rows=len(prompts),
                       tokens=sum(lens), flops=fl):
            out = self._orig[0](prompts)
        for p, row in zip(prompts, out):
            self.scored.append((p, float(row[W.TRUE_ID]), float(row[W.FALSE_ID])))
        return out

    def _prefill(self, tokens, slot, extra=None):
        n = int(len(tokens))
        with _annotate(self.annotate, f"bench.{self.role}.prefill", tokens=n,
                       flops=self.flops.sequence_flops(self.cfg, n)):
            logits = self._orig[1](tokens, slot, extra=extra)
        seq = {"prompt": [int(t) for t in tokens], "served": []}
        self._slots[int(slot)] = seq
        self._started.append(seq)
        self._pending = ("prefill", int(slot))
        return logits

    def _decode(self, tokens, lens):
        live = [s for s, q in self._slots.items() if self._open(q, tokens, lens, s)]
        fl = sum(self.flops.decode_flops(self.cfg, int(lens[s]) + 1) for s in live)
        with _annotate(self.annotate, f"bench.{self.role}.decode", slots=len(live), flops=fl):
            logits = self._orig[2](tokens, lens)
        self._pending = ("decode", live)
        return logits

    def _open(self, seq, tokens, lens, s) -> bool:
        """Whether slot ``s`` still serves ``seq``: the scheduler feeds it the
        last token it was served at the next position."""
        served = seq["served"]
        return bool(served) and len(served) < self.max_new and served[-1] != 258 \
            and int(tokens[s]) == served[-1] \
            and int(lens[s]) == len(seq["prompt"]) + len(served) - 1

    def _sample(self, logits):
        toks = self._orig[4](logits)
        kind, where = self._pending or (None, None)
        self._pending = None
        if kind == "prefill":
            self._slots[where]["served"].append(int(toks[0]))
        elif kind == "decode":
            for s in where:
                self._slots[s]["served"].append(int(toks[s]))
        return toks

    def _generate(self, prompts, *, max_new_tokens=48, fault_hook=None):
        self._started = []
        with _annotate(self.annotate, f"bench.{self.role}.generate", rows=len(prompts)):
            texts = self._orig[3](prompts, max_new_tokens=max_new_tokens,
                                  fault_hook=fault_hook)
        # one prefill per request, in submission order (a retried request
        # prefills again: its last prefill is the one that was served)
        seqs = self._started[-len(prompts):] if len(self._started) >= len(prompts) else []
        for i, (p, t) in enumerate(zip(prompts, texts)):
            self.generated.append({"prompt_text": p, "text": t,
                                   "seq": seqs[i] if seqs else None})
        return texts


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def _device_ok(chips: int, err) -> list | None:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform}", file=err)
        return None
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devs)}", file=err)
        return None
    return devs


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devs)


def _warm_up(mix: dict, engines: dict, max_seq: int, max_new: int, seed: int):
    """Compile exactly the shapes the cell's traffic reaches: the scoring step
    at every row bucket and every width its prompts can take, and for the
    generate path prefill at every width, the slot write and decode."""
    from repro.core.operators import mapex
    from repro.engine.runner import _bucket

    gen = traffic.Traffic(mix, seed, warm=True)
    lo = {f: s["min"] for f, s in mix["fields"].items()}
    hi = {f: s["max"] for f, s in mix["fields"].items()}
    steps = 12
    probes = []
    for k in range(steps + 1):     # field lengths from the mix's min to its max
        q = gen.query({f: [int(lo[f] + (hi[f] - lo[f]) * k / steps)] * gen.n
                       for f in mix["fields"]})
        probes.append(q.records[0])
    if mix["operator"] == "map":
        prompts = [mapex.MAP_INSTRUCTION.format(
            task=mix["template"], item=traffic_render(mix["template"], r)) for r in probes]
        limit = max_seq - max_new - 1
        by_width = {}
        for p in prompts:
            n = min(len(p.encode()) + 1, limit)
            by_width.setdefault(min(_bucket(n), max_seq), p)
        engines["oracle"].generate(list(by_width.values()), max_new_tokens=2)
        return sorted(by_width)
    prompts = _predicate_prompts(mix, probes)
    by_width: dict[int, list[str]] = {}
    for p in prompts:
        n = min(len(p.encode()) + 1, max_seq)
        by_width.setdefault(min(_bucket(n), max_seq), []).append(p)
    widths = sorted(by_width)
    for role in ("oracle", "proxy"):
        for w in widths:
            base = by_width[w]
            for rows in (8, 16, 32):
                engines[role]._last_logits([base[i % len(base)] for i in range(rows)])
    return widths


def _predicate_prompts(mix: dict, records: list[dict]) -> list[str]:
    from repro.core.operators import filter as F
    return [F.PREDICATE_INSTRUCTION.format(claim=traffic_render(mix["template"], r))
            for r in records]


def _shape_labels(weights: dict, cfg: dict, mix: dict, seed: int, max_seq: int) -> dict:
    """The oracle's <false> head column, set so that the label margin varies
    from row to row around the mix's selectivity (``weights.label_direction``),
    judged by the plain reference's final hidden states on ``LABEL_ROWS``
    prompts of the mix.  They come from a stream of their own (never served)
    with the window's tags, spread over as many queries as a window sends,
    so that they read like the window's rows."""
    t0 = time.perf_counter()
    gen = traffic.Traffic(mix, seed, stream=LABEL_STREAM)
    per = LABEL_ROWS // LABEL_QUERIES
    rows = [r for _ in range(LABEL_QUERIES) for r in gen.query().records[:per]]
    seqs = [check.encode(p)[:max_seq] for p in _predicate_prompts(mix, rows)]
    ref = check.reference_module(cfg)
    hidden = []
    for i in range(0, len(seqs), check.BLOCK):
        toks, at = check.block_inputs(seqs[i:i + check.BLOCK], max_seq)
        hidden.append(np.asarray(ref.hidden_at(weights, cfg, toks, at))[:, 0])
    pred = mix["predicate"]
    delta = W.label_direction(np.concatenate(hidden), selectivity=pred["selectivity"],
                              margin_sd=pred["margin_sd"])
    print(f"labels: |delta| {float(np.linalg.norm(delta)):.6f} from {len(seqs)} "
          f"prompts in {time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    return W.set_label_margin(weights, delta)


def traffic_render(template: str, rec: dict) -> str:
    from repro.core.langex import as_langex
    return as_langex(template).render(rec)


def setup_jax(cache_dir: Path | None) -> None:
    """Keep every compiled program in the checkout's persistent cache."""
    import jax
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@dataclasses.dataclass
class Built:
    drawn: dict
    engines: dict
    recorders: dict
    session: object
    gw: object
    widths: list


def build(cell: Cell, seed: int, *, trace: bool, fault=None) -> Built:
    """Weights, engines, session and gateway for the cell, every shape of its
    traffic compiled, and one warm query through the gateway."""
    from repro.core.backends.jax_engine import EngineModel
    from repro.core.frame import Session
    from repro.engine.engine import InferenceEngine
    from repro.models import registry
    from repro.serve import Gateway

    mix, cfg = cell.mix, cell.config
    max_seq, slots = int(cfg["serving"]["max_seq"]), int(cfg["serving"]["max_slots"])
    max_new = int(mix.get("max_new_tokens", 24))
    roles = {"oracle": cfg}
    if mix["operator"] == "filter_cascade":
        roles["proxy"] = cfg["proxy"]
    drawn, engines, recorders = {}, {}, {}
    for i, (role, rcfg) in enumerate(roles.items()):
        drawn[role] = W.draw(rcfg, seed, salt=i + 1)
        if role == "oracle" and "predicate" in mix:
            drawn[role] = _shape_labels(drawn[role], rcfg, mix, seed, max_seq)
        pcfg = program_config(rcfg)
        W.check_layout(drawn[role], registry.param_specs(pcfg))
        engines[role] = InferenceEngine(pcfg, params=drawn[role], seed=W.seed32(seed, 9),
                                        max_slots=slots, max_seq=max_seq)
    widths = _warm_up(mix, engines, max_seq, max_new, seed)
    for role, eng in engines.items():
        recorders[role] = Recorder(role, eng, roles[role], max_new=max_new)
    session = Session(oracle=EngineModel(engines["oracle"], max_new_tokens=max_new),
                      proxy=EngineModel(engines["proxy"], max_new_tokens=max_new)
                      if "proxy" in engines else None)
    gw = Gateway(session, audit=False, **mix.get("gateway", {}))
    if trace:
        _annotate_dispatcher(gw)
    # one warm query through the gateway: host paths, threads, the store
    warm = traffic.Traffic(mix, seed, warm=True).query()
    warm.records = warm.records[:16]
    gw.submit(traffic.build_pipeline(mix, warm, gw.session)).result(timeout=600)
    if fault is not None:
        fault(engines, session)
    for r in recorders.values():
        r.reset()
        r.annotate = bool(trace)
    return Built(drawn, engines, recorders, session, gw, widths)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, t_process: float,
        out=None, err=None, require_tpu: bool = True, cache_dir: Path | None = CACHE_DIR,
        fault=None, control: bool = False) -> int:
    """One run; prints the result line last on ``out``.  ``control`` also
    holds the lower-precision reference, in the program's place, to the same
    limits (``control`` and ``control_correct`` in the result: the limits'
    upper readings; the benchmark's own runs never set it)."""
    out = out or sys.stdout
    err = err or sys.stderr
    import jax
    setup_jax(cache_dir)
    if require_tpu:
        devs = _device_ok(cell.chips, err)
        if devs is None:
            return 2
    else:
        devs = jax.devices()
    devs = devs[: cell.chips]
    clock = CompileClock()
    b = build(cell, seed, trace=trace, fault=fault)
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_process
    compiles0 = clock.compiles
    print(f"setup: {setup_s:.3f} s, compiles {clock.compiles}, persistent cache hits "
          f"{clock.cache_hits}, compile s {clock.compile_s:.3f}, scoring widths {b.widths}",
          file=err, flush=True)

    totals0 = _totals(b.engines, b.gw)
    driver = Driver(b.gw, cell.mix, seed, seconds, trace=trace)
    driver.run()
    totals = {k: v - totals0.get(k, 0) for k, v in _totals(b.engines, b.gw).items()}
    peak = _peak_bytes(devs)
    window_compiles = clock.compiles - compiles0
    store = b.gw.store.stats()
    b.gw.close()
    qs = driver.queries
    done = [q for q in qs if q.status == "done"]
    totals["rows_filtered"] = sum(len(q.query.records) for q in done)
    lat = sorted(q.latency for q in done)
    late = [q.late for q in qs]
    print(f"window: {len(qs)} queries, {len(done)} done, {window_compiles} compiles inside, "
          f"semantic cache hits {store.get('hits')}, generator lateness p50 "
          f"{_q(late, 0.5):.6f} s max {max(late, default=0.0):.6f} s, "
          f"peak_bytes_in_use {peak}", file=err, flush=True)

    # free the program's state (its caches) before the reference runs
    keep = {"scored": {r: rec.scored for r, rec in b.recorders.items()},
            "generated": b.recorders["oracle"].generated}
    drawn = b.drawn
    driver.gw = None
    del b
    gc.collect()

    result = {"correct": False, "attempted": len(qs), "failed": len(qs) - len(done)}
    t_check = time.perf_counter()
    info: dict = {}
    checks = check.compare(cell, drawn, keep, qs, seed=seed, info=info)
    result["correct"] = check.passes(checks)
    print(f"reference check: {time.perf_counter() - t_check:.3f} s, {info}", file=err,
          flush=True)

    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    if trace:
        metrics, breakdown, busy = _per_layer(cell, totals, dev)
        device.update(busy)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown
    else:
        window_s = driver.t_end
        e2e = {"setup_s": setup_s,
               "rows_per_s": totals["rows_filtered"] / window_s if window_s > 0 else 0.0,
               "query_p50_s": _q(lat, 0.5)}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = device
        print(f"window_s {window_s:.6f}, rows {totals['rows_filtered']}, latencies "
              f"n={len(lat)}, totals {totals}", file=err, flush=True)
    if control:
        low = check.compare(cell, drawn, keep, qs, seed=seed, control=True, info=info)
        print(f"control: {info}", file=err, flush=True)
        result["control"] = {k: c["value"] for k, c in low.items()}
        result["control_correct"] = check.passes(low)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


def _q(xs, p: float) -> float:
    """The p-quantile of a sample, as ``statistics.quantiles`` cuts it."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    if len(xs) == 1:
        return float(xs[0])
    n = 100
    cuts = statistics.quantiles(xs, n=n, method="inclusive")
    return float(cuts[int(round(p * n)) - 1])


def _totals(engines: dict, gw) -> dict:
    d = gw.dispatcher.stats()
    out = {"fused_batches": d["fused_batches"], "backend_prompts": d["backend_prompts"]}
    for role, e in engines.items():
        out[f"{role}_rows"] = e.stats.lm_calls
        out[f"{role}_prompt_tokens"] = e.stats.prompt_tokens
    return out


def _annotate_dispatcher(gw) -> None:
    import jax
    d = gw.dispatcher
    orig = d._execute

    def execute(key, calls):
        with jax.profiler.TraceAnnotation(f"bench.dispatch.{key[0]}.{key[1]}",
                                          calls=len(calls)):
            return orig(key, calls)

    d._execute = execute


@dataclasses.dataclass
class Sent:
    query: traffic.Query
    due: float                  # seconds after the window opened
    sent: float = 0.0
    done_at: float | None = None
    status: str = "pending"
    handle: object = None

    @property
    def late(self) -> float:
        return self.sent - self.due

    @property
    def latency(self) -> float:
        return self.done_at - self.due


class Driver:
    """Offers the mix's load to the gateway from one thread: a closed loop of
    ``clients`` callers, each sending its next query when its last one comes
    back, until the window closes; then waits for every query sent in it."""

    def __init__(self, gw, mix: dict, seed: int, seconds: float, *, trace: bool):
        self.gw, self.mix, self.seconds, self.trace = gw, mix, float(seconds), trace
        self.gen = traffic.Traffic(mix, seed)
        self.queries: list[Sent] = []
        self.t0 = self.t_end = 0.0

    def _send(self, due: float) -> Sent:
        q = self.gen.query()
        s = Sent(q, due)
        with _annotate(self.trace, "bench.gateway.submit", rows=len(q.records)):
            s.handle = self.gw.submit(traffic.build_pipeline(self.mix, q, self.gw.session),
                                      tenant=f"tenant{q.qid % 4}")
        s.sent = time.perf_counter() - self.t0
        self.queries.append(s)
        return s

    def run(self) -> None:
        self.t0 = time.perf_counter()
        tracing = self._trace_start() if self.trace else None
        inflight = [self._send(0.0) for _ in range(int(self.mix["clients"]))]
        while True:
            now = time.perf_counter() - self.t0
            if tracing is not None and now >= min(self.seconds, TRACE_S):
                self._trace_stop(tracing)
                tracing = None
            still = []
            for s in inflight:
                if s.handle.wait(0):
                    s.done_at = time.perf_counter() - self.t0
                    s.status = s.handle.status
                    if s.done_at < self.seconds:
                        still.append(self._send(s.done_at))
                else:
                    still.append(s)
            inflight = still
            if now >= self.seconds and not inflight:
                break
            if now >= self.seconds + DRAIN_S:
                break                    # what has not come by now never came
            time.sleep(POLL_S)
        if tracing is not None:
            self._trace_stop(tracing)
        done = [s.done_at for s in self.queries if s.done_at is not None]
        self.t_end = max(done) if done else time.perf_counter() - self.t0

    def _trace_start(self):
        import jax
        import shutil
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        ann = jax.profiler.TraceAnnotation("bench.window")
        ann.__enter__()
        return ann

    def _trace_stop(self, tracing) -> None:
        import jax
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()


def _per_layer(cell: Cell, totals: dict, dev):
    from bench import flops, trace_reduce
    red = trace_reduce.reduce(trace_reduce.find_xplane(str(TRACE_DIR)))
    ctx = MetricContext(trace=red, totals=totals, cell=cell, peak=flops.peak(dev.device_kind))
    metrics = {}
    for m in cell.per_layer:
        v = reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {"device_ops": red.device_ops(10), "idle_gaps": red.idle_gaps(10)}
    return metrics, breakdown, {"busy_s": red.busy_s, "window_s": red.window_s}


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader may read: the reduced trace
    (``trace_reduce.Reduced``), counter deltas over the whole window, the
    cell and the chip's peaks."""
    trace: object
    totals: dict
    cell: Cell
    peak: dict
