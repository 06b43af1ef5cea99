"""Each configuration names its architecture and reference modules, and the
harness reaches every architecture-specific piece through them: a module
registered under a new name drives whole runs with no harness code naming
it, a configuration that names none stops before any weight is drawn, and
each role is checked against its own reference.  The dense module reads
exactly what the harness read before it existed (the pins below)."""
import collections
import sys
import time
import types

import pytest

from bench import configs, harness, weights
from bench.configs import dense, dense_reference
from bench.tests.tiny import run_cell, tiny_cell

CASCADE = {"records_per_query": 64, "clients": 2}
GIST = {"records_per_query": 4, "clients": 2}
FUNCTIONS = ("program_config", "leaf_plan", "sequence_flops", "decode_flops")


def _register(monkeypatch, name: str, **attrs) -> str:
    mod = types.ModuleType(f"bench.configs.{name}")
    mod.__dict__.update(attrs)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return name


def _counted_dense(monkeypatch, calls: collections.Counter) -> str:
    """dense's four functions under a new module name, each call counted by
    (function, configuration name)."""
    def counted(fn):
        def call(cfg, *args, **kw):
            calls[(fn, cfg["name"])] += 1
            return getattr(dense, fn)(cfg, *args, **kw)
        return call

    return _register(monkeypatch, "counted_dense", **{fn: counted(fn) for fn in FUNCTIONS})


def _with_architecture(cell, arch: str):
    for rcfg in (cell.config, cell.config["proxy"]):
        rcfg["architecture"] = arch
    return cell


@pytest.mark.parametrize("mix, over, want", [
    ("cascade", CASCADE, {"tiny": FUNCTIONS[:3], "tiny_proxy": FUNCTIONS[:3]}),
    ("gist", GIST, {"tiny": FUNCTIONS}),
])
def test_a_new_architecture_module_drives_the_run(capsys, monkeypatch, mix, over, want):
    calls = collections.Counter()
    cell = _with_architecture(tiny_cell(mix, **over), _counted_dense(monkeypatch, calls))
    res = run_cell(cell, capsys)
    assert res["correct"] is True, res["checks"]
    for role, fns in want.items():
        for fn in fns:
            assert calls[(fn, role)] > 0, (fn, role, dict(calls))
    assert {role for _, role in calls} == set(want)


@pytest.mark.parametrize("role", ["oracle", "proxy"])
@pytest.mark.parametrize("key", ["architecture", "reference"])
def test_a_configuration_naming_no_module_stops_before_any_draw(monkeypatch, role, key):
    drawn = []
    monkeypatch.setattr(weights, "draw", lambda *a, **k: drawn.append(a) or {})
    cell = tiny_cell("cascade", **CASCADE)
    rcfg = cell.config if role == "oracle" else cell.config["proxy"]
    del rcfg[key]
    with pytest.raises(KeyError, match=f"{rcfg['name']}.*{key}"):
        harness.run(cell, seed=2**33 + 5, seconds=1.0, trace=False,
                    t_process=time.perf_counter(), require_tpu=False, cache_dir=None)
    assert not drawn


def test_each_role_is_checked_against_its_own_reference(capsys, monkeypatch):
    """A proxy reference that reads the <true> logit 1 nat high, named by the
    proxy alone: the proxy's gap shows it, and the oracle's reference never
    sees the proxy's configuration."""
    seen = []

    def logits_at(w, cfg, tokens, positions, *, control=False):
        seen.append(cfg["name"])
        lg = dense_reference.logits_at(w, cfg, tokens, positions, control=control)
        return lg.at[..., weights.TRUE_ID].add(1.0)

    cell = tiny_cell("cascade", **CASCADE)
    cell.config["proxy"]["reference"] = _register(
        monkeypatch, "shifted_reference", logits_at=logits_at,
        hidden_at=dense_reference.hidden_at)
    res = run_cell(cell, capsys)
    checks = res["checks"]
    assert set(seen) == {"tiny_proxy"}
    assert checks["proxy_logprob_gap"]["value"] > max(0.9, checks["proxy_logprob_gap"]["limit"])
    assert checks["oracle_logprob_gap"]["value"] <= checks["oracle_logprob_gap"]["limit"]
    assert res["correct"] is False


# ---------------------------------------------------------------------------
# The dense module against the values the harness computed before it
# ---------------------------------------------------------------------------

BF, F32 = "bfloat16", "float32"
QWEN_05B_PLAN = (
    (("embed", "embedding"), ((151936, 1024), BF, "normal", 0.03125)),
    (("final_norm", "scale"), ((1024,), F32, "one_plus", 0.1)),
    (("layers", "attn", "bk"), ((24, 16, 64), F32, "normal", 0.1)),
    (("layers", "attn", "bq"), ((24, 16, 64), F32, "normal", 0.1)),
    (("layers", "attn", "bv"), ((24, 16, 64), F32, "normal", 0.1)),
    (("layers", "attn", "wk"), ((24, 1024, 16, 64), BF, "normal", 0.03125)),
    (("layers", "attn", "wo"), ((24, 16, 64, 1024), BF, "normal", 0.03125)),
    (("layers", "attn", "wq"), ((24, 1024, 16, 64), BF, "normal", 0.03125)),
    (("layers", "attn", "wv"), ((24, 1024, 16, 64), BF, "normal", 0.03125)),
    (("layers", "attn_norm", "scale"), ((24, 1024), F32, "one_plus", 0.1)),
    (("layers", "ffn", "w_down"), ((24, 2816, 1024), BF, "normal", 0.018844459036110227)),
    (("layers", "ffn", "w_gate"), ((24, 1024, 2816), BF, "normal", 0.03125)),
    (("layers", "ffn", "w_up"), ((24, 1024, 2816), BF, "normal", 0.03125)),
    (("layers", "ffn_norm", "scale"), ((24, 1024), F32, "one_plus", 0.1)),
)
QWEN_05B = {
    "plan": QWEN_05B_PLAN,
    "model": {"name": "qwen15_0p5b", "num_layers": 24, "d_model": 1024, "num_heads": 16,
              "num_kv_heads": 16, "d_ff": 2816, "vocab_size": 151936,
              "rope_theta": 1000000.0, "qkv_bias": True, "tie_embeddings": True},
    "seq": (927825920.0, 1544585216.0, 2161442816.0, 161385021440.0, 328234434560.0,
            328901328896.0, 83343186067456.0),
    "dec": (927825920.0, 927924224.0, 952893440.0, 978059264.0, 487906607104.0),
}
PINS = {
    ("qwen15_4b", "oracle"): {
        "plan": (
            (("embed", "embedding"), ((151936, 2560), BF, "normal", 1.0)),
            (("final_norm", "scale"), ((2560,), F32, "one_plus", 0.1)),
            (("layers", "attn", "bk"), ((40, 20, 128), F32, "normal", 0.1)),
            (("layers", "attn", "bq"), ((40, 20, 128), F32, "normal", 0.1)),
            (("layers", "attn", "bv"), ((40, 20, 128), F32, "normal", 0.1)),
            (("layers", "attn", "wk"), ((40, 2560, 20, 128), BF, "normal", 0.01976423537605237)),
            (("layers", "attn", "wo"), ((40, 20, 128, 2560), BF, "normal", 0.01976423537605237)),
            (("layers", "attn", "wq"), ((40, 2560, 20, 128), BF, "normal", 0.01976423537605237)),
            (("layers", "attn", "wv"), ((40, 2560, 20, 128), BF, "normal", 0.01976423537605237)),
            (("layers", "attn_norm", "scale"), ((40, 2560), F32, "one_plus", 0.1)),
            (("layers", "ffn", "w_down"), ((40, 6912, 2560), BF, "normal", 0.012028130608117204)),
            (("layers", "ffn", "w_gate"), ((40, 2560, 6912), BF, "normal", 0.01976423537605237)),
            (("layers", "ffn", "w_up"), ((40, 2560, 6912), BF, "normal", 0.01976423537605237)),
            (("layers", "ffn_norm", "scale"), ((40, 2560), F32, "one_plus", 0.1)),
            (("out", "head"), ((2560, 151936), BF, "normal", 0.01976423537605237)),
        ),
        "model": {"name": "qwen15_4b", "num_layers": 40, "d_model": 2560, "num_heads": 20,
                  "num_kv_heads": 20, "d_ff": 6912, "vocab_size": 151936,
                  "rope_theta": 5000000.0, "qkv_bias": True, "tie_embeddings": False},
        "seq": (7122206720.0, 13466910720.0, 19812024320.0, 1638286622720.0,
                3296085278720.0, 3302638878720.0, 842744348016640.0),
        "dec": (7122206720.0, 7122616320.0, 7226654720.0, 7331512320.0, 3700152074240.0),
    },
    ("deepseek7b_half", "oracle"): {
        "plan": (
            (("embed", "embedding"), ((102400, 4096), BF, "normal", 1.0)),
            (("final_norm", "scale"), ((4096,), F32, "one_plus", 0.1)),
            (("layers", "attn", "wk"), ((15, 4096, 32, 128), BF, "normal", 0.015625)),
            (("layers", "attn", "wo"), ((15, 32, 128, 4096), BF, "normal", 0.015625)),
            (("layers", "attn", "wq"), ((15, 4096, 32, 128), BF, "normal", 0.015625)),
            (("layers", "attn", "wv"), ((15, 4096, 32, 128), BF, "normal", 0.015625)),
            (("layers", "attn_norm", "scale"), ((15, 4096), F32, "one_plus", 0.1)),
            (("layers", "ffn", "w_down"), ((15, 11008, 4096), BF, "normal", 0.009531160645787792)),
            (("layers", "ffn", "w_gate"), ((15, 4096, 11008), BF, "normal", 0.015625)),
            (("layers", "ffn", "w_up"), ((15, 4096, 11008), BF, "normal", 0.015625)),
            (("layers", "ffn_norm", "scale"), ((15, 4096), F32, "one_plus", 0.1)),
            (("out", "head"), ((4096, 102400), BF, "normal", 0.015625)),
        ),
        "model": {"name": "deepseek7b_half", "num_layers": 15, "d_model": 4096,
                  "num_heads": 32, "num_kv_heads": 32, "d_ff": 11008, "vocab_size": 102400,
                  "rope_theta": 10000.0, "qkv_bias": False, "tie_embeddings": False},
        "seq": (6910361600.0, 12982108160.0, 19054100480.0, 1563164672000.0,
                3135399526400.0, 3141596610560.0, 803285090959360.0),
        "dec": (6910361600.0, 6910607360.0, 6973030400.0, 7035944960.0, 3570254479360.0),
    },
    ("qwen15_4b", "proxy"): QWEN_05B,
    ("deepseek7b_half", "proxy"): QWEN_05B,
}


@pytest.mark.parametrize("config, role", sorted(PINS))
def test_dense_module_reads_as_the_harness_did(config, role):
    pin = PINS[(config, role)]
    cfg = harness.load_json(harness.BENCH / "configs" / f"{config}.json")
    rcfg = cfg if role == "oracle" else cfg["proxy"]
    assert tuple(sorted(weights.leaf_plan(rcfg).items())) == pin["plan"]
    model = harness.program_config(rcfg)
    want = dict(pin["model"], family="dense", head_dim=0, norm_eps=1e-06, dtype="bfloat16")
    assert {k: getattr(model, k) for k in want} == want
    seq = [dense.sequence_flops(rcfg, n) for n in range(1, 513)]
    dec = [dense.decode_flops(rcfg, c) for c in range(1, 513)]
    assert tuple(seq[n - 1] for n in (1, 2, 3, 256, 511, 512)) + (sum(seq),) == pin["seq"]
    assert tuple(dec[c - 1] for c in (1, 2, 256, 512)) + (sum(dec),) == pin["dec"]


@pytest.mark.parametrize("entry", harness.load_json(harness.ROOT / "BENCHMARK.json")["configs"],
                         ids=lambda e: e["name"])
def test_every_configuration_names_modules_that_meet_the_contract(entry):
    cfg = harness.load_json(harness.ROOT / entry["file"])
    for rcfg in (cfg, cfg.get("proxy", cfg)):
        arch, ref = configs.architecture(rcfg), configs.reference(rcfg)
        assert all(callable(getattr(arch, fn, None)) for fn in FUNCTIONS), rcfg["name"]
        assert callable(ref.logits_at) and callable(ref.hidden_at), rcfg["name"]
