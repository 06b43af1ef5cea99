"""The harness's contract at test size: it refuses to run without a chip,
finds every piece of a cell by name, and lays out the weights as the system
takes them."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, weights
from bench.tests.tiny import tiny_config

ROOT = harness.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", "--workload", "cascade.qwen15_4b",
                           "--seed", str(2**32 + 3), "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_no_chip_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0 and not _has_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_bare_benchmark_directory_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and not _has_result(p.stdout)


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_json(ROOT / "BENCHMARK.json")["workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = harness.load_cell(workload)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert hasattr(harness.reader(m["name"]), "read")
    limits = cell.config["limits"]
    assert {"queries_failed", "oracle_logprob_gap"} <= set(limits) or \
        cell.mix["operator"] == "map"
    assert harness.program_config(cell.config).num_layers == cell.config["num_hidden_layers"]


def test_drawn_weights_have_the_systems_layout():
    from repro.models import registry
    cfg = tiny_config()
    for c in (cfg, cfg["proxy"]):
        w = weights.draw(c, 2**33 + 9)
        weights.check_layout(w, registry.param_specs(harness.program_config(c)))
    w = weights.draw(cfg, 5)
    w2 = weights.draw(cfg, 5)
    assert (w["embed"]["embedding"] == w2["embed"]["embedding"]).all()
    bad = dict(w, extra={"x": w["final_norm"]["scale"]})
    with pytest.raises(ValueError):
        weights.check_layout(bad, registry.param_specs(harness.program_config(cfg)))


def test_label_direction_spreads_the_margin_around_the_selectivity():
    rng = np.random.default_rng(3)
    # nearly equal hidden states: a shared part, small variation per row
    shared = rng.normal(size=64) * 4
    hidden = shared + 0.05 * rng.normal(size=(400, 64))
    delta = weights.label_direction(hidden[:64], selectivity=1 / 3, margin_sd=2.0)
    m = hidden @ delta
    assert abs(np.mean(m > 0) - 1 / 3) < 0.1
    assert 1.5 < m.std() < 2.5


def test_set_label_margin_moves_only_the_false_column():
    cfg = tiny_config()
    w = weights.draw(cfg, 2**33 + 17)
    head = np.asarray(w["out"]["head"], np.float32)
    delta = np.linspace(-1, 1, head.shape[0]).astype(np.float32)
    got = np.asarray(weights.set_label_margin(w, delta)["out"]["head"], np.float32)
    keep = [i for i in range(head.shape[1]) if i != weights.FALSE_ID]
    assert (got[:, keep] == head[:, keep]).all()
    want = head[:, weights.TRUE_ID] - delta
    assert np.abs(got[:, weights.FALSE_ID] - want).max() <= 0.01 * np.abs(want).max()
