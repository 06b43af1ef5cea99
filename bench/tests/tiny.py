"""A cell at test size: the tiny configuration beside this file, a mix of
the benchmark's own, shrunk so that a CPU run takes seconds."""
import json
import time
from pathlib import Path

from bench import harness, traffic

HERE = Path(__file__).resolve().parent


def tiny_config() -> dict:
    return json.loads((HERE / "tiny.json").read_text())


def tiny_cell(mix_name: str, **mix_over) -> harness.Cell:
    """The tiny configuration under a mix, with the end-to-end metrics that
    ``BENCHMARK.json`` gives the mix's cells (those of every cell where the
    mix has none)."""
    mix = dict(traffic.load_mix(mix_name), **mix_over)
    bm = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cells = {w["name"] for w in bm["workloads"] if w["traffic"] == mix_name}

    def mine(m):
        return "workloads" not in m or cells & set(m["workloads"])

    return harness.Cell(f"{mix_name}.tiny", tiny_config(), mix, 1,
                        [m for m in bm["end_to_end"] if mine(m)],
                        [m for m in bm["per_layer"] if mine(m)])


def run_cell(cell, capsys, *, seed=2**33 + 1, seconds=2.0, fault=None, control=False,
             with_err=False):
    """Drive the whole run at test size on the CPU (no look for a chip) and
    return its result line (and, ``with_err``, what it wrote to stderr)."""
    rc = harness.run(cell, seed=seed, seconds=seconds, trace=False,
                     t_process=time.perf_counter(), require_tpu=False,
                     cache_dir=None, fault=fault, control=control)
    assert rc == 0
    cap = capsys.readouterr()
    res = json.loads(cap.out.strip().splitlines()[-1])
    return (res, cap.err) if with_err else res
