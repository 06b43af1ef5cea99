"""HLO analyzer calibration: exact FLOP counting through scan loops (the
whole reason hlo_analysis exists — XLA's cost_analysis does not multiply
while-loop trip counts), byte/collective parsing, roofline terms.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import roofline
from repro.launch.hlo_analysis import analyze_text, shape_bytes

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _flops_of(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return analyze_text(c.as_text()).flops, c


def test_plain_matmul_exact():
    A = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    flops, _ = _flops_of(lambda a, b: a @ b, A, A)
    assert flops == 2 * 256 ** 3


def test_scan_trip_counts_multiplied():
    A = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def scanned(a, w):
        x, _ = jax.lax.scan(lambda x, _: (x @ w, None), a, None, length=12)
        return x

    flops, c = _flops_of(scanned, A, A)
    assert flops == 12 * 2 * 128 ** 3
    # document the XLA undercount this module corrects for:
    xla = float(roofline.xla_cost_analysis(c).get("flops", 0.0))
    assert xla < flops / 5


def test_nested_scan_trips():
    A = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def nested(a, w):
        def outer(x, _):
            y, _ = jax.lax.scan(lambda x, _: (x @ w, None), x, None, length=5)
            return y, None
        x, _ = jax.lax.scan(outer, a, None, length=4)
        return x

    flops, _ = _flops_of(nested, A, A)
    assert flops == 20 * 2 * 64 ** 3


def test_shape_bytes_parsing():
    assert shape_bytes("bf16[8,128]{1,0}") == 8 * 128 * 2
    assert shape_bytes("(f32[4,4], s32[2])") == 64 + 8
    assert shape_bytes("pred[16]") == 16
    assert shape_bytes("token[]") == 0


def test_collective_bytes_multi_device_subprocess():
    code = """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_analysis import analyze_text
        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((8,), ("d",))
        x = jax.ShapeDtypeStruct((1024, 64), jnp.float32)
        with jax.set_mesh(mesh):
            # contraction over the sharded dim forces an all-reduce
            c = jax.jit(lambda a: (a * a).sum(),
                        in_shardings=NamedSharding(mesh, P("d", None))).lower(x).compile()
        costs = analyze_text(c.as_text())
        assert costs.coll.get("all-reduce", 0) > 0, costs.coll
        print("OK")
    """
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True,
                       env=dict(os.environ, PYTHONPATH=SRC), timeout=300)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_roofline_terms_and_bottleneck():
    rl = roofline.Roofline(
        arch="x", shape="train_4k", mesh="single", chips=256,
        hlo_flops_per_dev=197e12,          # exactly 1s of compute
        hlo_bytes_per_dev=819e9 * 0.5,     # 0.5s of memory
        coll_bytes_per_dev=50e9 * 0.25,    # 0.25s of collectives
        model_flops=256 * 197e12 * 0.5, mem_per_dev={}, coll_breakdown={})
    assert rl.bottleneck == "compute"
    assert abs(rl.step_time - 1.0) < 1e-9
    assert abs(rl.mfu - 0.5) < 1e-9
    assert abs(rl.useful_flops_ratio - 0.5) < 1e-9


def test_flash_adjustment_reduces_memory_term():
    rl = roofline.Roofline(
        arch="x", shape="prefill_32k", mesh="single", chips=256,
        hlo_flops_per_dev=1e12, hlo_bytes_per_dev=1e12,
        coll_bytes_per_dev=0.0, model_flops=1e14, mem_per_dev={},
        coll_breakdown={}, scopes={"attn_core": [5e11, 9e11]}, seq_len=32768)
    assert rl.flash_adjusted_bytes < rl.hlo_bytes_per_dev
    assert rl.t_memory_flash < rl.t_memory


def test_model_flops_for_cell():
    from repro.configs import SHAPES, get_config
    cfg = get_config("llama3.2-3b")
    f_train = roofline.model_flops_for_cell(cfg, SHAPES["train_4k"])
    f_dec = roofline.model_flops_for_cell(cfg, SHAPES["decode_32k"])
    n = cfg.active_param_count()
    assert abs(f_train - 6 * n * 256 * 4096) / f_train < 1e-9
    assert abs(f_dec - 2 * n * 128) / f_dec < 1e-9
