"""Distribution layer: sharding-rule resolution (pure logic, no devices) +
multi-device behaviors (context-parallel decode, pipeline parallelism,
elastic checkpoint resharding) exercised in subprocesses with a forced
8-device CPU topology — device count locks at first jax init, so they cannot
share this process.
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env, timeout=600)


# ---------------------------------------------------------------------------
# rule resolution (no devices needed)
# ---------------------------------------------------------------------------


def test_resolve_pspec_divisibility_fallbacks():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.dist.sharding import RULE_TABLES, resolve_pspec
    mesh = AbstractMesh((2, 4), ("data", "model"))
    rules = RULE_TABLES["serve_replicated"]
    # kv_heads=8 divisible by model=4 -> sharded; 6 not -> fallback None
    assert resolve_pspec((512, 8, 128), ("embed_in", "kv_heads", "qkv"), mesh, rules) \
        == P(None, "model", None)
    assert resolve_pspec((512, 6, 128), ("embed_in", "kv_heads", "qkv"), mesh, rules) \
        == P(None, None, None)


def test_resolve_pspec_axis_used_once():
    from jax.sharding import PartitionSpec as P
    from jax.sharding import AbstractMesh
    from repro.dist.sharding import RULE_TABLES, resolve_pspec
    mesh = AbstractMesh((2, 4), ("data", "model"))
    rules = RULE_TABLES["default"]
    # batch takes data; kv_seq then takes model only (data already used)
    spec = resolve_pspec((8, 64, 8, 128), ("batch", "kv_seq", "kv_heads", "qkv"),
                         mesh, rules)
    assert spec == P("data", "model", None, None)
    # batch=1 not divisible -> kv_seq grabs (data, model)
    spec = resolve_pspec((1, 64, 8, 128), ("batch", "kv_seq", "kv_heads", "qkv"),
                         mesh, rules)
    assert spec == P(None, ("data", "model"), None, None)


# ---------------------------------------------------------------------------
# multi-device subprocess tests
# ---------------------------------------------------------------------------


def test_context_parallel_decode_matches_reference():
    r = _run("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_test_mesh
        from repro.configs import get_smoke
        from repro.models import attention as A
        from repro.dist import context_parallel as CP
        from repro.common import init_params
        cfg = get_smoke("llama3.2-3b")
        mesh = make_test_mesh((2, 4), ("data", "model"))
        params = init_params(A.attention_spec(cfg), jax.random.PRNGKey(0))
        B, S = 4, 64
        kc = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.num_kv_heads, cfg.hd))
        vc = jax.random.normal(jax.random.PRNGKey(2), (B, S, cfg.num_kv_heads, cfg.hd))
        x = jax.random.normal(jax.random.PRNGKey(3), (B, 1, cfg.d_model))
        lens = jnp.asarray([3, 33, 63, 0], jnp.int32)
        ref, krf, vrf = A.decode_self_attention(params, x, kc, vc, lens, cfg=cfg)
        with jax.set_mesh(mesh):
            kcs = jax.device_put(kc, NamedSharding(mesh, P("data", "model", None, None)))
            vcs = jax.device_put(vc, NamedSharding(mesh, P("data", "model", None, None)))
            out, k2, v2 = jax.jit(lambda p, x, k, v, l: CP.cp_decode_self_attention(
                p, x, k, v, l, cfg=cfg, mesh=mesh))(params, x, kcs, vcs, lens)
        assert jnp.allclose(out, ref, atol=3e-5), float(jnp.max(jnp.abs(out - ref)))
        assert jnp.allclose(k2, krf, atol=1e-5)
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_pipeline_parallel_matches_reference():
    r = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_test_mesh
        from repro.configs import get_smoke
        from repro.models import registry
        from repro.dist.pipeline_parallel import make_pp_loss, pp_forward
        from repro.train.trainstep import loss_fn as ref_loss
        from repro.data.tokenizer import TOKENIZER
        cfg = get_smoke("llama3.2-3b").with_(vocab_size=TOKENIZER.vocab_size, num_layers=4)
        mesh = make_test_mesh((2, 4), ("pod", "data"))
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 32), 0, 200)
        labels = jax.random.randint(jax.random.PRNGKey(2), (16, 32), 0, 200)
        ref, _ = registry.forward(cfg, params, tokens)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda p, t: pp_forward(cfg, mesh, p, t, n_micro=4))(params, tokens)
            assert jnp.allclose(got, ref, atol=1e-4)
            loss = make_pp_loss(cfg, mesh, n_micro=4)
            l, g = jax.jit(jax.value_and_grad(loss))(params, tokens, labels)
            (rl, _), rg = jax.jit(jax.value_and_grad(
                lambda p, t, y: ref_loss(cfg, p, t, y), has_aux=True))(params, tokens, labels)
            assert abs(float(l) - float(rl)) < 1e-4
            gerr = max(float(jnp.max(jnp.abs(a - b)))
                       for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(rg)))
            assert gerr < 5e-4, gerr
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_elastic_checkpoint_reshard():
    r = _run("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import checkpoint as ckpt
        from repro.launch.mesh import make_test_mesh
        d = tempfile.mkdtemp()
        mesh1 = make_test_mesh((8,), ("data",))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh1, P("data", None)))
        ckpt.save(d, 1, {"params": {"w": w}})
        # restart on a DIFFERENT topology
        mesh2 = make_test_mesh((2, 4), ("data", "model"))
        sh = {"params": {"w": NamedSharding(mesh2, P("data", "model"))}}
        step, out = ckpt.restore_sharded(d, sh)
        got = out["params"]["w"]
        assert got.sharding.spec == P("data", "model")
        np.testing.assert_array_equal(np.asarray(got), np.arange(64.0).reshape(8, 8))
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_gspmd_train_step_with_rules():
    """A sharded train step on an 8-device mesh produces finite metrics and
    params identical to the unsharded step."""
    r = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.configs import get_smoke
        from repro.models import registry
        from repro.dist import sharding as shd
        from repro.train import optimizer as opt
        from repro.train.trainstep import make_train_step
        from repro.data.tokenizer import TOKENIZER
        cfg = get_smoke("llama3.2-3b").with_(vocab_size=384, d_model=64, d_ff=128)
        mesh = make_test_mesh((2, 4), ("data", "model"))
        params = registry.init_params(cfg, jax.random.PRNGKey(0))
        ocfg = opt.OptimizerConfig(total_steps=2, warmup_steps=0)
        state = opt.init_state(params, ocfg)
        step = make_train_step(cfg, ocfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 384),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, 384)}
        p_ref, _, m_ref = jax.jit(step)(params, state, batch)
        pspecs = registry.param_specs(cfg)
        ospecs = opt.state_specs(pspecs, ocfg)
        with jax.set_mesh(mesh), shd.activation_rules(mesh, "default"):
            sh = (shd.spec_shardings(pspecs, mesh), shd.spec_shardings(ospecs, mesh), None)
            p2, s2, m2 = jax.jit(step, in_shardings=sh, out_shardings=(sh[0], sh[1], None))(
                params, state, batch)
        assert np.isfinite(float(m2["loss"]))
        assert abs(float(m2["loss"]) - float(m_ref["loss"])) < 1e-3
        err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                  for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)))
        assert err < 5e-3, err
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr
