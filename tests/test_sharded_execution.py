"""Sharded-execution integration tests.

Runs REAL pjit execution (not just lowering) on small host-device meshes
in subprocesses (the device count must be set before jax initializes, so
each case gets a fresh interpreter).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_executes():
    """One real AdamW step of a smoke arch on a 2x4 mesh."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.sharding.policy import (make_policy, train_state_specs,
                                           batch_specs, to_shardings)
        from repro.training.train_loop import init_train_state, make_train_step

        mesh = make_host_mesh((2, 4))
        cfg = smoke_config("qwen3-8b")
        model = LM(cfg)
        state = init_train_state(model, jax.random.PRNGKey(0))
        pol = make_policy(mesh, batch_size=4)
        st_sh = to_shardings(mesh, train_state_specs(
            pol, jax.eval_shape(lambda: state)))
        state = jax.device_put(state, st_sh)
        rng = np.random.default_rng(0)
        toks = rng.integers(1, cfg.vocab_size, (4, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}
        b_sh = to_shardings(mesh, batch_specs(
            pol, jax.eval_shape(lambda: batch)))
        batch = jax.device_put(batch, b_sh)
        step = jax.jit(make_train_step(model), in_shardings=(st_sh, b_sh),
                       out_shardings=(st_sh, None))
        losses = []
        for _ in range(3):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("LOSSES", losses)
    """)
    assert "LOSSES" in out


def test_sharded_decode_matches_single_device():
    """Sharded serve_step == single-device decode_step numerically."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.sharding.policy import (make_policy, param_specs,
                                           decode_state_specs, to_shardings)

        mesh = make_host_mesh((2, 4))
        cfg = smoke_config("qwen3-8b")
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 8)), jnp.int32)

        # reference: single-device
        _, st_ref = model.prefill(params, toks, max_len=16)
        tok = jnp.full((4, 1), 7, jnp.int32)
        logits_ref, _ = model.decode_step(params, st_ref, tok)

        # sharded
        pol = make_policy(mesh, batch_size=4)
        p_sh = to_shardings(mesh, param_specs(
            pol, jax.eval_shape(lambda: params)))
        params_s = jax.device_put(params, p_sh)
        _, st = jax.jit(lambda p, t: model.prefill(p, t, max_len=16))(
            params_s, toks)
        st_specs = to_shardings(mesh, decode_state_specs(
            pol, jax.eval_shape(lambda: st)))
        st = jax.device_put(st, st_specs)
        logits_s, _ = jax.jit(model.decode_step)(params_s, st, tok)
        np.testing.assert_allclose(np.asarray(logits_ref),
                                   np.asarray(logits_s),
                                   rtol=2e-4, atol=2e-4)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_shard_map_flash_decode_matches_reference():
    """The §Perf decode optimization is numerically exact on a real mesh."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.config import LayerGroup, ModelConfig
        from repro.models.layers import attention as att

        mesh = make_host_mesh((2, 4))
        cfg = ModelConfig(
            name="t", arch_type="dense", d_model=64, vocab_size=128,
            num_heads=8, num_kv_heads=4, head_dim=16, d_ff=128,
            layer_plan=(LayerGroup(mixer="attn", ffn="dense", count=1),),
        ).validate()
        p = att.gqa_params(jax.random.PRNGKey(0), cfg)
        b, s_max = 4, 32
        x = jax.random.normal(jax.random.PRNGKey(1), (b, 1, cfg.d_model))
        ck = jax.random.normal(jax.random.PRNGKey(2),
                               (b, s_max, 4, 16)) * 0.3
        cv = jax.random.normal(jax.random.PRNGKey(3),
                               (b, s_max, 4, 16)) * 0.3
        pos = jnp.asarray([5, 11, 17, 29], jnp.int32)

        y_ref, ck_ref, cv_ref = att.attn_decode(p, cfg, x, ck, cv, pos)

        ck_s = jax.device_put(ck, NamedSharding(
            mesh, P("data", "model", None, None)))
        cv_s = jax.device_put(cv, NamedSharding(
            mesh, P("data", "model", None, None)))
        y_sm, ck_sm, cv_sm = jax.jit(
            lambda *a: att.attn_decode_seq_sharded(
                p, cfg, *a, mesh=mesh, seq_axis="model",
                batch_axes=("data",))
        )(x, ck_s, cv_s, pos)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_sm),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(ck_ref), np.asarray(ck_sm),
                                   rtol=1e-6, atol=1e-6)
        print("MATCH")
    """)
    assert "MATCH" in out


def test_moe_sharded_forward_matches_single_device():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_host_mesh
        from repro.configs import smoke_config
        from repro.models.model import LM
        from repro.sharding.policy import make_policy, param_specs, to_shardings

        mesh = make_host_mesh((2, 4))
        cfg = smoke_config("qwen3-moe-30b-a3b")
        model = LM(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (4, 16)), jnp.int32)
        ref = model.train_logits(params, toks)["logits"]

        pol = make_policy(mesh, batch_size=4)
        p_sh = to_shardings(mesh, param_specs(
            pol, jax.eval_shape(lambda: params)))
        params_s = jax.device_put(params, p_sh)
        out = jax.jit(lambda p, t: model.train_logits(p, t)["logits"])(
            params_s, toks)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=3e-4, atol=3e-4)
        print("MATCH")
    """)
    assert "MATCH" in out
