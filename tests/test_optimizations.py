"""Correctness of the beyond-paper perf optimizations (§Perf): every opt
must be semantics-preserving — same numbers (or documented approximation)
as the paper-faithful baseline path."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.registry import get_config, model_fns, reduce_config

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestDusCacheUpdate:
    @pytest.mark.parametrize("arch", ["qwen3-4b", "hymba-1.5b",
                                      "deepseek-v2-236b"])
    def test_decode_identical_with_dus(self, arch, rng):
        cfg0 = reduce_config(get_config(arch))
        cfg1 = cfg0.replace(opt_dus_cache=True)
        fns0, fns1 = model_fns(cfg0), model_fns(cfg1)
        params = fns0.init(jax.random.PRNGKey(0))
        B, S = 2, 16
        toks = jnp.asarray(rng.integers(1, cfg0.vocab_size, (B, S + 3)),
                           jnp.int32)
        lg0, c0 = fns0.prefill(params, {"tokens": toks[:, :S]}, S + 3)
        lg1, c1 = fns1.prefill(params, {"tokens": toks[:, :S]}, S + 3)
        for t in range(3):
            lg0, c0 = fns0.decode_step(params, toks[:, S + t], c0)
            lg1, c1 = fns1.decode_step(params, toks[:, S + t], c1)
            np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1),
                                       atol=1e-5)


class TestBf16Params:
    def test_loss_close_to_f32(self, rng):
        cfg0 = reduce_config(get_config("llama3.2-3b")).replace(
            compute_dtype="bfloat16")
        cfg1 = cfg0.replace(opt_bf16_params=True)
        fns0, fns1 = model_fns(cfg0), model_fns(cfg1)
        params = fns0.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.asarray(rng.integers(1, 512, (2, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 512, (2, 32)),
                                       jnp.int32)}
        l0, _ = fns0.loss(params, batch)
        l1, _ = fns1.loss(params, batch)
        # identical math (compute was already bf16); cast site differs only
        assert abs(float(l0) - float(l1)) < 1e-2

    def test_grads_flow_through_cast(self, rng):
        cfg = reduce_config(get_config("llama3.2-3b")).replace(
            compute_dtype="bfloat16", opt_bf16_params=True)
        fns = model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        g = jax.grad(lambda p: fns.loss(p, batch)[0])(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)
        # grads arrive in the PARAM dtype (f32 master)
        p_leaves = jax.tree_util.tree_leaves(params)
        assert all(l.dtype == p.dtype for l, p in zip(leaves, p_leaves))


class TestAbsorbedMLA:
    def test_equivalent_to_expanded(self, rng):
        from repro.models import mla as mla_mod
        from repro.models.schema import init_params
        cfg = reduce_config(get_config("deepseek-v2-236b"))
        params = init_params(jax.random.PRNGKey(0), mla_mod.mla_schema(cfg))
        x = jnp.asarray(rng.normal(size=(2, 24, cfg.d_model)), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
        y0 = mla_mod.mla_apply(params, x, cfg, positions=pos)
        y1 = mla_mod.mla_apply(params, x,
                               cfg.replace(opt_mla_absorbed=True),
                               positions=pos)
        rel = float(jnp.abs(y0 - y1).max()) / float(jnp.abs(y0).max())
        assert rel < 1e-4, rel


class TestMoEShardMap:
    def test_matches_global_when_no_drops(self):
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np, dataclasses
            from repro.models.registry import get_config, reduce_config
            from repro.models import moe as moe_mod
            from repro.parallel.sharding import sharding_context, DEFAULT_RULES
            from repro.models.schema import init_params
            cfg = reduce_config(get_config("moonshot-v1-16b-a3b"))
            cfg = cfg.replace(moe=dataclasses.replace(
                cfg.moe, capacity_factor=50.0))
            params = init_params(jax.random.PRNGKey(0),
                                 moe_mod.moe_schema(cfg))
            x = jnp.asarray(np.random.default_rng(0).normal(
                size=(4, 16, cfg.d_model)), jnp.float32)
            y_g, _ = moe_mod._moe_apply_global(params, x, cfg)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            with sharding_context(mesh, DEFAULT_RULES):
                y_s, _ = jax.jit(lambda p, xx: moe_mod.moe_apply_shard_map(
                    p, xx, cfg, mesh))(params, x)
            rel = float(jnp.abs(y_g - y_s).max()) / float(jnp.abs(y_g).max())
            assert rel < 1e-4, rel
            print("REL", rel)
        """)
        assert "REL" in out

    def test_seq_parallel_rules_lower_train(self):
        """SP rules + all opts lower and compile a small sharded train step."""
        out = run_sub("""
            import jax
            import repro.launch.dryrun as dr
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            dr.make_production_mesh = lambda multi_pod=False: mesh
            from repro.models.registry import get_config, reduce_config
            cfg = reduce_config(get_config("moonshot-v1-16b-a3b")).replace(
                vocab_pad_to=64).with_opts(True)
            compiled, report = dr.lower_cell(
                "moonshot-v1-16b-a3b", "train_4k", cfg_override=cfg)
            print("DOM", report["roofline"]["dominant"])
        """)
        assert "DOM" in out


class TestCacheSeqShard:
    def test_decode_lowering_shards_cache(self):
        out = run_sub("""
            import jax
            import repro.launch.dryrun as dr
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            dr.make_production_mesh = lambda multi_pod=False: mesh
            from repro.models.registry import get_config, reduce_config
            cfg = reduce_config(get_config("qwen3-4b")).replace(
                vocab_pad_to=64).with_opts(True)
            compiled, report = dr.lower_cell(
                "qwen3-4b", "decode_32k", cfg_override=cfg)
            args_gb = report["memory_analysis"]["argument_size_in_bytes"]
            # baseline would replicate the cache over model (4x); sharded
            # cache argument bytes must be well below that
            cfg0 = reduce_config(get_config("qwen3-4b")).replace(
                vocab_pad_to=64)
            compiled0, report0 = dr.lower_cell(
                "qwen3-4b", "decode_32k", cfg_override=cfg0)
            args0 = report0["memory_analysis"]["argument_size_in_bytes"]
            print("RATIO", args0 / args_gb)
            assert args0 / args_gb > 2.0, (args0, args_gb)
        """)
        assert "RATIO" in out


class TestInt8KVCache:
    def test_decode_close_to_fp_cache(self, rng):
        cfg0 = reduce_config(get_config("qwen3-4b"))
        cfg1 = cfg0.replace(opt_int8_kv=True, opt_dus_cache=True)
        fns0, fns1 = model_fns(cfg0), model_fns(cfg1)
        params = fns0.init(jax.random.PRNGKey(1))
        B, S = 2, 24
        toks = jnp.asarray(rng.integers(1, cfg0.vocab_size, (B, S + 4)),
                           jnp.int32)
        lg0, c0 = fns0.prefill(params, {"tokens": toks[:, :S]}, S + 4)
        lg1, c1 = fns1.prefill(params, {"tokens": toks[:, :S]}, S + 4)
        assert c1["k"].dtype == jnp.int8
        scale = float(jnp.abs(lg0).max())
        for t in range(4):
            lg0, c0 = fns0.decode_step(params, toks[:, S + t], c0)
            lg1, c1 = fns1.decode_step(params, toks[:, S + t], c1)
            rel = float(jnp.abs(lg1 - lg0).max()) / scale
            assert rel < 0.05, rel

    def test_quantize_roundtrip(self, rng):
        from repro.models.attention import dequantize_kv, quantize_kv
        t = jnp.asarray(rng.normal(size=(2, 4, 64)) * 3, jnp.float32)
        q, s = quantize_kv(t)
        back = dequantize_kv(q, s, jnp.float32)
        rel = float(jnp.abs(back - t).max()) / float(jnp.abs(t).max())
        assert rel < 0.02, rel


class TestOnehotEmbed:
    def test_decode_identical(self, rng):
        cfg0 = reduce_config(get_config("llama3.2-3b"))
        cfg1 = cfg0.replace(opt_onehot_embed=True)
        fns0, fns1 = model_fns(cfg0), model_fns(cfg1)
        params = fns0.init(jax.random.PRNGKey(0))
        B, S = 2, 16
        toks = jnp.asarray(rng.integers(1, cfg0.vocab_size, (B, S + 2)),
                           jnp.int32)
        _, c0 = fns0.prefill(params, {"tokens": toks[:, :S]}, S + 2)
        _, c1 = fns1.prefill(params, {"tokens": toks[:, :S]}, S + 2)
        for t in range(2):
            lg0, c0 = fns0.decode_step(params, toks[:, S + t], c0)
            lg1, c1 = fns1.decode_step(params, toks[:, S + t], c1)
            np.testing.assert_allclose(np.asarray(lg0), np.asarray(lg1),
                                       atol=1e-4)
