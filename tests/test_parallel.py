"""Distribution tests: sharding rules engine (pure), and multi-device
collectives/DDP/sharded-train in subprocesses with 8 virtual CPU devices
(the main test process must keep seeing 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.parallel.sharding import (DEFAULT_RULES, LONG_CONTEXT_RULES,
                                     logical_to_physical)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": SRC}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class TestShardingRules:
    def setup_method(self):
        # AbstractMesh avoids touching real devices
        from jax.sharding import AbstractMesh
        self.mesh = AbstractMesh((16, 16), ("data", "model"))
        self.mp = AbstractMesh((2, 16, 16), ("pod", "data", "model"))

    def test_divisible_dims_shard(self):
        spec = logical_to_physical(("embed", "mlp"), (4096, 12800),
                                   DEFAULT_RULES, self.mesh)
        assert spec == jax.sharding.PartitionSpec("data", "model")

    def test_non_divisible_degrades_to_replication(self):
        # 8 kv heads on a 16-way model axis → replicate
        spec = logical_to_physical(("kv_heads",), (8,), DEFAULT_RULES,
                                   self.mesh)
        assert spec == jax.sharding.PartitionSpec(None)

    def test_mesh_axis_used_once(self):
        spec = logical_to_physical(("heads", "mlp"), (32, 128),
                                   DEFAULT_RULES, self.mesh)
        # both map to "model"; only the first dim gets it
        assert spec == jax.sharding.PartitionSpec("model", None)

    def test_batch_spans_pod_and_data(self):
        spec = logical_to_physical(("batch", None), (256, 4096),
                                   DEFAULT_RULES, self.mp)
        assert spec == jax.sharding.PartitionSpec(("pod", "data"), None)

    def test_batch_one_long_context_shards_seq(self):
        spec = logical_to_physical(("batch", "seq", None), (1, 524288, 64),
                                   LONG_CONTEXT_RULES, self.mp)
        assert spec == jax.sharding.PartitionSpec(
            None, ("pod", "data"), None)

    def test_partial_tuple_prefix(self):
        # batch=16 divisible by data(16) but not pod*data(32) on multi-pod:
        # order is ("pod","data") → pod(2) divides 16, pod*data=32 doesn't →
        # keeps ("pod",) only
        spec = logical_to_physical(("batch",), (16,), DEFAULT_RULES, self.mp)
        # ("pod",) and "pod" are the same placement; older jax
        # PartitionSpec doesn't normalize the 1-tuple, so accept either
        assert spec in (jax.sharding.PartitionSpec(("pod",)),
                        jax.sharding.PartitionSpec("pod"))


class TestMultiDevice:
    def test_compressed_psum_matches_exact_within_quant_error(self):
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.parallel.collectives import compressed_psum
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            x = jnp.arange(8 * 32, dtype=jnp.float32).reshape(8, 32) / 77.0
            def f(xs):
                mean, resid = compressed_psum(xs, "data")
                return mean, resid
            y, r = jax.jit(jax.shard_map(f, mesh=mesh,
                in_specs=jax.sharding.PartitionSpec("data"),
                out_specs=(jax.sharding.PartitionSpec(),
                           jax.sharding.PartitionSpec("data")),
                check_vma=False))(x)
            exact = jnp.mean(x.reshape(8, 1, 32), 0)
            err = float(jnp.abs(y[0] - exact).max())
            amax = float(jnp.abs(x).max())
            assert err <= amax / 127 + 1e-6, (err, amax / 127)
            print("ERR", err)
        """)
        assert "ERR" in out

    def test_ddp_train_step_with_compression(self):
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.models.registry import get_config, reduce_config, model_fns
            from repro.configs.base import TrainConfig
            from repro.optim import adamw
            from repro.train.step import make_ddp_train_step
            cfg = reduce_config(get_config("llama3.2-3b"))
            fns = model_fns(cfg)
            params = fns.init(jax.random.PRNGKey(0))
            opt = adamw.init_state(params)
            errors = jax.tree_util.tree_map(jnp.zeros_like, params)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            tc = TrainConfig(grad_compression=True, learning_rate=1e-3)
            step = jax.jit(make_ddp_train_step(fns.loss, tc, mesh))
            batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                     "labels": jnp.ones((8, 32), jnp.int32)}
            p2, o2, e2, m = step(params, opt, errors, batch)
            assert np.isfinite(float(m["loss"]))
            print("LOSS", float(m["loss"]))
        """)
        assert "LOSS" in out

    def test_sharded_train_step_matches_single_device(self):
        """pjit on a 4x2 mesh computes the same loss as 1 device."""
        out = run_sub("""
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.models.registry import get_config, reduce_config, model_fns
            from repro.configs.base import TrainConfig
            from repro.optim import adamw
            from repro.train import make_train_step
            from repro.parallel.sharding import (DEFAULT_RULES,
                logical_to_physical, sharding_context)
            cfg = reduce_config(get_config("qwen3-4b")).replace(
                vocab_pad_to=16)
            fns = model_fns(cfg)
            params = fns.init(jax.random.PRNGKey(0))
            opt = adamw.init_state(params)
            tc = TrainConfig(learning_rate=1e-3)
            batch = {"tokens": jnp.ones((8, 32), jnp.int32),
                     "labels": jnp.ones((8, 32), jnp.int32)}
            # single device
            _, _, m1 = jax.jit(make_train_step(fns.loss, tc))(params, opt, batch)
            # sharded
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            with sharding_context(mesh, DEFAULT_RULES):
                sh = jax.tree_util.tree_map(
                    lambda spec, a: NamedSharding(mesh, logical_to_physical(
                        spec, a.shape, DEFAULT_RULES, mesh)),
                    fns.specs, params,
                    is_leaf=lambda x: isinstance(x, tuple) and all(
                        isinstance(e, (str, type(None))) for e in x))
                ps = jax.device_put(params, sh)
                _, _, m2 = jax.jit(make_train_step(fns.loss, tc))(ps, opt, batch)
            d = abs(float(m1["loss"]) - float(m2["loss"]))
            assert d < 1e-3, d
            print("DELTA", d)
        """)
        assert "DELTA" in out

    def test_dryrun_single_cell_small_mesh(self):
        """The dry-run path itself works end-to-end on a small mesh."""
        out = run_sub("""
            import jax
            from repro.launch.dryrun import lower_cell
            from repro.models.registry import get_config, reduce_config
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4, 2), ("data", "model"))
            import repro.launch.dryrun as dr
            import repro.launch.mesh as lm
            lm_orig = lm.make_production_mesh
            dr.make_production_mesh = lambda multi_pod=False: mesh
            cfg = reduce_config(get_config("qwen3-4b"))
            compiled, report = dr.lower_cell(
                "qwen3-4b", "train_4k", cfg_override=cfg.replace(
                    vocab_pad_to=64))
            assert report["roofline"]["flops_per_chip"] > 0
            print("OK", report["roofline"]["dominant"])
        """)
        assert "OK" in out
