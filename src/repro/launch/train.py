"""Production training launcher.

Composes: mesh (trivial on a dev box, production 16×16 / 2×16×16 with real
devices), sharding rules, sharded param init, fault-tolerant loop
(checkpoint/restart, straggler monitor). On this CPU container run it with a
reduced config:

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \
        --steps 30 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import TrainConfig
from repro.data import SyntheticLMData
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.registry import (GRID_ARCHS, get_config, model_fns,
                                   reduce_config)
from repro.optim import adamw
from repro.parallel.sharding import (DEFAULT_RULES, logical_to_physical,
                                     sharding_context)
from repro.train import make_train_step, train
from repro.utils.logging import get_logger

log = get_logger("launch.train")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(GRID_ARCHS), default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU dev box)")
    ap.add_argument("--optimized", action="store_true")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use make_production_mesh (needs ≥256 devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    if args.optimized:
        cfg = cfg.with_opts(True)

    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh())
    log.info("mesh: %s", dict(mesh.shape))

    fns = model_fns(cfg)
    with sharding_context(mesh, DEFAULT_RULES):
        params = fns.init(jax.random.PRNGKey(0))
        from jax.sharding import NamedSharding
        sh = jax.tree_util.tree_map(
            lambda spec, a: NamedSharding(mesh, logical_to_physical(
                spec, a.shape, DEFAULT_RULES, mesh)),
            fns.specs, params,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
        params = jax.device_put(params, sh)

        tc = TrainConfig(total_steps=args.steps,
                         warmup_steps=max(args.steps // 10, 1),
                         learning_rate=args.lr,
                         microbatches=args.microbatches,
                         checkpoint_every=max(args.steps // 3, 1))
        data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, seed=0)
        step = jax.jit(make_train_step(fns.loss, tc))
        out = train(train_step=step, params=params, data=data, tc=tc,
                    ckpt_dir=args.ckpt_dir,
                    log_every=max(args.steps // 20, 1))
    h = out["history"]
    log.info("done: loss %.4f -> %.4f; stragglers flagged: %d",
             h[0], h[-1], out["straggler_flags"])


if __name__ == "__main__":
    main()
