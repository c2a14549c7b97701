"""Grouped/tiled/split paged decode kernel vs the per-head single-block
kernel (long-context mixed-length serving).

Replays the decode shape that dominates long-context serving — a fused
batch where ONE long request stretches the (pow2-bucketed, batch-shared)
block-table width that every short request's lane must also walk — through
the two kernel dataflows at an EQUAL pool budget (both sides read the same
physical pool through the same tables):

* **per-head** — the pre-restructure kernel (``flash_decode_paged_single``):
  grid ``(B*Hq, W)``, every query head of a GQA group re-gathering the
  group's shared KV block (group× redundant operand movement), one
  ``block_size``-row block per kv grid step, the whole walk serialized on
  one lane per head.
* **grouped**  — ``flash_decode_paged``: grid ``(B, split_k)``, each
  program walking only its row's live ``kv_tile_blocks``-block KV tiles —
  one whole-block copy (every KV head) feeds every query head (``(Hkv,
  group, D)`` batched MXU tiles instead of ``(1, D)`` vector dots), table
  columns past a row's length are neither copied nor computed, and the
  split partials merge through the associative Softermax combine.

Two measurements:

1. **Kernel-level decode tok/s** (the headline, asserted ≥ 1.5× in full
   mode): N decode steps of the whole batch through each kernel, lengths
   advancing per step, best-of over strictly alternating rounds. On TPU
   this times the compiled kernels; elsewhere both kernels run under the
   Pallas *interpreter*, whose per-call cost tracks grid steps and
   per-step operand movement — exactly the quantities the restructure
   amortizes on hardware (the JSON records which mode produced the
   number). The modeled per-token gather traffic (the serve/README DMA
   math) is reported alongside as the hardware-side view.
2. **Engine-level greedy equality**: whole-prompt chunks (cold +
   cached/COW-fork) and 16-token chunks at baseline and at tiled/split
   grid settings, bf16 and int8, must produce identical token streams
   per dtype — the grid knobs are layout, not math.

Full mode writes ``BENCH_decode.json`` (repo root) for the perf
trajectory. Prints ``decode_paged_bench,...`` CSV lines, last one the
tok/s ratio.

    PYTHONPATH=src python benchmarks/decode_paged_bench.py [--smoke]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _mixed_lengths(rng, requests, long_tokens, short_blocks_max,
                   block_size):
    """One long-context request + short mixed-length rest (the regime
    where the shared table width punishes the per-head kernel)."""
    lens = [long_tokens]
    for _ in range(requests - 1):
        lens.append(int(rng.integers(block_size,
                                     short_blocks_max * block_size + 1)))
    return np.asarray(lens, np.int64)


def _time_kernels(args, rng):
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_decode_paged import (flash_decode_paged,
                                                  flash_decode_paged_single)
    from repro.serve.paged_step import table_width_bucket

    B, Hq, Hkv = args.requests, args.hq, args.hkv
    D, BS = args.head_dim, args.block_size
    lens0 = _mixed_lengths(rng, B, args.long_blocks * BS,
                           args.short_blocks_max, BS)
    need = int(-(-(lens0.max() + args.steps) // BS))
    W = table_width_bucket(need)          # the engine's decode width policy
    N = int(sum(-(-(l + args.steps) // BS) for l in lens0)) + 1  # pool
    kp = jnp.asarray(rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(N, Hkv, BS, D)), jnp.float32)
    bt = np.zeros((B, W), np.int32)
    nxt = 1
    for b, l in enumerate(lens0):         # disjoint tables, pool-faithful
        nb = -(-(int(l) + args.steps) // BS)
        bt[b, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
    bt = jnp.asarray(bt)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32) / np.sqrt(D)
    interpret = jax.default_backend() != "tpu"
    lens_steps = [jnp.asarray(lens0 + s, jnp.int32)
                  for s in range(args.steps)]

    def run_single():
        for ln in lens_steps:
            o = flash_decode_paged_single(q, kp, vp, bt, ln,
                                          interpret=interpret)
        return o

    def run_grouped():
        for ln in lens_steps:
            o = flash_decode_paged(q, kp, vp, bt, ln,
                                   kv_tile_blocks=args.tile_blocks,
                                   split_k=args.split_k,
                                   interpret=interpret)
        return o

    # parity first (and compiles both), then strictly alternating rounds
    o_s = np.asarray(jax.block_until_ready(run_single()))
    o_g = np.asarray(jax.block_until_ready(run_grouped()))
    np.testing.assert_allclose(o_g, o_s, atol=1e-5)
    single_s, grouped_s = [], []
    for _ in range(args.repeats):
        t0 = time.time()
        jax.block_until_ready(run_single())
        single_s.append(time.time() - t0)
        t0 = time.time()
        jax.block_until_ready(run_grouped())
        grouped_s.append(time.time() - t0)

    # modeled gather traffic per decoded token, per layer (README math)
    # for a row that fills the table: the per-head kernel walks W blocks
    # once per *query* head; the grouped kernel copies each live block
    # once, every KV head in one copy
    itm = np.dtype(np.float32).itemsize
    bytes_single = 2 * Hq * W * BS * D * itm
    bytes_grouped = 2 * Hkv * W * BS * D * itm
    return (float(min(single_s)), float(min(grouped_s)),
            {"mode": "compiled-tpu" if not interpret else "pallas-interpret",
             "table_width": int(W),
             "gather_bytes_per_token_per_layer": {
                 "single": int(bytes_single), "grouped": int(bytes_grouped),
                 "ratio": round(bytes_single / bytes_grouped, 3)}})


def _engine_equality(args, rng):
    """Five serving paths (whole-prompt chunks cold, cached incl. COW
    fork and rehit, 16-token chunks), baseline vs tiled/split grids,
    bf16 + int8: greedy streams must be identical per dtype."""
    import jax
    from repro.models.registry import get_config, model_fns, reduce_config
    from repro.serve import ContinuousEngine

    cfg = reduce_config(get_config(args.arch))
    params = model_fns(cfg).init(jax.random.PRNGKey(0))
    shared = rng.integers(1, cfg.vocab_size, (21,)).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.integers(1, cfg.vocab_size, (n,))]).astype(np.int32)
        for n in (13, 30, 7)]

    def serve(**kw):
        eng = ContinuousEngine(cfg, params, block_size=8, num_blocks=64,
                               max_batch=4, max_len=96, **kw)
        hs = [eng.submit(p, 6) for p in prompts]
        res = eng.run()
        return [res[h.req_id].tokens for h in hs], eng

    grid = dict(kv_tile_blocks=args.tile_blocks,
                decode_split_k=args.split_k)
    cow_seen = 0
    for dtype in ("bf16", "int8"):
        kd = dict(kv_dtype=dtype) if dtype == "int8" else {}
        base, _ = serve(**kd)
        cold, _ = serve(prefix_cache=False, **grid, **kd)
        cached, e1 = serve(**grid, **kd)
        chunked, _ = serve(prefill_chunk=16, **grid, **kd)
        assert base == cold == cached == chunked, \
            f"{dtype}: greedy streams diverged across paths/grids"
        cow_seen += e1.metrics.cow_copies
    assert cow_seen >= 2, "COW-fork path was not exercised"
    return True


def main(argv=None) -> float:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--hq", type=int, default=8,
                    help="query heads (kernel-level workload)")
    ap.add_argument("--hkv", type=int, default=2,
                    help="KV heads — hq/hkv is the GQA group whose "
                         "redundant gather the restructure removes")
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--long-blocks", type=int, default=44,
                    help="resident blocks of the long-context request; its "
                         "pow2-bucketed cover is the table width EVERY "
                         "row's lane walks")
    ap.add_argument("--short-blocks-max", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4,
                    help="decode steps timed per round (lengths advance)")
    ap.add_argument("--tile-blocks", type=int, default=4,
                    help="kv_tile_blocks for the grouped kernel")
    ap.add_argument("--split-k", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=2,
                    help="alternating rounds; best-of reported")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="BENCH_decode.json")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast mode for CI (asserts kernel parity + "
                         "engine greedy equality; speed reported, not "
                         "gated)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = 2
        args.hq, args.hkv, args.head_dim = 4, 2, 16
        args.long_blocks, args.short_blocks_max = 6, 2
        args.steps, args.repeats = 2, 1
        args.tile_blocks, args.split_k = 2, 2

    rng = np.random.default_rng(args.seed)
    print(f"decode_paged_bench,workload,requests,{args.requests},"
          f"hq,{args.hq},hkv,{args.hkv},long_blocks,{args.long_blocks},"
          f"block_size,{args.block_size},tile_blocks,{args.tile_blocks},"
          f"split_k,{args.split_k}")

    single_s, grouped_s, meta = _time_kernels(args, rng)
    toks = args.requests * args.steps
    ratio = single_s / grouped_s
    print(f"decode_paged_bench,per_head,decode_s,{single_s:.3f},"
          f"tok_s,{toks / single_s:.1f}")
    print(f"decode_paged_bench,grouped,decode_s,{grouped_s:.3f},"
          f"tok_s,{toks / grouped_s:.1f}")
    dma = meta["gather_bytes_per_token_per_layer"]
    print(f"decode_paged_bench,gather_bytes_ratio,{dma['ratio']},"
          f"mode,{meta['mode']}")

    _engine_equality(args, rng)
    print("decode_paged_bench,engine,greedy_equal,1")
    print(f"decode_paged_bench,ratio_per_head_over_grouped,{ratio:.2f}")

    if not args.smoke:
        assert ratio >= 1.5, (
            f"grouped/tiled/split decode speedup {ratio:.2f}x < 1.5x")
        from benchmarks.provenance import provenance
        record = {
            "bench": "decode_paged",
            "provenance": provenance(mode=meta["mode"]),
            "workload": {
                "requests": args.requests, "hq": args.hq, "hkv": args.hkv,
                "head_dim": args.head_dim, "block_size": args.block_size,
                "long_blocks": args.long_blocks,
                "short_blocks_max": args.short_blocks_max,
                "steps": args.steps, "arch": args.arch, "reduced": True},
            "grid": {"kv_tile_blocks": args.tile_blocks,
                     "split_k": args.split_k},
            "measurement": meta,
            "backend": __import__("jax").default_backend(),
            "per_head": {"decode_s": round(single_s, 4),
                         "tok_s": round(toks / single_s, 2)},
            "grouped": {"decode_s": round(grouped_s, 4),
                        "tok_s": round(toks / grouped_s, 2)},
            "ratio_per_head_over_grouped": round(ratio, 3),
            "greedy_equal": True,
        }
        with open(args.out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print(f"decode_paged_bench,wrote,{args.out}")
    return ratio


if __name__ == "__main__":
    main()
