"""Bring-up check: qwen3-4b at full width, served on a TPU by the paged engine.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four replicas behind the fleet router

One chip, in order:

1. the device is a TPU (there is no CPU fallback);
2. qwen3-4b at its published widths (36 layers, d_model 2560) with seeded
   bf16 weights, drawn the way the serve launcher draws them;
3. both paged Pallas kernels agree with their pure-JAX references at those
   widths, on bf16 and int8 pools;
4. ``ContinuousEngine``, built as the launcher builds it (1024-block bf16
   pool of 16-token blocks, chunked prefill of 256 tokens), is warmed up
   and serves 8 requests of 300 to 1000 prompt tokens, half of them sharing
   a 256-token prefix, 32 greedy tokens each;
5. its compiled decode and chunk-prefill programs hold the Pallas kernels
   (``tpu_custom_call``), so the dispatchers did not fall back to the refs;
6. its decode logits, for four steps of all eight requests, agree with a
   float32 ``lm_forward`` of the same tokens.

``--four-chips`` runs only the fleet: one one-replica reference run, freed,
then four replicas, one per chip, behind ``FleetSupervisor`` and the
prefix-affinity router; their streams must match the reference byte for
byte, with no crash and no failover.

Exits non-zero, before any result line, when JAX finds no TPU or any check
fails. The last line of a passing run is one JSON object naming the device.
Times printed here are smoke figures, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

ARCH = "qwen3-4b"
SEED = 0
BLOCK_SIZE = 16
NUM_BLOCKS = 1024
N_REQUESTS = 8
PROMPT_MIN, PROMPT_MAX = 300, 1000
SHARED_PREFIX = 256        # one chunk: a prefix hit lands on a chunk edge
PREFILL_CHUNK = 256
MAX_NEW = 32
TAP_STEPS = 4              # decode steps whose logits are checked
FOUR = 4

# max |kernel - ref| / max |ref|. Kernel and reference run the same
# Softermax recurrence in f32 on bf16 inputs and round the output to bf16
# (relative step 2^-8); two such steps cover that rounding and the f32
# reassociation of the kernel's tiled sums. A wrong table entry, mask or
# rescale moves whole rows and misses by orders of magnitude.
KERNEL_TOL = 2.0 ** -7
# max |engine - reference| / rms(reference) over a logit row. The engine
# computes in bf16 (the configuration's compute dtype) against a float32,
# highest-precision reference: bf16 rounding of every activation through
# 36 layers moves the logits by a few percent of their rms (LOGIT_TOL is
# about three times what that costs). Reading the wrong position, block or
# layer decorrelates the rows and misses by more than 1.
LOGIT_TOL = 0.15


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(n_chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    check(devices[0].platform == "tpu",
          f"needs a TPU, JAX found {devices[0].platform}")
    check(len(devices) >= n_chips,
          f"needs {n_chips} chips, JAX found {len(devices)}")
    return devices


class CompileClock:
    """Sums the backend compile time and persistent-cache hits JAX
    reports while it is installed."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def line(self) -> str:
        return (f"{self.seconds:.3f} s in {self.compiles} backend compiles, "
                f"{self.cache_hits} persistent-cache hits")


def workload(vocab: int, seed: int):
    """N_REQUESTS prompts of PROMPT_MIN..PROMPT_MAX tokens; the even ones
    open with one shared SHARED_PREFIX-token prefix. Alternating sharers
    with others means the second sharer is admitted a step after the
    first has published its first chunk, so it hits the radix cache."""
    rng = np.random.default_rng(seed)
    lens = rng.permutation(
        np.linspace(PROMPT_MIN, PROMPT_MAX, N_REQUESTS).astype(int))
    prefix = rng.integers(1, vocab, SHARED_PREFIX)
    prompts = []
    for i, n in enumerate(lens):
        p = rng.integers(1, vocab, int(n))
        if i % 2 == 0:
            p[:SHARED_PREFIX] = prefix
        prompts.append(p.astype(np.int32))
    return prompts


def kernel_parity(cfg, seed: int, interpret: bool = False):
    """Both paged kernels against their references at the configuration's
    attention widths. Returns {case: relative error}."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_decode_paged import (flash_decode_paged,
                                                  paged_decode_ref)
    from repro.kernels.flash_prefill_paged import (flash_prefill_paged,
                                                   paged_prefill_ref)
    from repro.models.attention import quantize_kv

    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    n_blocks = NUM_BLOCKS + 1
    width = -(-PROMPT_MAX // BLOCK_SIZE)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    rng = np.random.default_rng(seed)

    def normal(key, shape, scale=1.0):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(jnp.bfloat16)

    kp = normal(keys[0], (n_blocks, hkv, BLOCK_SIZE, d))
    vp = normal(keys[1], (n_blocks, hkv, BLOCK_SIZE, d))
    k8, ksc = quantize_kv(kp)
    v8, vsc = quantize_kv(vp)
    pools = {"bf16": (kp, vp, {}),
             "int8": (k8, v8, {"k_scale": ksc, "v_scale": vsc})}

    def table(rows: int):
        return jnp.asarray(np.stack([
            rng.permutation(np.arange(1, n_blocks))[:width]
            for _ in range(rows)]), jnp.int32)

    def rel(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))

    errors = {}
    batch = N_REQUESTS
    q = normal(keys[2], (batch, hq, d), d ** -0.5)
    bt = table(batch)
    lens = jnp.asarray(rng.integers(PROMPT_MIN, PROMPT_MAX + 1, batch),
                       jnp.int32)
    for name, (k, v, sc) in pools.items():
        with jax.default_matmul_precision("highest"):
            want = paged_decode_ref(q, k, v, bt, lens, **sc)
        for tile, split in ((1, 1), (8, 2)):
            got = flash_decode_paged(q, k, v, bt, lens, kv_tile_blocks=tile,
                                     split_k=split, interpret=interpret,
                                     **sc)
            errors[f"decode/{name}/T{tile}/S{split}"] = rel(got, want)

    batch = 2
    qp = normal(keys[3], (batch, hq, PREFILL_CHUNK, d), d ** -0.5)
    bt = table(batch)
    pos0 = jnp.asarray([SHARED_PREFIX, PROMPT_MAX - PREFILL_CHUNK],
                       jnp.int32)
    for name, (k, v, sc) in pools.items():
        with jax.default_matmul_precision("highest"):
            want = paged_prefill_ref(qp, k, v, bt, pos0, **sc)
        for tile in (1, 8):
            got = flash_prefill_paged(qp, k, v, bt, pos0,
                                      kv_tile_blocks=tile,
                                      interpret=interpret, **sc)
            errors[f"prefill/{name}/T{tile}"] = rel(got, want)
    return errors


def build_engine(cfg, params):
    """The engine as ``repro.launch.serve`` builds it for this traffic,
    warmed up as its fleet path does."""
    from repro.serve import ContinuousEngine
    eng = ContinuousEngine(cfg, params, block_size=BLOCK_SIZE,
                           num_blocks=NUM_BLOCKS, max_batch=N_REQUESTS,
                           max_len=PROMPT_MAX + MAX_NEW,
                           prefill_chunk=PREFILL_CHUNK)
    eng.warmup()
    return eng


class DecodeLogitTap:
    """Wraps an engine's jitted decode step and keeps the logits of the
    first ``n_steps`` calls in which every batch row is decoding, with
    each row's request id and cache length."""

    def __init__(self, eng, n_steps: int):
        self.eng, self.step_fn, self.n_steps = eng, eng._decode, n_steps
        self.steps = []          # [({row: req_id}, lengths, logits)]
        eng._decode = self

    def __call__(self, *args, **kwargs):
        out = self.step_fn(*args, **kwargs)
        rows = {i: r.req_id for i, r in enumerate(self.eng._rows)
                if r is not None}
        if len(self.steps) < self.n_steps and \
                len(rows) == self.eng.max_batch:
            self.steps.append((rows, np.asarray(args[3]), out[1]))
        return out


def programs_hold_kernels(eng) -> dict:
    """Compile the engine's own decode and chunk-prefill programs at a
    serving shape and look for the Pallas custom call in each."""
    import jax.numpy as jnp
    b, c, w = eng.max_batch, eng.prefill_chunk, eng.nb_max
    zeros = jnp.zeros
    decode = eng._decode.lower(
        eng.params, zeros((b,), jnp.int32), zeros((b, w), jnp.int32),
        zeros((b,), jnp.int32), *eng._pools(), tile=eng.kv_tile_blocks,
        split=eng.decode_split_k).compile().as_text()
    chunk = eng._prefill_chunk_fn.lower(
        eng.params, zeros((1, c), jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray([c - 1], jnp.int32),
        zeros((1, c // eng.block_size), jnp.int32), zeros((c,), jnp.int32),
        zeros((c,), jnp.int32), *eng._pools()).compile().as_text()
    return {"decode": "tpu_custom_call" in decode,
            "chunk_prefill": "tpu_custom_call" in chunk}


def reference_logits(cfg, params, seqs, positions):
    """float32 ``lm_forward`` of each sequence (right-padded to one
    length; causality keeps the pad out), at highest matmul precision;
    returns the logit rows at ``positions[i]`` of sequence i."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import lm_forward
    ref_cfg = cfg.replace(compute_dtype="float32", remat="none")
    n = max(len(s) for s in seqs)

    @jax.jit
    def rows(p, toks, pos):
        lg, _ = lm_forward(p, toks[None], ref_cfg)
        return lg[0, pos, :cfg.vocab_size]

    out = []
    for s, pos in zip(seqs, positions):
        toks = np.zeros((n,), np.int32)
        toks[:len(s)] = s
        with jax.default_matmul_precision("highest"):
            out.append(np.asarray(rows(params, jnp.asarray(toks),
                                       jnp.asarray(pos, jnp.int32))))
    return out


def weight_summary(params):
    import jax
    leaves = jax.tree_util.tree_leaves(params)
    dtypes = sorted({str(a.dtype) for a in leaves})
    return (sum(int(a.size) for a in leaves),
            sum(int(a.nbytes) for a in leaves), dtypes)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def setup(n_chips: int):
    """Device check, compile cache, bf16 params — shared by both modes."""
    devices = require_tpu(n_chips)
    from repro.launch.compile_cache import configure_compile_cache
    from repro.launch.serve import serving_params
    from repro.models.registry import get_config
    say(f"device: {devices[0].device_kind}, count {len(devices)}, "
        f"platform {devices[0].platform}")
    say(f"compile cache: {configure_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    cfg, params = serving_params(get_config(ARCH), SEED)
    import jax
    jax.block_until_ready(params)
    n, nbytes, dtypes = weight_summary(params)
    say(f"model: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} q heads / {cfg.n_kv_heads} kv heads x "
        f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    say(f"weights: {n} params, {nbytes} bytes, dtypes {dtypes}, "
        f"drawn in {time.perf_counter() - t0:.3f} s")
    check(dtypes == ["bfloat16"], f"weights are not all bf16: {dtypes}")
    return devices, cfg, params, clock


def serve(eng, prompts):
    """Submit every prompt, run to completion; returns the finished
    requests in submission order and the window's wall seconds."""
    handles = [eng.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return [done[h.req_id] for h in handles], wall


def check_finished(reqs, what: str) -> None:
    from repro.serve import FINISH_LENGTH
    reasons = [r.finish_reason for r in reqs]
    check(all(x == FINISH_LENGTH for x in reasons),
          f"{what}: finish reasons {reasons}")
    check(all(len(r.tokens) == MAX_NEW for r in reqs),
          f"{what}: token counts {[len(r.tokens) for r in reqs]}")


def run_one_chip() -> dict:
    import jax
    devices, cfg, params, clock = setup(1)

    errors = kernel_parity(cfg, SEED)
    for case, err in errors.items():
        say(f"kernel parity {case}: max|kernel-ref|/max|ref| = {err:.6g} "
            f"(tol {KERNEL_TOL:.6g})")
    bad = {c: e for c, e in errors.items() if not e <= KERNEL_TOL}
    check(not bad, f"kernel parity out of tolerance: {bad}")

    t0 = time.perf_counter()
    eng = build_engine(cfg, params)
    say(f"engine warmup: {time.perf_counter() - t0:.3f} s wall; compile "
        f"so far {clock.line()}")
    say(f"kv pool: {eng.pool.kv_dtype}, {NUM_BLOCKS} blocks x {BLOCK_SIZE} "
        f"tokens, {eng.pool.hbm_bytes} bytes")
    kernels = programs_hold_kernels(eng)
    say(f"tpu_custom_call in compiled programs: {kernels}")
    check(all(kernels.values()), f"Pallas kernel missing: {kernels}")

    tap = DecodeLogitTap(eng, TAP_STEPS)
    prompts = workload(cfg.vocab_size, SEED)
    reqs, wall = serve(eng, prompts)
    m, cs = eng.metrics, eng.prefix_cache.stats
    say(f"served window (smoke figure): {len(reqs)} requests, "
        f"{sum(len(p) for p in prompts)} prompt tokens, "
        f"{sum(len(r.tokens) for r in reqs)} generated, {wall:.3f} s wall")
    say(f"engine: {m.prefill_chunks} prefill chunks, {m.decode_steps} "
        f"decode steps, {m.preemptions} preemptions, prefix hits "
        f"{cs.hit_tokens}/{cs.lookup_tokens} prompt tokens")
    check_finished(reqs, "served window")
    check(m.preemptions == 0, f"{m.preemptions} preemptions")
    check(cs.hit_tokens >= SHARED_PREFIX, f"prefix hits {cs.hit_tokens}")
    check(m.prefill_chunks > 0 and m.decode_steps > 0,
          "chunked prefill or decode never ran")
    check(len(tap.steps) == TAP_STEPS,
          f"only {len(tap.steps)} decode steps had every row busy")
    say(f"peak_bytes_in_use after serving: {peak_bytes(devices[0])}")
    say(f"compile total: {clock.line()}")

    # engine logits of the tapped steps, then free the engine (its pool)
    # before the float32 reference runs beside the weights
    by_req = {r.req_id: r for r in reqs}
    got, want_pos, seqs = {}, {}, {}
    for rows, lengths, lg in tap.steps:
        lg = np.asarray(lg)[:, :cfg.vocab_size]
        check(bool(np.isfinite(lg).all()), "non-finite decode logits")
        for row, rid in rows.items():
            got.setdefault(rid, []).append(lg[row])
            want_pos.setdefault(rid, []).append(int(lengths[row]))
    for rid in got:
        r = by_req[rid]
        seqs[rid] = np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                         np.int32)])
    del eng, tap, reqs, by_req
    gc.collect()

    rids = sorted(got)
    refs = reference_logits(cfg, params, [seqs[r] for r in rids],
                            [want_pos[r] for r in rids])
    worst = 0.0
    for rid, ref in zip(rids, refs):
        eng_lg = np.stack(got[rid])
        rms = np.sqrt(np.mean(ref.astype(np.float64) ** 2, axis=1))
        err = np.max(np.abs(eng_lg - ref), axis=1) / rms
        worst = max(worst, float(err.max()))
    say(f"logit parity: {len(rids)} requests x {TAP_STEPS} decode steps, "
        f"max |engine-ref|/rms(ref) = {worst:.6g} (tol {LOGIT_TOL})")
    check(worst <= LOGIT_TOL, f"engine logits off the reference: {worst}")
    say(f"peak_bytes_in_use after reference: {peak_bytes(devices[0])}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(jax.devices())}


def array_devices(eng) -> set:
    import jax
    arrays = jax.tree_util.tree_leaves(eng.params) + list(eng._pools())
    return set().union(*(a.devices() for a in arrays))


def run_four_chips() -> dict:
    import jax

    from repro.serve import FleetSupervisor, Router, replica_device
    devices, cfg, params, clock = setup(FOUR)
    prompts = workload(cfg.vocab_size, SEED)

    # one-replica reference, freed before the fleet: two pools beside the
    # weights would not fit one chip
    with jax.default_device(replica_device(0)):
        eng = build_engine(cfg, params)
    reqs, wall = serve(eng, prompts)
    check_finished(reqs, "one-replica reference")
    reference = [list(r.tokens) for r in reqs]
    say(f"one replica: {len(reqs)} requests in {wall:.3f} s wall "
        f"(smoke figure)")
    del eng, reqs
    gc.collect()

    t0 = time.perf_counter()
    engines = []
    for i in range(FOUR):
        with jax.default_device(replica_device(i)):
            engines.append(build_engine(cfg, params))
    say(f"fleet warmup: {time.perf_counter() - t0:.3f} s wall; compile so "
        f"far {clock.line()}")
    placed = [array_devices(e) for e in engines]
    for i, devs in enumerate(placed):
        say(f"replica {i}: arrays on {sorted(d.id for d in devs)}")
    check(all(len(d) == 1 for d in placed), "a replica spans devices")
    check(len(set().union(*placed)) == FOUR, "replicas share a device")

    sup = FleetSupervisor(engines, router=Router("affinity"),
                          step_parallel=True)
    treqs = [sup.submit(p, MAX_NEW) for p in prompts]
    t0 = time.perf_counter()
    sup.run_until_drained()
    wall = time.perf_counter() - t0
    sup.close()
    streams = [list(t.result.tokens) for t in treqs]
    per_replica = [e.metrics.prefills for e in engines]
    crashed = int(sup.c_crashed.value)
    failovers = int(sup.tracker.c_failovers.value)
    say(f"fleet: {len(treqs)} requests in {sup.ticks} ticks, {wall:.3f} s "
        f"wall (smoke figure); prefills per replica {per_replica}; "
        f"crashed {crashed}, failovers {failovers}")
    for i, dev in enumerate(devices[:FOUR]):
        say(f"device {i} peak_bytes_in_use: {peak_bytes(dev)}")
    say(f"compile total: {clock.line()}")
    check(crashed == 0 and failovers == 0,
          f"crashed {crashed}, failovers {failovers}: "
          f"{[str(r.error) for r in sup.replicas if r.error]}")
    check_finished([t.result for t in treqs], "fleet")
    same = [s == r for s, r in zip(streams, reference)]
    say(f"streams identical to one replica: {sum(same)}/{len(same)}")
    check(all(same), "fleet streams differ from the one-replica run")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(jax.devices())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica fleet phase")
    args = ap.parse_args()
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    sys.path.insert(0, src)
    try:
        import repro.serve  # noqa: F401
    except ImportError as e:
        fail(f"the repository's sources are not beside this script: {e}")
    device = run_four_chips() if args.four_chips else run_one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
