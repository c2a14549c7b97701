"""Serving engines: static-slot batching and continuous batching over a
paged KV cache.

Two engines share the model zoo and the softermax sampling head:

* ``ServeEngine`` — the original static-slot engine: one jitted prefill +
  decode closure over a contiguous ``(max_batch, max_len)`` cache; a batch of
  prompts runs to completion together. Every model family works here
  (decoder-only LMs directly; whisper composes the step functions itself).
  Kept as the general-purpose fallback and as the baseline the throughput
  benchmark measures against.

* ``ContinuousEngine`` — the production path for attention-family LMs:
  per-request admission from a FIFO (``serve/scheduler.py``), KV in
  fixed-size physical blocks from a shared pool (``serve/kv_pool.py``),
  decode as ONE fused step over the whole running batch through per-request
  block tables (``serve/paged_step.py`` → ``kernels/flash_decode_paged``).
  Requests join the fused decode batch within the same step() as their
  prefill and leave the moment they finish, returning their blocks to the
  pool; when the pool runs dry, unreferenced prefix-cache blocks are evicted
  first and only then is the youngest request preempted and recomputed
  later. A radix-tree prefix cache (``serve/radix_cache.py``, on by
  default) shares prompt-prefix KV blocks between requests: admission
  charges only the uncached suffix, prefill runs offset-aware from the
  first uncached token, and finished requests release their prompt blocks
  — and their drained generated tokens — back to the tree, so multi-turn
  conversations readmit as near-full hits. Prompts prefill in chunks of
  ``prefill_chunk`` tokens through the flash-prefill kernel
  (``kernels/flash_prefill_paged``), the one prefill path: one chunk per
  request per step, interleaved with decode steps (``prefill_budget`` caps
  the *total* chunk tokens dealt per step across requests), each chunk
  attending the cached prefix and every earlier chunk directly out of the
  pool — no quadratic score matrix, no per-layer prefix gather. With
  ``kv_dtype="int8"`` (the default when ``cfg.opt_int8_kv`` is set) the
  pool stores K/V as int8 with per-row scales — half the gather bytes,
  ~2x the tokens at equal HBM — quantizing on scatter and dequantizing
  inside the paged kernels, fp32 accumulation throughout. ``submit()``
  enqueues, ``step()`` advances the world one iteration and reports freshly
  decoded tokens per request (streaming), ``run()`` drives to completion and
  returns per-request results plus throughput/latency metrics.

Softermax is load-bearing in both: decode attention is the paper's
Unnormed-Softmax-Unit recurrence (running IntMax + power-of-two rescales),
which is what lets the paged engine visit cache blocks in table order with
no pre-pass, and the serve-time logits softmax runs through the base-2 form.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels.flash_decode_paged.ref import (decode_tile_blocks,
                                                  split_layout)
from repro.core.softermax import softmax_base2
from repro.models.registry import model_fns
from repro.serve.autotune import (AUTOTUNE_MODES, GridPlanner,
                                  default_candidates)
from repro.serve.faults import FAULT_REQ, FaultInjector, TransientFault
from repro.serve.guard import (EngineGuard, EngineSheddingError,
                               GuardSignals)
from repro.serve.kernel_costs import decode_launch_cost, prefill_launch_cost
from repro.serve.kv_pool import PagedKVCache, PoolExhausted
from repro.serve.paged_step import (check_paged_support, paged_decode_step,
                                    paged_prefill_chunked, scale_kwargs,
                                    table_width_bucket)
from repro.serve.radix_cache import RadixCache
from repro.serve.scheduler import (FINISH_DEADLINE, FINISH_QUARANTINED,
                                   PREFILL, Request, Scheduler)
from repro.serve.telemetry import Telemetry, span

# prompt tokens a prefill chunk computes when the caller names no size
PREFILL_CHUNK = 256


def _current_device():
    """The device new arrays land on: ``jax.default_device`` if one is
    in force, else the default backend's first device."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.devices()[0]
    return jax.devices(dev)[0] if isinstance(dev, str) else dev


def sample_tokens(lg: jax.Array, key, temperature: float,
                  cfg: ModelConfig) -> jax.Array:
    """Greedy or temperature sampling over the softermax distribution."""
    lg = lg[:, :cfg.vocab_size]     # drop TP vocab padding
    if temperature <= 0:
        return jnp.argmax(lg, axis=-1).astype(jnp.int32)
    p = softmax_base2(lg / temperature, fold_log2e=True)
    return jax.random.categorical(key, jnp.log(p + 1e-20)).astype(jnp.int32)


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray           # (B, max_new)
    steps: int


class ServeEngine:
    """Static-slot batch engine (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params, max_len: int = 512):
        self.cfg = cfg
        if cfg.opt_bf16_params:
            # cast matrix params ONCE at load — decode steps then run on the
            # resident bf16 copy (the in-step cast is an identity)
            from repro.models.lm import maybe_cast_params
            params = maybe_cast_params(params, cfg)
        self.params = params
        self.max_len = max_len
        self.fns = model_fns(cfg)
        self._decode = jax.jit(
            lambda p, t, c: self.fns.decode_step(p, t, c))
        self._prefill = jax.jit(
            lambda p, b: self.fns.prefill(p, b, max_len),
            static_argnames=())

    def _sample(self, lg: jax.Array, key, temperature: float) -> jax.Array:
        return sample_tokens(lg, key, temperature, self.cfg)

    def generate(self, prompts: np.ndarray, max_new: int,
                 temperature: float = 0.0, seed: int = 0) -> GenerateResult:
        """prompts: (B, S) int32 full-length prompts."""
        key = jax.random.PRNGKey(seed)
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        lg, cache = self._prefill(self.params, batch)
        out = []
        tok = self._sample(lg, key, temperature)
        out.append(tok)
        for i in range(max_new - 1):
            key, sub = jax.random.split(key)
            lg, cache = self._decode(self.params, tok, cache)
            tok = self._sample(lg, sub, temperature)
            out.append(tok)
        return GenerateResult(np.stack([np.asarray(t) for t in out], 1),
                              max_new)


# ---------------------------------------------------------------------------
# Continuous batching over the paged pool
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EngineMetrics:
    steps: int = 0
    decode_steps: int = 0
    prefills: int = 0
    prefill_chunks: int = 0      # chunked-prefill model steps run
    preemptions: int = 0
    tokens_out: int = 0          # tokens sampled (includes later-discarded)
    tokens_discarded: int = 0    # sampled but thrown away by preemption
    wall_s: float = 0.0
    peak_blocks: int = 0
    # pool capacity (constant per engine; int8 pools fit ~2x the tokens of
    # a bf16 pool at equal HBM — see PagedKVCache.bytes_per_block)
    kv_dtype: str = ""           # resolved storage dtype name
    #                              ("float32"/"bfloat16"/"int8")
    pool_token_capacity: int = 0     # num_blocks * block_size
    kv_pool_bytes: int = 0           # device bytes held by the pool arrays
    # prefix-cache counters (zero when the cache is disabled)
    prefill_tokens: int = 0      # prompt tokens actually run through prefill
    prefix_hit_tokens: int = 0   # prompt tokens reused from the radix tree
    cache_evictions: int = 0     # blocks evicted from the tree
    cow_copies: int = 0          # partial tail blocks copied on write
    shared_blocks_peak: int = 0  # peak blocks referenced by >1 owner
    # resilience counters (PR 8; zero when faults/guard/deadlines are off)
    cancelled: int = 0           # client cancellations honored
    deadline_misses: int = 0     # requests cancelled on deadline/TTFT breach
    quarantined: int = 0         # requests cancelled by the readback audit
    shed: int = 0                # submissions refused while SHEDDING
    faults_injected: int = 0     # injector firings (mirror of the log)
    transient_retries: int = 0   # TransientFaults absorbed by retry
    readback_audits: int = 0     # scatter-readback integrity audits run
    # where the step's time goes (cumulative, like the counters above)
    forced_syncs: int = 0        # device waits the engine made on its own:
    #                              a drain on a finishing step or before
    #                              host sampling (a caller's drain() is not
    #                              counted)
    decode_rows: int = 0         # occupied rows of every decode step, summed
    admit_blocked_steps: int = 0  # steps whose admission stopped at the
    #                               queue's head for want of pool blocks
    #                               while a batch row was free

    @property
    def tok_per_s(self) -> float:
        """Delivered-token throughput (discarded work doesn't count)."""
        kept = self.tokens_out - self.tokens_discarded
        return kept / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def prefill_savings(self) -> float:
        """Ratio of prompt tokens submitted to prompt tokens computed —
        the prefix cache's prefill-work reduction (1.0 = no reuse)."""
        total = self.prefill_tokens + self.prefix_hit_tokens
        return total / max(self.prefill_tokens, 1)


class ContinuousEngine:
    """Continuous batching + paged KV serving engine (attention LMs)."""

    def __init__(self, cfg: ModelConfig, params, *,
                 block_size: int = 16, num_blocks: int = 128,
                 max_batch: int = 8, max_len: int = 512,
                 max_admit_per_step: int = 2, seed: int = 0,
                 prefix_cache: bool = True, evict_policy: str = "lru",
                 prefill_chunk: int = PREFILL_CHUNK,
                 prefill_budget: int = 0,
                 kv_dtype: Optional[str] = None,
                 kv_tile_blocks: Optional[int] = None,
                 decode_split_k: int = 1,
                 autotune: str = "off",
                 autotune_candidates=None,
                 telemetry: Optional[Telemetry] = None,
                 clock: Optional[Callable[[], float]] = None,
                 faults: Optional[FaultInjector] = None,
                 guard: Optional[EngineGuard] = None,
                 deadline_s: Optional[float] = None,
                 ttft_budget_s: Optional[float] = None,
                 step_fault_retries: int = 3,
                 retry_backoff_s: float = 0.005):
        check_paged_support(cfg)
        self.cfg = cfg
        # Observability is strictly opt-in: with telemetry=None (default)
        # every hook site is one attribute load + None check. An attached
        # Telemetry shares its clock with the engine and scheduler (unless
        # ``clock`` overrides), so every lifecycle stamp — including
        # ManualClock test time — comes from one source.
        self.telemetry = telemetry
        self._clock: Callable[[], float] = clock or (
            telemetry.clock if telemetry is not None else time.monotonic)
        if cfg.opt_bf16_params:
            from repro.models.lm import maybe_cast_params
            params = maybe_cast_params(params, cfg)
        # An engine lives on the device current while it is built (a fleet
        # builds each replica under ``jax.default_device``): committing the
        # weights there pins every jitted step, and so the pool, to it.
        self.params = jax.device_put(params, _current_device())
        self.block_size = block_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.max_admit_per_step = max_admit_per_step
        # Prompts are computed ``prefill_chunk`` tokens at a time through
        # the flash-prefill kernel (one chunk per prefilling request per
        # step, interleaved with decode steps). The chunk is rounded up to
        # a block multiple so chunk boundaries and block boundaries line
        # up and every non-final chunk scatters whole rows, and is cut to
        # ``max_len`` rounded up the same way: no prompt needs more.
        if prefill_chunk <= 0:
            raise ValueError(f"prefill_chunk must be > 0, "
                             f"got {prefill_chunk}")
        self.prefill_chunk = -(-min(prefill_chunk, max_len) // block_size) \
            * block_size
        # Prefill token budget per step: caps the TOTAL chunk tokens dealt
        # across requests each step (not one chunk per request), so a herd
        # of concurrent long prompts can't crowd decode steps out. 0 = no
        # cap. The oldest prefilling request always advances regardless,
        # so prefill can never livelock.
        if prefill_budget < 0:
            raise ValueError(f"prefill_budget must be >= 0, "
                             f"got {prefill_budget}")
        self.prefill_budget = prefill_budget
        # Kernel grid knobs (layout, not math — every setting computes the
        # same attention; tiling preserves the visit order exactly, split-K
        # reassociates the partition sums within fp rounding, the rescales
        # staying exact power-of-two shifts): ``kv_tile_blocks`` pool
        # blocks are gathered per kv grid step of both paged kernels, and
        # decode's KV walk is partitioned across ``decode_split_k``
        # parallel lanes merged by the associative Softermax combine.
        # Without a tile, decode takes the one its pool's block shape
        # gives (``decode_tile_blocks``, resolved below) and the chunk
        # program keeps T = 1. Both only reach the Pallas kernels (TPU /
        # interpret_kernels); the CPU ref path ignores them. See
        # serve/README.md "Kernel grid & tiling".
        if (kv_tile_blocks is not None and kv_tile_blocks < 1) \
                or decode_split_k < 1:
            raise ValueError(
                f"kv_tile_blocks and decode_split_k must be >= 1, got "
                f"{kv_tile_blocks}/{decode_split_k}")
        self.decode_split_k = decode_split_k
        if autotune not in AUTOTUNE_MODES:
            raise ValueError(f"autotune must be one of {AUTOTUNE_MODES}, "
                             f"got {autotune!r}")
        self.autotune = autotune
        # KV pool storage: None/"auto" follow cfg.opt_int8_kv (the
        # --optimized serving path falls back to the compute dtype when the
        # flag is off); "bf16"/"int8" force that storage. Resolution lives
        # in PagedKVCache so direct pool construction agrees.
        self.pool = PagedKVCache(cfg, num_blocks, block_size,
                                 kv_dtype=kv_dtype or "auto")
        self.quantized = self.pool.quantized
        self.kv_tile_blocks = kv_tile_blocks or decode_tile_blocks(
            block_size, cfg.n_kv_heads, cfg.head_dim_,
            jnp.dtype(self.pool.kv_dtype).itemsize)
        self._prefill_tile = kv_tile_blocks or 1
        self.prefix_cache = (RadixCache(self.pool, evict_policy)
                             if prefix_cache else None)
        self.sched = Scheduler(self.pool, max_batch, max_len,
                               cache=self.prefix_cache, clock=self._clock)
        self.nb_max = -(-max_len // block_size)
        # Resilience layer (serve/faults.py, serve/guard.py): both nullable
        # hooks following the telemetry pattern. Engine-level defaults for
        # per-request deadlines apply to every submit() without explicit
        # budgets; TransientFaults are absorbed by bounded exponential
        # retry (step_fault_retries attempts, retry_backoff_s base delay —
        # the backoff sleeps through ManualClock.advance when the clock
        # supports it, keeping fault tests deterministic).
        self.guard = guard
        self.default_deadline_s = deadline_s
        self.default_ttft_budget_s = ttft_budget_s
        if step_fault_retries < 0 or retry_backoff_s < 0:
            raise ValueError("step_fault_retries and retry_backoff_s "
                             "must be >= 0")
        self.step_fault_retries = step_fault_retries
        self.retry_backoff_s = retry_backoff_s
        self.faults: Optional[FaultInjector] = None
        self._fault_pressure_blocks = 0   # blocks held under FAULT_REQ
        self._step_logit_err = 0.0        # max audited error this step
        if faults is not None:
            self.attach_faults(faults)
        # Kernel grid autotuning (serve/autotune.py): "static" consults
        # the analytic cost model once, here, on the worst-case batch
        # (every row at max_len) and rebinds the grid knobs; "per-step"
        # keeps a live planner that re-ranks the warmed candidate grids
        # from each decode step's actual lengths vector. Either way the
        # candidate set is closed at construction — serving never
        # compiles a grid warmup didn't see.
        self.planner: Optional[GridPlanner] = None
        if autotune != "off":
            self.planner = GridPlanner(
                autotune_candidates
                or default_candidates(self.kv_tile_blocks, decode_split_k),
                n_q_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim, block_size=block_size,
                kv_dtype=self.pool.kv_dtype,
                registry=telemetry.registry if telemetry else None)
            if autotune == "static":
                dec = self.planner.plan_decode(
                    np.full((max_batch,), max_len, np.int64),
                    table_width_bucket(self.nb_max, nb_max=self.nb_max))
                self.kv_tile_blocks = self._prefill_tile = dec.kv_tile_blocks
                self.decode_split_k = dec.split_k
        # Telemetry-path decode LaunchCost memo. Exact: the kernel attends
        # lengths+1, and every cost term depends on a row only through
        # q = len // block_size (ceil((len+1)/BS) = q+1 and
        # ceil((len+1)/(T*BS)) = q//T + 1), so keying on the q-vector is
        # lossless and hits on every step that crosses no block boundary.
        self._cost_cache: Dict[tuple, object] = {}
        self.metrics = self._fresh_metrics()
        self._key = jax.random.PRNGKey(seed)
        # Decode batch rows are STABLE: a request keeps its row from
        # admission to eviction, and vacated rows idle as harmless zombies
        # (length 0, garbage block 0) until reused. That makes the sampled
        # (B,) token vector of step N directly the input of step N+1 — no
        # recomposition, no host sync in the decode loop. Token values are
        # materialized lazily (drain).
        self._rows: List[Optional[Request]] = [None] * max_batch
        self._vec = jnp.zeros((max_batch,), jnp.int32)
        self._pending: List = []     # [(device vector, [(req, epoch, row)])]

        # The pool travels through every jitted step as a trailing *pools
        # group — (k, v) for bf16/f32 storage, (k, v, k_scale, v_scale) for
        # int8 — so the engine's call sites are mode-agnostic: they splat
        # ``self._pools()`` in and rebind whatever comes back.
        np_ = 4 if self.quantized else 2

        # greedy argmax is fused into the jitted steps so the common
        # (temperature 0) path never materializes logits on the host
        def _amax(lg):
            return jnp.argmax(lg[:, :cfg.vocab_size], -1).astype(jnp.int32)

        # grid knobs are trace-time constants: static kwargs of the jit,
        # so the per-step planner can swap grids without retracing tricks
        # — each (tile, split, table-width) lands in its own cache entry,
        # all of which warmup() pre-compiles when autotuning is on
        def _decode_fn(p, t, bt, ln, *pools, tile=None, split=1):
            out = paged_decode_step(p, t, pools[0], pools[1], bt, ln, cfg,
                                    kv_tile_blocks=tile,
                                    decode_split_k=split,
                                    **scale_kwargs(pools))
            return (_amax(out[0]), out[0]) + tuple(out[1:])

        def _prefill_chunk_fn(p, t, pos0, last_rel, pt, blk, off, *pools):
            out = paged_prefill_chunked(p, t, pos0, last_rel, pools[0],
                                        pools[1], pt, blk, off, cfg,
                                        kv_tile_blocks=self._prefill_tile,
                                        **scale_kwargs(pools))
            return (_amax(out[0]), out[0]) + tuple(out[1:])

        # On accelerators, donate the pools: they are rebound to the returned
        # arrays every call, so the update aliases in-place instead of
        # holding 2x pool memory. On CPU donation serializes dispatch and
        # breaks the async decode pipeline (~4x slower steps) — skip it.
        def _donate(first):
            if jax.default_backend() == "cpu":
                return ()
            return tuple(range(first, first + np_))

        self._prefill_chunk_fn = jax.jit(_prefill_chunk_fn,
                                         donate_argnums=_donate(7))
        self._decode = jax.jit(_decode_fn, donate_argnums=_donate(4),
                               static_argnames=("tile", "split"))

    # -- public API -------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int,
               temperature: float = 0.0,
               req_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               ttft_budget_s: Optional[float] = None,
               t_submit: Optional[float] = None,
               ttft_observed: bool = False) -> Request:
        """Enqueue one request; returns its (streaming) Request handle.
        ``deadline_s``/``ttft_budget_s`` override the engine defaults
        (None = engine default; the engine cancels on breach). While the
        guard is SHEDDING this raises ``EngineSheddingError`` — the
        degradation ladder's front door (counted in
        ``requests_shed_total``) — carrying the guard's
        ``retry_after_steps`` backoff hint. ``t_submit``/``ttft_observed``
        are the fleet-failover migration stamps (see Scheduler.submit)."""
        if self.guard is not None and not self.guard.submit_allowed():
            self.metrics.shed += 1
            if self.telemetry is not None:
                self.telemetry.on_shed()
            hint = self.guard.retry_after_steps()
            raise EngineSheddingError(
                "engine is shedding load (guard state: "
                f"{self.guard.state}; reason: {self.guard.last_reason}) — "
                f"retry after >= {hint} clean steps",
                retry_after_steps=hint)
        req = self.sched.submit(
            np.asarray(prompt, np.int32), max_new, temperature, req_id,
            deadline_s=(deadline_s if deadline_s is not None
                        else self.default_deadline_s),
            ttft_budget_s=(ttft_budget_s if ttft_budget_s is not None
                           else self.default_ttft_budget_s),
            t_submit=t_submit, ttft_observed=ttft_observed)
        if self.telemetry is not None:
            self.telemetry.on_submit(req)
        return req

    def cancel(self, req_id: int, reason: str = "cancelled") -> bool:
        """Client cancellation: terminate a queued or running request,
        freeing its blocks and radix pins mid-prefill or mid-decode. Safe
        against the async pipeline (the epoch bump staleness-guards any
        in-flight token vector). Idempotent — returns False when the id is
        not queued/running."""
        req = self.sched.cancel(req_id, reason)
        if req is None:
            return False
        self._sync_rows()
        self.metrics.cancelled += 1
        if reason == FINISH_DEADLINE:
            self.metrics.deadline_misses += 1
        if self.telemetry is not None:
            self.telemetry.on_cancel(req, reason)
        return True

    def attach_faults(self, faults: Optional[FaultInjector]) -> None:
        """Thread the fault injector through engine, scheduler, and pool
        (one nullable hook each). Attach AFTER ``warmup()`` — warmup's
        synthetic steps would otherwise consume the plan's step indices."""
        self.faults = faults
        self.sched.faults = faults
        self.pool.faults = faults

    def warmup(self) -> None:
        """Take the greedy serving path's compiles out of serving latency:
        jit shapes first (the chunk program per chunk-table-width bucket,
        decode per table-width bucket; writes only into the reserved
        garbage block), then a synthetic mini-workload through the real
        submit/step path so the one-time eager-op compiles (token fetches,
        host→device converts) happen now too; with the prefix cache on,
        the synthetic prompts share prefixes, so the copy-on-write path
        compiles as well. The cache is flushed afterwards. Temperature-
        sampled requests use eager host-side sampling whose small one-time
        compiles are not covered. Call once before serving traffic."""
        if self.sched.has_work():
            raise RuntimeError(
                "warmup() must run before any requests are submitted "
                "(its synthetic workload would consume and discard them)")
        zeros = jnp.zeros
        # the chunk step once per table-width bucket (all writes land in
        # the reserved garbage block 0; inputs are shape-only — wide
        # tables with pos0=0 break the split-path table contract, so
        # outputs are garbage, but they are finite and discarded)
        C = self.prefill_chunk
        cq = C // self.block_size
        # exactly the serve-time bucket set: every cover width any
        # in-range request can produce, through the one shared policy
        widths = sorted({table_width_bucket(n, chunk_blocks=cq)
                         for n in range(1, self.nb_max + 1)})
        for w in widths:
            _, _, *pools = self._prefill_chunk_fn(
                self.params, zeros((1, C), jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray([C - 1], jnp.int32),
                zeros((1, w), jnp.int32),
                zeros((C,), jnp.int32), zeros((C,), jnp.int32),
                *self._pools())
            self._set_pools(pools)
        # per-step autotuning picks among these exact entries at serve
        # time, so the whole candidate × width grid compiles here — the
        # planner never triggers a mid-serve compile
        grids = (self.planner.candidates
                 if self.planner is not None and self.autotune == "per-step"
                 else ((self.kv_tile_blocks, self.decode_split_k),))
        for w in sorted({table_width_bucket(n, nb_max=self.nb_max)
                         for n in range(1, self.nb_max + 1)}):
            for (ti, sp) in grids:
                _, _, *pools = self._decode(
                    self.params, zeros((self.max_batch,), jnp.int32),
                    zeros((self.max_batch, w), jnp.int32),
                    zeros((self.max_batch,), jnp.int32), *self._pools(),
                    tile=ti, split=sp)
                self._set_pools(pools)

        bs = self.block_size
        for nb in range(1, self.nb_max + 1):
            plen = (nb - 1) * bs + 1
            try:
                self.submit(np.ones((plen,), np.int32), 2)
            except ValueError:
                break                      # trajectory exceeds max_len/pool
        while self.sched.has_work():
            self.step()
        # the synthetic workload's allocations shouldn't show up in the
        # serving stats (notably peak_in_use → metrics.peak_blocks), and
        # its prompts shouldn't linger in the prefix cache
        self.reset()

    def reset(self) -> None:
        """Zero every engine-side aggregate coherently — EngineMetrics,
        PoolStats, CacheStats, scheduler counters, the finished set, and
        any attached telemetry — so a run/reset/run sequence reports the
        second run exactly as a fresh engine would (the run/reset/re-run
        equality test pins this). The prefix-cache *tree* is flushed too:
        keeping cached KV while zeroing hit counters would make the second
        run's stats incoherent with its actual work. Refuses to run with
        requests in flight."""
        if self.sched.has_work():
            raise RuntimeError("reset() with requests queued or running")
        self.drain()
        # vacate the decode rows and zero the on-device token vector:
        # no running requests means every row is a zombie, and a stale
        # Request reference (or pending vector) surviving reset would
        # leak the previous run's objects into the next one
        self._rows = [None] * self.max_batch
        self._vec = jnp.zeros((self.max_batch,), jnp.int32)
        self._pending.clear()
        self._release_pool_pressure()    # injector-held blocks go back
        if self.faults is not None:
            self.faults.reset()
        if self.guard is not None:
            self.guard.reset()
        self.sched.finished.clear()
        self.sched.n_preemptions = 0
        self.sched.n_admit_blocked = 0
        self.sched.tokens_discarded = 0
        self.metrics = self._fresh_metrics()
        if self.prefix_cache is not None:
            from repro.serve.radix_cache import CacheStats
            self.prefix_cache.reset()
            self.prefix_cache.stats = CacheStats()
        from repro.serve.kv_pool import PoolStats
        self.pool.stats = PoolStats(self.pool.num_blocks)
        if self.telemetry is not None:
            self.telemetry.reset()

    def step(self) -> Dict[int, List[int]]:
        """Advance the world one iteration: admit+prefill (one *chunk* per
        prefilling request — long prompts never stall in-flight decodes),
        join, one fused decode step,
        evict. Returns {req_id: fresh tokens} — temperature-sampled tokens
        appear here each step; greedy tokens normally stay on device until
        ``drain()`` (``run(on_token=...)`` drains every step for
        streaming), EXCEPT that with a prefix cache attached (the default)
        a step on which some request finishes drains the whole pipeline —
        the finishing request's generated tokens are published to the
        radix tree, which needs their values — so drained greedy tokens
        land in that step's events."""
        with span("serve.step", step_num=self.metrics.steps):
            return self._step()

    def _step(self) -> Dict[int, List[int]]:
        tel = self.telemetry
        inj = self.faults
        t0 = self._clock()
        events: Dict[int, List[int]] = {}
        self._step_logit_err = 0.0
        if inj is not None:
            inj.begin_step(self.metrics.steps, telemetry=tel)
            self._apply_fault_front(inj, tel)
        with span("serve.admit"):
            self._enforce_deadlines()
            self._sync_rows()
            max_admit: Optional[int] = self.max_admit_per_step
            budget = self.prefill_budget
            if self.guard is not None:
                max_admit = self.guard.effective_max_admit(
                    max_admit if max_admit is not None else self.max_batch)
                budget = self.guard.effective_prefill_budget(budget)
            admitted = self.sched.admit(max_admit)
            if tel is not None:
                for req in admitted:
                    tel.on_admit(req)
            # admitted requests stay PREFILL; prefilling requests advance
            # one chunk each, oldest first, until the per-step prefill
            # token budget (if any) is spent — decodes keep their share of
            # every step even under a herd of long prompts
            todo = self.sched.chunk_schedule(self.prefill_chunk, budget)
        for req in todo:
            self._do_prefill_chunk(req, events)
        self._drain_if_finishing(events)
        self._evict_finished(tel)                # max_new == 1 requests

        before_discard = self.sched.tokens_discarded
        preempted = self._with_retry(self.sched.ensure_decode_blocks)
        self.metrics.preemptions += len(preempted)
        self.metrics.tokens_discarded += \
            self.sched.tokens_discarded - before_discard
        if tel is not None:
            for req in preempted:
                tel.on_preempt(req)
        self._sync_rows()
        if any(r.state != PREFILL for r in self.sched.running):
            self._do_decode_step(events)
            self._drain_if_finishing(events)
            self._evict_finished(tel)

        self.metrics.steps += 1
        if inj is not None:
            self.metrics.faults_injected = inj.faults_injected
        dt = self._clock() - t0
        self.metrics.wall_s += dt
        self.metrics.peak_blocks = self.pool.stats.peak_in_use
        self.metrics.shared_blocks_peak = self.pool.stats.peak_shared
        self.metrics.cow_copies = self.pool.stats.cow_copies
        self.metrics.admit_blocked_steps = self.sched.n_admit_blocked
        if self.prefix_cache is not None:
            self.metrics.cache_evictions = self.prefix_cache.stats.evictions
        if self.guard is not None:
            self._observe_guard(t0, dt, tel)
        if tel is not None:
            tel.on_step_end(self, t0, dt)
        return events

    def _evict_finished(self, tel: Optional[Telemetry]) -> None:
        with span("serve.evict"):
            for req in self.sched.evict_finished():
                if tel is not None:
                    tel.on_finish(req)

    def _sync_rows(self) -> None:
        """Vacate rows whose request left the running set (finished or
        preempted); the row idles as a zombie until reassigned."""
        live = {id(r) for r in self.sched.running}
        for i, r in enumerate(self._rows):
            if r is not None and id(r) not in live:
                self._rows[i] = None

    # -- resilience internals (faults / guard / deadlines) ----------------

    def _sleep(self, dt: float) -> None:
        """Clock-aware sleep: ManualClock advances (deterministic tests),
        a real clock sleeps for real (injected stalls cost real time)."""
        if dt <= 0:
            return
        adv = getattr(self._clock, "advance", None)
        if adv is not None:
            adv(dt)
        else:
            time.sleep(dt)

    def _with_retry(self, fn):
        """Bounded retry-with-backoff around a step phase that can raise
        ``TransientFault`` (injected or real). The wrapped phases are
        idempotent (``ensure_decode_blocks`` skips requests whose table
        already grew), so re-entry after a partial pass is safe."""
        delay = self.retry_backoff_s
        for attempt in range(self.step_fault_retries + 1):
            try:
                return fn()
            except TransientFault:
                if attempt >= self.step_fault_retries:
                    raise
                self.metrics.transient_retries += 1
                if self.telemetry is not None:
                    self.telemetry.on_retry()
                self._sleep(delay)
                delay *= 2

    def _apply_fault_front(self, inj: FaultInjector, tel) -> None:
        """The injections that hit at the top of a step: pool pressure,
        stalls, preemption storms, and the step-level transient fault."""
        # pool pressure: steal free blocks under the FAULT_REQ sentinel so
        # admission back-off, cache eviction, and preemption all feel REAL
        # scarcity through their normal paths; released when the window
        # closes (target 0)
        want = inj.pool_pressure_target(self.pool.num_blocks)
        if want > self._fault_pressure_blocks:
            take = min(want - self._fault_pressure_blocks,
                       self.pool.num_free)
            if take > 0:
                self.pool.alloc(FAULT_REQ, take)
                self._fault_pressure_blocks += take
        elif want == 0:
            self._release_pool_pressure()
        stall = inj.stall_seconds()
        if stall > 0:
            self._sleep(stall)
        n_storm = inj.preempt_storm_count()
        if n_storm:
            before_discard = self.sched.tokens_discarded
            victims = self.sched.force_preempt(n_storm)
            self.metrics.preemptions += len(victims)
            self.metrics.tokens_discarded += \
                self.sched.tokens_discarded - before_discard
            if victims:
                inj.record("preempt_storm_victims", step=inj.step_idx,
                           req_ids=[v.req_id for v in victims])
            if tel is not None:
                for v in victims:
                    tel.on_preempt(v)
        self._with_retry(inj.check_step_fault)

    def _release_pool_pressure(self) -> None:
        if self._fault_pressure_blocks > 0:
            self.pool.free(FAULT_REQ)
            self._fault_pressure_blocks = 0

    def _enforce_deadlines(self) -> None:
        """Cancel queued/running requests past their deadline or TTFT
        budget (reason "deadline"; counted in deadline_misses_total)."""
        now = self._clock()
        overdue = [r for r in
                   list(self.sched.waiting) + list(self.sched.running)
                   if (r.deadline_s is not None and
                       now - r.t_submit >= r.deadline_s) or
                      (r.ttft_budget_s is not None and
                       r.t_first_token == 0.0 and
                       now - r.t_submit >= r.ttft_budget_s)]
        for req in overdue:
            self.cancel(req.req_id, FINISH_DEADLINE)

    def _observe_guard(self, t0: float, dt: float, tel) -> None:
        """Assemble this step's ``GuardSignals`` from the live PR 6/7
        surfaces and advance the degradation ladder."""
        now = self._clock()
        waiting = self.sched.waiting
        queue_wait = max((now - r.t_submit for r in waiting), default=0.0)
        spike = self.faults.numerics_spike() if self.faults is not None \
            else 0.0
        err = max(self._step_logit_err, spike)
        if tel is not None and err > 0:
            tel.registry.gauge(
                "numerics_logit_error",
                "latest probe's max |full - int8| logit delta").set(err)
        sig = GuardSignals(pool_util=self.pool.utilization,
                           logit_error=err,
                           queue_wait=queue_wait,
                           queue_depth=len(waiting),
                           step_seconds=dt)
        change = self.guard.observe(sig, step=self.metrics.steps)
        if change is not None and tel is not None:
            tel.on_guard(*change, step=self.metrics.steps)
        elif tel is not None:
            tel.g_guard_state.set(float(self.guard.level))

    def _quarantine(self, req: Request, err: float) -> None:
        """The audited logit error of ``req``'s freshly scattered KV
        exceeded the quarantine bound: purge every tree node its blocks
        back (so no later prefix hit serves poisoned KV) and cancel the
        request. Runs right after join, before any decode step consumed
        the bad state."""
        if self.prefix_cache is not None:
            purged = self.prefix_cache.purge(req.req_id)
        else:
            purged = 0
        self.metrics.quarantined += 1
        if self.faults is not None:
            self.faults.record("quarantine", step=self.faults.step_idx,
                               req_id=req.req_id, logit_error=err,
                               purged_nodes=purged)
        self.cancel(req.req_id, FINISH_QUARANTINED)

    def _corrupt_request_blocks(self, req: Request) -> None:
        """kv_corrupt landing site: flip the payload of every block ONLY
        this request owns (refcount 1 — shared prefix blocks belong to
        other owners and the tree; the fault models a bad scatter of THIS
        request's fresh rows)."""
        blocks = [b for b in self.pool.blocks_of(req.req_id)
                  if self.pool.refcount(b) == 1]
        for b in blocks:
            self.pool.corrupt_block(b)
        self.faults.record("kv_corrupt", step=self.faults.step_idx,
                           req_id=req.req_id, blocks=blocks)
        if self.telemetry is not None:
            self.telemetry.on_fault("kv_corrupt_hit", self.faults.step_idx,
                                    req_id=req.req_id)

    def _readback_audit(self, req: Request, lg) -> float:
        """Scatter-readback KV-integrity audit: recompute the final prompt
        token's logits as a one-token chunk that READS every prompt row,
        its own included, out of the just-written blocks, and compare
        against the prefill's own final logits. Clean pools agree to
        within rounding; corrupted blocks produce a large delta →
        quarantine. Returns the max-abs logit delta (0.0 when the prompt
        is too short to audit)."""
        plen = req.prompt_len
        m = plen - 1
        if m < 1:
            return 0.0
        bs, C = self.block_size, self.prefill_chunk
        tokens = np.zeros((1, C), np.int32)
        tokens[0, 0] = req.prompt[m]
        cover = -(-plen // bs)
        pt = np.zeros((1, table_width_bucket(cover, chunk_blocks=C // bs)),
                      np.int32)
        pt[0, :cover] = self.pool.blocks_of(req.req_id)[:cover]
        # every row's write goes to garbage block 0: the audit leaves the
        # request's blocks as they are
        rows = jnp.zeros((C,), jnp.int32)
        _, lg2, *pools = self._prefill_chunk_fn(
            self.params, jnp.asarray(tokens), jnp.asarray(m, jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray(pt), rows, rows,
            *self._pools())
        self._set_pools(pools)
        V = self.cfg.vocab_size
        err = float(jnp.max(jnp.abs(lg2[:, :V] - lg[:, :V])))
        self.metrics.readback_audits += 1
        self._step_logit_err = max(self._step_logit_err, err)
        if self.telemetry is not None:
            self.telemetry.on_readback(req, err)
        return err

    def _audit_and_quarantine(self, req: Request, lg) -> None:
        """Post-join integrity pass: run the readback audit when the guard
        asks for it and quarantine on a bound breach."""
        g = self.guard
        if g is None or not g.config.readback_audit:
            return
        err = self._readback_audit(req, lg)
        if g.should_quarantine(err):
            self._quarantine(req, err)

    def drain(self, cause: str = "caller") -> Dict[int, List[int]]:
        """Materialize every in-flight sampled-token vector into its
        request's ``tokens`` list. Returns {req_id: fresh tokens}.
        ``cause`` names who waits: ``"caller"``, or the engine itself —
        ``"finish"`` (a step on which a request finishes) or ``"sample"``
        (host sampling needs the tokens); the engine's own waits count in
        ``metrics.forced_syncs``."""
        events: Dict[int, List[int]] = {}
        n = len(self._pending)
        if not n:
            return events
        if cause != "caller":
            self.metrics.forced_syncs += 1
        tel = self.telemetry
        t = self._clock() if tel is not None else 0.0
        with span("serve.sync", cause=cause):
            # host↔device sync point
            arrs = [np.asarray(vec) for vec, _ in self._pending]
        for arr, (_, rows) in zip(arrs, self._pending):
            for req, epoch, row in rows:
                if req.epoch == epoch:           # not preempted since
                    tok = int(arr[row])
                    req.tokens.append(tok)
                    events.setdefault(req.req_id, []).append(tok)
        self._pending.clear()
        if tel is not None:
            tel.on_drain(t, self._clock() - t, n)
        return events

    def _drain_if_finishing(self, events: Dict[int, List[int]]) -> None:
        """With a prefix cache attached, finished requests publish their
        *generated* tokens to the radix tree — which needs the token
        values. Materialize the async pipeline on steps where something is
        about to finish (the sync is confined to those steps)."""
        if self.prefix_cache is None or not self._pending:
            return
        if any(r.done for r in self.sched.running):
            for rid, toks in self.drain("finish").items():
                events.setdefault(rid, []).extend(toks)

    def run(self, on_token: Optional[Callable[[int, List[int]], None]] = None
            ) -> Dict[int, Request]:
        """Drive until every submitted request has finished. With an
        ``on_token`` callback, tokens are drained (synced) every step for
        low-latency streaming; without one the pipeline stays async (host
        syncs only for temperature sampling) and drains once at the end.
        In-flight vectors are (max_batch,) int32 — negligible to hold.
        ``metrics.wall_s`` is set to the true wall time of the drive,
        including the final drain (step() alone accumulates only host
        dispatch time, which understates async greedy work)."""
        t0 = self._clock()
        w0 = self.metrics.wall_s     # replace this run's per-step dispatch
        #                              times with its true wall time, while
        #                              staying cumulative across runs
        while self.sched.has_work():
            events = self.step()
            if on_token:
                for rid, toks in self.drain().items():
                    events.setdefault(rid, []).extend(toks)
                for rid, toks in events.items():
                    on_token(rid, toks)
        self.drain()
        self.metrics.wall_s = w0 + (self._clock() - t0)
        return self.pop_finished()

    def pop_finished(self) -> Dict[int, Request]:
        """Return-and-clear the finished set. Keeps a long-lived engine from
        accumulating every completed Request, and keeps consecutive run()
        calls from re-reporting earlier runs' results."""
        done = dict(self.sched.finished)
        self.sched.finished.clear()
        return done

    # -- internals --------------------------------------------------------

    def _fresh_metrics(self) -> EngineMetrics:
        """Zeroed counters with the engine-constant pool-capacity fields
        pre-stamped (valid before the first step, survive warmup's
        reset)."""
        return EngineMetrics(kv_dtype=self.pool.kv_dtype,
                             pool_token_capacity=self.pool.token_capacity,
                             kv_pool_bytes=self.pool.hbm_bytes)

    def _pools(self):
        """The pool arrays as the jitted steps' trailing *pools group."""
        if self.quantized:
            return (self.pool.k, self.pool.v, self.pool.k_scale,
                    self.pool.v_scale)
        return (self.pool.k, self.pool.v)

    def _set_pools(self, pools) -> None:
        if self.quantized:
            (self.pool.k, self.pool.v, self.pool.k_scale,
             self.pool.v_scale) = pools
        else:
            self.pool.k, self.pool.v = pools

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _do_prefill_chunk(self, req: Request,
                          events: Dict[int, List[int]]) -> None:
        """Advance one prefilling request by one chunk: compute + scatter
        ``prefill_chunk`` prompt tokens through the flash-prefill step (the
        chunk attends the cached prefix and every earlier chunk straight
        out of the pool). The final chunk's last-token logits seed decoding
        and the request joins the fused batch."""
        tel = self.telemetry
        t = self._clock() if tel is not None else 0.0
        bs = self.block_size
        C = self.prefill_chunk
        m, sl = self.sched.next_chunk(req, C)
        if m == req.n_prefix_hit:        # first chunk of this admission
            self.metrics.prefix_hit_tokens += m
        cover = -(-(m + sl) // bs)       # blocks holding positions < m+sl
        # chunk tables bucket to multiples of the chunk's own block count
        # (not pow2) — see table_width_bucket for why that bound is also
        # the paged_prefill_chunked table contract
        w = table_width_bucket(cover, chunk_blocks=C // bs)
        with span("serve.prefill_chunk", req=req.req_id, tokens=sl,
                  width=w):
            tokens = np.zeros((1, C), np.int32)
            tokens[0, :sl] = req.prompt[m:m + sl]
            table = np.asarray(self.pool.blocks_of(req.req_id), np.int32)
            pt = np.zeros((1, w), np.int32)
            pt[0, :cover] = table[:cover]
            pos = m + np.arange(C)
            blk = np.zeros((C,), np.int32)   # pad rows -> garbage block 0
            off = np.zeros((C,), np.int32)
            blk[:sl] = table[pos[:sl] // bs]
            off[:sl] = pos[:sl] % bs
            greedy, lg, *pools = self._prefill_chunk_fn(
                self.params, jnp.asarray(tokens), jnp.asarray(m, jnp.int32),
                jnp.asarray([sl - 1], jnp.int32), jnp.asarray(pt),
                jnp.asarray(blk), jnp.asarray(off), *self._pools())
            self._set_pools(pools)
            req.n_prefilled = m + sl
            self.metrics.prefill_tokens += sl
            self.metrics.prefill_chunks += 1
            if tel is not None:
                # modeled cost of the chunk's paged-prefill kernel launch
                # (per layer); pos0 = m, one row, real table cover = cover
                cost = prefill_launch_cost(
                    C, [m], [cover], w, n_q_heads=self.cfg.n_heads,
                    n_kv_heads=self.cfg.n_kv_heads,
                    head_dim=self.cfg.head_dim, block_size=self.block_size,
                    kv_tile_blocks=self._prefill_tile,
                    kv_dtype=self.pool.kv_dtype)
                tel.on_prefill(req, sl, w, t, self._clock() - t, cost=cost,
                               launches=self.cfg.n_layers)
            if req.n_prefilled == req.prompt_len:
                if self.faults is not None and self.faults.take_kv_corrupt():
                    self._corrupt_request_blocks(req)  # bad scatter, post hoc
                self._join_decode(req, greedy, lg, events)
                if tel is not None:
                    probe = tel.maybe_numerics_probe(self, req)
                    if probe:
                        self._step_logit_err = max(
                            self._step_logit_err,
                            float(probe.get("logit_error", 0.0)))
                self._audit_and_quarantine(req, lg)
            elif self.prefix_cache is not None:
                # publish completed chunks as they land — including a partial
                # tail block (its leaf is promoted in place by insert() once
                # later chunks fill the block, so no stale double-owner
                # survives) — so a request admitted while this long prompt is
                # still mid-prefill gets the maximal possible hit
                self.prefix_cache.insert(req.req_id,
                                         req.prompt[:req.n_prefilled])

    def _join_decode(self, req: Request, greedy, lg,
                     events: Dict[int, List[int]]) -> None:
        """Prefill completed: publish the prompt to the prefix cache,
        sample the first token from the final logits, and give the request
        a stable decode row."""
        if self.prefix_cache is not None:
            # publish the freshly computed prompt blocks right away so
            # requests admitted next step share with this in-flight one
            self.prefix_cache.insert(req.req_id, req.prompt)
        B = self.max_batch
        row = self._rows.index(None)     # guaranteed: running < max_batch
        self._rows[row] = req
        mask = np.zeros((B,), bool)
        mask[row] = True
        if req.temperature <= 0:
            # stays on device; materialized at the next drain
            self._pending.append((greedy, [(req, req.epoch, 0)]))
            self._vec = jnp.where(jnp.asarray(mask),
                                  jnp.broadcast_to(greedy, (B,)), self._vec)
        else:
            tok = int(sample_tokens(lg, self._next_key(), req.temperature,
                                    self.cfg)[0])
            req.tokens.append(tok)
            self._vec = jnp.where(jnp.asarray(mask),
                                  jnp.asarray(np.full((B,), tok, np.int32)),
                                  self._vec)
            events.setdefault(req.req_id, []).append(tok)
        req.n_generated = 1
        req.state = "decoding"
        # Dispatch-time stamp: exact when streaming (per-step drain keeps
        # the pipeline ≤1 step deep); optimistic by the pipeline depth for a
        # pure-async run() — t_finish (eviction) has the same convention,
        # so latencies stay internally consistent.
        req.t_first_token = self._clock()
        req.t_last_token = req.t_first_token
        self.metrics.prefills += 1
        self.metrics.tokens_out += 1
        if self.telemetry is not None:
            self.telemetry.on_first_token(req)

    def _pow2_bucket(self, need: int) -> int:
        """Decode table width via the stack-wide bucketing policy
        (``serve/paged_step.table_width_bucket``)."""
        return table_width_bucket(need, nb_max=self.nb_max)

    def _table_width(self, occ) -> int:
        """Decode block-table width covering the longest running request."""
        return self._pow2_bucket(
            max(self.pool.n_blocks_of(r.req_id) for _, r in occ))

    def _do_decode_step(self, events: Dict[int, List[int]]) -> None:
        tel = self.telemetry
        t = self._clock() if tel is not None else 0.0
        B = self.max_batch
        occ = [(i, r) for i, r in enumerate(self._rows) if r is not None]
        greedy_only = all(r.temperature <= 0 for _, r in occ)

        if greedy_only:
            tokens1 = self._vec          # previous step's vector, on device
        else:
            for rid, toks in self.drain("sample").items():
                events.setdefault(rid, []).extend(toks)
            t1 = np.zeros((B,), np.int32)
            for i, req in occ:
                t1[i] = req.tokens[-1]
            tokens1 = jnp.asarray(t1)

        w = self._table_width(occ)
        lengths = np.zeros((B,), np.int32)
        for i, req in occ:
            lengths[i] = req.n_cached
        # the kernel attends lengths+1 on every row (zombies included,
        # masked) — plan and account against what it actually does
        tile, split = self.kv_tile_blocks, self.decode_split_k
        plan = None
        if self.planner is not None and self.autotune == "per-step":
            plan = self.planner.plan_decode(lengths + 1, w)
            tile, split = plan.kv_tile_blocks, plan.split_k
        # the table's tiles: B rows x S*spl tiles of T blocks, of which
        # the kernel walks each row's first ceil((len+1)/(T*BS)); the
        # rest (grid_tiles - live_tiles) are the padded tiles it skips
        T, S, spl, _ = split_layout(w, tile, split)
        live = -(-(lengths + 1) // (T * self.block_size))
        with span("serve.decode", rows=len(occ), width=w, tile=T,
                  live_tiles=int(live.sum()), grid_tiles=B * S * spl):
            bt = np.zeros((B, w), np.int32)
            bt[[i for i, _ in occ]] = self.pool.table_array(
                [r.req_id for _, r in occ], w)
            greedy, lg, *pools = self._decode(
                self.params, tokens1, jnp.asarray(bt), jnp.asarray(lengths),
                *self._pools(), tile=tile, split=split)
            self._set_pools(pools)

        if greedy_only:
            # async: token values stay on device until drained; bookkeeping
            # (finish, block growth) is purely count-based
            self._vec = greedy
            self._pending.append(
                (greedy, [(r, r.epoch, i) for i, r in occ]))
            for _, req in occ:
                req.n_generated += 1
                req.n_cached += 1
        else:
            toks = self._sample_rows(lg, [
                self._rows[i].temperature if self._rows[i] else 0.0
                for i in range(B)], greedy)
            for i, req in occ:
                tok = int(toks[i])
                req.tokens.append(tok)
                req.n_generated += 1
                req.n_cached += 1
                events.setdefault(req.req_id, []).append(tok)
            self._vec = jnp.asarray(toks)
        self.metrics.decode_steps += 1
        self.metrics.decode_rows += len(occ)
        self.metrics.tokens_out += len(occ)
        if tel is not None:
            now = self._clock()
            if plan is not None:
                cost = plan.cost
            else:
                key = (w, tile, split,
                       (lengths // self.block_size).tobytes())
                cost = self._cost_cache.get(key)
                if cost is None:
                    if len(self._cost_cache) >= 4096:
                        self._cost_cache.clear()
                    cost = decode_launch_cost(
                        lengths + 1, w, n_q_heads=self.cfg.n_heads,
                        n_kv_heads=self.cfg.n_kv_heads,
                        head_dim=self.cfg.head_dim,
                        block_size=self.block_size,
                        kv_tile_blocks=tile, split_k=split,
                        kv_dtype=self.pool.kv_dtype)
                    self._cost_cache[key] = cost
            tel.on_decode_step(rows=len(occ), table_width=w, t_start=t,
                               dur=now - t, split_k=split,
                               kv_tile_blocks=tile, cost=cost,
                               launches=self.cfg.n_layers)
            if plan is not None:
                self.planner.observe_measured(plan, now - t)
            tel.on_decode_tokens([r for _, r in occ], now)

    def _sample_rows(self, lg: jax.Array, temps: List[float],
                     greedy_dev: Optional[jax.Array] = None) -> np.ndarray:
        """Per-row sampling; reuses the jit-fused argmax when provided."""
        lg = lg[:len(temps), :self.cfg.vocab_size]
        greedy = np.asarray(greedy_dev[:len(temps)] if greedy_dev is not None
                            else jnp.argmax(lg, axis=-1), np.int32)
        if all(t <= 0 for t in temps):
            return greedy
        tv = jnp.asarray([max(t, 1e-6) for t in temps], jnp.float32)
        p = softmax_base2(lg / tv[:, None], fold_log2e=True)
        samp = np.asarray(
            jax.random.categorical(self._next_key(), jnp.log(p + 1e-20)),
            np.int32)
        return np.where(np.asarray(temps) > 0, samp, greedy)
