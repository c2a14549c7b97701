"""Model step functions over the paged KV cache (attention-family LMs).

These mirror ``models/lm.py``'s forward but speak the block-pool layout
instead of a contiguous per-request cache. The serving engine runs two
programs, and both run the same transformer layer (``_layer``); they
differ only in how a layer writes its new K/V rows and attends:

* ``paged_prefill_chunked`` — one *chunk* of a prompt, the only prefill
  path: per layer the chunk's K/V rows are scattered into the pool first,
  then the chunk's queries attend the pool directly through the block
  table (``kernels/flash_prefill_paged``) — cached prefix, earlier
  chunks, and the chunk's own causal triangle are all one KV source, so
  nothing is gathered-and-concatenated and no score matrix ever exceeds
  (chunk, prefix+chunk). A chunk may start at any position, mid-block
  included (a radix-cache hit resumes after its copy-on-write tail). The
  online-normalization state is carried across KV tiles inside the
  kernel; across chunk boundaries no state is handed over at all —
  earlier chunks' contribution is pool-resident and the Softermax
  recurrence is order-free, so re-attending it online is exact.
* ``paged_decode_step`` — one token for the whole running batch: per layer,
  write the new K/V row through the block table, then run paged Softermax
  decode attention over the pool. Inactive batch slots carry block table 0
  (the pool's garbage block) and length 0, so their writes and reads are
  harmless and their logits are ignored by the engine.

``paged_prefill_audit`` is not a serving path: it is the telemetry
numerics probe, a dense full-precision vs int8-fake-quant forward of one
prompt that shares the layer's FFN half (``_ffn``).

Attention math is identical to the contiguous path (same Unnormed-Softmax-
Unit recurrence): on TPU / under ``cfg.interpret_kernels`` the Pallas
kernels run; elsewhere pure-JAX gather references keep CPU tests fast.

**Int8 pools (quantize-on-scatter).** Both step functions take the pool's
optional per-row scale tensors (``k_scale``/``v_scale``; ``None`` for
bf16/f32 pools — the pool dtype, static under jit, selects the path).
Writers quantize rows symmetrically per (head, token) at the moment they
scatter (the projections stay full precision); readers dequantize at
gather — fused into the Pallas kernels on TPU, post-gather in the refs —
and accumulate in fp32, so the only precision loss is the int8 rounding
of the stored K/V rows, the same contract as the dense
``models/attention.py`` int8 decode branch. Both return the new scale
tensors after the new pools (callers unpack by mode).

**The pool is updated in place.** Both programs carry the whole
``(L, N, Hkv, BS, Dh)`` pool (and its scale pools) through the layer
scan, scatter each layer's new rows at ``[layer, blk, :, off]`` into it,
and hand both kernels the whole pool with the layer index. Only the new
rows move: with the engine's donation the pool aliases from the
program's input to its output, and no program slices a layer out of it
or holds a second copy.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.numerics import NEG_INF
from repro.kernels.flash_decode_paged import flash_decode_paged_op
from repro.kernels.flash_prefill_paged import flash_prefill_paged_op
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.layers import embed, logits, mlp, rmsnorm, rope
from repro.models.lm import maybe_cast_params
from repro.parallel.sharding import shard_act


def check_paged_support(cfg: ModelConfig) -> None:
    """Paged serving covers the GQA attention families; everything else
    stays on the static engine."""
    if cfg.family not in ("dense", "moe"):
        raise ValueError(f"paged serving: unsupported family {cfg.family!r}")
    if cfg.mla is not None or cfg.ssm is not None:
        raise ValueError("paged serving: MLA/SSM caches not supported")
    if cfg.moe.first_dense:
        raise ValueError("paged serving: leading dense head blocks "
                         "not supported")
    if cfg.window:
        raise ValueError("paged serving: sliding-window archs not supported")


def table_width_bucket(need: int, *, nb_max: Optional[int] = None,
                       chunk_blocks: Optional[int] = None) -> int:
    """THE block-table width policy for the whole serving stack — engine
    decode tables, chunked-prefill covers, warmup shape enumeration,
    and the benches' engine-faithful replay all quantize through this one
    helper, so jit bucket counts stay bounded and the split-ref table
    contract lives in one place.

    * ``chunk_blocks`` set — chunked-prefill cover policy: round ``need``
      up to a multiple of the chunk's own block count (``nb_max`` is
      ignored — a cover never exceeds the request's own table). Bucket
      count stays bounded (max-table / chunk_blocks of them) AND the pad
      never exceeds the masked tail region the CPU split oracle assumes —
      this is exactly the ``paged_prefill_chunked`` table contract
      (``paged_prefill_split_ref``'s CONTRACT note), so changing the
      policy here is changing the contract.
    * otherwise — pow2 policy (decode tables): next
      power of two covering ``need``, clamped to ``nb_max`` (few buckets
      instead of every width; the clamp never truncates — any in-range
      table fits in ``nb_max`` blocks).
    """
    if chunk_blocks is not None:
        # a 0 here would silently fall through to the pow2 policy and
        # break the split-ref contract — fail loudly instead
        if chunk_blocks < 1:
            raise ValueError(f"chunk_blocks must be >= 1, "
                             f"got {chunk_blocks}")
        return -(-need // chunk_blocks) * chunk_blocks
    w = 1
    while w < need:
        w *= 2
    if nb_max is not None:
        w = max(min(w, nb_max), need)
    return w


# ---------------------------------------------------------------------------
# Numerics audit (the telemetry probe, not a serving path)
# ---------------------------------------------------------------------------


def _fake_quant_kv(t: jax.Array) -> jax.Array:
    """Round-trip ``t`` through the pool's int8 representation. Re-
    quantizing the result reproduces the exact same int8 codes (the amax
    row maps back to ±127 and the scale round-trips within ~2^-24, far
    inside round-to-nearest's 0.5 margin), so the audit's quantized branch
    attends bit-identical values to what every serving reader dequantizes
    from an int8 pool."""
    q8, sc = attn_mod.quantize_kv(t)
    return attn_mod.dequantize_kv(q8, sc, t.dtype)


def _audit_attention(q, k, v, intmax):
    """Dense causal softermax in closed form: same exp2 / running-IntMax
    recurrence as the serving kernels (order-free, so the closed form is
    exact), returning the per-row IntMax and raw scores for the numerics
    monitors alongside the output."""
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((Sq, Sq), bool))
    s = jnp.where(causal[None, None, None], s, NEG_INF)
    m = jnp.max(jnp.ceil(s) if intmax else s, axis=-1, keepdims=True)
    p = jnp.exp2(s - m)
    d = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", (p / d).astype(v.dtype), v)
    return (o.reshape(B, Hq, Sq, D).astype(q.dtype), m[..., 0],
            s, causal[None, None, None])


def paged_prefill_audit(
    params,
    tokens: jax.Array,       # (B, Sp) probe prompt (no padding expected)
    last_pos: jax.Array,     # (B,) int32 index of the true last token
    cfg: ModelConfig,
):
    """Lockstep full-precision vs int8-fake-quant prefill of one prompt —
    the online numerics monitor behind ``Telemetry.numerics_probe``.

    One scan carries *both* residual streams: the reference branch attends
    the exact projected K/V, the quantized branch round-trips each layer's
    K/V through the pool's int8 grid first (``_fake_quant_kv`` — bit-
    identical to what a later reader dequantizes from the pool), and both
    use the same dense Softermax closed form, so the returned logit delta
    isolates exactly the int8 storage error PR 4's offline bench bounds at
    ≤ 0.1. Along the way the quantized branch's scores and K/V feed the
    hardware-margin monitors:

    * ``score_intmax_max`` / ``intmax_overflow_rows`` — the running-IntMax
      values every Softermax row tracks vs the paper's Q(6,2) LocalMax
      format: an overflow row is one whose IntMax a real accumulator
      would saturate.
    * ``score_inp_clip_vals`` — causally-valid score entries outside the
      Q(6,2) Inp format (pre-normalization saturation).
    * ``kv_amax_max`` / ``kv_scale_sat_rows`` — per-(head, token) K/V amax
      vs a per-layer 99.999%-percentile static scale: rows a statically
      calibrated int8 pool (as opposed to our per-row scales) would clip.

    Returns ``(lg_ref, lg_q, stats)`` with logits (B, V-padded) and stats
    a dict of scalar jax arrays (summed/maxed over layers).
    """
    from repro.core.quant import (DEFAULT_BITWIDTHS, percentile_scale,
                                  qformat_clip_count)

    B, Sp = tokens.shape
    params = maybe_cast_params(params, cfg)
    dh = cfg.head_dim_
    premult, intmax = attn_mod._mode(cfg)
    qscale = premult * dh ** -0.5
    positions = jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32), (B, Sp))
    x0 = embed(params["embed"], tokens, cfg)
    fmt_max = DEFAULT_BITWIDTHS.localmax
    fmt_inp = DEFAULT_BITWIDTHS.inp

    def body(carry, bp):
        x_ref, x_q = carry
        # reference branch: exact K/V
        h = rmsnorm(bp["ln1"], x_ref, cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
        o, _, _, _ = _audit_attention(q * jnp.asarray(qscale, q.dtype),
                                      k, v, intmax)
        x_ref = _ffn(bp, x_ref + attn_mod._out_proj(bp["mixer"], o, cfg),
                     cfg)
        # quantized branch: K/V through the pool's int8 grid
        h = rmsnorm(bp["ln1"], x_q, cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
        amax_k = jnp.max(jnp.abs(k), axis=-1)         # (B, Hkv, Sp)
        amax_v = jnp.max(jnp.abs(v), axis=-1)
        kv_amax = jnp.maximum(jnp.max(amax_k), jnp.max(amax_v))
        sat = (jnp.sum(amax_k > 127.0 * percentile_scale(k)) +
               jnp.sum(amax_v > 127.0 * percentile_scale(v)))
        kq = _fake_quant_kv(k)
        vq = _fake_quant_kv(v)
        o, m, s, valid = _audit_attention(
            q * jnp.asarray(qscale, q.dtype), kq, vq, intmax)
        x_q = _ffn(bp, x_q + attn_mod._out_proj(bp["mixer"], o, cfg), cfg)
        stats = (jnp.max(m),
                 jnp.sum(m > fmt_max.max_value),
                 qformat_clip_count(s, fmt_inp,
                                    where=jnp.broadcast_to(valid, s.shape)),
                 kv_amax, sat)
        return (x_ref, x_q), stats

    (x_ref, x_q), (mx, ovf, clip, amax, sat) = jax.lax.scan(
        body, (x0, x0), params["blocks"])

    def head(x):
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        x_last = jnp.take_along_axis(
            x, last_pos[:, None, None].astype(jnp.int32), axis=1)
        return logits(params["embed"], x_last, cfg)[:, 0]

    stats = {
        "score_intmax_max": jnp.max(mx),
        "intmax_overflow_rows": jnp.sum(ovf),
        "score_inp_clip_vals": jnp.sum(clip),
        "kv_amax_max": jnp.max(amax),
        "kv_scale_sat_rows": jnp.sum(sat),
    }
    return head(x_ref), head(x_q), stats


# ---------------------------------------------------------------------------
# The layer both serving programs run
# ---------------------------------------------------------------------------


def _pool_tuple(k_pool, v_pool, k_scale, v_scale):
    """The pools a step carries through its layer scan: ``(k, v)``, or
    ``(k, v, k_scale, v_scale)`` for an int8 pool."""
    if k_pool.dtype == jnp.int8:
        return (k_pool, v_pool, k_scale, v_scale)
    return (k_pool, v_pool)


def _write_rows(pools, layer, blk, off, k, v):
    """Scatter new K/V rows ``(R, Hkv, Dh)`` into the carried pools of
    every layer, row ``r`` at ``[layer, blk[r], :, off[r]]``; with an int8
    pool each row is quantized against its own amax and its scales land
    at the same coordinates of the scale pools. Returns the new pools
    tuple. Inside the layer scan the pools are the carry, so XLA updates
    them in place: only the rows move, never a layer's slice."""
    h = jnp.arange(k.shape[1])
    at = (layer, blk[:, None], h[None, :], off[:, None])
    kp, vp = pools[:2]
    scales = ()
    if kp.dtype == jnp.int8:
        k, k_sc = attn_mod.quantize_kv(k)             # (R, Hkv) row scales
        v, v_sc = attn_mod.quantize_kv(v)
        scales = (pools[2].at[at].set(k_sc), pools[3].at[at].set(v_sc))
    return (kp.at[at].set(k.astype(kp.dtype)),
            vp.at[at].set(v.astype(vp.dtype))) + scales


def scale_kwargs(pools):
    """The ``k_scale``/``v_scale`` keywords of a pools tuple: ``(k, v)``,
    or ``(k, v, k_scale, v_scale)`` for an int8 pool."""
    return {"k_scale": pools[2], "v_scale": pools[3]} \
        if len(pools) == 4 else {}


def _ffn(bp, x, cfg: ModelConfig):
    """A layer's second half: rmsnorm, the dense MLP or the MoE
    (``cfg.family``), and the residual. ``x`` is (B, S, d) or (B, d)."""
    h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        # the router takes (B, S, d): decode rows ride as S = 1
        f, _ = moe_mod.moe_apply(
            bp["ffn"], h2.reshape(x.shape[0], -1, x.shape[-1]), cfg)
        f = f.reshape(x.shape)
    else:
        f = mlp(bp["ffn"], h2, cfg.activation)
    return x + f


def _layer(bp, x, pools, layer, positions, attend, cfg: ModelConfig):
    """One transformer layer of a serving program: ``x`` is (B, S, d) for
    a prompt chunk or (B, d) for the decode rows, at absolute
    ``positions`` (B, S) or (B,). rmsnorm, the Q/K/V projection with
    qk-norm and RoPE, then ``attend(q, k, v, pools, layer)`` — q pre-
    scaled (B, Hq, [S,] Dh), k/v (B, Hkv, [S,] Dh) — which writes the new
    K/V rows into the carried pools and returns ``(o, pools)``; the out
    projection and residual, and the FFN half. Returns ``(x, pools)``.
    The two programs differ only in ``attend``; the projections keep the
    seq axis only where there is one, so each program keeps its own dot
    layout."""
    dt = cfg.compute_dtype_
    mx = bp["mixer"]
    premult, _ = attn_mod._mode(cfg)

    h = rmsnorm(bp["ln1"], x, cfg.norm_eps)

    def proj(w):
        return jnp.einsum("b...d,dhk->bh...k", h, mx[w].astype(dt))

    q, k, v = proj("wq"), proj("wk"), proj("wv")
    if cfg.qk_norm:
        q = rmsnorm(mx["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(mx["k_norm"], k, cfg.norm_eps)
    if cfg.rope_theta > 0:
        # RoPE runs over a seq axis, broadcast over the heads; decode rows
        # take one of length 1
        pos = positions.reshape(x.shape[0], 1, -1)
        q, k = (rope(t.reshape(*t.shape[:2], -1, t.shape[-1]), pos,
                     cfg.rope_theta).reshape(t.shape) for t in (q, k))
    q = q * jnp.asarray(premult * cfg.head_dim_ ** -0.5, q.dtype)
    o, pools = attend(q, k, v, pools, layer)
    x = _ffn(bp, x + jnp.einsum("bh...k,hkd->b...d", o, mx["wo"].astype(dt)),
             cfg)
    seq = ("seq",) * (x.ndim - 2)
    return shard_act(x, ("batch",) + seq + ("act_embed",)), pools


def _layers(params, x, pools, positions, attend, cfg: ModelConfig):
    """Scan ``_layer`` over the stacked blocks with ``(x, pools)`` as the
    carry and the layer index as ``xs``."""
    def body(carry, xs):
        bp, layer = xs
        return _layer(bp, *carry, layer, positions, attend, cfg), None

    layers = jnp.arange(pools[0].shape[0])
    (x, pools), _ = jax.lax.scan(body, (x, pools),
                                 (params["blocks"], layers))
    return x, pools


# ---------------------------------------------------------------------------
# Chunked prefill (flash-prefill kernel over the block table)
# ---------------------------------------------------------------------------


def paged_prefill_chunked(
    params,
    tokens: jax.Array,        # (1, C) one prompt chunk, right-padded
    pos0: jax.Array,          # () int32 absolute position of tokens[:, 0]
    last_rel: jax.Array,      # (1,) index of the chunk's true last token
    k_pool: jax.Array,        # (L, N, Hkv, BS, Dh)
    v_pool: jax.Array,
    table: jax.Array,         # (1, W) physical blocks covering every
    #                           position <= pos0 + C - 1 (logical order);
    #                           W must be the exact cover
    #                           ceil((pos0+C)/BS), or that cover rounded
    #                           up to a multiple of ceil(C/BS) with pad
    #                           entries = garbage block 0 (the CPU fast
    #                           path skips causal masking on the leading
    #                           blocks under exactly this guarantee)
    blk: jax.Array,           # (C,) int32 physical block per chunk row
    off: jax.Array,           # (C,) int32 row within that block
    cfg: ModelConfig,
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32 scale pools (int8)
    v_scale: jax.Array = None,
    kv_tile_blocks: int = 1,     # static: pool blocks per kernel kv step
):
    """One chunk of a chunked prefill. Per layer: scatter the chunk's K/V
    rows into the pool at (blk, off) — pad rows route to garbage block 0 —
    then run chunk-queries-over-pool attention through ``table``. The
    scatter comes *first*, so the attention sees [cached prefix ‖ earlier
    chunks ‖ this chunk] as one logical KV stream and the positional causal
    mask does the rest; the pool update (instead of a returned K/V stack)
    is also what the next chunk of the same prompt resumes from. With an
    int8 pool the chunk's rows are quantized before the scatter, so the
    chunk attends its *own* rows through the same dequant path as the
    prefix — every reader of a given token sees identical values.

    Returns (chunk-last-token logits (1, V), new k_pool, new v_pool[, new
    k_scale, new v_scale]). The logits matter only for the final chunk
    (they seed decoding); computing them per chunk costs one (1, d) @
    (d, V) matmul.
    """
    B, C = tokens.shape
    params = maybe_cast_params(params, cfg)
    _, intmax = attn_mod._mode(cfg)
    positions = pos0 + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                        (B, C))
    qpos0 = jnp.broadcast_to(pos0, (B,)).astype(jnp.int32)
    # off the TPU the dispatcher runs the split oracle, which leaves the
    # causal mask off all but these last blocks: safe under the table
    # contract above
    tail = 2 * (-(-C // k_pool.shape[3])) + 1

    def attend(q, k, v, pools, layer):
        pools = _write_rows(pools, layer, blk, off,
                            jnp.swapaxes(k[0], 0, 1),   # (C, Hkv, Dh)
                            jnp.swapaxes(v[0], 0, 1))
        o = flash_prefill_paged_op(q, pools[0], pools[1], table, qpos0,
                                   layer=layer, intmax=intmax,
                                   kv_tile_blocks=kv_tile_blocks,
                                   interpret=cfg.interpret_kernels,
                                   split_tail_blocks=tail,
                                   **scale_kwargs(pools))
        return o, pools

    x, pools = _layers(params, embed(params["embed"], tokens, cfg),
                       _pool_tuple(k_pool, v_pool, k_scale, v_scale),
                       positions, attend, cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x_last = jnp.take_along_axis(
        x, last_rel[:, None, None].astype(jnp.int32), axis=1)  # (1, 1, d)
    lg = logits(params["embed"], x_last, cfg)[:, 0]
    return (lg,) + pools


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def paged_decode_step(
    params,
    tokens1: jax.Array,       # (B,) current token ids
    k_pool: jax.Array,        # (L, N, Hkv, BS, Dh)
    v_pool: jax.Array,
    block_tables: jax.Array,  # (B, nb) int32
    lengths: jax.Array,       # (B,) tokens already in cache
    cfg: ModelConfig,
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32 scale pools (int8)
    v_scale: jax.Array = None,
    kv_tile_blocks: Optional[int] = None,  # static: pool blocks per
    #                              kernel kv step; None: from the block shape
    decode_split_k: int = 1,     # static: parallel KV partitions per lane
):
    """One continuous-batch decode step: the layer of
    ``paged_prefill_chunked`` at one token a row.

    Writes each sequence's new K/V row at logical position ``lengths[b]``
    (physical: table[b, pos // BS] offset pos % BS), attends over
    ``lengths + 1`` entries, and returns (logits (B, V), new pools[, new
    scale pools]). With an int8 pool the new row is quantized against its
    own amax before the write — per-row scales make the append O(1) — and
    attention dequantizes on gather. The caller advances its host-side
    lengths by one afterwards.
    """
    params = maybe_cast_params(params, cfg)
    BS = k_pool.shape[3]
    _, intmax = attn_mod._mode(cfg)
    blk = jnp.take_along_axis(block_tables, (lengths // BS)[:, None],
                              axis=1)[:, 0]           # (B,) physical block
    off = lengths % BS
    new_len = lengths + 1

    def attend(q, k, v, pools, layer):
        pools = _write_rows(pools, layer, blk, off, k, v)
        o = flash_decode_paged_op(q, pools[0], pools[1], block_tables,
                                  new_len, layer=layer, intmax=intmax,
                                  kv_tile_blocks=kv_tile_blocks,
                                  split_k=decode_split_k,
                                  interpret=cfg.interpret_kernels,
                                  **scale_kwargs(pools))
        return o, pools

    table = params["embed"]["embedding"].astype(cfg.compute_dtype_)
    x, pools = _layers(params,
                       shard_act(table[tokens1], ("batch", "act_embed")),
                       _pool_tuple(k_pool, v_pool, k_scale, v_scale),
                       lengths, attend, cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    lg = logits(params["embed"], x[:, None, :], cfg)[:, 0]
    return (lg,) + pools
