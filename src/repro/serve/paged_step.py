"""Model step functions over the paged KV cache (attention-family LMs).

These mirror ``models/lm.py``'s prefill/decode pair but speak the block-pool
layout instead of a contiguous per-request cache:

* ``paged_prefill``     — full-prompt forward (prompts right-padded to a
  block multiple; causality keeps pad junk out of the real tokens) returning
  the true-last-token logits plus the per-layer K/V to scatter into the pool.
* ``scatter_prefill``   — place a prefilled request's K/V into its allocated
  physical blocks (one fused device scatter).
* ``paged_prefill_suffix`` — offset-aware prefill for the radix prefix
  cache: only the *uncached* prompt suffix runs through the model (absolute
  positions ``pos0..``), with each layer's attention reading the cached
  prefix K/V straight out of the pool through the request's block table.
  One shot: the whole suffix is a single dense attention, quadratic in the
  suffix and linear in the prefix — fine for chat-sized prompts, the wrong
  shape for long documents.
* ``scatter_prefill_offset`` — place suffix K/V rows at arbitrary
  (block, row) coordinates: the suffix may start mid-block when a matched
  partial tail block was extended copy-on-write.
* ``paged_prefill_chunked`` — one *chunk* of a long prompt (engine-driven
  chunked prefill): per layer the chunk's K/V rows are scattered into the
  pool first, then the chunk's queries attend the pool directly through
  the block table (``kernels/flash_prefill_paged``) — cached prefix,
  earlier chunks, and the chunk's own causal triangle are all one KV
  source, so nothing is gathered-and-concatenated and no score matrix ever
  exceeds (chunk, prefix+chunk). The online-normalization state is carried
  across KV tiles inside the kernel; across chunk boundaries no state is
  handed over at all — earlier chunks' contribution is pool-resident and
  the Softermax recurrence is order-free, so re-attending it online is
  exact.
* ``paged_decode_step`` — one token for the whole running batch: per layer,
  write the new K/V row through the block table, then run paged Softermax
  decode attention over the pool. Inactive batch slots carry block table 0
  (the pool's garbage block) and length 0, so their writes and reads are
  harmless and their logits are ignored by the engine.

Attention math is identical to the contiguous path (same Unnormed-Softmax-
Unit recurrence): on TPU / under ``cfg.interpret_kernels`` the Pallas
``flash_decode_paged`` kernel runs; elsewhere a pure-JAX gather fallback
keeps CPU tests fast.

**Int8 pools (quantize-on-scatter).** Every step function takes the pool's
optional per-row scale tensors (``k_scale``/``v_scale``; ``None`` for
bf16/f32 pools — the pool dtype, static under jit, selects the path).
Writers quantize rows symmetrically per (head, token) at the moment they
scatter (``attention_apply``'s projections stay full precision); readers
dequantize at gather — fused into the Pallas kernels on TPU, post-gather
in the refs — and accumulate in fp32, so the only precision loss is the
int8 rounding of the stored K/V rows, the same contract as the dense
``models/attention.py`` int8 decode branch. Functions that update the pool
return the new scale tensors after the new pools (callers unpack by mode).

**The pool is updated in place.** ``paged_prefill_chunked`` and
``paged_decode_step`` carry the whole ``(L, N, Hkv, BS, Dh)`` pool (and
its scale pools) through the layer scan, scatter each layer's new rows at
``[layer, blk, :, off]`` into it, and hand both kernels the whole pool
with the layer index. Only the new rows move: with the engine's donation
the pool aliases from the program's input to its output, and no program
slices a layer out of it or holds a second copy.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.numerics import NEG_INF
from repro.kernels.flash_decode_paged import flash_decode_paged_op
from repro.kernels.flash_decode_paged.ref import gather_kv_dequant
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
    decode/suffix tables, chunked-prefill covers, warmup shape enumeration,
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
    * otherwise — pow2 policy (decode and one-shot suffix tables): next
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
# Prefill
# ---------------------------------------------------------------------------


def _fake_quant_kv(t: jax.Array) -> jax.Array:
    """Round-trip ``t`` through the pool's int8 representation. Re-
    quantizing the result reproduces the exact same int8 codes (the amax
    row maps back to ±127 and the scale round-trips within ~2^-24, far
    inside round-to-nearest's 0.5 margin), so a prefill that attends
    fake-quantized rows sees bit-identical values to every later reader
    that dequantizes the scattered block — chunked prefill, decode, and
    prefix-cache rehits all agree on what a cached token "is"."""
    q8, sc = attn_mod.quantize_kv(t)
    return attn_mod.dequantize_kv(q8, sc, t.dtype)


def paged_prefill(
    params,
    tokens: jax.Array,       # (B, Sp) prompts right-padded to a block multiple
    last_pos: jax.Array,     # (B,) int32 index of the true last prompt token
    cfg: ModelConfig,
    kv_quantize: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (true-last-token logits (B, V), k, v (L, B, Hkv, Sp, Dh)).

    ``kv_quantize`` (int8 pools) round-trips each layer's K/V through the
    int8 grid *before* the in-prompt attention, so the prompt attends the
    same values the pool will store (the scatter's re-quantization is
    code-exact on fake-quantized rows) — without it, a chunked re-prefill
    of the same prompt would see slightly different KV than the one-shot
    path computed. The quantized branch runs XLA-level chunked softermax
    attention directly (flash/ring impl selection doesn't apply — the KV
    it would attend is no longer what ``attention_apply`` projects)."""
    B, Sp = tokens.shape
    params = maybe_cast_params(params, cfg)
    dh = cfg.head_dim_
    premult, intmax = attn_mod._mode(cfg)
    positions = jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32), (B, Sp))
    x = embed(params["embed"], tokens, cfg)

    def body(x, bp):
        h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
        if kv_quantize:
            q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
            k = _fake_quant_kv(k)
            v = _fake_quant_kv(v)
            q = q * jnp.asarray(premult * dh ** -0.5, q.dtype)
            q = shard_act(q, ("batch", "act_heads", "seq", "head_dim"))
            k = shard_act(k, ("batch", "act_heads", "seq", "head_dim"))
            v = shard_act(v, ("batch", "act_heads", "seq", "head_dim"))
            o = attn_mod.chunked_attention(q, k, v, causal=True,
                                           intmax=intmax,
                                           chunk=cfg.attention_chunk)
            y = attn_mod._out_proj(bp["mixer"], o, cfg)
        else:
            y, k, v = attn_mod.attention_apply(
                bp["mixer"], h, cfg, positions=positions, causal=True,
                return_kv=True)
        x = x + y
        h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_apply(bp["ffn"], h2, cfg)
        else:
            f = mlp(bp["ffn"], h2, cfg.activation)
        x = shard_act(x + f, ("batch", "seq", "act_embed"))
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x_last = jnp.take_along_axis(
        x, last_pos[:, None, None].astype(jnp.int32), axis=1)  # (B, 1, d)
    lg = logits(params["embed"], x_last, cfg)[:, 0]
    return lg, ks, vs


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

    def ffn(bp, x):
        h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_apply(bp["ffn"], h2, cfg)
        else:
            f = mlp(bp["ffn"], h2, cfg.activation)
        return x + f

    def body(carry, bp):
        x_ref, x_q = carry
        # reference branch: exact K/V
        h = rmsnorm(bp["ln1"], x_ref, cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
        o, _, _, _ = _audit_attention(q * jnp.asarray(qscale, q.dtype),
                                      k, v, intmax)
        x_ref = ffn(bp, x_ref + attn_mod._out_proj(bp["mixer"], o, cfg))
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
        x_q = ffn(bp, x_q + attn_mod._out_proj(bp["mixer"], o, cfg))
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


def scatter_prefill(
    k_pool: jax.Array,       # (L, N, Hkv, BS, Dh)
    v_pool: jax.Array,
    ks: jax.Array,           # (L, 1, Hkv, Sp, Dh) from paged_prefill (B=1)
    vs: jax.Array,
    block_ids: jax.Array,    # (nb,) int32 physical blocks, nb*BS == Sp
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32 scale pools (int8)
    v_scale: jax.Array = None,
):
    """Returns (k_pool, v_pool) — or (k_pool, v_pool, k_scale, v_scale)
    when the pool is int8: rows are quantized per (layer, head, token) at
    scatter time and their scales land in the sibling scale pools."""
    L, _, Hkv, Sp, Dh = ks.shape
    BS = k_pool.shape[3]
    nb = Sp // BS

    def place(pool, seq):
        blocks = seq[:, 0].reshape(L, Hkv, nb, BS, Dh)
        blocks = jnp.moveaxis(blocks, 2, 1)          # (L, nb, Hkv, BS, Dh)
        return pool.at[:, block_ids].set(blocks.astype(pool.dtype))

    def place_scale(pool, sc):                       # sc (L, 1, Hkv, Sp)
        blocks = jnp.moveaxis(sc[:, 0].reshape(L, Hkv, nb, BS), 2, 1)
        return pool.at[:, block_ids].set(blocks)

    if k_pool.dtype != jnp.int8:
        return place(k_pool, ks), place(v_pool, vs)
    kq, ksc = attn_mod.quantize_kv(ks)
    vq, vsc = attn_mod.quantize_kv(vs)
    return (place(k_pool, kq), place(v_pool, vq),
            place_scale(k_scale, ksc), place_scale(v_scale, vsc))


# ---------------------------------------------------------------------------
# Offset prefill (radix prefix cache: compute only the uncached suffix)
# ---------------------------------------------------------------------------


def _suffix_attention(q, k_pre, v_pre, k_suf, v_suf, pre_valid, q_pos,
                      intmax):
    """Dense softermax attention of suffix queries over [cached prefix ‖
    in-flight suffix].

    q (B, Hq, Sq, D) pre-scaled; k_pre/v_pre (B, Hkv, Sk, D) gathered from
    the pool (rows >= prefix_len are garbage — masked by ``pre_valid``);
    k_suf/v_suf (B, Hkv, Sq, D); q_pos (B, Sq) absolute positions. Same
    exp2 / running-IntMax math as the chunked prefill and the paged decode
    kernel, in closed form (one prompt, modest lengths)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k_pre.shape
    group = Hq // Hkv
    k = jnp.concatenate([k_pre, k_suf], axis=2)
    v = jnp.concatenate([v_pre, v_suf], axis=2)
    qg = q.reshape(B, Hkv, group, Sq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    # prefix columns: valid rows are strictly before every suffix query;
    # suffix columns: causal within the suffix (pad rows sit at the end,
    # after every true position, so causality keeps their junk inert).
    valid_pre = jnp.broadcast_to(pre_valid[:, None, :], (B, Sq, Sk))
    valid_suf = q_pos[:, :, None] >= q_pos[:, None, :]
    valid = jnp.concatenate([valid_pre, valid_suf], axis=2)   # (B, Sq, Sk+Sq)
    s = jnp.where(valid[:, None, None, :, :], s, NEG_INF)
    m = jnp.max(jnp.ceil(s) if intmax else s, axis=-1, keepdims=True)
    p = jnp.exp2(s - m)
    d = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(d > 0, p / jnp.where(d > 0, d, 1.0), 0.0)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p.astype(v.dtype), v)
    return o.reshape(B, Hq, Sq, D).astype(q.dtype)


def paged_prefill_suffix(
    params,
    tokens: jax.Array,        # (B, Sp) uncached suffix, right-padded
    pos0: jax.Array,          # () int32 absolute position of tokens[:, 0]
    last_rel: jax.Array,      # (B,) index of the true last token in tokens
    k_pool: jax.Array,        # (L, N, Hkv, BS, Dh)
    v_pool: jax.Array,
    prefix_table: jax.Array,  # (B, W) physical blocks of the cached prefix
    prefix_len: jax.Array,    # (B,) cached tokens (pad rows masked out)
    cfg: ModelConfig,
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32 scale pools (int8)
    v_scale: jax.Array = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill only the uncached suffix of a prompt whose first ``pos0``
    tokens are already resident in the pool (radix prefix-cache hit).

    Per layer the suffix Q/K/V are computed at absolute positions
    ``pos0 + i`` (RoPE stays consistent with the cold path) and attention
    runs over the cached prefix — gathered from the pool through
    ``prefix_table``, dequantized when the pool is int8 — concatenated with
    the in-flight suffix. Returns (true-last-token logits (B, V), ks, vs
    (L, B, Hkv, Sp, Dh)); the caller scatters ks/vs with
    ``scatter_prefill_offset`` (which quantizes them for int8 pools).
    ``pos0 == 0`` with an empty prefix degenerates to ``paged_prefill``'s
    math.
    """
    B, Sp = tokens.shape
    params = maybe_cast_params(params, cfg)
    dh = cfg.head_dim_
    premult, intmax = attn_mod._mode(cfg)
    quantized = k_pool.dtype == jnp.int8
    positions = pos0 + jnp.broadcast_to(jnp.arange(Sp, dtype=jnp.int32),
                                        (B, Sp))
    x = embed(params["embed"], tokens, cfg)
    W = prefix_table.shape[1]
    BS = k_pool.shape[3]
    pre_valid = jnp.arange(W * BS, dtype=jnp.int32)[None, :] < \
        prefix_len[:, None]                                   # (B, W*BS)

    def body(x, xs):
        if quantized:
            bp, kp_l, vp_l, ksc_l, vsc_l = xs
        else:
            bp, kp_l, vp_l = xs
            ksc_l = vsc_l = None
        h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
        if quantized:
            # the in-flight suffix must attend the same values the pool
            # will store (see _fake_quant_kv) — the cached prefix is
            # already the dequantized pool rows
            k = _fake_quant_kv(k)
            v = _fake_quant_kv(v)
        q = q * jnp.asarray(premult * dh ** -0.5, q.dtype)
        k_pre = gather_kv_dequant(kp_l, ksc_l, prefix_table).astype(k.dtype)
        v_pre = gather_kv_dequant(vp_l, vsc_l, prefix_table).astype(v.dtype)
        o = _suffix_attention(q, k_pre, v_pre, k, v, pre_valid, positions,
                              intmax)
        y = attn_mod._out_proj(bp["mixer"], o, cfg)
        x = x + y
        h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_apply(bp["ffn"], h2, cfg)
        else:
            f = mlp(bp["ffn"], h2, cfg.activation)
        x = shard_act(x + f, ("batch", "seq", "act_embed"))
        return x, (k, v)

    xs = (params["blocks"], k_pool, v_pool, k_scale, v_scale) if quantized \
        else (params["blocks"], k_pool, v_pool)
    x, (ks, vs) = jax.lax.scan(body, x, xs)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x_last = jnp.take_along_axis(
        x, last_rel[:, None, None].astype(jnp.int32), axis=1)  # (B, 1, d)
    lg = logits(params["embed"], x_last, cfg)[:, 0]
    return lg, ks, vs


def scatter_prefill_offset(
    k_pool: jax.Array,       # (L, N, Hkv, BS, Dh)
    v_pool: jax.Array,
    ks: jax.Array,           # (L, 1, Hkv, Sp, Dh) from paged_prefill_suffix
    vs: jax.Array,
    blk: jax.Array,          # (Sp,) int32 physical block per suffix row
    off: jax.Array,          # (Sp,) int32 row within that block
    k_scale: jax.Array = None,   # (L, N, Hkv, BS) f32 scale pools (int8)
    v_scale: jax.Array = None,
):
    """Row-granular scatter for an offset prefill: suffix row ``i`` lands at
    ``pool[:, blk[i], :, off[i], :]``. The suffix may start mid-block (a
    copy-on-write tail continues where the cached rows end), so unlike
    ``scatter_prefill`` the destination is not whole blocks; the caller
    routes padding rows to garbage block 0. Int8 pools quantize rows here
    and return the updated scale pools as well."""
    L, _, Hkv, Sp, Dh = ks.shape
    h = jnp.arange(Hkv)

    def place(pool, seq):
        rows = jnp.swapaxes(seq[:, 0], 1, 2)          # (L, Sp, Hkv, Dh)
        return pool.at[:, blk[:, None], h[None, :], off[:, None], :].set(
            rows.astype(pool.dtype))

    def place_scale(pool, sc):                        # sc (L, 1, Hkv, Sp)
        rows = jnp.swapaxes(sc[:, 0], 1, 2)           # (L, Sp, Hkv)
        return pool.at[:, blk[:, None], h[None, :], off[:, None]].set(rows)

    if k_pool.dtype != jnp.int8:
        return place(k_pool, ks), place(v_pool, vs)
    kq, ksc = attn_mod.quantize_kv(ks)
    vq, vsc = attn_mod.quantize_kv(vs)
    return (place(k_pool, kq), place(v_pool, vq),
            place_scale(k_scale, ksc), place_scale(v_scale, vsc))


# ---------------------------------------------------------------------------
# Chunked prefill (flash-prefill kernel over the block table)
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


def _chunk_attention(q, pools, layer, table, pos0, cfg, intmax,
                     kv_tile_blocks=1):
    """Chunk queries over block-table-resident KV of one layer of the
    pools through the one shared dispatcher: Pallas kernel on TPU / under
    ``cfg.interpret_kernels``; elsewhere the pure-JAX split oracle, which
    skips the causal mask on the provably-valid prefix bulk. Passing
    ``split_tail_blocks`` is safe here because ``paged_prefill_chunked``
    requires an exact-cover or chunk-quantized table (see the contract in
    its signature)."""
    BS = pools[0].shape[3]
    tail = 2 * (-(-q.shape[2] // BS)) + 1
    return flash_prefill_paged_op(q, pools[0], pools[1], table, pos0,
                                  layer=layer, intmax=intmax,
                                  kv_tile_blocks=kv_tile_blocks,
                                  interpret=cfg.interpret_kernels,
                                  split_tail_blocks=tail,
                                  **scale_kwargs(pools))


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
    (d, V) matmul. ``pos0 == 0`` with a chunk covering the whole prompt
    degenerates to ``paged_prefill``'s math, which is what the
    chunked-vs-one-shot greedy-equality test pins.
    """
    B, C = tokens.shape
    params = maybe_cast_params(params, cfg)
    dh = cfg.head_dim_
    premult, intmax = attn_mod._mode(cfg)
    positions = pos0 + jnp.broadcast_to(jnp.arange(C, dtype=jnp.int32),
                                        (B, C))
    x = embed(params["embed"], tokens, cfg)
    qpos0 = jnp.broadcast_to(pos0, (B,)).astype(jnp.int32)
    pools = _pool_tuple(k_pool, v_pool, k_scale, v_scale)

    def body(carry, xs):
        x, pools = carry
        bp, layer = xs
        h = rmsnorm(bp["ln1"], x, cfg.norm_eps)
        q, k, v = attn_mod._project_qkv(bp["mixer"], h, cfg, positions)
        pools = _write_rows(pools, layer, blk, off,
                            jnp.swapaxes(k[0], 0, 1),   # (C, Hkv, Dh)
                            jnp.swapaxes(v[0], 0, 1))
        q = q * jnp.asarray(premult * dh ** -0.5, q.dtype)
        o = _chunk_attention(q, pools, layer, table, qpos0, cfg, intmax,
                             kv_tile_blocks)
        y = attn_mod._out_proj(bp["mixer"], o, cfg)
        x = x + y
        h2 = rmsnorm(bp["ln2"], x, cfg.norm_eps)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_apply(bp["ffn"], h2, cfg)
        else:
            f = mlp(bp["ffn"], h2, cfg.activation)
        x = shard_act(x + f, ("batch", "seq", "act_embed"))
        return (x, pools), None

    layers = jnp.arange(k_pool.shape[0])
    (x, pools), _ = jax.lax.scan(body, (x, pools),
                                 (params["blocks"], layers))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x_last = jnp.take_along_axis(
        x, last_rel[:, None, None].astype(jnp.int32), axis=1)  # (1, 1, d)
    lg = logits(params["embed"], x_last, cfg)[:, 0]
    return (lg,) + pools


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------


def _paged_attention(q, pools, layer, block_tables, new_len, cfg, intmax,
                     kv_tile_blocks=None, split_k=1):
    """Fused-batch decode attention over one layer of the pools through
    the one shared dispatcher (``kernels/flash_decode_paged/ops.py``):
    grouped/tiled/split Pallas kernel on TPU or under
    ``cfg.interpret_kernels``, the pure-JAX gather oracle elsewhere
    (tile/split are layout knobs — same math)."""
    return flash_decode_paged_op(q, pools[0], pools[1], block_tables,
                                 new_len, layer=layer, intmax=intmax,
                                 kv_tile_blocks=kv_tile_blocks,
                                 split_k=split_k,
                                 interpret=cfg.interpret_kernels,
                                 **scale_kwargs(pools))


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
    """One continuous-batch decode step.

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
    dt = cfg.compute_dtype_
    dh = cfg.head_dim_
    premult, intmax = attn_mod._mode(cfg)

    table = params["embed"]["embedding"].astype(dt)
    x1 = shard_act(table[tokens1], ("batch", "act_embed"))

    blk = jnp.take_along_axis(block_tables, (lengths // BS)[:, None],
                              axis=1)[:, 0]           # (B,) physical block
    off = lengths % BS
    new_len = lengths + 1
    pools = _pool_tuple(k_pool, v_pool, k_scale, v_scale)

    def body(carry, xs):
        x1, pools = carry
        bp, layer = xs
        h = rmsnorm(bp["ln1"], x1, cfg.norm_eps)
        q = jnp.einsum("bd,dhk->bhk", h, bp["mixer"]["wq"].astype(dt))
        k = jnp.einsum("bd,dhk->bhk", h, bp["mixer"]["wk"].astype(dt))
        v = jnp.einsum("bd,dhk->bhk", h, bp["mixer"]["wv"].astype(dt))
        if cfg.qk_norm:
            q = rmsnorm(bp["mixer"]["q_norm"], q, cfg.norm_eps)
            k = rmsnorm(bp["mixer"]["k_norm"], k, cfg.norm_eps)
        if cfg.rope_theta > 0:
            pos = lengths[:, None]                    # (B, 1): next position
            q = rope(q[:, :, None, :], pos[:, :, None], cfg.rope_theta)[:, :, 0]
            k = rope(k[:, :, None, :], pos[:, :, None], cfg.rope_theta)[:, :, 0]
        pools = _write_rows(pools, layer, blk, off, k, v)
        q = q * jnp.asarray(premult * dh ** -0.5, q.dtype)
        o = _paged_attention(q, pools, layer, block_tables, new_len, cfg,
                             intmax, kv_tile_blocks, decode_split_k)
        y = jnp.einsum("bhk,hkd->bd", o, bp["mixer"]["wo"].astype(dt))
        x1 = x1 + y
        h2 = rmsnorm(bp["ln2"], x1, cfg.norm_eps)
        if cfg.family == "moe":
            f, _ = moe_mod.moe_apply(bp["ffn"], h2[:, None, :], cfg)
            f = f[:, 0]
        else:
            f = mlp(bp["ffn"], h2, cfg.activation)
        return (x1 + f, pools), None

    layers = jnp.arange(k_pool.shape[0])
    (x1, pools), _ = jax.lax.scan(body, (x1, pools),
                                  (params["blocks"], layers))
    x1 = rmsnorm(params["final_norm"], x1, cfg.norm_eps)
    lg = logits(params["embed"], x1[:, None, :], cfg)[:, 0]
    return (lg,) + pools
