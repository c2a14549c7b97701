"""Mixture-of-Experts FFN with sort-based capacity dispatch (TPU-native).

Routing pipeline (per layer, tokens flattened to T = B·S):

1. Router logits → probabilities. **Beyond-paper extension**: the router
   softmax also runs through Softermax (base-2) — the paper only touches
   attention, but every softmax in the network benefits from the same
   hardware-friendly form (``cfg.moe.router_softmax``).
2. top-k experts per token, weights renormalized over the selected k.
3. Capacity-bounded dispatch: assignments are sorted by expert id; each
   assignment's rank within its expert is its capacity slot; overflow
   (rank ≥ C) is dropped (standard Switch semantics). The gathered
   ``(E, C, d)`` buffer is *expert-sharded* over the model axis — the
   token-sharded → expert-sharded handoff lowers to an all-to-all under
   pjit, which is the EP communication pattern.
4. Per-expert gated MLP via batched einsum with ``(E, d, ff)`` weights.
5. Combine back with routing weights; add shared experts (DeepSeek) when
   configured.

Aux losses: switch load-balance loss + router z-loss.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.softermax import attention_softmax
from repro.models.layers import _activate, mlp, mlp_schema
from repro.models.schema import ParamSpec
from repro.parallel.sharding import current_mesh, shard_act


def moe_schema(cfg: ModelConfig):
    m = cfg.moe
    d = cfg.d_model
    s = {
        "router": ParamSpec((d, m.n_experts), ("embed", "experts"), std=0.02),
        "wi": ParamSpec((m.n_experts, d, m.d_expert),
                        ("experts", "embed", "expert_mlp")),
        "wg": ParamSpec((m.n_experts, d, m.d_expert),
                        ("experts", "embed", "expert_mlp")),
        "wo": ParamSpec((m.n_experts, m.d_expert, d),
                        ("experts", "expert_mlp", "embed")),
    }
    if m.n_shared > 0:
        s["shared"] = mlp_schema(d, m.n_shared * (m.d_shared or m.d_expert))
    return s


def moe_apply(params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (y, aux_loss). Dispatches to the shard_map EP path
    when enabled and applicable (see moe_apply_shard_map)."""
    mesh = current_mesh()
    if (cfg.opt_moe_shard_map and mesh is not None
            and "model" in mesh.shape and mesh.shape["model"] > 1
            and x.shape[1] % mesh.shape["model"] == 0
            and cfg.moe.n_experts % mesh.shape["model"] == 0):
        return moe_apply_shard_map(params, x, cfg, mesh)
    return _moe_apply_global(params, x, cfg)


def _moe_apply_global(params, x: jax.Array, cfg: ModelConfig
                      ) -> Tuple[jax.Array, jax.Array]:
    """Global (pjit-only) dispatch — the §Roofline baseline. The scatter
    into the expert-sharded buffer costs a full-buffer all-reduce under
    SPMD; kept as the fallback for decode (S=1) and tiny meshes."""
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    dt = x.dtype
    xf = x.reshape(-1, d)                                     # (T, d)
    T = xf.shape[0]

    # --- router (fp32 logits; softermax probabilities) ---
    rl = (xf @ params["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = attention_softmax(rl, impl=m.router_softmax, axis=-1)
    weights, sel = jax.lax.top_k(probs, k)                    # (T, k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)

    # --- aux losses ---
    # load-balance: E * sum_e mean_prob_e * frac_dispatched_e
    me = jnp.mean(probs, axis=0)                              # (E,)
    one_hot_sel = jax.nn.one_hot(sel, E, dtype=jnp.float32)   # (T, k, E)
    ce = jnp.mean(jnp.sum(one_hot_sel, axis=1), axis=0) / k   # (E,)
    aux = E * jnp.sum(me * ce) * m.aux_loss_weight
    aux = aux + 1e-4 * jnp.mean(jax.nn.logsumexp(rl, axis=-1) ** 2)

    # --- capacity-bounded sort dispatch ---
    C = int(max(8, -(-T * k // E) * m.capacity_factor))       # slots/expert
    C = -(-C // 8) * 8
    flat_e = sel.reshape(-1)                                  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))        # (E,)
    rank_sorted = jnp.arange(T * k) - starts[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = rank < C                                           # (T*k,)
    slot = jnp.where(keep, flat_e * C + rank, E * C)          # overflow→dummy
    tok = jnp.arange(T * k) // k

    buf = jnp.zeros((E * C + 1, d), dt).at[slot].add(
        xf[tok] * keep[:, None].astype(dt))
    h = buf[:-1].reshape(E, C, d)
    h = shard_act(h, ("experts", None, "act_embed"))

    # --- expert gated MLP (batched einsum; E sharded over model axis) ---
    wi = params["wi"].astype(dt)
    wg = params["wg"].astype(dt)
    wo = params["wo"].astype(dt)
    a = _activate(jnp.einsum("ecd,edf->ecf", h, wi), cfg.activation)
    a = a * jnp.einsum("ecd,edf->ecf", h, wg)
    y_buf = jnp.einsum("ecf,efd->ecd", a, wo)
    y_buf = shard_act(y_buf, ("experts", None, "act_embed"))

    # --- combine ---
    y_flat = y_buf.reshape(E * C, d)
    safe_slot = jnp.minimum(slot, E * C - 1)
    y_tok = y_flat[safe_slot] * (keep[:, None] * weights.reshape(-1)[:, None]
                                 ).astype(dt)
    y = jnp.sum(y_tok.reshape(T, k, d), axis=1)

    if m.n_shared > 0:
        y = y + mlp(params["shared"], xf, cfg.activation)

    return y.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (opt_moe_shard_map)
# ---------------------------------------------------------------------------
#
# The global path's scatter into the expert-sharded (E·C, d) buffer lowers to
# a full-buffer all-reduce under SPMD (measured: 8.9 TB/chip wire for the
# deepseek train cell — EXPERIMENTS.md §Perf). This path instead:
#
#   1. enters shard_map over (batch→data, seq→model): T_loc tokens per chip;
#   2. routes + capacity-dispatches LOCALLY into (E, C_loc, d);
#   3. all_to_all over "model" sends each expert block to its owner
#      (payload ≈ tokens·k·d — the EP-minimal wire);
#   4. expert FFN with explicitly all-gathered (bf16) weight shards;
#   5. all_to_all back + local combine.
#
# Routing decisions are identical to the global path per token; capacity is
# enforced per (token-shard × expert) instead of globally — the standard EP
# approximation (local capacity C_loc = C_global / n_shards).


def _local_dispatch(xf, probs, k, E, C, dt):
    """Sort-based capacity dispatch on LOCAL tokens.

    xf: (T, d); probs: (T, E). Returns (buf (E, C, d), slot (T*k,),
    keep (T*k,), weights (T, k))."""
    T = xf.shape[0]
    weights, sel = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    flat_e = sel.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    rank_sorted = jnp.arange(T * k) - starts[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    keep = rank < C
    slot = jnp.where(keep, flat_e * C + rank, E * C)
    tok = jnp.arange(T * k) // k
    buf = jnp.zeros((E * C + 1, xf.shape[1]), dt).at[slot].add(
        xf[tok] * keep[:, None].astype(dt))
    return buf[:-1].reshape(E, C, xf.shape[1]), slot, keep, weights, sel


def moe_apply_shard_map(params, x: jax.Array, cfg: ModelConfig, mesh
                        ) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    B, S, d = x.shape
    E, k = m.n_experts, m.top_k
    n_model = mesh.shape["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    n_data = 1
    for a in batch_axes:
        n_data *= mesh.shape[a]
    b_loc = B // n_data if B % n_data == 0 else B
    T_loc = b_loc * (S // n_model)
    C = int(max(4, -(-T_loc * k // E) * m.capacity_factor))
    C = -(-C // 4) * 4
    E_loc = E // n_model
    dt = x.dtype

    def _inner(x_l, router, wi, wg, wo):
        # x_l: (b_loc, S_loc, d); wi/wg: (E_loc, d_shard, ff); wo transposed
        T = x_l.shape[0] * x_l.shape[1]
        xf = x_l.reshape(T, d)
        rl = (xf @ router.astype(dt)).astype(jnp.float32)
        probs = attention_softmax(rl, impl=m.router_softmax, axis=-1)
        buf, slot, keep, weights, sel = _local_dispatch(
            xf, probs, k, E, C, dt)

        # aux losses from local statistics (pmean over shards)
        me = jnp.mean(probs, axis=0)
        ce_frac = jnp.mean(
            jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=1),
            axis=0) / k
        aux = E * jnp.sum(me * ce_frac) * m.aux_loss_weight
        aux = aux + 1e-4 * jnp.mean(jax.nn.logsumexp(rl, axis=-1) ** 2)
        aux = jax.lax.pmean(jax.lax.pmean(aux, "model"),
                            batch_axes) if batch_axes else \
            jax.lax.pmean(aux, "model")

        # ship expert blocks to their owners: (n_model, E_loc·C, d)
        send = buf.reshape(n_model, E_loc * C, d)
        recv = jax.lax.all_to_all(send, "model", split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (n_model, E_loc·C, d) — rows from every sender for MY experts
        h = recv.reshape(n_model, E_loc, C, d).transpose(1, 0, 2, 3) \
            .reshape(E_loc, n_model * C, d)

        # FSDP: gather the expert weights' d-shard (bf16 when opt_bf16)
        wi_g = jax.lax.all_gather(wi, "data", axis=1, tiled=True) \
            if "data" in mesh.shape else wi
        wg_g = jax.lax.all_gather(wg, "data", axis=1, tiled=True) \
            if "data" in mesh.shape else wg
        wo_g = jax.lax.all_gather(wo, "data", axis=2, tiled=True) \
            if "data" in mesh.shape else wo

        a = _activate(jnp.einsum("ecd,edf->ecf", h, wi_g.astype(dt)),
                      cfg.activation)
        a = a * jnp.einsum("ecd,edf->ecf", h, wg_g.astype(dt))
        y_h = jnp.einsum("ecf,efd->ecd", a, wo_g.astype(dt))

        # return to senders
        back = y_h.reshape(E_loc, n_model, C, d).transpose(1, 0, 2, 3) \
            .reshape(n_model, E_loc * C, d)
        y_buf = jax.lax.all_to_all(back, "model", split_axis=0,
                                   concat_axis=0, tiled=False)
        y_flat = y_buf.reshape(E * C, d)
        safe_slot = jnp.minimum(slot, E * C - 1)
        y_tok = y_flat[safe_slot] * (
            keep[:, None] * weights.reshape(-1)[:, None]).astype(dt)
        y = jnp.sum(y_tok.reshape(T, k, d), axis=1)
        return y.reshape(x_l.shape), aux

    x_spec = P(batch_axes if B % n_data == 0 else None, "model", None)
    out = jax.shard_map(
        _inner, mesh=mesh,
        in_specs=(x_spec,
                  P(None, None),                    # router replicated
                  P("model", "data" if "data" in mesh.shape else None, None),
                  P("model", "data" if "data" in mesh.shape else None, None),
                  P("model", None, "data" if "data" in mesh.shape else None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])
    y, aux = out

    if m.n_shared > 0:
        y = y + mlp(params["shared"], x, cfg.activation)
    return y, aux
