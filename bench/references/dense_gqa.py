"""Plain reference of a dense decoder with grouped-query attention (Qwen3,
Granite 3), and the seeded weights both it and the served program run.

Nothing here imports the program under test. The weights are laid out as
the program's parameter tree expects them (``embed``, ``final_norm`` and
the layer-stacked ``blocks``), drawn on the device in one jitted call from
the seed, in bf16, the type they are served in.

The forward pass is straightforward ``jax.numpy`` in float32 at the
highest matmul precision: pre-norm RMSNorm layers, per-head RMSNorm of q
and k where the model has it, rotary embeddings on the two halves of each
head, causal attention whose weights are ``2**s / sum 2**s`` for scores
``s = q.k / sqrt(head_dim)`` (the Softermax the repository serves, a
base-e softmax of ``s * ln 2``), a SiLU-gated MLP, and logits from the
unembedding or, where tied, the embedding's transpose. Attention runs in
blocks of query rows, so a sequence of several thousand tokens fits beside
the weights.

``precision="fp8"`` is the control: every matmul operand is rounded to
float8 e4m3 (scaled per row to its largest magnitude) before a float32
product, the next precision below the bf16 the configuration serves in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512                 # query rows per attention block
VOCAB_PAD = 256               # the program pads the vocabulary to this


def sizes_of(cfg: dict) -> dict:
    """The sizes the reference needs, from a configuration file."""
    vocab = int(cfg["vocab_size"])
    return {
        "layers": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "ff": int(cfg["intermediate_size"]),
        "vocab": vocab,
        "padded_vocab": -(-vocab // VOCAB_PAD) * VOCAB_PAD,
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "qk_norm": bool(cfg["qk_norm"]),
    }


def _hashable(sizes: dict) -> tuple:
    return tuple(sorted(sizes.items()))


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, sizes_t):
    s = dict(sizes_t)
    L, d, H, K, dh, ff, V = (s["layers"], s["d"], s["heads"], s["kv_heads"],
                             s["head_dim"], s["ff"], s["padded_vocab"])
    shapes = {
        "embedding": ((V, d), d ** -0.5),
        "unembed": ((d, V), d ** -0.5),
        "wq": ((L, d, H, dh), d ** -0.5),
        "wk": ((L, d, K, dh), d ** -0.5),
        "wv": ((L, d, K, dh), d ** -0.5),
        "wo": ((L, H, dh, d), (H * dh) ** -0.5),
        "wi": ((L, d, ff), d ** -0.5),
        "wg": ((L, d, ff), d ** -0.5),
        "w2": ((L, ff, d), ff ** -0.5),
    }
    keys = jax.random.split(key, len(shapes))
    w = {name: jax.random.normal(k, shp, jnp.bfloat16) * std
         for k, (name, (shp, std)) in zip(keys, shapes.items())}
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    mixer = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if s["qk_norm"]:
        mixer["q_norm"] = {"scale": ones((L, dh))}
        mixer["k_norm"] = {"scale": ones((L, dh))}
    embed = {"embedding": w["embedding"]}
    if not s["tied"]:
        embed["unembed"] = w["unembed"]
    return {
        "embed": embed,
        "final_norm": {"scale": ones((d,))},
        "blocks": {
            "ln1": {"scale": ones((L, d))},
            "mixer": mixer,
            "ln2": {"scale": ones((L, d))},
            "ffn": {"wi": w["wi"], "wg": w["wg"], "wo": w["w2"]},
        },
    }


def make_weights(sizes: dict, seed: int):
    """The weights of one run, on the default device, in one jitted call.
    The seed may be any whole number; it is folded to 31 bits."""
    import numpy as np
    s31 = int(np.random.SeedSequence(seed % 2 ** 64).generate_state(1)[0]
              ) & 0x7FFFFFFF
    return _draw(jax.random.PRNGKey(s31), _hashable(sizes))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fp8(x):
    """Round to float8 e4m3, scaled per row (last axis) to its amax."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8: bool, a_axis: int = -1, b_axis: int = 0):
    """einsum at float32/highest; for the control both operands are first
    rounded to fp8 along their contracted axis."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if fp8:
        a = jnp.moveaxis(_fp8(jnp.moveaxis(a, a_axis, -1)), -1, a_axis)
        b = jnp.moveaxis(_fp8(jnp.moveaxis(b, b_axis, -1)), -1, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, heads, dh); rotation of the two halves of each head."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = pos[:, None, None].astype(jnp.float32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(x, lw, s, fp8):
    S = x.shape[0]
    H, K, dh = s["heads"], s["kv_heads"], s["head_dim"]
    g = H // K
    pos = jnp.arange(S)
    mx = lw["mixer"]
    h = _rms(x, lw["ln1"]["scale"], s["eps"])
    q = _mm("sd,dhk->shk", h, mx["wq"], fp8)
    k = _mm("sd,dhk->shk", h, mx["wk"], fp8)
    v = _mm("sd,dhk->shk", h, mx["wv"], fp8)
    if s["qk_norm"]:
        q = _rms(q, mx["q_norm"]["scale"], s["eps"])
        k = _rms(k, mx["k_norm"]["scale"], s["eps"])
    q = _rope(q, pos, s["theta"]) * dh ** -0.5
    k = _rope(k, pos, s["theta"])
    qb = q.reshape(S // Q_BLOCK, Q_BLOCK, K, g, dh)

    def block(args):
        qi, i = args
        sc = _mm("qkgd,tkd->kgqt", qi, k, fp8, -1, -1)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(rows[:, None] >= pos[None, :], sc, -jnp.inf)
        p = jnp.exp2(sc - jnp.max(sc, -1, keepdims=True))
        p = p / jnp.sum(p, -1, keepdims=True)
        return _mm("kgqt,tkd->qkgd", p, v, fp8, -1, 0)

    o = jax.lax.map(block, (qb, jnp.arange(S // Q_BLOCK)))
    x = x + _mm("sf,fd->sd", o.reshape(S, H * dh),
                mx["wo"].reshape(H * dh, -1), fp8)
    h2 = _rms(x, lw["ln2"]["scale"], s["eps"])
    f = lw["ffn"]
    a = _mm("sd,df->sf", h2, f["wi"], fp8)
    b = _mm("sd,df->sf", h2, f["wg"], fp8)
    return x + _mm("sf,fd->sd", jax.nn.silu(a) * b, f["wo"], fp8)


def _hidden(w, tokens, s, fp8):
    x = w["embed"]["embedding"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, s, fp8), None

    x, _ = jax.lax.scan(body, x, w["blocks"])
    return _rms(x, w["final_norm"]["scale"], s["eps"])


def _logits(w, x, s, fp8):
    if s["tied"]:
        lg = _mm("pd,vd->pv", x, w["embed"]["embedding"], fp8, -1, -1)
    else:
        lg = _mm("pd,dv->pv", x, w["embed"]["unembed"], fp8)
    return lg[:, :s["vocab"]]


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gaps(w, tokens, positions, served, sizes_t, control):
    s = dict(sizes_t)
    x = _hidden(w, tokens, s, False)[positions]
    ref = _logits(w, x, s, False)
    best = jnp.max(ref, -1)
    gap = best - jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    if not control:
        return gap, gap
    xc = _hidden(w, tokens, s, True)[positions]
    top = jnp.argmax(_logits(w, xc, s, True), -1)
    gap_c = best - jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
    return gap, gap_c


def served_gaps(w, sizes: dict, prompt, tokens, seq_len: int, n_pos: int,
                control: bool = False):
    """For one served request (``prompt`` then its served ``tokens``):
    at each position that produced a served token, by how much the
    reference's logit of that token lies below the reference's best.
    With ``control``, also the same gap for the token the fp8 control puts
    first. The sequence is right-padded to ``seq_len`` (a multiple of
    ``Q_BLOCK``) and the positions to ``n_pos``, so one program serves
    every request of a cell; causality keeps the pad out.
    Returns float32 numpy arrays (gap, control gap), one entry per token."""
    import numpy as np
    prompt = np.asarray(prompt, np.int32)
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    seq = np.concatenate([prompt, tokens[:-1]])
    assert len(seq) <= seq_len and n <= n_pos and seq_len % Q_BLOCK == 0
    toks = np.zeros(seq_len, np.int32)
    toks[:len(seq)] = seq
    pos = np.zeros(n_pos, np.int32)
    pos[:n] = len(prompt) - 1 + np.arange(n)
    srv = np.zeros(n_pos, np.int32)
    srv[:n] = tokens
    gap, gap_c = _gaps(w, jnp.asarray(toks), jnp.asarray(pos),
                       jnp.asarray(srv), _hashable(sizes), control)
    return np.asarray(gap)[:n], np.asarray(gap_c)[:n]
