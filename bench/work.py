"""The work that the algorithm needs, counted from shapes and live lengths.

These are the numerators of the roofline and MFU shares. They count what
attention at a row's live length requires: K and V of the tokens attended,
q and the output, and ``4 * Hq * D`` FLOPs per query-key pair (QK and AV).
Table padding, power-of-two buckets, tiles, splits and fetches of the
garbage block are how an implementation does the work, not the work: no
argument here describes them, so a change to them moves only the time.
"""
from __future__ import annotations

from typing import Iterable, Tuple

KV_BYTES = 2        # bf16 pool
ACT_BYTES = 2       # bf16 q and output


def attention(n_queries_keys: Iterable[Tuple[int, int]], s: dict
              ) -> Tuple[float, float]:
    """(flops, bytes) of one layer of paged attention for a list of
    ``(queries, keys)`` items: each item's queries attend ``keys`` tokens
    in all (their causal triangle included), and its K/V are read once."""
    hq, hkv, d = s["heads"], s["kv_heads"], s["head_dim"]
    flops = byts = 0.0
    for nq, pairs, keys in n_queries_keys:
        flops += 4.0 * hq * d * pairs
        byts += 2.0 * keys * hkv * d * KV_BYTES + 2.0 * nq * hq * d * ACT_BYTES
    return flops, byts


def decode_items(lengths: Iterable[int]):
    """A decode row whose cache holds ``n`` tokens attends ``n + 1`` (the
    new token's K/V included): one query, ``n + 1`` pairs and keys."""
    return [(1, n + 1, n + 1) for n in lengths]


def chunk_items(chunks: Iterable[Tuple[int, int]]):
    """A prefill chunk of ``sl`` tokens starting at position ``m``: query
    ``j`` attends ``m + j + 1`` keys; the K/V of ``m + sl`` tokens are
    read."""
    return [(sl, sl * m + sl * (sl + 1) // 2, m + sl) for m, sl in chunks]


def roofline_s(flops: float, byts: float, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = byts / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


def dense_params(s: dict) -> float:
    """Matmul parameters of the layers (embedding and norms excluded)."""
    d, hq, hkv, dh, ff = (s["d"], s["heads"], s["kv_heads"], s["head_dim"],
                          s["ff"])
    return s["layers"] * (2.0 * d * hq * dh + 2.0 * d * hkv * dh
                          + 3.0 * d * ff)


def model_flops(n_tokens: int, n_logit_rows: int, attn_pairs: float,
                s: dict) -> float:
    """Model FLOPs of a step: ``2 * dense params`` per token through the
    layers, ``4 * Hq * D`` per query-key pair in every layer, and the
    ``2 * d * vocab`` projection of each row whose logits are computed."""
    return (2.0 * dense_params(s) * n_tokens
            + 4.0 * s["heads"] * s["head_dim"] * attn_pairs * s["layers"]
            + 2.0 * s["d"] * s["vocab"] * n_logit_rows)
