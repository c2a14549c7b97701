"""Shares of the chip's peaks, from a traced run's work records and its
trace; the per-layer readers in ``bench/metrics/`` call these. Each
returns None where the traced window held none of the work it
measures."""
from __future__ import annotations

from typing import Optional

from bench import work

DECODE_KERNEL = "flash_decode_paged"
PREFILL_KERNEL = "flash_prefill_paged"
DECODE_PROGRAM = "jit__decode_fn"
CHUNK_PROGRAM = "jit__prefill_chunk_fn"


def _roofline(calls, kernel_s: float, ctx) -> Optional[float]:
    """100 x (least time of every call, summed) / measured kernel time.
    ``calls`` lists one item list per kernel launch of one layer."""
    if not calls or kernel_s <= 0:
        return None
    least = 0.0
    for items in calls:
        f, b = work.attention(items, ctx.sizes)
        least += work.roofline_s(f, b, ctx.peaks)[0]
    return 100.0 * least * ctx.sizes["layers"] / kernel_s


def decode_roofline(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    calls = [work.decode_items(rows) for rows in ctx.decode_rows if rows]
    return _roofline(calls, ctx.trace.ops_matching(DECODE_KERNEL)[0], ctx)


def prefill_roofline(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    calls = [work.chunk_items([c]) for c in ctx.chunks]
    return _roofline(calls, ctx.trace.ops_matching(PREFILL_KERNEL)[0], ctx)


def decode_flops(ctx) -> float:
    return sum(work.model_flops(len(rows), len(rows),
                                sum(n + 1 for n in rows), ctx.sizes)
               for rows in ctx.decode_rows if rows)


def chunk_flops(ctx) -> float:
    return sum(work.model_flops(sl, 1, sl * m + sl * (sl + 1) // 2,
                                ctx.sizes) for m, sl in ctx.chunks)


def mfu(flops: float, seconds: float, ctx) -> Optional[float]:
    if flops <= 0 or seconds <= 0:
        return None
    return 100.0 * flops / (seconds * ctx.peaks["bf16_flops_per_s"])


def idle_share(ctx) -> Optional[float]:
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
