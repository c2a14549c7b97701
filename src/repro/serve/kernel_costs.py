"""Analytic per-launch cost model for the paged Pallas kernels — the
kernel cost observatory's measurement core.

Softermax's whole argument is a hardware cost model (energy/area per
softmax op); this module is the serving-side equivalent for our kernels:
closed-form accounting of what one ``flash_decode_paged`` /
``flash_prefill_paged`` launch *moves and computes*, as a pure function of
the launch geometry — ``(lengths, table_width, heads, block_size,
kv_tile_blocks, split_k, kv_dtype)``. Nothing here touches a device: the
numbers are derived from the same ``split_layout`` geometry the kernel
wrappers use, and they are pinned against the ref layer's *measuring*
oracles (``flash_decode_paged.ref.decode_gather_oracle`` /
``flash_prefill_paged.ref.prefill_gather_oracle``, which build the actual
gathered arrays and count bytes) by ``tests/test_kernel_costs.py``.

What is counted, and why it is exact:

* **Gather-DMA bytes, decode.** Grid ``(B, S)``: each program walks its
  lane's live tiles (``ref.live_tile_range``) and copies each live
  block of its row once, whole (``Hkv * BS * D * itemsize`` bytes of K,
  as many of V, plus the int8 pools' two lane-dense scale rows,
  ``ref.scale_rows``); columns past a row's live blocks are never
  copied, however wide the table. The ``B * W`` int32 block table,
  copied to SMEM as the scalar-prefetch operand, is counted too.
* **Gather-DMA bytes, prefill.** One pool block per (KV head, tile slot,
  grid step), unconditionally; ``@pl.when`` skips *compute* on tiles
  past the diagonal, not the DMA. The table is padded to ``Wp`` blocks
  (``split_layout``), and the walk re-streams once per query tile
  (``nq`` of them).
* **Waste bytes.** What a launch moves for table entries at or past a
  row's real block count (``ceil(len / BS)``; the engine's pow2
  bucketing and dead preallocated tail blocks produce them): prefill's
  masked block-0 gathers. Decode copies only live blocks and the table
  it needs to find them, so its waste is 0.
* **MXU FLOPs.** Per *computed* kv tile (a live tile of decode's walk,
  resp. the prefill diagonal check) the QK and AV dots each run their full tile
  shape regardless of masking: ``2 * rows * D * (T * BS)`` FLOPs apiece.
  Masked columns inside a computed tile still cost FLOPs (that is how the
  kernel runs) — only whole skipped tiles don't.
* **VMEM working set / grid steps / lanes.** The per-step tile residency
  and the grid decomposition, for roofline-style latency estimates.

``estimate_seconds`` turns a ``LaunchCost`` into a scalar latency proxy
under a ``CostParams`` machine model (HBM bandwidth, MXU rate, per-step
overhead, parallel cores). It is a *planning* model — monotone, smooth,
deliberately simple — used by ``serve/autotune.py`` to rank grid
candidates; absolute seconds are not the point, the argmin is.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import numpy as np

from repro.kernels.flash_decode_paged.ref import (SCALE_LANES,
                                                  decode_tile_blocks,
                                                  live_tile_range,
                                                  split_layout)

# storage itemsizes by resolved pool dtype name (np.dtype("bfloat16")
# does not exist, so a mapping instead of np.dtype().itemsize)
KV_ITEMSIZE: Dict[str, int] = {"float32": 4, "bfloat16": 2,
                               "float16": 2, "int8": 1}
SCALE_BYTES = 4          # f32 per-row scale siblings of an int8 pool
TABLE_ENTRY_BYTES = 4    # int32 block-table entries (scalar prefetch)
ACC_BYTES = 4            # kernels accumulate in f32


def _itemsize(kv_dtype: str) -> int:
    try:
        return KV_ITEMSIZE[kv_dtype]
    except KeyError:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                         f"expected one of {sorted(KV_ITEMSIZE)}")


@dataclasses.dataclass(frozen=True)
class LaunchCost:
    """Per-launch (= per-layer) cost of one paged kernel invocation.

    Extensive fields (bytes / FLOPs / steps) are for ONE launch; the
    engine runs the kernel once per layer inside the scan, so callers
    scale by ``n_layers`` (``scaled``) when accounting a whole model step.
    """

    kind: str                # "decode" | "prefill"
    grid_steps: int          # total grid iterations of the launch
    lanes: int               # parallel grid extent (B*S / B*Hkv*nq)
    steps_per_lane: int      # most sequential kv iterations of a lane
    gather_bytes: int        # KV (+scale) HBM->VMEM bytes the gather
    #                          moves (decode: and its SMEM block table)
    waste_bytes: int         # subset of gather_bytes spent on table
    #                          entries past a row's live blocks
    io_bytes: int            # non-gather operand traffic (q in, out/partials)
    flops: int               # MXU matmul FLOPs actually executed (QK + AV)
    merge_flops: int         # second-stage softermax_merge work (split-K)
    tile_bytes: int          # KV (+scale) bytes of ONE kv tile
    vmem_bytes: int          # per-step VMEM working set (tiles + scratch)

    @property
    def useful_bytes(self) -> int:
        return self.gather_bytes - self.waste_bytes

    def scaled(self, n: int) -> "LaunchCost":
        """The extensive fields times ``n`` (e.g. launches per model
        step = n_layers); per-step intensities (tile/vmem) unchanged."""
        return dataclasses.replace(
            self, grid_steps=self.grid_steps * n,
            gather_bytes=self.gather_bytes * n,
            waste_bytes=self.waste_bytes * n, io_bytes=self.io_bytes * n,
            flops=self.flops * n, merge_flops=self.merge_flops * n)

    def to_dict(self) -> Dict[str, int]:
        d = dataclasses.asdict(self)
        d["useful_bytes"] = self.useful_bytes
        return d


def _block_bytes(block_size: int, head_dim: int, kv_dtype: str) -> int:
    """Bytes one gathered pool block moves: K + V values, plus the f32
    scale rows when the pool is int8 (scales ride the same gather)."""
    b = 2 * block_size * head_dim * _itemsize(kv_dtype)
    if kv_dtype == "int8":
        b += 2 * block_size * SCALE_BYTES
    return b


def decode_launch_cost(
    lengths: Sequence[int],   # (B,) kv lengths the kernel attends (new_len)
    table_width: int,         # W — table width as passed to the kernel
    *,
    n_q_heads: int,
    n_kv_heads: int,
    head_dim: int,
    block_size: int,
    kv_tile_blocks: Optional[int] = None,   # None: the kernel's derived T
    split_k: int = 1,
    kv_dtype: str = "float32",
) -> LaunchCost:
    """Cost of one ``flash_decode_paged`` launch (one layer).

    Mirrors the kernel wrapper exactly: the same derived T when none is
    given, ``split_layout``'s clamp, a grid ``(B, S)`` whose programs walk
    only their lane's live tiles (``ref.live_tile_range``), copying each
    live block once, and computing every tile they walk. ``grid_steps``
    is ``B * S``, ``steps_per_lane`` the most tiles one lane walks.
    """
    B = len(lengths)
    W, BS, D = table_width, block_size, head_dim
    Hq, Hkv = n_q_heads, n_kv_heads
    G = Hq // Hkv
    if kv_tile_blocks is None:
        kv_tile_blocks = decode_tile_blocks(BS, Hkv, D, _itemsize(kv_dtype))
    T, S, spl, _ = split_layout(W, kv_tile_blocks, split_k)
    bb = Hkv * 2 * BS * D * _itemsize(kv_dtype)       # one whole block
    if kv_dtype == "int8":                            # + its scale rows
        bb += 2 * -(-Hkv * BS // SCALE_LANES) * SCALE_LANES * SCALE_BYTES

    lens = np.asarray(lengths, np.int64)
    lo, hi, n_blocks = live_tile_range(lens[:, None], np.arange(S)[None, :],
                                       spl, T, BS, W, xp=np)
    walked = np.maximum(hi - lo, 0)                   # (B, S) tiles
    gather = int(n_blocks[:, 0].sum()) * bb + B * W * TABLE_ENTRY_BYTES
    tiles = int(walked.sum())
    flops = tiles * Hkv * 4 * G * D * T * BS          # QK + AV full tiles
    merge = B * Hq * D * 8 * S if S > 1 else 0        # jnp merge stage

    q_in = B * Hq * D * ACC_BYTES
    part_out = B * S * Hkv * (G * D + 2 * G) * ACC_BYTES
    vmem = (Hkv * G * D * ACC_BYTES                   # q tile (f32 in-kernel)
            + 2 * T * bb                              # 2 x T-block buffers
            + Hkv * (G * D + 2 * G) * ACC_BYTES       # acc/m/d scratch
            + Hkv * (G * D + 2 * G) * ACC_BYTES)      # partial outputs
    return LaunchCost(kind="decode", grid_steps=B * S,
                      lanes=B * S, steps_per_lane=int(walked.max(initial=0)),
                      gather_bytes=gather, waste_bytes=0,
                      io_bytes=q_in + part_out, flops=flops,
                      merge_flops=merge, tile_bytes=T * bb,
                      vmem_bytes=vmem)


def prefill_launch_cost(
    q_len: int,               # Sq — chunk length as passed (incl. padding)
    q_pos0: Sequence[int],    # (B,) absolute position of each row's q[0]
    cover_blocks: Sequence[int],   # (B,) REAL table entries per row (the
    #                                rest of the width is block-0 padding)
    table_width: int,         # W — table width as passed to the kernel
    *,
    n_q_heads: int,
    n_kv_heads: int,
    head_dim: int,
    block_size: int,
    kv_tile_blocks: int = 1,
    block_q: int = 128,
    kv_dtype: str = "float32",
) -> LaunchCost:
    """Cost of one ``flash_prefill_paged`` launch (one layer).

    The kv walk re-streams once per query tile (grid ``(B*Hkv, nq, nk)``),
    compute is skipped for tiles entirely above the causal diagonal
    (``k_start <= q_start + BQ - 1``), and table entries at or past a
    row's real cover are clamped block-0 waste.
    """
    B = len(q_pos0)
    if len(cover_blocks) != B:
        raise ValueError("q_pos0 and cover_blocks must align per row")
    W, BS, D = table_width, block_size, head_dim
    Hq, Hkv = n_q_heads, n_kv_heads
    G = Hq // Hkv
    T, _, nk, Wp = split_layout(W, kv_tile_blocks, 1)
    BQ = min(block_q, q_len)
    Sqp = -(-q_len // BQ) * BQ
    nq = Sqp // BQ
    bb = _block_bytes(BS, D, kv_dtype)

    gather = B * Hkv * nq * Wp * bb
    waste = sum(Hkv * nq * (Wp - min(int(c), Wp)) * bb
                for c in cover_blocks)

    flops = 0
    for p0 in q_pos0:
        for i in range(nq):
            q_end = int(p0) + i * BQ + BQ - 1
            ct = min(q_end // (T * BS) + 1, nk)        # diagonal check
            flops += ct * Hkv * 4 * G * BQ * D * T * BS
    q_in = B * Hq * Sqp * D * ACC_BYTES
    out = B * Hq * Sqp * D * ACC_BYTES
    vmem = (G * BQ * D * ACC_BYTES + T * bb
            + (G * BQ * D + 2 * G * BQ) * ACC_BYTES
            + G * BQ * D * ACC_BYTES)
    return LaunchCost(kind="prefill", grid_steps=B * Hkv * nq * nk,
                      lanes=B * Hkv * nq, steps_per_lane=nk,
                      gather_bytes=gather, waste_bytes=waste,
                      io_bytes=q_in + out, flops=flops, merge_flops=0,
                      tile_bytes=T * bb, vmem_bytes=vmem)


# ---------------------------------------------------------------------------
# Latency proxy (the planner's objective)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CostParams:
    """Machine model for ``estimate_seconds``. The defaults are
    TPU-shaped round numbers (HBM ~0.8 TB/s, MXU ~20 f32 TFLOP/s,
    megacore = 2 parallel cores); they are planning weights, not
    measurements — the planner only consumes the argmin over candidates,
    which is robust to the absolute scale. Raise ``cores`` on parts with
    more parallel lanes (it is what makes split-K pay for its padding)."""

    hbm_bytes_per_s: float = 8.0e11
    flops_per_s: float = 2.0e13
    grid_step_overhead_s: float = 2e-6   # per sequential grid iteration
    launch_overhead_s: float = 1e-5
    cores: int = 2                       # parallel lanes executed at once


DEFAULT_COST_PARAMS = CostParams()


def estimate_seconds(cost: LaunchCost,
                     params: CostParams = DEFAULT_COST_PARAMS) -> float:
    """Scalar latency proxy for one launch: fixed launch overhead, the
    sequential grid-iteration wall (lanes spread over ``cores``, each
    walking its ``steps_per_lane`` kv steps), and the throughput floor —
    whichever of HBM streaming or MXU compute binds — plus the split
    merge. Monotone in every extensive cost, which is all the planner's
    argmin needs."""
    wall_steps = math.ceil(cost.lanes / params.cores) * cost.steps_per_lane
    t_overhead = (params.launch_overhead_s
                  + wall_steps * params.grid_step_overhead_s)
    t_stream = max((cost.gather_bytes + cost.io_bytes)
                   / params.hbm_bytes_per_s,
                   cost.flops / params.flops_per_s)
    t_merge = cost.merge_flops / params.flops_per_s
    return t_overhead + t_stream + t_merge
