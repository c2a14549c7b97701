"""Backend dispatch for paged decode attention.

Single dispatcher for every caller (the serving engine's fused decode step
routes here too):

* TPU backend          — the compiled Pallas kernel: one program per
  (row, split lane), each walking only its row's live tiles of
  ``kv_tile_blocks`` whole pool blocks (every KV head) with a
  double-buffered copy loop, ``split_k`` parallel partitions merged by the
  associative Softermax combine; with no tile given, T comes from the
  block shape (``ref.decode_tile_blocks``).
* ``interpret=True``   — the same kernel under the Pallas TPU interpreter
  (CPU CI exercises the exact walk/tile/split dataflow, its DMAs and
  semaphores, this way).
* anywhere else        — pure JAX: the gather oracle ``paged_decode_ref``.
  The tile/split parameters are *layout* knobs, not math knobs — every
  setting computes the identical attention — so the CPU fallback always
  runs the single-pass oracle (the fastest XLA evaluation) regardless of
  the requested tiling; ``paged_decode_split_ref`` exists for parity
  testing the partition structure itself.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.flash_decode_paged.flash_decode_paged import (
    flash_decode_paged, flash_decode_paged_single)
from repro.kernels.flash_decode_paged.ref import (decode_gather_oracle,
                                                  gather_kv, gather_scales,
                                                  gather_kv_dequant,
                                                  paged_decode_ref,
                                                  paged_decode_split_ref)


def flash_decode_paged_op(q, k_pool, v_pool, block_tables, lengths, *,
                          layer=None, k_scale=None, v_scale=None,
                          intmax: bool = True,
                          kv_tile_blocks: Optional[int] = None,
                          split_k: int = 1,
                          interpret: bool = False) -> jax.Array:
    kw = dict(layer=layer, k_scale=k_scale, v_scale=v_scale, intmax=intmax)
    if interpret or jax.default_backend() == "tpu":
        return flash_decode_paged(q, k_pool, v_pool, block_tables, lengths,
                                  kv_tile_blocks=kv_tile_blocks,
                                  split_k=split_k, interpret=interpret, **kw)
    return paged_decode_ref(q, k_pool, v_pool, block_tables, lengths, **kw)


__all__ = ["flash_decode_paged_op", "paged_decode_ref",
           "paged_decode_split_ref", "flash_decode_paged_single",
           "gather_kv", "gather_scales", "gather_kv_dequant",
           "decode_gather_oracle"]
