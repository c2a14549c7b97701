"""Backend dispatch for paged chunked-prefill attention.

Single dispatcher for every caller (the serving engine's chunk step routes
here too):

* TPU backend          — the compiled Pallas kernel (scalar-prefetch gather).
* ``interpret=True``   — the same kernel under the Pallas interpreter (CPU
  CI exercises the exact kernel dataflow this way).
* anywhere else        — pure JAX: the gather oracle ``paged_prefill_ref``,
  or the faster ``paged_prefill_split_ref`` when the caller passes
  ``split_tail_blocks`` (promising that the table width honors the split
  contract — exact cover or chunk-quantized; see ref.py). Identical math
  either way, so CPU serving stays fast (the interpreter is orders of
  magnitude slower than XLA on the same shapes).

``kv_tile_blocks`` is a kernel *layout* knob (pool blocks gathered per kv
grid step), not a math knob — the pure-JAX fallbacks compute the identical
attention and ignore it.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels.flash_prefill_paged.flash_prefill_paged import (
    flash_prefill_paged)
from repro.kernels.flash_prefill_paged.ref import (paged_prefill_ref,
                                                   paged_prefill_split_ref,
                                                   prefill_gather_oracle)


def flash_prefill_paged_op(q, k_pool, v_pool, block_tables, q_pos0, *,
                           layer=None, k_scale=None, v_scale=None,
                           intmax: bool = True,
                           kv_tile_blocks: int = 1,
                           interpret: bool = False,
                           split_tail_blocks: Optional[int] = None
                           ) -> jax.Array:
    kw = dict(layer=layer, k_scale=k_scale, v_scale=v_scale, intmax=intmax)
    if interpret or jax.default_backend() == "tpu":
        return flash_prefill_paged(q, k_pool, v_pool, block_tables, q_pos0,
                                   kv_tile_blocks=kv_tile_blocks,
                                   interpret=interpret, **kw)
    if split_tail_blocks is not None:
        return paged_prefill_split_ref(q, k_pool, v_pool, block_tables,
                                       q_pos0, tail_blocks=split_tail_blocks,
                                       **kw)
    return paged_prefill_ref(q, k_pool, v_pool, block_tables, q_pos0, **kw)


__all__ = ["flash_prefill_paged_op", "flash_prefill_paged",
           "paged_prefill_ref", "paged_prefill_split_ref",
           "prefill_gather_oracle"]
