"""Pallas TPU kernel: streaming compaction metadata merge.

Same VMEM tiling as chain_resolve (pages on lanes, layers on sublanes) but
the reduction direction is bottom-up with last-write-wins, producing the
merged base layer the provider's streaming job writes (paper §4.1). The
data movement of streaming is the separate ``cow_gather`` pass over the
winning pointers.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import first_hit_walk

PAGE_TILE = 512


def _merge_kernel(alloc_ref, ptr_ref, found_ref, out_ptr_ref, src_ref):
    # last write wins bottom-up == the first hit walking down from the top
    def row_at(idx):
        return alloc_ref[pl.ds(idx, 1), :] != 0, ptr_ref[pl.ds(idx, 1), :]

    k = alloc_ref.shape[0]
    src, ptr = first_hit_walk(k, k, row_at,
                              jnp.zeros(out_ptr_ref.shape, jnp.uint32))
    found_ref[...] = (src >= 0).astype(jnp.uint32)
    out_ptr_ref[...] = ptr
    src_ref[...] = src


@partial(jax.jit, static_argnames=("interpret",))
def merge_pallas(alloc, ptrs, *, interpret: bool = True):
    """alloc/ptrs: (K, N), N a multiple of 128 → (found, ptr, src)."""
    k, n = alloc.shape
    tile = min(PAGE_TILE, n)
    in_spec = pl.BlockSpec((k, tile), lambda i: (0, i))
    out_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    found, ptr, src = pl.pallas_call(
        _merge_kernel,
        grid=(pl.cdiv(n, tile),),
        in_specs=[in_spec, in_spec],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.uint32),
            jax.ShapeDtypeStruct((1, n), jnp.uint32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(alloc.astype(jnp.uint32), ptrs.astype(jnp.uint32))
    return found[0] != 0, ptr[0], src[0]
