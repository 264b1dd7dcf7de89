"""Pallas TPU kernel: gather resolved pages from the HBM pool.

The classic scalar-prefetch dynamic-gather pattern: the resolved row ids
are prefetched as scalars, and each grid step's BlockSpec index_map picks
the pool row to stage into VMEM — the gather is free at the memory-system
level (one HBM→VMEM DMA per page, no scatter/gather ALU work). Rows of
``page_size`` are lane-aligned (pad to 128).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(rows_ref, found_ref, pool_ref, out_ref):
    ok = found_ref[pl.program_id(0)] != 0
    out_ref[...] = jnp.where(ok, pool_ref[...], jnp.zeros_like(pool_ref))


def _row_block(p, index_map):
    """A (1, p) block of a (..., 1, P) view: the squeezed leading dims
    leave a whole second-minor dim, which the TPU tiling accepts where a
    (1, p) block over (R, P) is refused."""
    return pl.BlockSpec((None, 1, p), index_map)


@partial(jax.jit, static_argnames=("interpret",))
def gather_pallas(pool, rows, found, *, interpret: bool = True):
    """pool: (R, P); rows: (B,); found: (B,) → (B, P)."""
    r, p = pool.shape
    b = rows.shape[0]
    safe_rows = jnp.where(found, rows, 0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[_row_block(p, lambda i, rows_ref, found_ref:
                             (rows_ref[i], 0, 0))],
        out_specs=_row_block(p, lambda i, rows_ref, found_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, p), pool.dtype),
        interpret=interpret,
    )(safe_rows, found.astype(jnp.int32), pool.reshape(r, 1, p))
    return out.reshape(b, p)


def _gather_fleet_kernel(rows_ref, found_ref, pool_ref, out_ref):
    ok = found_ref[pl.program_id(0), pl.program_id(1)] != 0
    out_ref[...] = jnp.where(ok, pool_ref[...], jnp.zeros_like(pool_ref))


@partial(jax.jit, static_argnames=("interpret",))
def gather_fleet_pallas(pool, rows, found, *, interpret: bool = True):
    """Stacked fleet gather: the pool is global, so one kernel serves every
    tenant. ``pool``: (R, P); ``rows``/``found``: (T, B) → (T, B, P).

    Same scalar-prefetch pattern as the single-chain gather, with a
    (tenant, request) grid: each grid step's index_map picks the pool row
    for one tenant's request out of the prefetched (T, B) row table.
    """
    r, p = pool.shape
    t, b = rows.shape
    safe_rows = jnp.where(found, rows, 0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, b),
        in_specs=[_row_block(p, lambda ti, bi, rows_ref, found_ref:
                             (rows_ref[ti, bi], 0, 0))],
        out_specs=pl.BlockSpec((None, None, 1, p),
                               lambda ti, bi, rows_ref, found_ref:
                               (ti, bi, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_fleet_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, b, 1, p), pool.dtype),
        interpret=interpret,
    )(safe_rows, found.astype(jnp.int32), pool.reshape(r, 1, p))
    return out.reshape(t, b, p)
