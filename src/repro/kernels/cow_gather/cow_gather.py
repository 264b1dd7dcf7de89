"""Pallas TPU kernel: gather resolved pages from the HBM pool.

The classic scalar-prefetch dynamic-gather pattern: the resolved row ids
are prefetched as scalars, and each grid step's BlockSpec index_map picks
the pool block to stage into VMEM — the gather is free at the memory-system
level (one HBM→VMEM DMA per page, no scatter/gather ALU work). Rows of
``page_size`` are lane-aligned (pad to 128).

The pool is read in its own layout. On the chip an ``(R, P)`` 32-bit pool
lies in (8, 128) tiles, so its ``(R // 8, 8, P)`` view is a bitcast: each
grid step stages the 8-row sublane group holding the requested row and
picks the row in the kernel. Any other pool is read through an
``(R, 1, P)`` view, which XLA has to copy whole before every call: one
whose ``R`` is no multiple of 8 (small test fleets; ``lease_quantum``
keeps real pools a multiple of 64), or one of 8- or 16-bit pages, whose
packed tiles Mosaic cannot pick a row of at a dynamic offset.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import SUBLANES


def gather_group(n_rows: int, dtype) -> int:
    """Rows the gathers stage per page from an ``(n_rows, P)`` pool: its
    whole sublane group for a 32-bit pool whose ``n_rows`` is a multiple
    of 8 (a bitcast view), else one row (a view XLA copies whole)."""
    ok = jnp.dtype(dtype).itemsize == 4 and n_rows % SUBLANES == 0
    return SUBLANES if ok else 1


def gather_form(n_rows: int, dtype) -> str:
    """``"group"`` or ``"row"``: which view of the pool the gathers read."""
    return "group" if gather_group(n_rows, dtype) > 1 else "row"


def _pool_groups(pool):
    """``pool`` (R, P) as (R // g, g, P) groups of ``g = gather_group``
    rows, and ``g``."""
    r, p = pool.shape
    g = gather_group(r, pool.dtype)
    return pool.reshape(r // g, g, p), g


def _group_block(g, p, index_map):
    """A (g, p) block of the (R // g, g, P) view: a whole second-minor
    dim, which the TPU tiling accepts where a (1, p) block over (R, P) is
    refused."""
    return pl.BlockSpec((None, g, p), index_map)


def _pick(pool_ref, row, g, ok):
    """Row ``row % g`` of the staged group, or zeros where not ``ok``."""
    page = pool_ref[pl.ds(row % g, 1), :]
    return jnp.where(ok, page, jnp.zeros_like(page))


def _gather_kernel(rows_ref, found_ref, pool_ref, out_ref, *, g):
    i = pl.program_id(0)
    out_ref[...] = _pick(pool_ref, rows_ref[i], g, found_ref[i] != 0)


@partial(jax.jit, static_argnames=("interpret",))
def gather_pallas(pool, rows, found, *, interpret: bool = True):
    """pool: (R, P); rows: (B,); found: (B,) → (B, P)."""
    groups, g = _pool_groups(pool)
    p = pool.shape[1]
    b = rows.shape[0]
    safe_rows = jnp.where(found, rows, 0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[_group_block(g, p, lambda i, rows_ref, found_ref:
                               (rows_ref[i] // g, 0, 0))],
        out_specs=_group_block(1, p, lambda i, rows_ref, found_ref:
                               (i, 0, 0)),
    )
    out = pl.pallas_call(
        partial(_gather_kernel, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, p), pool.dtype),
        interpret=interpret,
    )(safe_rows, found.astype(jnp.int32), groups)
    return out.reshape(b, p)


def _gather_fleet_kernel(rows_ref, found_ref, pool_ref, out_ref, *, g):
    ti, bi = pl.program_id(0), pl.program_id(1)
    out_ref[...] = _pick(pool_ref, rows_ref[ti, bi], g,
                         found_ref[ti, bi] != 0)


@partial(jax.jit, static_argnames=("interpret",))
def gather_fleet_pallas(pool, rows, found, *, interpret: bool = True):
    """Stacked fleet gather: the pool is global, so one kernel serves every
    tenant. ``pool``: (R, P); ``rows``/``found``: (T, B) → (T, B, P).

    Same scalar-prefetch pattern as the single-chain gather, with a
    (tenant, request) grid: each grid step's index_map picks the pool
    group for one tenant's request out of the prefetched (T, B) row table.
    """
    groups, g = _pool_groups(pool)
    p = pool.shape[1]
    t, b = rows.shape
    safe_rows = jnp.where(found, rows, 0).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t, b),
        in_specs=[_group_block(g, p, lambda ti, bi, rows_ref, found_ref:
                               (rows_ref[ti, bi] // g, 0, 0))],
        out_specs=pl.BlockSpec((None, None, 1, p),
                               lambda ti, bi, rows_ref, found_ref:
                               (ti, bi, 0, 0)),
    )
    out = pl.pallas_call(
        partial(_gather_fleet_kernel, g=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, b, 1, p), pool.dtype),
        interpret=interpret,
    )(safe_rows, found.astype(jnp.int32), groups)
    return out.reshape(t, b, p)
