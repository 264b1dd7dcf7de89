"""Pallas TPU kernels for chain resolution — single-chain and fleet layouts.

The vanilla path is the paper's chain walk recast for a TPU: a first-hit
reduction over the chain axis instead of a per-request pointer chase, with
bytes-touched cost O(C) per page. The direct kernel touches one layer:
O(1). The ``*_fleet_pallas`` entry points extend both to the stacked
(T, C, P) multi-tenant layout of ``core.fleet``: the grid runs over the
tenant axis, per-tenant chain ``length`` is prefetched as a scalar (the
direct kernel's BlockSpec index_map stages *only* the sublane group
holding each tenant's active layer), and the fleet kernels consume the packed L2 words of
``core.format`` directly — the kernel reads the actual table format, as
the paper's sQemu data plane does. ``resolve_batch_fleet_pallas``
resolves only a batch of requested pages, reading each one's 128-page
tile column of the packed index in place.

See ``docs/kernels.md`` for the full cost model, tiling constraints
(pages on the 128-lane axis, chain axis in sublanes) and the
interpret-mode CI story.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import format as fmt
from repro.kernels.common import LANES, SUBLANES, first_hit_walk

PAGE_TILE = 512  # lanes per grid step (4 × 128)
WALK_UNROLL = 8  # layers per loop step of the batch kernel's walk


def _vanilla_kernel(length_ref, alloc_ref, ptr_ref, owner_ref, out_ptr_ref):
    def row_at(idx):
        return (alloc_ref[pl.ds(idx, 1), :] != 0, ptr_ref[pl.ds(idx, 1), :])

    owner, ptr = first_hit_walk(length_ref[0], alloc_ref.shape[0], row_at,
                                jnp.zeros(out_ptr_ref.shape, jnp.uint32))
    owner_ref[...] = owner
    out_ptr_ref[...] = ptr


@partial(jax.jit, static_argnames=("interpret",))
def resolve_vanilla_pallas(alloc, ptrs, length, *, interpret: bool = True):
    """alloc/ptrs: (C, N); length scalar. N must be a multiple of 128."""
    c, n = alloc.shape
    n_tiles = pl.cdiv(n, PAGE_TILE)
    tile = min(PAGE_TILE, n)
    owner, ptr = pl.pallas_call(
        _vanilla_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((c, tile), lambda i, ln: (0, i)),
                pl.BlockSpec((c, tile), lambda i, ln: (0, i)),
            ],
            out_specs=[
                pl.BlockSpec((1, tile), lambda i, ln: (0, i)),
                pl.BlockSpec((1, tile), lambda i, ln: (0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.uint32),
        ],
        interpret=interpret,
    )(jnp.asarray(length, jnp.int32)[None], alloc.astype(jnp.uint32),
      ptrs.astype(jnp.uint32))
    return owner[0], ptr[0]


def _direct_kernel(alloc_ref, bfi_ref, ptr_ref, owner_ref, out_ptr_ref):
    a = alloc_ref[...] != 0
    owner_ref[...] = jnp.where(a, bfi_ref[...].astype(jnp.int32), -1)
    out_ptr_ref[...] = jnp.where(a, ptr_ref[...], jnp.uint32(0))


@partial(jax.jit, static_argnames=("interpret",))
def resolve_direct_pallas(alloc_active, bfi_active, ptrs_active, *,
                          interpret: bool = True):
    """All inputs (N,). One VMEM pass over the active layer only."""
    n = alloc_active.shape[0]
    tile = min(PAGE_TILE, n)
    spec2 = pl.BlockSpec((1, tile), lambda i: (0, i))
    owner, ptr = pl.pallas_call(
        _direct_kernel,
        grid=(pl.cdiv(n, tile),),
        in_specs=[spec2, spec2, spec2],
        out_specs=[spec2, spec2],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.uint32),
        ],
        interpret=interpret,
    )(alloc_active.astype(jnp.uint32)[None], bfi_active.astype(jnp.uint32)[None],
      ptrs_active.astype(jnp.uint32)[None])
    return owner[0], ptr[0]


# -- stacked (T, C, P) fleet layout ------------------------------------------


def _vanilla_fleet_kernel(length_ref, w0_ref, owner_ref, hit_ref):
    def row_at(idx):
        w = w0_ref[pl.ds(idx, 1), :]
        return (w & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0, w

    owner, hit = first_hit_walk(length_ref[pl.program_id(0)],
                                w0_ref.shape[0], row_at,
                                jnp.zeros(hit_ref.shape, jnp.uint32))
    owner_ref[...] = owner
    hit_ref[...] = hit


def _row_out(tile):
    """Out block of the fleet kernels: one tenant's row of a (T, 1, P)
    array. The squeezed tenant dim leaves a (1, tile) block over a (1, P)
    extent — a whole second-minor dim, which the TPU tiling accepts where
    a (1, tile) block over (T, P) is refused."""
    return pl.BlockSpec((None, 1, tile), lambda ti, pi, *prefetch: (ti, 0, pi))


def _row_shapes(t, p, *dtypes):
    return [jax.ShapeDtypeStruct((t, 1, p), d) for d in dtypes]


@partial(jax.jit, static_argnames=("interpret",))
def resolve_vanilla_fleet_pallas(w0, lengths, *, interpret: bool = True):
    """Stacked first-hit chain walk over every tenant's full page table.

    ``w0``: (T, C, P) uint32 — packed L2 word0 (``core.format`` layout:
    ALLOCATED/ZERO flags + pool ptr); ``lengths``: (T,) int32. P should be
    a multiple of 128 (``ops.resolve_vanilla_fleet`` pads).

    Returns ``(owner (T, P) int32 [-1 if absent], hit (T, P) uint32)``
    where ``hit`` is the owning layer's raw word0 (0 where absent).
    """
    t, c, p = w0.shape
    tile = min(PAGE_TILE, p)
    owner, hit = pl.pallas_call(
        _vanilla_fleet_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(t, pl.cdiv(p, tile)),
            in_specs=[
                pl.BlockSpec((None, c, tile), lambda ti, pi, ln: (ti, 0, pi)),
            ],
            out_specs=[_row_out(tile), _row_out(tile)],
        ),
        out_shape=_row_shapes(t, p, jnp.int32, jnp.uint32),
        interpret=interpret,
    )(lengths.astype(jnp.int32), w0.astype(jnp.uint32))
    return owner[:, 0], hit[:, 0]


def _direct_fleet_kernel(length_ref, group_ref, w0_ref, w1_ref, owner_ref,
                         h0_ref, h1_ref):
    # the block is the sublane group holding the active layer; pick its row
    r = (length_ref[pl.program_id(0)] - 1) % w0_ref.shape[0]
    w0 = w0_ref[pl.ds(r, 1), :]
    w1 = w1_ref[pl.ds(r, 1), :]
    alloc = (w0 & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0
    bfi = (w1 & jnp.uint32(fmt.BFI_MASK)).astype(jnp.int32)
    owner_ref[...] = jnp.where(alloc, bfi, -1)
    h0_ref[...] = w0
    h1_ref[...] = w1


@partial(jax.jit, static_argnames=("interpret",))
def resolve_direct_fleet_pallas(w0, w1, lengths, *, interpret: bool = True):
    """Stacked sQEMU direct access: one layer per tenant, picked by the
    BlockSpec index_map from the prefetched ``lengths`` — only the
    sublane group (``SUBLANES`` layers) holding each tenant's active layer
    is ever staged into VMEM, so the bytes-touched cost is O(1) per page
    regardless of chain length.

    ``w0``/``w1``: (T, C, P) uint32 packed L2 words; ``lengths``: (T,).

    Returns ``(owner (T, P) int32 [-1 if unallocated], h0 (T, P) uint32,
    h1 (T, P) uint32)`` — the active layer's raw entry words.
    """
    t, c, p = w0.shape
    tile = min(PAGE_TILE, p)
    sub = min(SUBLANES, c)
    lengths = lengths.astype(jnp.int32)
    in_spec = pl.BlockSpec((None, sub, tile),
                           lambda ti, pi, ln, grp: (ti, grp[ti], pi))
    owner, h0, h1 = pl.pallas_call(
        _direct_fleet_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(t, pl.cdiv(p, tile)),
            in_specs=[in_spec, in_spec],
            out_specs=[_row_out(tile)] * 3,
        ),
        out_shape=_row_shapes(t, p, jnp.int32, jnp.uint32, jnp.uint32),
        interpret=interpret,
    )(lengths, (lengths - 1) // sub, w0.astype(jnp.uint32),
      w1.astype(jnp.uint32))
    return owner[:, 0], h0[:, 0], h1[:, 0]


def _put_lane(out_ref, b, value):
    """Write the (1, 1) ``value`` into lane ``b`` of the resident row
    ``out_ref``, leaving its other lanes as they are."""
    at_b = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1) == b
    out_ref[...] = jnp.where(at_b, value, out_ref[...])


def _batch_fleet_kernel(length_ref, tile_ref, lane_ref, col_ref, owner_ref,
                        hit_ref, h0_ref, h1_ref):
    t, b = pl.program_id(0), pl.program_id(1)
    n_layers = col_ref.shape[0]
    length = length_ref[t]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    at_page = lanes == lane_ref[t, b]

    # the page's lane of ``row``, as a (1, 1) vector of ``dtype`` (summed
    # as int32: Mosaic reduces no unsigned type)
    def pick(row, dtype):
        bits = jax.lax.bitcast_convert_type(row, jnp.int32)
        lane = jnp.sum(jnp.where(at_page, bits, 0), axis=1, keepdims=True)
        return jax.lax.bitcast_convert_type(lane, dtype)

    # the walk: the highest live allocated layer of every lane in the
    # column, found by one ascending pass in which a later hit wins
    def layer(i, carry):
        owner, hit = carry
        w0 = col_ref[i, pl.ds(0, 1), :]
        take = ((w0 & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0) & (i < length)
        return jnp.where(take, i, owner), jnp.where(take, w0, hit)

    def group(g, carry):  # WALK_UNROLL layers per loop step
        for j in range(WALK_UNROLL):
            carry = layer(g * WALK_UNROLL + j, carry)
        return carry

    n_groups = n_layers // WALK_UNROLL
    carry = jax.lax.fori_loop(0, n_groups, group,
                              (jnp.full((1, LANES), -1, jnp.int32),
                               jnp.zeros((1, LANES), jnp.uint32)))
    for i in range(n_groups * WALK_UNROLL, n_layers):
        carry = layer(i, carry)
    owner, hit = carry
    active = col_ref[jnp.clip(length - 1, 0, n_layers - 1)]      # (2, 128)
    _put_lane(owner_ref, b, pick(owner, jnp.int32))
    _put_lane(hit_ref, b, pick(hit, jnp.uint32))
    _put_lane(h0_ref, b, pick(active[0:1], jnp.uint32))
    _put_lane(h1_ref, b, pick(active[1:2], jnp.uint32))


@partial(jax.jit, static_argnames=("interpret",))
def resolve_batch_fleet_pallas(l2t, lengths, ids, *, interpret: bool = True):
    """Walk and direct results of the requested pages only.

    ``l2t``: (T, C, 2, P) uint32 — the packed L2 index with the entry's
    two words second-minor (``jnp.swapaxes(l2, 2, 3)``: on a TPU this is
    the index's own HBM layout, so the view costs no copy); ``lengths``:
    (T,) int32; ``ids``: (T, B) int32 page ids in ``[0, P)``, P a
    multiple of 128.

    Grid (T, B): each step stages the requested page's 128-page tile
    column across the whole chain — both words of every layer, picked by
    the index_map from the prefetched id — and reduces it to that page's
    results. Two consecutive steps that ask for the same tile fetch it
    once.

    Returns ``(owner, hit, h0, h1)``, each (T, B): the first-hit walk's
    owning layer (-1 if absent) and that layer's word0 (0 if absent), as
    ``resolve_vanilla_fleet_pallas`` gives them, and the active layer's
    raw words, as ``resolve_direct_fleet_pallas`` does.
    """
    t, c, _, p = l2t.shape
    b = ids.shape[1]
    ids = ids.astype(jnp.int32)
    # the tile is clamped into the index: a DMA is not bounds-checked
    tiles = jnp.clip(ids // LANES, 0, p // LANES - 1)
    col = pl.BlockSpec((None, c, 2, LANES),
                       lambda ti, bi, ln, tile, lane: (ti, 0, 0, tile[ti, bi]))
    out = pl.BlockSpec((None, 1, b), lambda ti, bi, *prefetch: (ti, 0, 0))
    res = pl.pallas_call(
        _batch_fleet_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(t, b),
            in_specs=[col],
            out_specs=[out] * 4,
        ),
        out_shape=[jax.ShapeDtypeStruct((t, 1, b), d) for d in
                   (jnp.int32, jnp.uint32, jnp.uint32, jnp.uint32)],
        interpret=interpret,
    )(lengths.astype(jnp.int32), tiles, ids % LANES, l2t.astype(jnp.uint32))
    return tuple(r[:, 0] for r in res)
