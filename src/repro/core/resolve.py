"""Page resolution: the vanilla chain walk vs sQEMU direct access.

Given a batch of logical page ids, resolution answers: *which snapshot owns
the latest version of each page, and at which pool row does it live?*

``resolve_vanilla``
    The vanilla Qcow2 strategy (paper §2): starting from the active volume,
    consult each backing file in turn until an allocated entry is found.
    On TPU this is expressed as a vectorized first-hit scan over the chain
    axis — the cost (bytes touched and index lookups) is O(chain length)
    per request, faithfully modelling the paper's Eq. 1 scaling.

``resolve_direct``
    The sQEMU strategy (paper §5.3): a single lookup of the active volume's
    L2 entry, which carries ``backing_file_index``. O(1) per request.
    Falls back to the chain walk for entries whose BFI_VALID bit is unset
    (vanilla-format images read by a scalable driver — backward compat).

Both return identical ``(owner, ptr)`` on scalable chains — a property the
test suite checks exhaustively (hypothesis) — because pool rows are global.

The actual lookup math lives in the ``*_tables`` helpers, which operate on
bare ``(C, n_pages, 2)`` L2 arrays plus a chain length. The single-chain
entry points are thin wrappers; ``core.fleet`` vmaps the same helpers over
a stacked tenant axis, so one implementation serves both scales.

A second implementation of each strategy lives in the Pallas kernels of
``kernels/chain_resolve``: the ``resolve_*_stacked`` functions here run
them over the whole stacked (T, C, P) fleet layout in one kernel launch
(compiled on TPU, interpret mode elsewhere — CI exercises the kernel
path on CPU); ``resolve_auto_stacked`` resolves a batch that is narrow
beside the table with one kernel that reads only the requested pages'
tile columns (``batch_path_ok``). Single chains reach the same kernels
through the ``"pallas_vanilla"``/``"pallas_direct"`` registry entries,
which view a chain as a one-tenant fleet. See ``docs/kernels.md``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import format as fmt
from repro.core.chain import Chain
from repro.kernels.chain_resolve import ops as _kernel_ops
from repro.kernels.common import LANES


class ResolveResult(NamedTuple):
    owner: jax.Array    # (B,) int32 — owning snapshot index; -1 if not found
    ptr: jax.Array      # (B,) uint32 — pool row (valid only where found);
                        # a host-tier row where ``cold``
    found: jax.Array    # (B,) bool
    zero: jax.Array     # (B,) bool — qcow2 "zero cluster"
    lookups: jax.Array  # (B,) int32 — #L2 consultations performed (cost)
    cold: jax.Array     # (B,) bool — hit lives in the host tier (FLAG_COLD);
                        # device gathers must mask it, promotion makes it hot


def tables_from_hits(owner: jax.Array, hit: jax.Array) -> jax.Array:
    """Direct block tables from a stacked first-hit resolve.

    ``owner``/``hit`` are the outputs of the fleet walk
    (``kernels.chain_resolve``): the owning layer per page (-1 = miss)
    and the owning layer's raw L2 word0. Returns int32 tables — the pool
    row where found, -1 holes — the exact shape the paged-attention
    plane consumes. Shared by the serving plane's table materialization
    and the fused-attention oracle so the hole convention cannot drift.
    """
    ptr = (hit & jnp.uint32(fmt.PTR_MASK)).astype(jnp.int32)
    return jnp.where(owner >= 0, ptr, -1)


def resolve_vanilla_tables(l2: jax.Array, length: jax.Array,
                           page_ids: jax.Array) -> ResolveResult:
    """First-hit scan from the active volume down the chain. O(chain).

    ``l2``: (C, n_pages, 2) uint32; ``length``: () int32; ``page_ids``: (B,).
    """
    max_chain = l2.shape[0]
    page_ids = page_ids.astype(jnp.int32)
    entries = l2[:, page_ids]                             # (C, B, 2)
    live = jnp.arange(max_chain, dtype=jnp.int32)[:, None] < length
    alloc = fmt.entry_allocated(entries) & live           # (C, B)
    idx = jnp.arange(max_chain, dtype=jnp.int32)[:, None]
    owner = jnp.max(jnp.where(alloc, idx, -1), axis=0)    # (B,)
    found = owner >= 0
    picked = jnp.take_along_axis(
        entries, jnp.maximum(owner, 0)[None, :, None], axis=0
    )[0]                                                   # (B, 2)
    # Walk cost: active volume down to the owner (inclusive); a miss walks
    # the entire chain.
    lookups = jnp.where(found, length - owner, length)
    return ResolveResult(
        owner=owner,
        ptr=fmt.entry_ptr(picked),
        found=found,
        zero=fmt.entry_zero(picked) & found,
        lookups=lookups.astype(jnp.int32),
        cold=fmt.entry_cold(picked) & found,
    )


def resolve_direct_tables(l2: jax.Array, length: jax.Array,
                          page_ids: jax.Array) -> ResolveResult:
    """Single active-volume lookup using backing_file_index. O(1)."""
    page_ids = page_ids.astype(jnp.int32)
    active = length - 1
    entries = jax.lax.dynamic_index_in_dim(l2, active, 0, keepdims=False)[page_ids]
    alloc = fmt.entry_allocated(entries)
    valid = fmt.entry_bfi_valid(entries)
    owner = jnp.where(alloc, fmt.entry_bfi(entries).astype(jnp.int32), -1)
    return ResolveResult(
        owner=owner,
        ptr=fmt.entry_ptr(entries),
        found=alloc & valid,
        zero=fmt.entry_zero(entries) & alloc,
        lookups=jnp.ones_like(page_ids),
        cold=fmt.entry_cold(entries) & alloc,
    )


def combine_auto(trust: jax.Array, direct: ResolveResult,
                 walk: ResolveResult) -> ResolveResult:
    """Field-wise pick of ``direct`` where ``trust`` else ``walk``.

    ``trust`` must be "the active entry is allocated AND carries a valid
    backing_file_index" — exactly ``direct.found``. Anything else
    (allocated-without-bfi, or an empty active volume after a vanilla
    snapshot) must fall back to the chain walk. Shared by the jnp and the
    Pallas-kernel auto resolvers so the mixed-image semantics cannot
    drift between implementations.
    """
    pick = lambda d, w: jnp.where(trust, d, w)
    return ResolveResult(
        owner=pick(direct.owner, walk.owner),
        ptr=pick(direct.ptr, walk.ptr),
        found=pick(direct.found, walk.found),
        zero=pick(direct.zero, walk.zero),
        lookups=pick(direct.lookups, walk.lookups),
        cold=pick(direct.cold, walk.cold),
    )


def resolve_auto_tables(l2: jax.Array, length: jax.Array,
                        page_ids: jax.Array) -> ResolveResult:
    """Direct access where BFI_VALID, chain walk otherwise.

    This is what the sQEMU driver actually does on mixed images (paper
    §5.1 backward compatibility): pages written by a vanilla tool lack the
    extension bits and are resolved by walking; scalable pages are O(1).
    """
    direct = resolve_direct_tables(l2, length, page_ids)
    walk = resolve_vanilla_tables(l2, length, page_ids)
    # direct.found is precisely the trust condition: the active entry is
    # allocated and its backing_file_index is valid (scalable-written).
    return combine_auto(direct.found, direct, walk)


_TABLE_RESOLVERS = {
    "vanilla": resolve_vanilla_tables,
    "direct": resolve_direct_tables,
    "auto": resolve_auto_tables,
}


# -- Pallas-kernel resolvers over the stacked (T, C, P, 2) fleet layout ------


def _walk_result(owner, hit, lengths) -> ResolveResult:
    """A first-hit walk's (T, B) owners and owning word0s as a result."""
    found = owner >= 0
    ln = lengths.astype(jnp.int32)[:, None]
    return ResolveResult(
        owner=owner.astype(jnp.int32),
        ptr=hit & jnp.uint32(fmt.PTR_MASK),
        found=found,
        # a miss returns hit == 0, so the ZERO/COLD bits read as False there
        zero=(hit & jnp.uint32(fmt.FLAG_ZERO)) != 0,
        lookups=jnp.where(found, ln - owner, ln).astype(jnp.int32),
        cold=(hit & jnp.uint32(fmt.FLAG_COLD)) != 0,
    )


def _direct_result(owner, h0, h1) -> ResolveResult:
    """The active layer's (T, B) raw words (and bfi owners) as a result."""
    alloc = (h0 & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0
    return ResolveResult(
        owner=owner.astype(jnp.int32),
        ptr=h0 & jnp.uint32(fmt.PTR_MASK),
        found=alloc & ((h1 & jnp.uint32(fmt.FLAG_BFI_VALID)) != 0),
        zero=((h0 & jnp.uint32(fmt.FLAG_ZERO)) != 0) & alloc,
        lookups=jnp.ones(owner.shape, jnp.int32),
        cold=((h0 & jnp.uint32(fmt.FLAG_COLD)) != 0) & alloc,
    )


def resolve_vanilla_stacked(l2: jax.Array, lengths: jax.Array,
                            page_ids: jax.Array) -> ResolveResult:
    """Kernel-backed first-hit walk for a whole fleet in one launch.

    ``l2``: (T, C, n_pages, 2) uint32 stacked tables; ``lengths``: (T,);
    ``page_ids``: (T, B). The kernel resolves every tenant's *full* page
    table (the walk cost is amortized across the read batch); the batch's
    owners/pointers are then a cheap per-tenant gather. Results are
    bit-identical to ``resolve_vanilla_tables`` vmapped over tenants.
    """
    ids = page_ids.astype(jnp.int32)
    owner_map, hit_map = _kernel_ops.resolve_vanilla_fleet(l2[..., 0], lengths)
    return _walk_result(jnp.take_along_axis(owner_map, ids, axis=1),
                        jnp.take_along_axis(hit_map, ids, axis=1), lengths)


def resolve_direct_stacked(l2: jax.Array, lengths: jax.Array,
                           page_ids: jax.Array) -> ResolveResult:
    """Kernel-backed direct access for a whole fleet in one launch.

    Same contract as ``resolve_vanilla_stacked`` but O(1) per page: the
    kernel's BlockSpec stages only each tenant's active layer (picked by
    the prefetched ``lengths``). Bit-identical to
    ``resolve_direct_tables`` vmapped over tenants.
    """
    ids = page_ids.astype(jnp.int32)
    maps = _kernel_ops.resolve_direct_fleet(l2[..., 0], l2[..., 1], lengths)
    return _direct_result(*(jnp.take_along_axis(m, ids, axis=1)
                            for m in maps))


def batch_path_ok(n_pages: int, batch: int) -> bool:
    """Shape rule of ``resolve_auto_stacked``: resolve only the batch's
    pages where the page axis is a 128-lane multiple and the batch's tile
    columns cover less than the table (``B * 128 < P``); a batch as wide
    as the table (a full grid) is cheaper as one whole-table pass."""
    return n_pages % LANES == 0 and batch * LANES < n_pages


def resolve_auto_stacked(l2: jax.Array, lengths: jax.Array,
                         page_ids: jax.Array) -> ResolveResult:
    """Kernel-backed mixed-image resolution, then the same
    ``combine_auto`` trust pick as the jnp auto resolver.

    Where ``batch_path_ok`` holds, one kernel reads only the requested
    pages' tile columns of the index, in place, and yields both the walk
    and the direct result of each; otherwise both whole-table kernels
    run and the batch is gathered out of their maps. Bit-identical."""
    if batch_path_ok(l2.shape[2], page_ids.shape[1]):
        ids = page_ids.astype(jnp.int32)
        owner, hit, h0, h1 = _kernel_ops.resolve_batch_fleet(l2, lengths, ids)
        alloc = (h0 & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0
        bfi = (h1 & jnp.uint32(fmt.BFI_MASK)).astype(jnp.int32)
        direct = _direct_result(jnp.where(alloc, bfi, -1), h0, h1)
        walk = _walk_result(owner, hit, lengths)
    else:
        direct = resolve_direct_stacked(l2, lengths, page_ids)
        walk = resolve_vanilla_stacked(l2, lengths, page_ids)
    return combine_auto(direct.found, direct, walk)


def _stacked_as_chain(fn):
    """Run a stacked kernel resolver on a single chain (a 1-tenant fleet)."""

    @jax.jit
    def resolver(chain: Chain, page_ids: jax.Array) -> ResolveResult:
        res = fn(chain.l2[None], chain.length[None], page_ids[None])
        return ResolveResult(*(leaf[0] for leaf in res))

    return resolver


@jax.jit
def resolve_vanilla(chain: Chain, page_ids: jax.Array) -> ResolveResult:
    return resolve_vanilla_tables(chain.l2, chain.length, page_ids)


@jax.jit
def resolve_direct(chain: Chain, page_ids: jax.Array) -> ResolveResult:
    return resolve_direct_tables(chain.l2, chain.length, page_ids)


@jax.jit
def resolve_auto(chain: Chain, page_ids: jax.Array) -> ResolveResult:
    return resolve_auto_tables(chain.l2, chain.length, page_ids)


_RESOLVERS = {
    "vanilla": resolve_vanilla,
    "direct": resolve_direct,
    "auto": resolve_auto,
    # kernel-backed paths (interpret mode off-TPU): a chain is a 1-tenant
    # fleet, so the stacked Pallas kernels serve single chains too
    "pallas_vanilla": _stacked_as_chain(resolve_vanilla_stacked),
    "pallas_direct": _stacked_as_chain(resolve_direct_stacked),
}


def lookup_resolver(registry: dict, name: str):
    """Shared registry lookup (chain-, table- and fleet-level registries)."""
    try:
        return registry[name]
    except KeyError:
        raise ValueError(
            f"unknown resolver {name!r}; expected one of {sorted(registry)}"
        ) from None


def get_resolver(name: str):
    return lookup_resolver(_RESOLVERS, name)


def get_table_resolver(name: str):
    """Table-level resolver (used by ``core.fleet`` under vmap)."""
    return lookup_resolver(_TABLE_RESOLVERS, name)
