"""ChainFleet: N independent snapshot chains over one shared page pool.

The paper's evaluation (and ``chain.py``) operates on one chain at a time,
but the cloud trace in §3 is thousands of tenant disks hitting a shared
storage backend concurrently. ``ChainFleet`` is the fleet-granularity
substrate: a *stacked* representation of ``n_tenants`` chains —

* per-tenant L1/L2 index stacks ``(T, max_chain, ...)`` and per-tenant
  chain ``length`` / ``scalable`` / ``overflow`` state;
* **one global page pool** shared by every tenant (the single-HBM analogue
  of the provider's backend), carved into fixed-size *lease quanta* by a
  fleet-level allocator: a tenant acquires whole quanta on demand, and its
  n-th allocated row lives at ``lease_index[t, n // Q] * Q + n % Q``.
  Leases are disjoint, so concurrent tenant writes never collide and a
  tenant exhausting the pool flags only its own ``overflow``.

Every data-path operation is batched across the fleet inside a single jit:

* ``resolve_{vanilla,direct,auto}`` vmap the table-level resolvers from
  ``core.resolve`` over the tenant axis — one dispatch for the whole
  fleet instead of T dispatches (and T re-traces) of the per-chain path;
  the ``"pallas_vanilla"``/``"pallas_direct"`` resolver methods run the
  stacked (T, C, P) Pallas kernels of ``kernels/chain_resolve`` instead
  (compiled on TPU, interpret mode elsewhere), and ``method="auto"``
  picks the kernel path whenever the layout qualifies (page axis already
  a 128-lane multiple — see ``docs/kernels.md``);
* ``write`` performs fleet-wide COW: lease acquisition, pool scatter and
  per-tenant L1/L2 stamping for all tenants at once, with an optional
  per-tenant mask for partial batches;
* ``snapshot`` snapshots any subset of tenants, honouring each tenant's
  format flag (mixed scalable/vanilla fleets are first-class: ``scalable``
  is a traced per-tenant array, not a static).

The single-chain paths in ``chain.py``/``resolve.py`` share the same
helpers (``write_tables``, ``copy_forward_tables``, ``*_tables``
resolvers), so fleet and chain semantics cannot drift apart; the test
suite additionally property-checks per-tenant fleet resolution against a
python loop over single chains.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import chain as chain_lib
from repro.core import format as fmt
from repro.core import resolve as resolve_lib
from repro.core.chain import Chain, ChainSpec
from repro.kernels.cow_gather import ops as cow_ops


@dataclasses.dataclass(frozen=True)
class FleetSpec:
    """Static geometry of a fleet (hashable; safe as a jit static arg)."""

    n_tenants: int
    n_pages: int
    page_size: int
    max_chain: int
    pool_capacity: int       # global pool rows shared by the whole fleet
    lease_quantum: int = 64  # pool rows acquired per lease
    l2_per_table: int = 64
    slice_len: int = 16
    dtype: Any = jnp.float32

    def __post_init__(self):
        if self.n_tenants < 1:
            raise ValueError("n_tenants must be >= 1")
        if self.pool_capacity % self.lease_quantum != 0:
            raise ValueError("pool_capacity must be a multiple of lease_quantum")
        # delegate the per-chain validations (bit widths, divisibility)
        self.chain_spec()

    @property
    def n_quanta(self) -> int:
        return self.pool_capacity // self.lease_quantum

    @property
    def n_l1(self) -> int:
        return self.n_pages // self.l2_per_table

    def chain_spec(self) -> ChainSpec:
        """The per-tenant view: same geometry, the shared (global) pool."""
        return ChainSpec(
            n_pages=self.n_pages,
            page_size=self.page_size,
            max_chain=self.max_chain,
            pool_capacity=self.pool_capacity,
            l2_per_table=self.l2_per_table,
            slice_len=self.slice_len,
            dtype=self.dtype,
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChainFleet:
    spec: FleetSpec = dataclasses.field(metadata=dict(static=True))
    l1: jax.Array           # (T, max_chain, n_l1) uint32
    l2: jax.Array           # (T, max_chain, n_pages, 2) uint32
    pool: jax.Array         # (pool_capacity, page_size) dtype — shared
    lease_owner: jax.Array  # (n_quanta,) int32 — owning tenant, -1 = free
    lease_index: jax.Array  # (T, n_quanta) int32 — quantum ids in lease order
    lease_count: jax.Array  # (T,) int32 — leases held per tenant
    alloc_count: jax.Array  # (T,) int32 — pool rows allocated per tenant
    length: jax.Array       # (T,) int32 — chain length per tenant
    scalable: jax.Array     # (T,) bool — per-tenant format flag
    overflow: jax.Array     # (T,) bool — per-tenant pool-lease exhaustion
    snap_dropped: jax.Array  # (T,) bool — snapshot attempted at max_chain
    cold_count: jax.Array   # (T,) int32 — host-tier rows held per tenant
                            # (maintained by demote/promote_tenants)

    @property
    def n_tenants(self) -> int:
        return self.spec.n_tenants

    @property
    def active(self) -> jax.Array:
        return self.length - 1


def create(spec: FleetSpec, *, scalable=True) -> ChainFleet:
    """A fresh fleet: every tenant is a chain of length 1 with no leases.

    ``scalable`` may be a python bool (uniform fleet) or a (T,) bool array
    (mixed deployment: some tenants on the vanilla format).
    """
    t = spec.n_tenants
    scal = jnp.broadcast_to(jnp.asarray(scalable, bool), (t,))
    return ChainFleet(
        spec=spec,
        l1=jnp.zeros((t, spec.max_chain, spec.n_l1), jnp.uint32),
        l2=fmt.empty_entries((t, spec.max_chain, spec.n_pages)),
        pool=jnp.zeros((spec.pool_capacity, spec.page_size), spec.dtype),
        lease_owner=jnp.full((spec.n_quanta,), -1, jnp.int32),
        lease_index=jnp.full((t, spec.n_quanta), -1, jnp.int32),
        lease_count=jnp.zeros((t,), jnp.int32),
        alloc_count=jnp.zeros((t,), jnp.int32),
        length=jnp.ones((t,), jnp.int32),
        scalable=scal,
        overflow=jnp.zeros((t,), bool),
        snap_dropped=jnp.zeros((t,), bool),
        cold_count=jnp.zeros((t,), jnp.int32),
    )


# -- fleet allocator ---------------------------------------------------------


def _acquire_leases(fleet: ChainFleet, rows_needed: jax.Array):
    """Grant each tenant enough fresh quanta to cover ``rows_needed`` more
    rows. Fully vectorized: free quanta are ranked once and handed out in
    tenant order via an exclusive cumsum. Returns the updated lease state
    plus a per-tenant "went short" flag.
    """
    spec = fleet.spec
    q = spec.lease_quantum
    nq = spec.n_quanta
    t = spec.n_tenants

    new_total = fleet.alloc_count + rows_needed
    want_leases = jnp.maximum(-(-new_total // q) - fleet.lease_count, 0)

    free = fleet.lease_owner < 0
    free_ids = jnp.nonzero(free, size=nq, fill_value=-1)[0]     # (nq,)
    n_free = jnp.sum(free)

    start = jnp.cumsum(want_leases) - want_leases               # (T,) exclusive
    j = jnp.arange(nq, dtype=jnp.int32)[None, :]                # (1, nq)
    want = j < want_leases[:, None]                             # (T, nq)
    src = start[:, None] + j
    ok = want & (src < n_free)
    grant = jnp.where(ok, free_ids[jnp.clip(src, 0, nq - 1)], -1)  # (T, nq)
    # compare against want_leases itself, not the (T, nq) grid: one batch can
    # want more quanta than the whole pool holds (want_leases > nq)
    short = jnp.sum(ok, axis=1) < want_leases

    tids = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None], (t, nq))
    # drop-sentinel must be out-of-bounds HIGH: negative indices wrap in JAX
    # scatters even under mode="drop".
    scatter_idx = jnp.where(ok, grant, nq)
    lease_owner = fleet.lease_owner.at[scatter_idx.reshape(-1)].set(
        tids.reshape(-1), mode="drop"
    )

    def stitch(li, cnt, grant_t, ok_t):
        # granted positions are distinct and < nq (total leases can't exceed
        # n_quanta); non-grants scatter to the OOB-high drop sentinel, so no
        # duplicate-index collisions can clobber a real grant
        pos = jnp.where(ok_t, cnt + jnp.arange(nq, dtype=jnp.int32), nq)
        return li.at[pos].set(grant_t, mode="drop")

    lease_index = jax.vmap(stitch)(fleet.lease_index, fleet.lease_count,
                                   grant, ok)
    lease_count = fleet.lease_count + jnp.sum(ok, axis=1)
    return lease_owner, lease_index, lease_count, short


def _rows_for(spec: FleetSpec, lease_index: jax.Array,
              alloc_count: jax.Array, bsz: int):
    """Global pool rows for each tenant's next ``bsz`` allocations.

    Returns ``(rows (T, B) int32, leased (T, B) bool)`` — ``rows`` is -1
    where the tenant holds no lease for that slot.
    """
    q = spec.lease_quantum
    nq = spec.n_quanta
    local = alloc_count[:, None] + jnp.arange(bsz, dtype=jnp.int32)[None, :]
    slot = local // q
    # bound the gather: JAX clamps OOB indices to nq-1, which would alias
    # post-exhaustion writes onto the final quantum's (immutable) rows
    quantum = jnp.take_along_axis(lease_index, jnp.minimum(slot, nq - 1),
                                  axis=1)
    leased = (quantum >= 0) & (slot < nq)
    rows = jnp.where(leased, quantum * q + local % q, -1)
    return rows, leased


# -- batched data path -------------------------------------------------------


@jax.jit
def write(fleet: ChainFleet, page_ids: jax.Array, data: jax.Array,
          mask: jax.Array | None = None) -> ChainFleet:
    """Fleet-wide COW write: one batch of pages per tenant, one dispatch.

    ``page_ids``: (T, B) int32, unique within each tenant's batch;
    ``data``: (T, B, page_size); ``mask``: optional (T,) bool selecting
    which tenants participate (inactive tenants are untouched).

    Semantics per tenant match ``chain.write``: fresh pool rows, active
    volume's L1/L2 stamped, backing files immutable. Rows come from the
    tenant's leased quanta; the allocator grants new quanta on demand and
    flags ``overflow`` for tenants the pool cannot serve (their excess
    pages are dropped — never written into another tenant's lease).
    """
    spec = fleet.spec
    t, bsz = page_ids.shape
    page_ids = page_ids.astype(jnp.int32)
    tmask = (jnp.ones((t,), bool) if mask is None
             else jnp.asarray(mask, bool))
    need = jnp.where(tmask, bsz, 0).astype(jnp.int32)

    lease_owner, lease_index, lease_count, short = _acquire_leases(fleet, need)
    rows, leased = _rows_for(spec, lease_index, fleet.alloc_count, bsz)
    valid = leased & tmask[:, None]                       # (T, B)

    # drop-sentinel is out-of-bounds HIGH (negative indices wrap in scatters)
    flat_rows = jnp.where(valid, rows, spec.pool_capacity).reshape(-1)
    pool = fleet.pool.at[flat_rows].set(
        data.astype(spec.dtype).reshape(t * bsz, -1), mode="drop"
    )

    stamp = partial(chain_lib.write_tables, l2_per_table=spec.l2_per_table)
    l1, l2 = jax.vmap(
        lambda l1_t, l2_t, act, pids, rows_t, scal, m:
        stamp(l1_t, l2_t, act, pids, jnp.maximum(rows_t, 0),
              scalable=scal, mask=m)
    )(fleet.l1, fleet.l2, fleet.length - 1, page_ids, rows,
      fleet.scalable, valid)

    return dataclasses.replace(
        fleet,
        l1=l1,
        l2=l2,
        pool=pool,
        lease_owner=lease_owner,
        lease_index=lease_index,
        lease_count=lease_count,
        alloc_count=fleet.alloc_count + jnp.sum(valid, axis=1, dtype=jnp.int32),
        overflow=fleet.overflow | (short & tmask),
    )


@jax.jit
def snapshot(fleet: ChainFleet, mask: jax.Array | None = None,
             scalable: jax.Array | None = None) -> ChainFleet:
    """Per-tenant snapshot: freeze each selected tenant's active volume.

    ``mask``: optional (T,) bool — which tenants snapshot this step.
    ``scalable``: optional override (python bool or (T,) bool), as in
    ``chain.snapshot`` — models a vanilla tool snapshotting a scalable
    image. Defaults to each tenant's own format flag. Tenants already at
    ``max_chain`` are skipped and flagged ``snap_dropped``.
    """
    spec = fleet.spec
    t = spec.n_tenants
    tmask = (jnp.ones((t,), bool) if mask is None
             else jnp.asarray(mask, bool))
    scal = (fleet.scalable if scalable is None
            else jnp.broadcast_to(jnp.asarray(scalable, bool), (t,)))

    can = tmask & (fleet.length < spec.max_chain)

    def snap_one(l1_t, l2_t, len_t, do_copy):
        c1, c2 = chain_lib.copy_forward_tables(l1_t, l2_t, len_t)
        return (jnp.where(do_copy, c1, l1_t), jnp.where(do_copy, c2, l2_t))

    l1, l2 = jax.vmap(snap_one)(fleet.l1, fleet.l2, fleet.length, can & scal)
    return dataclasses.replace(
        fleet,
        l1=l1,
        l2=l2,
        length=fleet.length + can.astype(jnp.int32),
        snap_dropped=fleet.snap_dropped | (tmask & ~can),
    )


def _batched_resolver(name: str):
    fn = resolve_lib.get_table_resolver(name)

    @jax.jit
    def batched(fleet: ChainFleet, page_ids: jax.Array):
        return jax.vmap(fn)(fleet.l2, fleet.length,
                            page_ids.astype(jnp.int32))

    return batched


#: Batched resolvers: page_ids (T, B) → ResolveResult of (T, B) leaves.
resolve_vanilla = _batched_resolver("vanilla")
resolve_direct = _batched_resolver("direct")


def fused_layout_ok(n_pages: int) -> bool:
    """The lane-alignment rule the kernel plane's auto-selection shares:
    a stacked index whose page axis is a 128-lane multiple tiles the
    Pallas kernels with no padding. ``resolve_auto`` uses it to pick the
    kernel resolvers, and the serving engine uses it to pick the fused
    chain-resolve attention path (``Engine(decode_path="auto")``)."""
    return n_pages % 128 == 0


def _kernel_layout_ok(spec: FleetSpec) -> bool:
    """Static (trace-time) rule for ``method="auto"``: use the Pallas
    kernels only when the page axis is already a 128-lane multiple, so the
    stacked tables tile with no padding. Explicit ``pallas_*`` methods pad
    and run the kernel regardless."""
    return fused_layout_ok(spec.n_pages)


@jax.jit
def resolve_pallas_vanilla(fleet: ChainFleet, page_ids: jax.Array):
    """Stacked-kernel chain walk; bit-identical to ``resolve_vanilla``."""
    return resolve_lib.resolve_vanilla_stacked(fleet.l2, fleet.length,
                                               page_ids)


@jax.jit
def resolve_pallas_direct(fleet: ChainFleet, page_ids: jax.Array):
    """Stacked-kernel direct access; bit-identical to ``resolve_direct``."""
    return resolve_lib.resolve_direct_stacked(fleet.l2, fleet.length,
                                              page_ids)


@jax.jit
def resolve_auto(fleet: ChainFleet, page_ids: jax.Array):
    """Mixed-image resolution (direct where trusted, walk otherwise).

    Implementation is chosen statically at trace time: the stacked Pallas
    kernels when the layout qualifies (``_kernel_layout_ok``; of those,
    the batch kernel where the batch is narrow beside the table,
    ``resolve.batch_path_ok``), the vmapped jnp gather otherwise. All
    produce bit-identical results — only the data plane differs. Off-TPU
    the kernels run in interpret mode (so CI exercises them), which is
    slower than the vmapped gather; latency-sensitive CPU callers with
    lane-aligned layouts should pass an explicit jnp method
    (``"vanilla"``/``"direct"``/``"gather"``).
    """
    if _kernel_layout_ok(fleet.spec):
        return resolve_lib.resolve_auto_stacked(fleet.l2, fleet.length,
                                                page_ids)
    return jax.vmap(resolve_lib.get_table_resolver("auto"))(
        fleet.l2, fleet.length, page_ids.astype(jnp.int32)
    )


_RESOLVERS = {
    "vanilla": resolve_vanilla,
    # "gather" names the implementation rather than the strategy: the
    # vmapped-jnp walk, the baseline the benchmarks/tests compare the
    # Pallas kernels against
    "gather": resolve_vanilla,
    "direct": resolve_direct,
    "auto": resolve_auto,
    "pallas_vanilla": resolve_pallas_vanilla,
    "pallas_direct": resolve_pallas_direct,
}


def get_resolver(name: str):
    """Look up a batched fleet resolver by method name.

    Methods: ``"vanilla"`` (alias ``"gather"``) — vmapped O(chain) walk;
    ``"direct"`` — vmapped O(1) lookup; ``"pallas_vanilla"`` /
    ``"pallas_direct"`` — the same strategies as stacked Pallas kernels;
    ``"auto"`` — per-page direct-where-trusted semantics, kernel-backed
    when the layout qualifies. Every method returns a resolver with
    signature ``(fleet, page_ids (T, B)) -> ResolveResult`` of (T, B)
    leaves. Raises ``ValueError`` for unknown names.
    """
    return resolve_lib.lookup_resolver(_RESOLVERS, name)


def _uses_kernels(spec: FleetSpec, method: str) -> bool:
    return (method in ("pallas_vanilla", "pallas_direct")
            or (method == "auto" and _kernel_layout_ok(spec)))


def read(fleet: ChainFleet, page_ids: jax.Array, *, method: str = "auto"):
    """Batched whole-page read across the fleet.

    Args:
        fleet: the fleet state (untouched — reads are pure).
        page_ids: (T, B) int32 logical page indices, one batch per tenant.
        method: resolver method (see ``get_resolver``). The default
            ``"auto"`` resolves each page direct-where-trusted and uses
            the Pallas kernel data plane when the layout qualifies.

    Returns:
        ``(data, result)`` where ``data`` is (T, B, page_size) — the pool
        is global, so one gather serves every tenant — and ``result`` is
        the ``ResolveResult`` of (T, B) leaves the gather consumed.
        Unallocated or ZERO pages read as zeros, exactly as
        ``store.read``. Kernel methods gather through the stacked Pallas
        gather of ``kernels/cow_gather``; jnp methods use the shared
        ``store.gather_pages`` helper. Both are bit-identical.

    The call opens the host span ``snapstore.fleet.read`` around the
    enqueue of the compiled program (module ``jit_read``), with the pages
    asked for (T×B), the table entries the resolver resolves (T×n_pages on
    the whole-table kernel path, T×B on the jnp paths and where ``"auto"``
    takes the batch kernel, ``resolve.batch_path_ok``) and the method.
    ``read.lower`` lowers that program ahead of time, as on any jitted
    function.
    """
    t, b = np.shape(page_ids)
    with obs.span("fleet.read", pages=t * b,
                  resolved=resolved_entries(fleet.spec, method, b),
                  method=method):
        return _read(fleet, page_ids, method=method)


def resolved_entries(spec: FleetSpec, method: str, batch: int) -> int:
    """Table entries one read of ``batch`` pages per tenant resolves:
    every tenant's whole table on the whole-table kernel path, only the
    batch on the jnp paths and where ``"auto"`` takes the batch kernel."""
    whole_table = _uses_kernels(spec, method) and not (
        method == "auto" and resolve_lib.batch_path_ok(spec.n_pages, batch))
    return spec.n_tenants * (spec.n_pages if whole_table else batch)


def _read_program(fleet: ChainFleet, page_ids: jax.Array, *, method: str):
    """The device program of ``read``: resolve, then gather, each under
    its own named scope (``fleet.resolve``, ``fleet.gather``)."""
    from repro.core import store  # local import: store is the public API layer

    with jax.named_scope("fleet.resolve"):
        res = get_resolver(method)(fleet, page_ids)
    with jax.named_scope("fleet.gather"):
        if _uses_kernels(fleet.spec, method):
            # cold hits address the host tier — mask them like ZERO
            # clusters (read_tiered fills them from the TieredStore)
            ok = res.found & ~res.zero & ~res.cold
            rows = jnp.where(ok, res.ptr, 0).astype(jnp.int32)
            return cow_ops.gather_fleet(fleet.pool, rows, ok), res
        return store.gather_pages(fleet.pool, res), res


_read_program.__name__ = "read"     # the compiled module is ``jit_read``
_read = jax.jit(_read_program, static_argnames=("method",))
read.lower = _read.lower


def materialize(fleet: ChainFleet, *, method: str = "auto") -> jax.Array:
    """Read every tenant's full virtual disk: (T, n_pages, page_size).

    ``method`` is any ``get_resolver`` name; the fleet-wide 'dd' op.
    """
    spec = fleet.spec
    ids = jnp.broadcast_to(
        jnp.arange(spec.n_pages, dtype=jnp.int32)[None, :],
        (spec.n_tenants, spec.n_pages),
    )
    data, _ = read(fleet, ids, method=method)
    return data


# -- tenant lifecycle: attach / clone / fork / free / stamp ------------------
#
# The serving plane (``kvcache.paged``) runs each live sequence as a fleet
# tenant: these are the tenancy primitives it is built on. ``free_tenant``
# is also the maintenance plane's "tenant deletion" op (a retired disk's
# whole lease set returns to the allocator in one call).


def _tenant_sel(n_tenants: int, tenants) -> np.ndarray:
    """Normalize an int / id-list / bool-mask tenant selector to a mask."""
    t = np.asarray(tenants)
    if t.dtype == bool:
        return np.broadcast_to(t, (n_tenants,))
    sel = np.zeros(n_tenants, bool)
    if t.size:                     # an empty id list selects nothing
        sel[np.atleast_1d(t).astype(np.int64)] = True
    return sel


def free_tenant(fleet: ChainFleet, tenants, *, store=None,
                registry=None) -> ChainFleet:
    """Retire tenants wholesale: reset their chains and return each one's
    *entire* lease set to the allocator free list in one call.

    Unlike ``_reclaim`` — which repacks live rows and releases only the
    quanta past the packed prefix — this drops everything the tenant
    holds: every leased quantum goes back to the free list at once, the
    L1/L2 stacks reset to an empty length-1 chain, and the pressure flags
    clear. Host-side, like the other maintenance ops. The serving engine
    uses it for ``finish_request`` (a retired sequence's tenant slot).

    Args:
        fleet: the fleet state (returned updated, never mutated).
        tenants: an int tenant id, a sequence of ids, or a (T,) bool mask.
        store: the ``TieredStore`` holding any demoted pages of the freed
            tenants. Their host rows are returned to the store's free
            list here — a freed tenant must leave no orphaned host pages.
            Required iff a selected tenant holds cold rows.
        registry: the ``GoldenRegistry``, when the fleet runs one.
            Freeing a registered golden *owner* is refused (live or not,
            its rows may be pinned — ``unregister`` first); freeing a
            golden *fork* releases its pins on the shared base here, so
            callers cannot leak refcounts.

    Returns:
        The updated ``ChainFleet``. Pool rows formerly referenced by the
        freed tenants are garbage until their quanta are re-leased (rows
        are never zeroed, exactly as after ``_reclaim``).
    """
    spec = fleet.spec
    sel = _tenant_sel(spec.n_tenants, tenants)
    idx = np.flatnonzero(sel)
    if idx.size == 0:
        return fleet
    if registry is not None:
        owners = [int(t) for t in idx if registry.is_golden_owner(int(t))]
        if owners:
            raise ValueError(
                f"tenants {owners} are registered golden bases; "
                "unregister them before freeing (forks may pin their rows)"
            )
        for t in idx:
            if registry.is_fork(int(t)):
                registry.release(int(t))
    cold_held = np.asarray(fleet.cold_count)[idx]
    if np.any(cold_held > 0):
        if store is None:
            raise ValueError(
                f"tenants {idx[cold_held > 0].tolist()} hold host-tier "
                "rows; pass the TieredStore so free_tenant can release "
                "them (orphaned host pages otherwise)"
            )
        # sweep the freed tenants' L2 stacks for COLD entries and hand
        # their host rows back to the cold tier's free list
        for t in idx[cold_held > 0]:
            entries = np.asarray(fleet.l2[int(t), : int(fleet.length[int(t)])])
            coldm = (np.asarray(fmt.entry_cold(entries))
                     & np.asarray(fmt.entry_allocated(entries))
                     & ~np.asarray(fmt.entry_zero(entries)))
            host_rows = np.unique(
                np.asarray(fmt.entry_ptr(entries))[coldm].astype(np.int64)
            )
            store.free(host_rows)
    lease_owner = np.asarray(fleet.lease_owner).copy()
    lease_owner[np.isin(lease_owner, idx)] = -1
    lease_index = np.asarray(fleet.lease_index).copy()
    lease_index[idx] = -1
    rows = jnp.asarray(idx, jnp.int32)
    zero = lambda a: a.at[rows].set(0)
    return dataclasses.replace(
        fleet,
        l1=zero(fleet.l1),
        l2=zero(fleet.l2),
        lease_owner=jnp.asarray(lease_owner, jnp.int32),
        lease_index=jnp.asarray(lease_index, jnp.int32),
        lease_count=zero(fleet.lease_count),
        alloc_count=zero(fleet.alloc_count),
        length=fleet.length.at[rows].set(1),
        overflow=fleet.overflow.at[rows].set(False),
        snap_dropped=fleet.snap_dropped.at[rows].set(False),
        cold_count=fleet.cold_count.at[rows].set(0),
    )


def attach_tenant(fleet: ChainFleet, t: int, *,
                  scalable: bool | None = None,
                  registry=None) -> ChainFleet:
    """(Re)initialize tenant slot ``t`` for a new occupant: a fresh empty
    length-1 chain with the given format flag (default: keep the slot's
    current flag). Any leases the slot still held are released first
    (``free_tenant``, honouring ``registry`` pins), so reused slots can
    never leak a predecessor's rows or tables."""
    out = free_tenant(fleet, t, registry=registry)
    if scalable is None:
        return out
    return dataclasses.replace(
        out, scalable=out.scalable.at[t].set(bool(scalable))
    )


@partial(jax.jit, static_argnames=("bump",))
def _clone_rows(l1, l2, length, scalable, src, dst, *, bump: bool = False):
    # src/dst arrive TRACED so every fork of a fresh tenant slot reuses
    # one compiled scatter — python-int indexing would bake each new
    # tenant id into the HLO and recompile per fork (serving admission
    # forks at request rate; a compile per fork dwarfs the fork itself)
    new_len = length[src] + (1 if bump else 0)
    return (l1.at[dst].set(l1[src]),
            l2.at[dst].set(l2[src]),
            length.at[dst].set(new_len),
            scalable.at[dst].set(scalable[src]))


def _clone_into(fleet: ChainFleet, src: int, dst: int, *,
                bump: bool) -> ChainFleet:
    if int(fleet.cold_count[src]) > 0:
        raise ValueError(
            f"tenant {src} holds host-tier rows; promote_tenants before "
            "cloning (cold entries cannot be shared across tenants)"
        )
    l1, l2, length, scalable = _clone_rows(
        fleet.l1, fleet.l2, fleet.length, fleet.scalable,
        jnp.int32(src), jnp.int32(dst), bump=bump)
    return dataclasses.replace(fleet, l1=l1, l2=l2, length=length,
                               scalable=scalable)


def clone_tenant(fleet: ChainFleet, src: int, dst: int) -> ChainFleet:
    """Copy tenant ``src``'s chain metadata (L1/L2 stacks, length, format
    flag) into slot ``dst``. Pool rows are shared, not copied: the
    clone's entries keep referencing the source's rows, so the *caller*
    owns cross-tenant row lifetime (the serving plane refcounts KV blocks
    host-side). Do NOT run the lease-accounted maintenance ops
    (``stream_tenants``/``compact``) on a fleet holding clones — their
    repack assumes per-tenant row disjointness and would flag the shared
    rows as corruption. Raises if ``src`` holds demoted (host-tier) rows:
    a cloned COLD entry would alias the host row across tenants and
    freeing either tenant would dangle the other — promote first
    (``promote_tenants``)."""
    return _clone_into(fleet, src, dst, bump=False)


def fork_tenant(fleet: ChainFleet, src: int, dst: int) -> ChainFleet:
    """Serving-plane fork: clone ``src``'s chain into ``dst`` and open a
    fresh (all-zeros) active volume on top — the vanilla "snapshot into a
    new tenant". ``dst`` resolves exactly like ``src`` until it writes;
    ``src`` keeps writing its own active volume independently. Raises if
    ``src`` is already at ``max_chain`` (callers grow the fleet geometry
    first — see ``PagedKVCache._grow_fleet``)."""
    if int(fleet.length[src]) >= fleet.spec.max_chain:
        raise ValueError(
            f"tenant {src} is at max_chain={fleet.spec.max_chain}; "
            "grow the fleet geometry before forking"
        )
    return _clone_into(fleet, src, dst, bump=True)


def stamp_entries(fleet: ChainFleet, tenants, layers, pages,
                  entries) -> ChainFleet:
    """Raw batched L2/L1 stamp at explicit ``(tenant, layer, page)`` sites.

    The serving plane's COW-prepare write: pool rows are allocated by the
    caller (the KV cache's refcounted block pool), so unlike ``write`` no
    lease is acquired and the fleet pool is untouched — this stamps index
    metadata only, one scatter for the whole batch. ``entries``: (K, 2)
    uint32 packed via ``fmt.pack_entry``. A tenant id of ``n_tenants``
    (out-of-bounds HIGH) acts as a drop sentinel, so callers can pad the
    batch to a fixed K without re-tracing; negative ids are invalid (they
    would wrap in the scatter)."""
    spec = fleet.spec
    t = jnp.asarray(tenants, jnp.int32)
    lay = jnp.asarray(layers, jnp.int32)
    p = jnp.asarray(pages, jnp.int32)
    ent = jnp.asarray(entries, jnp.uint32)
    l2 = fleet.l2.at[t, lay, p].set(ent, mode="drop")
    l1 = fleet.l1.at[t, lay, p // spec.l2_per_table].set(
        jnp.uint32(1), mode="drop"
    )
    return dataclasses.replace(fleet, l1=l1, l2=l2)


# -- migration support: explicit row grants and whole-slot installs ----------
#
# ``core.migrate`` packs a tenant into a portable blob and re-attaches it
# on another fleet. The lease-accounted halves of that live here, in the
# state's owner module: granting device rows to a tenant outside the
# ``write`` path, and installing a complete chain (stacks + pool pages)
# into a slot in one shot.


def acquire_rows(fleet: ChainFleet, t: int, n: int):
    """Grant tenant ``t`` ownership of ``n`` fresh device pool rows.

    The lease-accounted allocation primitive for callers that place page
    data themselves (migration's attach path): quanta are acquired on
    demand exactly as in ``write``, and ``alloc_count`` grows by ``n`` so
    the granted rows are the tenant's next ``n`` lease-order slots.

    Args:
        fleet: the fleet state (returned updated, never mutated).
        t: the receiving tenant.
        n: device rows to grant.

    Returns:
        ``(fleet, rows)`` — ``rows`` is an (n,) int64 numpy array of
        global pool row ids, in lease order. Raises ``RuntimeError`` if
        the pool cannot serve the grant (no partial grants: the lease
        state is returned untouched in that case because the update is
        functional).
    """
    spec = fleet.spec
    if n <= 0:
        return fleet, np.zeros(0, np.int64)
    need = np.zeros(spec.n_tenants, np.int32)
    need[t] = n
    lease_owner, lease_index, lease_count, short = _acquire_leases(
        fleet, jnp.asarray(need)
    )
    if bool(np.asarray(short)[t]):
        raise RuntimeError(
            f"pool exhausted granting {n} rows to tenant {t}: free or "
            "stream other tenants first"
        )
    rows, leased = _rows_for(spec, lease_index, fleet.alloc_count, n)
    rows_t = np.asarray(rows)[t].astype(np.int64)
    if not np.asarray(leased)[t].all():
        raise RuntimeError(
            f"lease table cannot address {n} more rows for tenant {t}"
        )
    out = dataclasses.replace(
        fleet,
        lease_owner=lease_owner,
        lease_index=lease_index,
        lease_count=lease_count,
        alloc_count=fleet.alloc_count + jnp.asarray(need),
    )
    return out, rows_t


def grow(fleet: ChainFleet, *, tenants: int = 0,
         quanta: int = 0) -> ChainFleet:
    """The same fleet with ``tenants`` more tenant slots and ``quanta``
    more lease quanta of pool. New slots are empty length-1 chains
    (vanilla, no leases); new quanta are free; every existing tenant,
    lease and pool row keeps its place."""
    if tenants == 0 and quanta == 0:
        return fleet
    spec = dataclasses.replace(
        fleet.spec, n_tenants=fleet.spec.n_tenants + tenants,
        pool_capacity=fleet.spec.pool_capacity
        + quanta * fleet.spec.lease_quantum)

    def pad(a, n, value):
        return jnp.concatenate([a, jnp.full((n, *a.shape[1:]), value,
                                            a.dtype)])

    return ChainFleet(
        spec=spec,
        l1=pad(fleet.l1, tenants, 0),
        l2=pad(fleet.l2, tenants, 0),
        pool=pad(fleet.pool, quanta * spec.lease_quantum, 0),
        lease_owner=pad(fleet.lease_owner, quanta, -1),
        lease_index=jnp.pad(fleet.lease_index, ((0, tenants), (0, quanta)),
                            constant_values=-1),
        lease_count=pad(fleet.lease_count, tenants, 0),
        alloc_count=pad(fleet.alloc_count, tenants, 0),
        length=pad(fleet.length, tenants, 1),
        scalable=pad(fleet.scalable, tenants, False),
        overflow=pad(fleet.overflow, tenants, False),
        snap_dropped=pad(fleet.snap_dropped, tenants, False),
        cold_count=pad(fleet.cold_count, tenants, 0),
    )


def install_tenant(fleet: ChainFleet, t: int, *, l1, l2, length: int,
                   scalable: bool, cold_count: int = 0,
                   pool_rows=None, pool_data=None) -> ChainFleet:
    """Install a complete chain into tenant slot ``t`` in one shot.

    The attach half of migration: the slot's L1/L2 stacks are replaced
    wholesale (layers past ``length`` zeroed), its ``length``/format/
    ``cold_count`` set, and — when given — ``pool_data`` scattered into
    ``pool_rows`` (rows the caller obtained from ``acquire_rows``; this
    is the blob's page payload landing in the device pool). The pressure
    flags reset: an imported chain starts clean.

    The caller is responsible for slot hygiene (run ``free_tenant``
    first so a predecessor's leases are returned) and for the entries in
    ``l2`` pointing only at rows granted to ``t`` — ``core.migrate``
    remaps blob-local pointers before calling in, and the shared
    invariant suite (``core.invariants``) checks the result.
    """
    spec = fleet.spec
    length = int(length)
    if not 1 <= length <= spec.max_chain:
        raise ValueError(
            f"cannot install a length-{length} chain into a fleet with "
            f"max_chain={spec.max_chain}"
        )
    l1_full = np.zeros((spec.max_chain, spec.n_l1), np.uint32)
    l2_full = np.zeros((spec.max_chain, spec.n_pages, 2), np.uint32)
    l1_full[:length] = np.asarray(l1, np.uint32)
    l2_full[:length] = np.asarray(l2, np.uint32)
    pool = fleet.pool
    if pool_rows is not None and len(pool_rows):
        pool = pool.at[jnp.asarray(pool_rows, jnp.int32)].set(
            jnp.asarray(pool_data, spec.dtype)
        )
    return dataclasses.replace(
        fleet,
        l1=fleet.l1.at[t].set(jnp.asarray(l1_full)),
        l2=fleet.l2.at[t].set(jnp.asarray(l2_full)),
        pool=pool,
        length=fleet.length.at[t].set(length),
        scalable=fleet.scalable.at[t].set(bool(scalable)),
        overflow=fleet.overflow.at[t].set(False),
        snap_dropped=fleet.snap_dropped.at[t].set(False),
        cold_count=fleet.cold_count.at[t].set(int(cold_count)),
    )


# -- maintenance plane: streaming, GC, lease reclamation ---------------------


def _reclaim(fleet: ChainFleet, sel: np.ndarray, *,
             shared_rows=None) -> ChainFleet:
    """Repack each selected tenant's live rows into its leading lease
    quanta and return now-empty quanta to the allocator free list.

    Host-side (like ``chain.compact_pool``). Per selected tenant: gather
    the pool rows its live L2 entries reference, copy them — the streaming
    job's data movement — into the densest prefix of its leased quanta,
    remap the L2 pointers, then release every quantum past the packed
    prefix (``lease_owner`` → -1, ``lease_index``/``lease_count``/
    ``alloc_count`` shrink). ``overflow`` clears only for tenants whose
    row count actually shrank — reclaiming zero rows leaves the tenant as
    wedged as before, and clearing the flag would hide that.

    ``shared_rows`` (the golden registry's ``pinned_rows()``) marks rows
    a tenant may legally reference *without owning*: a golden fork's
    entries alias its base's frozen rows. Like COLD entries, shared rows
    are not repacked, keep their pointer verbatim, and never count
    toward the referencing tenant's lease footprint — the owner tenant
    (excluded from maintenance while registered) keeps them pinned.
    """
    spec = fleet.spec
    q = spec.lease_quantum
    lease_owner = np.asarray(fleet.lease_owner).copy()
    lease_index = np.asarray(fleet.lease_index).copy()
    lease_count = np.asarray(fleet.lease_count).copy()
    alloc_count = np.asarray(fleet.alloc_count).copy()
    lengths = np.asarray(fleet.length)
    reclaimed = np.zeros(spec.n_tenants, np.int64)
    pool = fleet.pool
    l2 = fleet.l2
    shared_lut = None
    if shared_rows is not None and len(shared_rows):
        shared_lut = np.zeros(spec.pool_capacity, bool)
        shared_lut[np.asarray(shared_rows, np.int64)] = True

    for t in np.flatnonzero(sel):
        length_t = int(lengths[t])
        entries = l2[t, :length_t]                    # (L, n_pages, 2)
        alloc = np.asarray(fmt.entry_allocated(entries))
        cold = np.asarray(fmt.entry_cold(entries))
        # ZERO clusters are allocated but their ptr is never dereferenced —
        # they pin no pool row; COLD entries point at the host tier, so
        # they pin no *device* row either (and their ptr must not be
        # remapped by the repack LUT below)
        live = alloc & ~np.asarray(fmt.entry_zero(entries)) & ~cold
        rows = np.asarray(fmt.entry_ptr(entries))
        sharedm = np.zeros(live.shape, bool)
        if shared_lut is not None:
            sharedm = live & shared_lut[np.where(live, rows, 0)]
            live = live & ~sharedm
        used = np.unique(rows[live]).astype(np.int64)  # sorted global rows
        n_live = len(used)
        if n_live and not np.all(lease_owner[used // q] == t):
            raise RuntimeError(
                f"tenant {t} references pool rows outside its leased "
                "quanta: fleet state is corrupt"
            )
        n_keep = -(-n_live // q)
        if n_live:
            keep = lease_index[t, :n_keep]
            i = np.arange(n_live)
            new_rows = keep[i // q] * q + i % q
            # gather-then-scatter: values materialize before the write, so
            # overlapping old/new rows inside the kept quanta are safe
            vals = pool[jnp.asarray(used, jnp.int32)]
            pool = pool.at[jnp.asarray(new_rows, jnp.int32)].set(vals)
            lut = np.zeros(spec.pool_capacity, np.uint32)
            lut[used] = new_rows.astype(np.uint32)
            # COLD entries keep their (host-tier) ptr verbatim, and so do
            # shared golden rows (another tenant's pinned, un-repacked
            # rows): the LUT maps this tenant's own device rows only
            safe = np.where(live, rows, 0)
            new_ptr = np.where(cold | sharedm, rows, lut[safe])
            new_entries = fmt.pack_entry(
                jnp.asarray(new_ptr.astype(np.uint32)),
                fmt.entry_bfi(entries),
                allocated=jnp.asarray(alloc),
                bfi_valid=fmt.entry_bfi_valid(entries),
                zero=fmt.entry_zero(entries),
                cold=jnp.asarray(cold),
            )
            l2 = l2.at[t, :length_t].set(new_entries)
        freed = lease_index[t, n_keep:lease_count[t]]
        lease_owner[freed] = -1
        lease_index[t, n_keep:] = -1
        lease_count[t] = n_keep
        reclaimed[t] = int(alloc_count[t]) - n_live
        alloc_count[t] = n_live

    overflow = np.asarray(fleet.overflow) & ~(reclaimed > 0)
    return dataclasses.replace(
        fleet,
        l2=l2,
        pool=pool,
        lease_owner=jnp.asarray(lease_owner, jnp.int32),
        lease_index=jnp.asarray(lease_index, jnp.int32),
        lease_count=jnp.asarray(lease_count, jnp.int32),
        alloc_count=jnp.asarray(alloc_count, jnp.int32),
        overflow=jnp.asarray(overflow, bool),
    )


def stream_tenants(fleet: ChainFleet, mask, merge_upto, *,
                   reclaim: bool = True, registry=None) -> ChainFleet:
    """Stream (merge layers ``[0, merge_upto]``) each selected tenant and
    return the pool quanta this frees to the lease allocator.

    The fleet-granularity analogue of ``chain.stream``: host-side
    maintenance over the stacked (T, C, P) layout, built on the same
    ``chain.merge_tables`` core so chain and fleet semantics cannot drift.

    Args:
        fleet: the fleet state (returned updated, never mutated).
        mask: (T,) bool, or a scalar broadcast over tenants — which
            tenants to stream this call.
        merge_upto: int or (T,) int — per tenant, merge layers
            ``[0, merge_upto]`` into the base. Tenants whose
            ``merge_upto`` does not fall strictly below their active
            volume are skipped (a background job must tolerate racing
            chain growth, where ``chain.stream`` raises).
        reclaim: run the shared ``_reclaim`` repack afterwards (default).
            Pass ``False`` for a metadata-only merge that frees nothing.
        registry: the ``GoldenRegistry``, when the fleet runs one.
            Registered golden *owners* are skipped (their chains are
            content-frozen; a merge would invalidate every fork's base)
            and forks' shared base rows ride through the repack
            untouched (``_reclaim(shared_rows=...)``).

    Returns:
        The updated ``ChainFleet``. With ``reclaim=True``, rows orphaned
        by the merge leave each tenant's lease footprint and freed quanta
        return to the allocator free list; ``overflow`` clears only for
        tenants that actually shrank, and ``snap_dropped`` clears only
        where streaming made room below ``max_chain``.
    """
    spec = fleet.spec
    t = spec.n_tenants
    mask = np.broadcast_to(np.asarray(mask, bool), (t,))
    upto = np.broadcast_to(np.asarray(merge_upto, np.int64), (t,))
    lengths = np.asarray(fleet.length).copy()
    # tenants holding demoted pages are skipped: merging layers would
    # collapse COLD entries across layer boundaries and strand their host
    # rows — promote_tenants first, then stream
    cold = np.asarray(fleet.cold_count)
    sel = mask & (upto >= 0) & (upto < lengths - 1) & (cold == 0)
    if registry is not None:
        sel &= ~registry.golden_owner_mask(t)

    l1, l2 = fleet.l1, fleet.l2
    snap_dropped = np.asarray(fleet.snap_dropped).copy()
    scalable = np.asarray(fleet.scalable)
    sel_idx = np.flatnonzero(sel)
    if sel_idx.size:
        merged_l1, merged_l2 = [], []
        for i in sel_idx:
            tl1, tl2, new_len = chain_lib.merge_tables(
                l1[i], l2[i], int(lengths[i]), int(upto[i]),
                scalable=bool(scalable[i]),
            )
            merged_l1.append(tl1)
            merged_l2.append(tl2)
            lengths[i] = new_len
            snap_dropped[i] &= new_len >= spec.max_chain
        # one stacked scatter per array: updating tenant-by-tenant would
        # copy the full (T, C, ...) stacks once per selected tenant
        idx = jnp.asarray(sel_idx, jnp.int32)
        l1 = l1.at[idx].set(jnp.stack(merged_l1))
        l2 = l2.at[idx].set(jnp.stack(merged_l2))
    out = dataclasses.replace(
        fleet,
        l1=l1,
        l2=l2,
        length=jnp.asarray(lengths, jnp.int32),
        snap_dropped=jnp.asarray(snap_dropped, bool),
    )
    if not reclaim:
        return out
    return _reclaim(
        out, sel,
        shared_rows=registry.pinned_rows() if registry is not None else None,
    )


def compact(fleet: ChainFleet, mask=None, *, registry=None) -> ChainFleet:
    """Fleet-level GC: repack every (selected) tenant's live rows and
    return the freed quanta to the allocator free list.

    The fleet analogue of ``chain.compact_pool`` — COW writes and
    streaming orphan pool rows; this is the background job that hands
    them back so long-running fleets reach a steady state instead of
    leaking the pool.

    Args:
        fleet: the fleet state (returned updated, never mutated).
        mask: optional (T,) bool selecting which tenants to repack;
            ``None`` (default) compacts every tenant.
        registry: the ``GoldenRegistry``, when the fleet runs one.
            Golden owners are never repacked (their pointer layout is
            part of the frozen fingerprint and their rows are pinned);
            forks repack only their own rows, aliased base rows ride
            through verbatim.

    Returns:
        The updated ``ChainFleet``: selected tenants' live rows repacked
        into their leading lease quanta, emptied quanta returned to the
        free list, and ``overflow`` cleared only for tenants whose rows
        were actually reclaimed (reclaiming nothing leaves the tenant as
        wedged as before).
    """
    t = fleet.spec.n_tenants
    sel = (np.ones(t, bool) if mask is None
           else np.broadcast_to(np.asarray(mask, bool), (t,)))
    if registry is None:
        return _reclaim(fleet, sel)
    sel = sel & ~registry.golden_owner_mask(t)
    return _reclaim(fleet, sel, shared_rows=registry.pinned_rows())


# -- tiering: HBM <-> host demotion and promotion ----------------------------
#
# The second tier of the page pool (paper's 15x memory headline at
# fleet granularity): immutable snapshot layers spill to a host-side
# ``store.TieredStore`` under HBM pressure and come back on demand. A
# demoted entry keeps its layer position — only its ptr is rewritten to a
# host-tier row under FLAG_COLD, so resolution semantics (owner, found,
# zero, lookups) are untouched and the stacked resolvers simply report
# ``cold``. See docs/memory.md for the full lifecycle.


def _tenant_cold_rows(l2_t: np.ndarray, length_t: int):
    """Cold entries of one tenant: (layer, page) mask + their host rows.

    Pure numpy on an already-synced L2 copy — the tiering maintenance
    paths stay off the device except for the actual page transfers."""
    w0 = l2_t[:length_t, ..., 0]
    coldm = ((w0 & np.uint32(fmt.FLAG_COLD)) != 0) \
        & ((w0 & np.uint32(fmt.FLAG_ALLOCATED)) != 0) \
        & ((w0 & np.uint32(fmt.FLAG_ZERO)) == 0)
    return coldm, (w0 & np.uint32(fmt.PTR_MASK)).astype(np.int64)


def demote_tenants(fleet: ChainFleet, store, tenants, *,
                   max_rows: int | None = None,
                   verify: bool = True, registry=None):
    """Demote immutable snapshot-layer pages of the selected tenants to
    the host tier, freeing their device rows.

    Only pages **owned by a layer below the active volume** are eligible —
    the active COW layer's own data never moves (it is the hot, mutable
    set). A page's owner is the lowest layer referencing its row, which
    under snapshot copy-forward means every upper layer (including the
    active one) referencing that row has its entry rewritten to the host
    row under ``FLAG_COLD`` in the same transfer, so the index never
    dangles. The freed device rows then leave the tenant's lease
    footprint via the shared ``_reclaim`` repack and their quanta return
    to the allocator free list — this is where the HBM actually comes
    back.

    Host-side (maintenance plane). Transfers are batched per call and
    bit-verified by default: the host copy is read back and compared
    bitwise against the device rows before the index is rewritten.

    Args:
        fleet: the fleet state (returned updated, never mutated).
        store: the ``TieredStore`` cold tier receiving the pages.
        tenants: int id, id sequence, or (T,) bool mask.
        max_rows: demote at most this many pool rows across the call
            (the scheduler's per-tick budget); ``None`` = no cap.
            Oldest layers go first, so repeated budgeted calls demote
            coldest-first.
        verify: bit-verify every transferred row (default True).
        registry: the ``GoldenRegistry``, when the fleet runs one.
            Registered golden owners are skipped entirely (the frozen
            base stays device-resident by contract), and rows pinned by
            the registry are never picked from *any* tenant — a fork's
            lower layers reference the shared base below its active
            volume, exactly the demotion-eligible shape, and spilling
            them would pull the base out from under every sibling fork.

    Returns:
        ``(fleet, report)`` where report is
        ``dict(rows_demoted=int, tenants=[ids that moved rows])``.
    """
    spec = fleet.spec
    sel = _tenant_sel(spec.n_tenants, tenants)
    pinned_lut = None
    if registry is not None:
        sel &= ~registry.golden_owner_mask(spec.n_tenants)
        pinned = registry.pinned_rows()
        if pinned.size:
            pinned_lut = np.zeros(spec.pool_capacity, bool)
            pinned_lut[pinned] = True
    lengths = np.asarray(fleet.length)
    cold_count = np.asarray(fleet.cold_count).copy()
    # one full host copy, modified in place and pushed back once: entry
    # rewriting stays in numpy at fixed shapes (per-tenant device slices
    # of varying chain length would recompile every tick)
    l2_np = np.array(fleet.l2)
    budget = np.inf if max_rows is None else int(max_rows)
    total = 0
    moved: list[int] = []

    for t in np.flatnonzero(sel):
        if budget <= 0:
            break
        length_t = int(lengths[t])
        if length_t < 2:
            continue                       # nothing below the active volume
        entries = l2_np[t, :length_t]                # (L, n_pages, 2) view
        w0 = entries[..., 0]
        alloc = (w0 & np.uint32(fmt.FLAG_ALLOCATED)) != 0
        cold = (w0 & np.uint32(fmt.FLAG_COLD)) != 0
        hot = alloc & ((w0 & np.uint32(fmt.FLAG_ZERO)) == 0) & ~cold
        rows = (w0 & np.uint32(fmt.PTR_MASK)).astype(np.int64)
        if pinned_lut is not None:
            # golden-pinned rows are immovable while any fork aliases them
            hot &= ~pinned_lut[np.where(hot, rows, 0)]
        if not hot.any():
            continue
        # a row's owner is the lowest layer referencing it (copy-forward
        # re-references ancestor rows from every upper layer)
        layer_idx = np.broadcast_to(
            np.arange(length_t)[:, None], hot.shape)
        flat_rows = rows[hot]
        flat_layer = layer_idx[hot]
        order = np.argsort(flat_rows, kind="stable")
        r_sorted, l_sorted = flat_rows[order], flat_layer[order]
        first = np.r_[True, r_sorted[1:] != r_sorted[:-1]]
        uniq_rows = r_sorted[first]
        owner_layer = np.minimum.reduceat(l_sorted, np.flatnonzero(first))
        eligible = owner_layer < length_t - 1        # never the active layer
        uniq_rows, owner_layer = uniq_rows[eligible], owner_layer[eligible]
        if uniq_rows.size == 0:
            continue
        # coldest first: demote the oldest layers' rows under the budget
        pick = np.argsort(owner_layer, kind="stable")
        if uniq_rows.size > budget:
            pick = pick[: int(budget)]
        dem_rows = uniq_rows[pick]
        n = int(dem_rows.size)

        host_rows = store.alloc(n)
        vals = np.asarray(fleet.pool[jnp.asarray(dem_rows, jnp.int32)])
        store.put(host_rows, vals)
        if verify and not np.array_equal(
                store.get(host_rows).view(np.uint8),
                vals.view(np.uint8)):
            raise RuntimeError(
                f"demotion transfer verification failed for tenant {t}"
            )
        # rewrite every entry (any layer) referencing a demoted row:
        # ptr -> host row, FLAG_COLD set; all other bits carried
        lut = np.zeros(spec.pool_capacity, np.int64)
        in_set = np.zeros(spec.pool_capacity, bool)
        lut[dem_rows] = host_rows
        in_set[dem_rows] = True
        hit = hot & in_set[np.where(hot, rows, 0)]
        new_ptr = np.where(hit, lut[np.where(hit, rows, 0)], rows)
        entries[..., 0] = np.where(
            hit,
            (w0 & ~np.uint32(fmt.PTR_MASK))
            | new_ptr.astype(np.uint32)
            | np.uint32(fmt.FLAG_COLD),
            w0,
        )
        cold_count[t] += n
        budget -= n
        total += n
        moved.append(int(t))

    if not moved:
        return fleet, dict(rows_demoted=0, tenants=[])
    out = dataclasses.replace(
        fleet, l2=jnp.asarray(l2_np),
        cold_count=jnp.asarray(cold_count, jnp.int32)
    )
    # repack: the demoted rows are no longer referenced by any hot entry,
    # so _reclaim returns their quanta to the allocator free list
    out = _reclaim(
        out, _tenant_sel(spec.n_tenants, moved),
        shared_rows=registry.pinned_rows() if registry is not None else None,
    )
    return out, dict(rows_demoted=total, tenants=moved)


def promote_tenants(fleet: ChainFleet, store, tenants, *,
                    max_rows: int | None = None,
                    verify: bool = True):
    """Promote the selected tenants' demoted pages back into the device
    pool (the inverse of ``demote_tenants``).

    Fresh device rows come from the tenant's own lease allocator
    (acquiring quanta on demand); the host copies are scattered in, every
    COLD entry referencing them is rewritten to the new device row with
    the residency bit cleared, and the host rows return to the store's
    free list. Bit-verified by default: the device rows are read back and
    compared against the host copies. Raises if the pool cannot grant
    enough quanta — callers demote (or free) someone else first.

    Args:
        fleet: the fleet state (returned updated, never mutated).
        store: the ``TieredStore`` the pages were demoted into.
        tenants: int id, id sequence, or (T,) bool mask.
        max_rows: promote at most this many rows across the call
            (``None`` = everything cold the selected tenants hold).
        verify: bit-verify every transferred row (default True).

    Returns:
        ``(fleet, report)``: ``dict(rows_promoted=int, tenants=[...])``.
    """
    spec = fleet.spec
    sel = _tenant_sel(spec.n_tenants, tenants)
    lengths = np.asarray(fleet.length)
    cold_count = np.asarray(fleet.cold_count)
    # one full host copy (see demote_tenants): entry rewriting stays in
    # numpy at fixed shapes and ships back to the device in one transfer
    l2_np = np.array(fleet.l2)
    budget = np.inf if max_rows is None else int(max_rows)

    # pick the host rows to promote per tenant, under the budget
    plans: dict[int, np.ndarray] = {}        # t -> host rows
    masks: dict[int, np.ndarray] = {}        # t -> cold entry mask
    rows_all: dict[int, np.ndarray] = {}     # t -> ptr field per entry
    need = np.zeros(spec.n_tenants, np.int32)
    for t in np.flatnonzero(sel & (cold_count > 0)):
        if budget <= 0:
            break
        coldm, rows = _tenant_cold_rows(l2_np[t], int(lengths[t]))
        host_rows = np.unique(rows[coldm])
        if host_rows.size > budget:
            host_rows = host_rows[: int(budget)]
        if host_rows.size == 0:
            continue
        plans[int(t)] = host_rows
        masks[int(t)] = coldm
        rows_all[int(t)] = rows
        need[t] = host_rows.size
        budget -= host_rows.size
    if not plans:
        return fleet, dict(rows_promoted=0, tenants=[])

    lease_owner, lease_index, lease_count, short = _acquire_leases(
        fleet, jnp.asarray(need)
    )
    short_np = np.asarray(short)
    if np.any(short_np[list(plans)]):
        bad = [t for t in plans if short_np[t]]
        raise RuntimeError(
            f"device pool exhausted promoting tenants {bad}: demote or "
            "free other tenants first"
        )
    bsz = int(np.max(need))
    dev_rows, _ = _rows_for(spec, lease_index, fleet.alloc_count, bsz)
    dev_rows = np.asarray(dev_rows)

    # one batched scatter for the whole call's data movement
    all_dev, all_host = [], []
    for t, host_rows in plans.items():
        all_dev.append(dev_rows[t, : host_rows.size])
        all_host.append(host_rows)
    dev_cat = np.concatenate(all_dev)
    host_cat = np.concatenate(all_host)
    vals = store.get(host_cat)
    pool = fleet.pool.at[jnp.asarray(dev_cat, jnp.int32)].set(
        jnp.asarray(vals)
    )
    if verify and not np.array_equal(
            np.asarray(pool[jnp.asarray(dev_cat, jnp.int32)]).view(np.uint8),
            vals.view(np.uint8)):
        raise RuntimeError("promotion transfer verification failed")

    # rewrite the promoted COLD entries: host row -> device row, bit clear
    alloc_count = np.asarray(fleet.alloc_count).copy()
    new_cold = np.asarray(fleet.cold_count).copy()
    for t, host_rows in plans.items():
        length_t = int(lengths[t])
        w0 = l2_np[t, :length_t, ..., 0]             # in-place view
        coldm, rows = masks[t], rows_all[t]
        promoting = coldm & np.isin(rows, host_rows)
        # host_rows is np.unique output (sorted) — searchsorted maps each
        # promoted entry's host row to its fresh device row
        idx = np.searchsorted(host_rows, rows[promoting])
        new_ptr = dev_rows[t, : host_rows.size][idx].astype(np.uint32)
        w0[promoting] = (
            (w0[promoting]
             & ~np.uint32(fmt.PTR_MASK) & ~np.uint32(fmt.FLAG_COLD))
            | new_ptr
        )
        alloc_count[t] += host_rows.size
        new_cold[t] -= host_rows.size
        store.free(host_rows)
        store.promoted_rows += int(host_rows.size)

    out = dataclasses.replace(
        fleet,
        l2=jnp.asarray(l2_np),
        pool=pool,
        lease_owner=lease_owner,
        lease_index=lease_index,
        lease_count=lease_count,
        alloc_count=jnp.asarray(alloc_count, jnp.int32),
        cold_count=jnp.asarray(new_cold, jnp.int32),
    )
    return out, dict(rows_promoted=int(sum(need)), tenants=sorted(plans))


def read_tiered(fleet: ChainFleet, store, page_ids, *,
                method: str = "auto"):
    """Batched fleet read that serves cold pages from the host tier.

    The device gather (``read``) masks cold hits to zeros; this host-side
    wrapper fills exactly those positions from the ``TieredStore``. The
    serving path never calls this — it promotes before reading — but the
    maintenance/verification plane (and the tiering benchmark's
    bit-verify pass) read through it without perturbing residency.

    Returns ``(data (T, B, page_size) numpy, ResolveResult)``.
    """
    data, res = read(fleet, jnp.asarray(page_ids, jnp.int32), method=method)
    data = np.array(data)        # writable host copy (asarray is read-only)
    coldm = np.asarray(res.cold & res.found & ~res.zero)
    if coldm.any():
        host_rows = np.asarray(res.ptr)[coldm].astype(np.int64)
        data[coldm] = store.get(host_rows)
    return data, res


# -- per-tenant views & host-side helpers ------------------------------------


def tenant_chain(fleet: ChainFleet, t: int) -> Chain:
    """A read-only single-``Chain`` view of tenant ``t``.

    Shares the fleet's global pool, so resolvers and reads on the view
    agree bit-for-bit with the batched fleet paths. Do **not** run any
    mutating single-chain op (``write``, ``stream``, ``compact_pool``,
    ``convert_to_scalable``) through the view: they allocate from a linear
    cursor, not the fleet allocator's leases, and would corrupt other
    tenants. The view's ``pool_cursor`` is pinned to ``pool_capacity`` so
    an accidental ``write`` flags overflow immediately and ``stream``
    raises rather than scribbling over foreign leases.
    """
    return Chain(
        spec=fleet.spec.chain_spec(),
        scalable=bool(fleet.scalable[t]),
        l1=fleet.l1[t],
        l2=fleet.l2[t],
        pool=fleet.pool,
        pool_cursor=jnp.asarray(fleet.spec.pool_capacity, jnp.int32),
        length=fleet.length[t],
        overflow=fleet.overflow[t],
        snap_dropped=fleet.snap_dropped[t],
    )


def check_pool_capacity(fleet: ChainFleet) -> None:
    """Raise if any tenant hit a resource limit (host-side guard)."""
    bad = np.flatnonzero(np.asarray(fleet.overflow))
    if bad.size:
        raise RuntimeError(
            f"page pool exhausted for tenants {bad.tolist()}: grow "
            "FleetSpec.pool_capacity or stream/compact their chains"
        )
    capped = np.flatnonzero(np.asarray(fleet.snap_dropped))
    if capped.size:
        raise RuntimeError(
            f"snapshot dropped for tenants {capped.tolist()}: their chains "
            "are at max_chain; stream them to make room"
        )


def fleet_stats(fleet: ChainFleet) -> dict:
    """Host-side occupancy summary (monitoring / benchmark reporting)."""
    owner = np.asarray(fleet.lease_owner)
    return dict(
        n_tenants=fleet.spec.n_tenants,
        quanta_total=fleet.spec.n_quanta,
        quanta_leased=int(np.sum(owner >= 0)),
        quanta_free=int(np.sum(owner < 0)),
        rows_allocated=int(np.sum(np.asarray(fleet.alloc_count))),
        mean_chain_length=float(np.mean(np.asarray(fleet.length))),
        overflowed_tenants=int(np.sum(np.asarray(fleet.overflow))),
        snapshot_capped_tenants=int(np.sum(np.asarray(fleet.snap_dropped))),
        rows_cold=int(np.sum(np.asarray(fleet.cold_count))),
        cold_tenants=int(np.sum(np.asarray(fleet.cold_count) > 0)),
    )


def tenant_stats(fleet: ChainFleet) -> dict:
    """Per-tenant occupancy arrays — the scheduler's ranking signal.

    The per-tenant counterpart of ``fleet_stats``: (T,) numpy arrays of
    chain ``length``, ``alloc_count`` (pool rows held), ``lease_count``
    (quanta held) and the ``overflow``/``snap_dropped`` pressure flags.
    """
    return dict(
        length=np.asarray(fleet.length),
        alloc_count=np.asarray(fleet.alloc_count),
        lease_count=np.asarray(fleet.lease_count),
        overflow=np.asarray(fleet.overflow),
        snap_dropped=np.asarray(fleet.snap_dropped),
        cold_count=np.asarray(fleet.cold_count),
    )
