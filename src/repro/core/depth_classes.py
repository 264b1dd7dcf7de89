"""Depth classes: a fleet whose disks each pay for their own chain depth.

A ``ChainFleet`` holds its index as one dense ``(T, max_chain, P)``
stack, so every disk pays for the deepest chain. The paper's cloud
(arXiv:2205.06842 §3) has chains of 1 to about 1,000 layers side by
side; at that spread a dense stack is mostly empty layers. A
``ClassedFleet`` is a tuple of dense fleets, one per depth capacity (a
*class*), and a host map from disk id to its (class, row):

* **read** runs one jitted program (module ``jit_read_classes``). For each
  class it takes that class's rows of the (disks, B) page ids and runs
  the dense read's own program body (``fleet._read_program``, with its
  ``fleet.resolve``/``fleet.gather`` scopes) under the named scope
  ``fleet.class<C>``, then puts the results back in disk order. A disk's
  ``owner`` is the layer within its own chain.
* **snapshot** of a disk at its class's capacity first promotes it to
  the next deeper class (one twice as deep where there is none) through
  ``migrate.migrate_tenant``, which bit-verifies the move before the
  source row is freed; then the disk snapshots there. Reads then return
  what a dense fleet deep enough for every disk returns.
* **write**, **free** and **attach** route through the map to the
  disk's class.

Capacities are those of the fleets a ``ClassedFleet`` is assembled from
(``assemble``) and those that promotion makes; a class whose rows are
all free is dropped.
"""

from __future__ import annotations

import dataclasses
import math
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import fleet as fleet_lib
from repro.core import migrate
from repro.core.fleet import ChainFleet


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ClassedFleet:
    classes: tuple[ChainFleet, ...]      # ascending max_chain
    # per class, the disk id held in each row (-1: a free row)
    disks: tuple[tuple[int, ...], ...] = dataclasses.field(
        metadata=dict(static=True))

    @property
    def n_disks(self) -> int:
        return sum(d >= 0 for rows in self.disks for d in rows)

    @property
    def capacities(self) -> tuple[int, ...]:
        return tuple(f.spec.max_chain for f in self.classes)

    def where(self, disk: int) -> tuple[int, int]:
        """The (class, row) that holds ``disk``."""
        for c, rows in enumerate(self.disks):
            if disk in rows:
                return c, rows.index(disk)
        raise KeyError(f"no disk {disk} in this fleet")

    def lengths(self) -> np.ndarray:
        """(disks,) chain length of each disk, on the host."""
        out = np.zeros(self.n_disks, np.int32)
        for f, rows in zip(self.classes, self.disks):
            length = np.asarray(f.length)
            for r, d in enumerate(rows):
                if d >= 0:
                    out[d] = length[r]
        return out

    def index_bytes(self) -> int:
        """Bytes of L2 index held on the device."""
        return sum(math.prod(f.l2.shape) * f.l2.dtype.itemsize
                   for f in self.classes)


def assemble(classes: Sequence[ChainFleet],
             disks: Sequence[Sequence[int]] | None = None) -> ClassedFleet:
    """A classed fleet of the given dense fleets, one class each.

    ``disks[c][r]`` is the disk id held in row ``r`` of ``classes[c]``
    (-1 for a free row); by default the rows are numbered across the
    classes in order. Disk ids must be 0..n-1, each once. The classes may
    come in any order and must differ in ``max_chain`` and agree on the
    guest-visible geometry.
    """
    if disks is None:
        first = np.cumsum([0] + [f.spec.n_tenants for f in classes])
        disks = [range(a, a + f.spec.n_tenants)
                 for a, f in zip(first, classes)]
    pairs = sorted(zip(classes, (tuple(int(d) for d in r) for r in disks)),
                   key=lambda p: p[0].spec.max_chain)
    caps = [f.spec.max_chain for f, _ in pairs]
    if len(set(caps)) != len(caps):
        raise ValueError(f"two classes share a depth capacity: {caps}")
    for f, rows in pairs:
        if len(rows) != f.spec.n_tenants:
            raise ValueError(f"class {f.spec.max_chain} has "
                             f"{f.spec.n_tenants} rows; {len(rows)} named")
        for key in ("n_pages", "page_size", "l2_per_table", "dtype"):
            if getattr(f.spec, key) != getattr(pairs[0][0].spec, key):
                raise ValueError(f"classes disagree on {key}")
    ids = sorted(d for _, rows in pairs for d in rows if d >= 0)
    if ids != list(range(len(ids))):
        raise ValueError(f"disk ids must be 0..n-1, each once; got {ids}")
    return ClassedFleet(classes=tuple(f for f, _ in pairs),
                        disks=tuple(rows for _, rows in pairs))


# -- the read -----------------------------------------------------------------


def read(classed: ClassedFleet, page_ids: jax.Array, *,
         method: str = "auto"):
    """Batched whole-page read of every disk, in one program.

    ``page_ids``: (disks, B) int32. Returns ``(data, result)`` as
    ``fleet.read`` does, in disk order: data (disks, B, page_size) and a
    ``ResolveResult`` of (disks, B) leaves, ``owner`` the layer within
    the disk's own chain and ``ptr`` a row of its class's pool.

    The call opens one ``snapstore.fleet.read`` span with ``fleet.read``'s
    arguments (``resolved`` summed over the classes) and ``classes`` (how
    many) and ``index_bytes`` (L2 bytes held on the device).
    """
    n, b = np.shape(page_ids)
    resolved = sum(fleet_lib.resolved_entries(f.spec, method, b)
                   for f in classed.classes)
    with obs.span("fleet.read", pages=n * b, resolved=resolved,
                  method=method, classes=len(classed.classes),
                  index_bytes=classed.index_bytes()):
        return _read(classed, page_ids, method=method)


def _read_program(classed: ClassedFleet, page_ids: jax.Array, *,
                  method: str):
    outs, slot, first = [], {}, 0
    for fl, rows in zip(classed.classes, classed.disks):
        # a free row reads disk 0's pages; its results are never picked
        ids = page_ids[np.maximum(np.asarray(rows), 0)]
        with jax.named_scope(f"fleet.class{fl.spec.max_chain}"):
            outs.append(fleet_lib._read_program(fl, ids, method=method))
        slot.update((d, first + r) for r, d in enumerate(rows) if d >= 0)
        first += len(rows)
    pick = np.asarray([slot[d] for d in range(len(slot))])
    return jax.tree.map(lambda *parts: jnp.concatenate(parts)[pick], *outs)


_read_program.__name__ = "read_classes"  # the module is ``jit_read_classes``
_read = jax.jit(_read_program, static_argnames=("method",))
read.lower = _read.lower


# -- writes, snapshots and the tenancy ops, through the map ------------------


def _selected(classed: ClassedFleet, mask) -> np.ndarray:
    n = classed.n_disks
    return (np.ones(n, bool) if mask is None
            else np.broadcast_to(np.asarray(mask, bool), (n,)))


def _replace_class(classed: ClassedFleet, c: int,
                   fl: ChainFleet) -> ClassedFleet:
    classes = list(classed.classes)
    classes[c] = fl
    return dataclasses.replace(classed, classes=tuple(classes))


def write(classed: ClassedFleet, page_ids: jax.Array, data: jax.Array,
          mask=None) -> ClassedFleet:
    """``fleet.write`` of each disk's batch, in the disk's class.
    ``page_ids``: (disks, B); ``data``: (disks, B, page_size); ``mask``:
    optional (disks,) bool of the disks that write."""
    sel = _selected(classed, mask)
    out = classed
    for c, (fl, rows) in enumerate(zip(classed.classes, classed.disks)):
        rows = np.asarray(rows)
        take = np.maximum(rows, 0)
        m = (rows >= 0) & sel[take]
        if m.any():
            out = _replace_class(out, c, fleet_lib.write(
                fl, page_ids[take], data[take], jnp.asarray(m)))
    return out


def snapshot(classed: ClassedFleet, mask=None, *,
             store=None) -> ClassedFleet:
    """Snapshot the selected disks (``mask``: optional (disks,) bool),
    each in its own format. The disks at their class's capacity are
    promoted first (``promote``, shallowest class first, so that a class
    a promotion empties is still there for the next), so no snapshot is
    dropped. ``store``: the ``TieredStore`` of any demoted pages a
    promoted disk holds."""
    sel = _selected(classed, mask)
    lengths = classed.lengths()
    full: dict[int, list[int]] = {}
    for d in np.flatnonzero(sel):
        cap = classed.classes[classed.where(int(d))[0]].spec.max_chain
        if lengths[d] >= cap:
            full.setdefault(cap, []).append(int(d))
    for cap in sorted(full):
        classed = promote(classed, full[cap], store=store)
    for c, (fl, rows) in enumerate(zip(classed.classes, classed.disks)):
        m = np.asarray([d >= 0 and sel[d] for d in rows])
        if m.any():
            classed = _replace_class(classed, c, fleet_lib.snapshot(
                fl, jnp.asarray(m)))
    return classed


def promote(classed: ClassedFleet, disks: Sequence[int], *,
            store=None) -> ClassedFleet:
    """Move ``disks``, all of one class, to the next deeper class, or to a
    new class twice as deep where there is none, each through
    ``migrate.migrate_tenant`` (export, import, bit-verify, then free the
    source row). The target class grows once: by the rows its free ones
    fall short of, and by each disk's share of its old class's pool (a
    row's share of the quanta, or the quanta the disk holds where that is
    more). A class left with no disk is dropped.

    Opens one span ``snapstore.fleet.promote`` per disk (``tenant``,
    ``from_depth`` and ``to_depth``, the two classes' capacities, and
    ``rows``, the device pool rows moved).
    """
    at = [classed.where(d) for d in disks]
    c = at[0][0]
    if any(ci != c for ci, _ in at):
        raise ValueError(f"disks {list(disks)} are not all of one class")
    src = classed.classes[c]
    spec = src.spec
    share = max(spec.n_quanta // spec.n_tenants, 1)
    lease_count = np.asarray(src.lease_count)
    need = sum(max(int(lease_count[r]), share) for _, r in at)
    classes, held = list(classed.classes), [list(d) for d in classed.disks]
    if c + 1 < len(classes):
        t, dst = c + 1, classes[c + 1]
        rows = [i for i, d in enumerate(held[t]) if d < 0][:len(disks)]
        new = len(disks) - len(rows)
        rows += range(dst.spec.n_tenants, dst.spec.n_tenants + new)
        dst = fleet_lib.grow(dst, tenants=new, quanta=need)
        held[t] += [-1] * new
    else:
        t, rows = len(classes), list(range(len(disks)))
        dst = fleet_lib.create(dataclasses.replace(
            spec, n_tenants=len(disks), max_chain=2 * spec.max_chain,
            pool_capacity=need * spec.lease_quantum))
        classes.append(dst)
        held.append([-1] * len(disks))
    for disk, (_, r), row in zip(disks, at, rows):
        with obs.span("fleet.promote", tenant=disk,
                      from_depth=spec.max_chain,
                      to_depth=dst.spec.max_chain) as ann:
            src, dst, report = migrate.migrate_tenant(
                src, r, dst, row, src_store=store, dst_store=store)
            ann.set_metadata(rows=report["rows_hot"])
        held[c][r], held[t][row] = -1, disk
    classes[c], classes[t] = src, dst
    keep = [i for i, rows in enumerate(held) if any(d >= 0 for d in rows)]
    return ClassedFleet(classes=tuple(classes[i] for i in keep),
                        disks=tuple(tuple(held[i]) for i in keep))


def free(classed: ClassedFleet, disks, *, store=None) -> ClassedFleet:
    """``fleet.free_tenant`` of each of ``disks`` (an id, ids or a
    (disks,) bool mask) in its class: each becomes an empty length-1
    chain in the row it held."""
    sel = fleet_lib._tenant_sel(classed.n_disks, disks)
    for c, rows in enumerate(classed.disks):
        hit = [r for r, d in enumerate(rows) if d >= 0 and sel[d]]
        if hit:
            classed = _replace_class(classed, c, fleet_lib.free_tenant(
                classed.classes[c], hit, store=store))
    return classed


def attach(classed: ClassedFleet, disk: int, *,
           scalable: bool | None = None) -> ClassedFleet:
    """``fleet.attach_tenant`` of ``disk`` in its class: a fresh empty
    chain, in the given format (default: the disk's own)."""
    c, r = classed.where(disk)
    return _replace_class(classed, c, fleet_lib.attach_tenant(
        classed.classes[c], r, scalable=scalable))
