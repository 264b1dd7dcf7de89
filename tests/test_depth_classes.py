"""Depth classes (``repro.core.depth_classes``) at small sizes on the CPU:
a fleet of disks at chains of different depths, each class paying index
for its own depth, read in one program. Checked against a dense fleet
grown by the same ``write``/``snapshot`` calls and against a plain
record of every write; promotion at a class's capacity, free and attach
through the map, and the read's span and scopes."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import depth_classes as dc
from repro.core import fleet as fleet_lib

from test_obs import record

DEPTHS = [1, 1, 3, 3, 8, 8, 17, 17]
SCALABLE = np.array([True, False] * 4)
BATCH = 4           # pages each disk writes into each of its layers
WORDS = 8


def dense_spec(n_disks, n_pages, max_chain):
    # five lease quanta of 16 rows a disk: room for 17 layers of 4 writes
    return fleet_lib.FleetSpec(
        n_tenants=n_disks, n_pages=n_pages, page_size=WORDS,
        max_chain=max_chain, pool_capacity=n_disks * 5 * 16,
        lease_quantum=16, l2_per_table=64, slice_len=16)


def grow_both(depths=DEPTHS, n_pages=192, seed=5, dense_depth=None):
    """A dense fleet as deep as the deepest disk (or ``dense_depth``) and
    a classed fleet that starts at depth 1, given the same writes and
    snapshots, and the plain record of them: per disk, page -> (layer,
    payload) of its newest write."""
    rng = np.random.default_rng(seed)
    n, deepest = len(depths), max(depths)
    spec = dense_spec(n, n_pages, dense_depth or deepest)
    dense = fleet_lib.create(spec, scalable=jnp.asarray(SCALABLE[:n]))
    classed = dc.assemble([fleet_lib.create(
        dataclasses.replace(spec, max_chain=1),
        scalable=jnp.asarray(SCALABLE[:n]))])
    want = [{} for _ in range(n)]
    for layer in range(deepest):
        ids = np.stack([rng.choice(n_pages, BATCH, replace=False)
                        for _ in range(n)]).astype(np.int32)
        data = rng.random((n, BATCH, WORDS), dtype=np.float32)
        live = np.asarray(depths) > layer
        dense = fleet_lib.write(dense, jnp.asarray(ids), jnp.asarray(data),
                                jnp.asarray(live))
        classed = dc.write(classed, jnp.asarray(ids), jnp.asarray(data),
                           live)
        for d in np.flatnonzero(live):
            for page, words in zip(ids[d], data[d]):
                want[d][int(page)] = (layer, words)
        snap = np.asarray(depths) > layer + 1
        if snap.any():
            dense = fleet_lib.snapshot(dense, jnp.asarray(snap))
            classed = dc.snapshot(classed, snap)
    assert not np.asarray(dense.overflow).any()
    for f in classed.classes:
        assert not np.asarray(f.overflow).any()
        assert not np.asarray(f.snap_dropped).any()
    return dense, classed, want


@pytest.fixture(scope="module")
def grown():
    return grow_both()


def full_grid(n_disks, n_pages):
    return jnp.broadcast_to(jnp.arange(n_pages, dtype=jnp.int32),
                            (n_disks, n_pages))


def assert_reads_alike(got, want, fields=("owner", "found", "zero",
                                          "lookups", "cold")):
    (d1, r1), (d2, r2) = got, want
    assert (np.asarray(d1).view(np.uint32)
            == np.asarray(d2).view(np.uint32)).all()
    for field in fields:
        assert (np.asarray(getattr(r1, field))
                == np.asarray(getattr(r2, field))).all(), field


def test_each_disk_pays_index_for_its_own_depth(grown):
    _, classed, _ = grown
    assert classed.capacities == (1, 4, 8, 32)
    assert (classed.lengths() == DEPTHS).all()
    by_class = {f.spec.max_chain: sorted(d for d in rows if d >= 0)
                for f, rows in zip(classed.classes, classed.disks)}
    assert by_class == {1: [0, 1], 4: [2, 3], 8: [4, 5], 32: [6, 7]}
    per_layer = 192 * 8
    assert classed.index_bytes() == sum(
        c * f.spec.n_tenants * per_layer
        for c, f in zip(classed.capacities, classed.classes))


@pytest.mark.parametrize("method", ["auto", "vanilla", "direct"])
def test_class_read_at_mixed_depths_is_the_dense_read(grown, method):
    dense, classed, _ = grown
    ids = full_grid(len(DEPTHS), 192)
    assert_reads_alike(dc.read(classed, ids, method=method),
                       fleet_lib.read(dense, ids, method=method))


@pytest.mark.parametrize("method", ["auto", "vanilla"])
def test_class_read_at_mixed_depths_matches_the_record(grown, method):
    _, classed, want = grown
    data, res = dc.read(classed, full_grid(len(DEPTHS), 192), method=method)
    data, owner, found = (np.asarray(a) for a in (data, res.owner,
                                                   res.found))
    for d, pages in enumerate(want):
        assert found[d].sum() == len(pages)
        for page, (layer, words) in pages.items():
            assert found[d, page] and (data[d, page] == words).all()
            # the walk stops at a scalable disk's newest copy of the page
            if method == "auto" or not SCALABLE[d]:
                assert owner[d, page] == layer
        assert (data[d][~found[d]] == 0).all()


def test_direct_reads_trust_only_the_scalable_disks(grown):
    _, classed, want = grown
    res = dc.read(classed, full_grid(len(DEPTHS), 192), method="direct")[1]
    found = np.asarray(res.found).sum(axis=1)
    for d in range(len(DEPTHS)):
        # a scalable disk's active layer holds every live page, trusted; a
        # vanilla disk marks none of its entries trusted
        assert found[d] == (len(want[d]) if SCALABLE[d] else 0), d


def _lane_aligned_classes(depths, n_pages, seed=9):
    """One dense fleet per depth, each grown to that depth, assembled."""
    rng = np.random.default_rng(seed)
    classes = []
    for depth in depths:
        fl = fleet_lib.create(dense_spec(2, n_pages, depth),
                              scalable=jnp.asarray([True, False]))
        for layer in range(depth):
            ids = np.stack([rng.choice(n_pages, BATCH, replace=False)
                            for _ in range(2)]).astype(np.int32)
            fl = fleet_lib.write(fl, jnp.asarray(ids), jnp.asarray(
                rng.random((2, BATCH, WORDS), dtype=np.float32)))
            if layer < depth - 1:
                fl = fleet_lib.snapshot(fl)
        classes.append(fl)
    return classes


def test_class_read_runs_each_class_through_the_batch_kernel():
    """Lane-aligned classes take the dense read's kernel path: the batch
    resolve (8 * 128 < 1152) and the Pallas gather, class by class."""
    classes = _lane_aligned_classes([3, 1, 8], 1152)
    classed = dc.assemble(classes, [[4, 5], [0, 1], [2, 3]])
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 1152, (6, 8)).astype(np.int32)
    got = dc.read(classed, jnp.asarray(ids))
    for fl, rows in zip(classed.classes, classed.disks):
        want = fleet_lib.read(fl, jnp.asarray(ids[list(rows)]))
        part = jax.tree.map(lambda a: a[np.asarray(rows)], got)
        assert_reads_alike(part, want, fields=("owner", "found", "zero",
                                               "lookups", "cold", "ptr"))
    hlo = dc.read.lower(classed, jnp.asarray(ids),
                        method="auto").compile().as_text()
    assert "resolve_batch_fleet_pallas" in hlo


def test_promotion_at_capacity_is_bit_for_bit_like_a_dense_fleet(tmp_path):
    """A disk that snapshots at its class's capacity moves to the next
    deeper class (into a new row there, or a free one), or to a new class
    twice as deep; reads stay those of one dense fleet deep enough for
    every disk, through later writes and snapshots too."""
    dense, classed, _ = grow_both(depths=[2, 2, 4, 4], seed=11, dense_depth=8)
    assert classed.disks == ((0, 1, -1, -1), (2, 3))
    out = []
    spans = record(tmp_path, lambda: out.append(
        dc.snapshot(classed, np.array([True, False, True, True]))))
    after = out[0]
    dense = fleet_lib.snapshot(dense, jnp.asarray([True, False, True, True]))
    assert after.capacities == (2, 4, 8)
    assert after.disks == ((-1, 1, -1, -1), (-1, -1, 0), (2, 3))
    assert (after.lengths() == [3, 2, 5, 5]).all()
    promotes = [s[3] for s in spans if s[0] == "fleet.promote"]
    assert [(a["tenant"], a["from_depth"], a["to_depth"])
            for a in promotes] == [(0, 2, 4), (2, 4, 8), (3, 4, 8)]
    assert all(a["rows"] > 0 for a in promotes)
    ids = full_grid(4, 192)
    for method in ("auto", "vanilla", "direct"):
        assert_reads_alike(dc.read(after, ids, method=method),
                           fleet_lib.read(dense, ids, method=method))
    # disk 1 takes a free row of class 4; class 2 is left empty and goes
    after = dc.snapshot(after, np.array([False, True, False, False]))
    dense = fleet_lib.snapshot(dense, jnp.asarray([False, True, False, False]))
    assert after.disks == ((1, -1, 0), (2, 3))
    rng = np.random.default_rng(2)
    ids4 = jnp.asarray(np.stack([rng.choice(192, BATCH, replace=False)
                                 for _ in range(4)]).astype(np.int32))
    data = jnp.asarray(rng.random((4, BATCH, WORDS), dtype=np.float32))
    after = dc.snapshot(dc.write(after, ids4, data))
    dense = fleet_lib.snapshot(fleet_lib.write(dense, ids4, data))
    assert (after.lengths() == np.asarray(dense.length)).all()
    for method in ("auto", "vanilla"):
        assert_reads_alike(dc.read(after, ids, method=method),
                           fleet_lib.read(dense, ids, method=method))


def test_a_class_left_with_no_disk_is_dropped():
    _, classed, _ = grow_both(depths=[1, 1], seed=4)
    assert classed.capacities == (1,)
    out = dc.snapshot(classed)
    assert out.capacities == (2,) and out.disks == ((0, 1),)
    assert (out.lengths() == [2, 2]).all()


def test_free_and_attach_route_through_the_map(grown):
    dense, classed, _ = grown
    freed = dc.free(classed, [3, 6])
    dense_freed = fleet_lib.free_tenant(dense, [3, 6])
    assert freed.disks == classed.disks
    assert (freed.lengths() == [1, 1, 3, 1, 8, 8, 1, 17]).all()
    ids = full_grid(len(DEPTHS), 192)
    assert_reads_alike(dc.read(freed, ids), fleet_lib.read(dense_freed, ids))
    assert not np.asarray(dc.read(freed, ids)[1].found)[[3, 6]].any()
    flipped = dc.attach(freed, 6, scalable=False)
    c, r = flipped.where(6)
    assert not bool(flipped.classes[c].scalable[r])
    assert bool(freed.classes[c].scalable[r])
    # the freed disk writes again from layer 0, in the row it held
    page = jnp.zeros((len(DEPTHS), 1), jnp.int32) + 7
    data = jnp.ones((len(DEPTHS), 1, WORDS))
    only6 = np.arange(len(DEPTHS)) == 6
    again = dc.write(flipped, page, data, only6)
    res = dc.read(again, page)[1]
    assert bool(res.found[6, 0]) and int(res.owner[6, 0]) == 0


def test_assemble_refuses_a_bad_map():
    one = fleet_lib.create(dense_spec(2, 192, 1))
    two = fleet_lib.create(dense_spec(2, 192, 2))
    with pytest.raises(ValueError, match="share a depth"):
        dc.assemble([one, one])
    with pytest.raises(ValueError, match="0..n-1"):
        dc.assemble([one, two], [[0, 1], [1, 2]])
    with pytest.raises(ValueError, match="rows"):
        dc.assemble([one], [[0]])
    assert dc.assemble([two, one]).capacities == (1, 2)


def test_grow_adds_empty_slots_and_free_quanta(grown):
    dense, _, _ = grown
    big = fleet_lib.grow(dense, tenants=2, quanta=3)
    n = len(DEPTHS)
    assert big.spec.n_tenants == n + 2
    assert big.spec.n_quanta == dense.spec.n_quanta + 3
    ids = full_grid(n + 2, 192)
    data, res = fleet_lib.read(big, ids)
    want = fleet_lib.read(dense, ids[:n])
    assert_reads_alike((data[:n], jax.tree.map(lambda a: a[:n], res)), want)
    assert not np.asarray(res.found)[n:].any()
    assert (np.asarray(big.lease_owner)[-3:] == -1).all()
    assert (np.asarray(big.length)[n:] == 1).all()


def test_one_read_span_per_class_read(tmp_path, grown):
    _, classed, _ = grown
    ids = jnp.zeros((len(DEPTHS), 8), jnp.int32)
    jax.block_until_ready(dc.read(classed, ids))

    def reads():
        for _ in range(3):
            jax.block_until_ready(dc.read(classed, ids))

    spans = record(tmp_path, reads)
    assert [s[0] for s in spans] == ["fleet.read"] * 3
    rows = sum(f.spec.n_tenants for f in classed.classes)   # free ones too
    for *_, args in spans:
        assert args == dict(pages=len(DEPTHS) * 8, resolved=rows * 8,
                            method="auto", classes=4,
                            index_bytes=classed.index_bytes())


def test_read_program_holds_each_class_under_its_scope(grown):
    _, classed, _ = grown
    ids = jnp.zeros((len(DEPTHS), 8), jnp.int32)
    hlo = dc.read.lower(classed, ids, method="auto").compile().as_text()
    assert hlo.startswith("HloModule jit_read_classes,")
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for cap in classed.capacities:
        for inner in ("fleet.resolve", "fleet.gather"):
            assert any(n.startswith(f"jit(read_classes)/fleet.class{cap}/"
                                    f"{inner}/") for n in names), (cap, inner)
