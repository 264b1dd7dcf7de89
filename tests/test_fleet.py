"""ChainFleet: batched multi-tenant ops ≡ a python loop over single chains.

The fleet layer's contract is that, tenant by tenant, every batched
operation (resolve_{vanilla,direct,auto}, write, snapshot, read) behaves
exactly like the corresponding single-``Chain`` operation — including
mixed scalable/vanilla fleets and pool-lease exhaustion. These tests
mirror scripted (and, with hypothesis, random) op sequences onto both
representations and compare them field-for-field. Pool row *pointers* are
the one legitimate difference (shared leased pool vs private linear
pools), so data equality is checked through reads, not ptrs.

Every equivalence check runs over all resolver methods — the vmapped jnp
gather ("vanilla"/"gather"/"direct"/"auto") *and* the stacked Pallas
kernels ("pallas_vanilla"/"pallas_direct", interpret mode on CPU) — each
pinned against the same single-chain jnp oracle, so the kernel and gather
implementations cannot drift apart.
"""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fleet, store

#: fleet resolver method → the single-chain oracle method it must match
METHODS = {
    "vanilla": "vanilla",
    "gather": "vanilla",            # alias: the vmapped-jnp implementation
    "direct": "direct",
    "auto": "auto",
    "pallas_vanilla": "vanilla",    # stacked kernel, walk semantics
    "pallas_direct": "direct",      # stacked kernel, direct semantics
}
N_PAGES, PAGE, MAXC = 64, 4, 8


def make_fleet(n_tenants, scalable, *, pool_capacity=2048, lease_quantum=32,
               max_chain=MAXC):
    spec = fleet.FleetSpec(
        n_tenants=n_tenants, n_pages=N_PAGES, page_size=PAGE,
        max_chain=max_chain, pool_capacity=pool_capacity,
        lease_quantum=lease_quantum, l2_per_table=32,
    )
    return fleet.create(spec, scalable=jnp.asarray(scalable, bool))


def make_chains(scalable, *, pool_capacity=2048, max_chain=MAXC):
    return [
        store.create(n_pages=N_PAGES, page_size=PAGE, max_chain=max_chain,
                     pool_capacity=pool_capacity, scalable=bool(s),
                     l2_per_table=32)
        for s in scalable
    ]


def apply_ops(ops, scalable):
    """Run (kind, mask, seed) ops on a fleet and mirrored single chains."""
    t = len(scalable)
    fl = make_fleet(t, scalable)
    chains = make_chains(scalable)
    for kind, mask, seed in ops:
        mask = np.asarray(mask, bool)
        if kind == "write":
            rng = np.random.default_rng(seed)
            ids = np.stack([rng.choice(N_PAGES, 6, replace=False)
                            for _ in range(t)]).astype(np.int32)
            data = rng.standard_normal((t, 6, PAGE)).astype(np.float32)
            fl = fleet.write(fl, jnp.asarray(ids), jnp.asarray(data),
                             jnp.asarray(mask))
            for i in range(t):
                if mask[i]:
                    chains[i] = store.write(chains[i], jnp.asarray(ids[i]),
                                            jnp.asarray(data[i]))
        else:
            # no length filter: both representations cap at max_chain and
            # flag overflow, so the mirror stays exact even past the cap
            fl = fleet.snapshot(fl, jnp.asarray(mask))
            for i in range(t):
                if mask[i]:
                    chains[i] = store.snapshot(chains[i])
    return fl, chains


def assert_equivalent(fl, chains):
    t = len(chains)
    np.testing.assert_array_equal(
        np.asarray(fl.length), [int(c.length) for c in chains])
    ids = jnp.broadcast_to(jnp.arange(N_PAGES, dtype=jnp.int32)[None],
                           (t, N_PAGES))
    for method, oracle in METHODS.items():
        fr = fleet.get_resolver(method)(fl, ids)
        fdata, _ = fleet.read(fl, ids, method=method)
        for i, ch in enumerate(chains):
            cdata, cr = store.read(ch, jnp.arange(N_PAGES, dtype=jnp.int32),
                                   method=oracle)
            for field in ("owner", "found", "zero", "lookups"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(fr, field)[i]),
                    np.asarray(getattr(cr, field)),
                    err_msg=f"{method} tenant {i} field {field}",
                )
            np.testing.assert_allclose(
                np.asarray(fdata[i]), np.asarray(cdata), rtol=1e-6,
                err_msg=f"{method} tenant {i} data",
            )


def test_scripted_mixed_fleet_equals_loop():
    """Masked writes/snapshots on a mixed scalable/vanilla fleet."""
    scalable = [True, False, True, False, True]
    ops = [
        ("write", [1, 1, 1, 1, 1], 0),
        ("snapshot", [1, 1, 0, 1, 1], None),
        ("write", [1, 0, 1, 1, 0], 1),
        ("snapshot", [0, 1, 1, 0, 1], None),
        ("write", [1, 1, 0, 0, 1], 2),
        ("snapshot", [1, 1, 1, 1, 1], None),
        ("write", [1, 1, 1, 1, 1], 3),
    ]
    fl, chains = apply_ops(ops, scalable)
    assert_equivalent(fl, chains)
    assert not bool(jnp.any(fl.overflow))


def test_vanilla_tenants_walk_scalable_go_direct():
    """Fleet-granularity Eq. 1: per-tenant lookup cost depends on the
    tenant's own format, within one batched resolve."""
    scalable = [True, False]
    ops = [("write", [1, 1], 0)] + [("snapshot", [1, 1], None)] * 4
    fl, chains = apply_ops(ops, scalable)
    ids = jnp.broadcast_to(jnp.arange(N_PAGES, dtype=jnp.int32)[None], (2, N_PAGES))
    res = fleet.resolve_auto(fl, ids)
    found = np.asarray(res.found)
    lookups = np.asarray(res.lookups)
    assert np.all(lookups[0][found[0]] == 1)        # scalable: O(1)
    assert np.all(lookups[1][found[1]] == 5)        # vanilla: walks 5 layers
    assert_equivalent(fl, chains)


def test_pallas_methods_ragged_and_inactive_tenants():
    """Kernel resolvers over a fleet with ragged chain lengths and an
    inactive tenant (never written, length 1 — its direct kernel stages
    an empty active volume and its walk kernel must find nothing)."""
    scalable = [True, False, True, True]
    ops = [
        ("write", [1, 1, 1, 0], 0),
        ("snapshot", [1, 0, 1, 0], None),
        ("write", [1, 0, 1, 0], 1),
        ("snapshot", [1, 1, 0, 0], None),
        ("write", [1, 1, 0, 0], 2),
    ]
    fl, chains = apply_ops(ops, scalable)
    assert np.asarray(fl.length).tolist() == [3, 2, 2, 1]
    assert_equivalent(fl, chains)
    # the untouched tenant resolves to nothing on every kernel path
    ids = jnp.broadcast_to(jnp.arange(N_PAGES, dtype=jnp.int32)[None], (4, N_PAGES))
    for method in ("pallas_vanilla", "pallas_direct"):
        res = fleet.get_resolver(method)(fl, ids)
        assert not np.asarray(res.found[3]).any()


def test_auto_uses_kernels_on_aligned_layout():
    """n_pages % 128 == 0 qualifies the layout: method="auto" resolves
    through the stacked kernels, bit-identical to the vmapped jnp auto."""
    import jax

    from repro.core import resolve as resolve_lib

    spec = fleet.FleetSpec(
        n_tenants=2, n_pages=128, page_size=PAGE, max_chain=4,
        pool_capacity=256, lease_quantum=32, l2_per_table=32,
    )
    assert fleet._kernel_layout_ok(spec)
    fl = fleet.create(spec, scalable=jnp.asarray([True, False]))
    ids8 = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (2, 8))
    fl = fleet.write(fl, ids8, jnp.ones((2, 8, PAGE)))
    fl = fleet.snapshot(fl)
    fl = fleet.write(fl, 8 + ids8, 2.0 * jnp.ones((2, 8, PAGE)))
    ids = jnp.broadcast_to(jnp.arange(128, dtype=jnp.int32)[None], (2, 128))
    got = fleet.resolve_auto(fl, ids)
    want = jax.vmap(resolve_lib.get_table_resolver("auto"))(
        fl.l2, fl.length, ids)
    for field in got._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=f"auto field {field}")
    data, res = fleet.read(fl, ids, method="auto")   # kernel gather path
    np.testing.assert_allclose(
        np.asarray(data),
        np.asarray(store.gather_pages(fl.pool, res)), rtol=1e-6)


def test_lease_exhaustion_isolated_per_tenant():
    """A tenant running the shared pool dry flags only itself; other
    tenants' leases and data are untouched and stay equivalent."""
    fl = make_fleet(3, [True, True, True], pool_capacity=32, lease_quantum=8)
    ids = jnp.broadcast_to(jnp.arange(8, dtype=jnp.int32)[None], (3, 8))
    fl = fleet.write(fl, ids, jnp.full((3, 8, PAGE), 1.0))   # 3/4 quanta gone
    fl = fleet.write(fl, ids, jnp.full((3, 8, PAGE), 2.0))   # only one fits
    over = np.asarray(fl.overflow)
    assert over.sum() == 2                     # exactly one tenant won round 2
    winner = int(np.flatnonzero(~over)[0])
    data = np.asarray(fleet.materialize(fl))
    assert np.all(data[winner, :8] == 2.0)
    for t in range(3):
        if t != winner:
            # losers keep their round-1 data; dropped writes corrupt nothing
            assert np.all(data[t, :8] == 1.0)
    with pytest.raises(RuntimeError, match="pool exhausted"):
        fleet.check_pool_capacity(fl)
    # every quantum is leased, and the winner holds exactly two of them
    owner = np.asarray(fl.lease_owner)
    assert (owner >= 0).all()
    assert (owner == winner).sum() == 2
    assert np.asarray(fl.alloc_count)[winner] == 16


def test_single_tenant_fills_entire_pool_then_drops():
    """A tenant leasing every quantum (including the final lease-list slot)
    keeps all its data; writes past pool capacity are dropped — never
    aliased onto the final quantum's immutable rows — and flag overflow."""
    fl = make_fleet(1, [True], pool_capacity=32, lease_quantum=8)
    ids = jnp.arange(8, dtype=jnp.int32)[None]
    for i in range(4):                       # exactly fills all 4 quanta
        fl = fleet.write(fl, ids + 8 * i, jnp.full((1, 8, PAGE), float(i + 1)))
    assert not bool(fl.overflow[0])
    assert int(fl.alloc_count[0]) == 32
    assert np.asarray(fl.lease_index[0]).min() >= 0   # last slot stitched
    data = np.asarray(fleet.materialize(fl))[0]
    for i in range(4):
        assert np.all(data[8 * i:8 * (i + 1)] == i + 1)
    fl = fleet.write(fl, ids, jnp.full((1, 8, PAGE), 99.0))  # pool is full
    assert bool(fl.overflow[0])
    assert int(fl.alloc_count[0]) == 32
    after = np.asarray(fleet.materialize(fl))[0]
    np.testing.assert_array_equal(after, data)        # nothing corrupted


def test_one_batch_wanting_more_quanta_than_pool_flags_overflow():
    """A single write batch needing more quanta than the pool holds must
    still set overflow (the wanted-lease count can exceed n_quanta)."""
    fl = make_fleet(1, [True], pool_capacity=32, lease_quantum=8)
    ids = jnp.arange(33, dtype=jnp.int32)[None]          # wants 5 of 4 quanta
    fl = fleet.write(fl, ids, jnp.ones((1, 33, PAGE)))
    assert bool(fl.overflow[0])
    assert int(fl.alloc_count[0]) == 32                  # 32 rows landed
    data = np.asarray(fleet.materialize(fl))[0]
    assert np.all(data[:32] == 1.0) and np.all(data[32] == 0.0)


def test_l1_presence_bit_survives_mid_batch_exhaustion():
    """A valid and a dropped page sharing one L2 table: the table's L1
    presence bit must end up set regardless of scatter order."""
    fl = make_fleet(1, [True], pool_capacity=8, lease_quantum=8)
    ids = jnp.arange(12, dtype=jnp.int32)[None]          # all in L2 table 0
    fl = fleet.write(fl, ids, jnp.ones((1, 12, PAGE)))
    assert bool(fl.overflow[0])
    assert int(fl.l1[0, 0, 0]) == 1                      # bit set by valid rows
    res = fleet.resolve_direct(fl, ids)
    assert np.asarray(res.found[0]).tolist() == [True] * 8 + [False] * 4


def test_snapshot_mask_and_chain_cap():
    fl = make_fleet(2, [True, True], max_chain=3)
    ids = jnp.broadcast_to(jnp.arange(4, dtype=jnp.int32)[None], (2, 4))
    fl = fleet.write(fl, ids, jnp.ones((2, 4, PAGE)))
    fl = fleet.snapshot(fl, jnp.asarray([True, False]))
    assert np.asarray(fl.length).tolist() == [2, 1]
    fl = fleet.snapshot(fl)                       # both advance
    assert np.asarray(fl.length).tolist() == [3, 2]
    fl = fleet.snapshot(fl)                       # tenant 0 at max_chain
    assert np.asarray(fl.length).tolist() == [3, 3]
    assert np.asarray(fl.snap_dropped).tolist() == [True, False]
    assert not np.asarray(fl.overflow).any()      # pool flag is separate


def test_tenant_chain_view_matches_batched_paths():
    ops = [("write", [1, 1, 1], 0), ("snapshot", [1, 1, 1], None),
           ("write", [1, 1, 1], 1)]
    fl, _ = apply_ops(ops, [True, False, True])
    full = np.asarray(fleet.materialize(fl))
    for t in range(3):
        view = fleet.tenant_chain(fl, t)
        np.testing.assert_allclose(
            np.asarray(store.materialize(view)), full[t], rtol=1e-6)


def test_fleet_property_random_ops():
    """Hypothesis: arbitrary masked write/snapshot interleavings over a
    mixed fleet keep fleet ≡ looped single chains for all resolvers."""
    pytest.importorskip("hypothesis",
                        reason="install extras: pip install -e .[test]")
    from hypothesis import given, settings, strategies as st

    n_t = 4
    op = st.tuples(
        st.sampled_from(["write", "snapshot"]),
        st.lists(st.booleans(), min_size=n_t, max_size=n_t),
        st.integers(0, 2**31 - 1),
    )

    @settings(deadline=None, max_examples=10)
    @given(st.lists(op, min_size=1, max_size=8),
           st.lists(st.booleans(), min_size=n_t, max_size=n_t))
    def run(ops, scalable):
        fl, chains = apply_ops(ops, scalable)
        assert_equivalent(fl, chains)

    run()


def test_free_tenant_returns_whole_lease_set():
    """``free_tenant`` drops a tenant's entire lease set in one call: its
    quanta return to the free list, its chain resets, other tenants are
    untouched, and a re-lease of the freed quanta never aliases."""
    ops = [("write", [True, True, True], 0), ("snapshot", [True, True, True], 0),
           ("write", [True, True, True], 1), ("write", [True, True, True], 2)]
    fl, chains = apply_ops(ops, [True, False, True])
    stats0 = fleet.fleet_stats(fl)
    held = int(np.asarray(fl.lease_count)[1])
    assert held > 0

    fl2 = fleet.free_tenant(fl, 1)
    stats1 = fleet.fleet_stats(fl2)
    # the whole lease set came back at once
    assert stats1["quanta_free"] == stats0["quanta_free"] + held
    owner = np.asarray(fl2.lease_owner)
    assert not np.any(owner == 1)
    assert int(fl2.length[1]) == 1 and int(fl2.alloc_count[1]) == 0
    # the freed tenant reads as an empty disk; the others are untouched
    data = np.asarray(fleet.materialize(fl2))
    np.testing.assert_array_equal(data[1], 0.0)
    ref = np.asarray(fleet.materialize(fl))
    np.testing.assert_allclose(data[0], ref[0], rtol=1e-6)
    np.testing.assert_allclose(data[2], ref[2], rtol=1e-6)

    # a new occupant re-leases the freed quanta without aliasing others
    fl3 = fleet.attach_tenant(fl2, 1, scalable=True)
    ids = jnp.arange(8, dtype=jnp.int32)[None]
    fl3 = fleet.write(fl3, jnp.broadcast_to(ids, (3, 8)),
                      jnp.full((3, 8, PAGE), 7.0),
                      mask=jnp.asarray([False, True, False]))
    data3 = np.asarray(fleet.materialize(fl3))
    np.testing.assert_allclose(data3[1, :8], 7.0, rtol=1e-6)
    np.testing.assert_allclose(data3[0], ref[0], rtol=1e-6)
    np.testing.assert_allclose(data3[2], ref[2], rtol=1e-6)


def test_free_tenant_mask_and_noop():
    fl, _ = apply_ops([("write", [True, True], 0)], [True, True])
    assert fleet.free_tenant(fl, np.zeros(2, bool)) is fl
    fl2 = fleet.free_tenant(fl, np.asarray([True, True]))
    assert fleet.fleet_stats(fl2)["quanta_leased"] == 0
    np.testing.assert_array_equal(np.asarray(fl2.length), [1, 1])


def test_fork_tenant_resolves_like_source_until_divergence():
    """``fork_tenant``/``clone_tenant``: the serving plane's fork — the
    clone resolves bit-identically to the source, then diverges when the
    caller stamps its own entries."""
    from repro.core import format as fmt

    fl, _ = apply_ops(
        [("write", [True, False, False], 0),
         ("snapshot", [True, False, False], 0),
         ("write", [True, False, False], 1)],
        [False, False, False],
    )
    fl = fleet.fork_tenant(fl, 0, 2)
    assert int(fl.length[2]) == int(fl.length[0]) + 1
    ids = jnp.broadcast_to(jnp.arange(N_PAGES, dtype=jnp.int32)[None],
                           (3, N_PAGES))
    res = fleet.resolve_vanilla(fl, ids)
    np.testing.assert_array_equal(np.asarray(res.ptr[2]),
                                  np.asarray(res.ptr[0]))
    np.testing.assert_array_equal(np.asarray(res.found[2]),
                                  np.asarray(res.found[0]))
    # divergence: stamp one entry into the fork's active layer only
    ent = fmt.pack_entry(jnp.uint32(3), jnp.uint32(0), allocated=True,
                         bfi_valid=False)
    fl = fleet.stamp_entries(fl, [2], [int(fl.length[2]) - 1], [0], ent[None])
    res2 = fleet.resolve_vanilla(fl, ids)
    assert int(res2.ptr[2, 0]) == 3
    np.testing.assert_array_equal(np.asarray(res2.ptr[0]),
                                  np.asarray(res.ptr[0]))


def test_free_tenant_empty_id_list_is_noop():
    fl, _ = apply_ops([("write", [True, True], 0)], [True, True])
    out = fleet.free_tenant(fl, [])
    np.testing.assert_array_equal(np.asarray(out.lease_count),
                                  np.asarray(fl.lease_count))


def _narrow_fleet(n_pages=2048):
    """Two tenants (scalable, vanilla), two layers, pages written in both,
    in one layer only, and never."""
    spec = fleet.FleetSpec(
        n_tenants=2, n_pages=n_pages, page_size=PAGE, max_chain=4,
        pool_capacity=256, lease_quantum=32, l2_per_table=32,
    )
    fl = fleet.create(spec, scalable=jnp.asarray([True, False]))
    ids = jnp.asarray([[0, 5, 130, n_pages - 1]] * 2, jnp.int32)
    fl = fleet.write(fl, ids, jnp.ones((2, 4, PAGE)))
    fl = fleet.snapshot(fl)
    return fleet.write(fl, ids[:, :2] + 1000,
                       2.0 * jnp.ones((2, 2, PAGE))), spec


def _kernels_in(lowered):
    """The resolve kernels named in the ops of a compiled program."""
    names = " ".join(re.findall(r'op_name="([^"]*)"',
                                lowered.compile().as_text()))
    return {k for k in ("resolve_batch_fleet_pallas",
                        "resolve_vanilla_fleet_pallas",
                        "resolve_direct_fleet_pallas") if k in names}


@pytest.mark.parametrize("b", [8, 2048])
def test_auto_read_resolves_only_the_batch_when_narrow(b):
    """On both sides of the shape rule (B * 128 < P), method="auto" reads
    what method="vanilla" reads, bit for bit (the owner aside: a scalable
    tenant's is its entry's bfi, not the layer a snapshot copied it to),
    and resolves like the vmapped jnp auto; a narrow batch takes the batch kernel, a full grid
    the two whole-table kernels."""
    import jax

    from repro.core import resolve as resolve_lib

    fl, spec = _narrow_fleet()
    rng = np.random.default_rng(b)
    ids = (np.stack([rng.permutation(spec.n_pages)[:b] for _ in range(2)])
           if b < spec.n_pages else np.tile(np.arange(b), (2, 1)))
    ids[:, :6] = [0, 5, 130, 1000, 1005, spec.n_pages - 1]
    ids = jnp.asarray(ids, jnp.int32)
    narrow = resolve_lib.batch_path_ok(spec.n_pages, b)
    assert narrow == (b == 8)
    assert _kernels_in(fleet.read.lower(fl, ids, method="auto")) == (
        {"resolve_batch_fleet_pallas"} if narrow else
        {"resolve_vanilla_fleet_pallas", "resolve_direct_fleet_pallas"})
    data, res = fleet.read(fl, ids, method="auto")
    vdata, vres = fleet.read(fl, ids, method="vanilla")
    np.testing.assert_array_equal(np.asarray(data), np.asarray(vdata))
    for field in ("ptr", "found", "zero", "cold"):
        np.testing.assert_array_equal(np.asarray(getattr(res, field)),
                                      np.asarray(getattr(vres, field)),
                                      err_msg=field)
    want = jax.vmap(resolve_lib.get_table_resolver("auto"))(
        fl.l2, fl.length, ids)
    for field in res._fields:
        np.testing.assert_array_equal(np.asarray(getattr(res, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=f"auto field {field}")


def test_full_grid_resolves_keep_the_whole_table_path():
    """materialize and the serving plane's table resolve pass B == P: they
    take the whole-table kernels, and materialize reads as the walk."""
    from repro.kvcache import paged

    fl, spec = _narrow_fleet(256)
    grid = jnp.broadcast_to(jnp.arange(spec.n_pages, dtype=jnp.int32)[None],
                            (2, spec.n_pages))
    assert _kernels_in(paged._fleet_tables.lower(fl, grid, "auto")) == {
        "resolve_vanilla_fleet_pallas", "resolve_direct_fleet_pallas"}
    np.testing.assert_array_equal(
        np.asarray(fleet.materialize(fl, method="auto")),
        np.asarray(fleet.materialize(fl, method="vanilla")))
