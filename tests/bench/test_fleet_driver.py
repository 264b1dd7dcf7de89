"""The fleet cells' driver at a tiny size on the CPU (Pallas kernels in
interpret mode): the on-device synthesis against a fleet replayed through
``fleet.write``/``fleet.snapshot``, a whole run, the control, and the
faults that must turn ``correct`` false."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.core import fleet as fleet_lib

TINY = dict(tenants=2, l2_entries_per_layer=256, l2_per_table=64, slice_len=16,
            chain_depth=6, live_clusters_per_disk=64, payload_words=128)


def tiny_cell(name="rocksdb-d500.readrandom", **traffic):
    cell = harness.find_cell(name)
    cell.config.update(TINY)
    cell.file["traffic"] = dict(cell.traffic, **traffic)
    return cell


def run_once(cell, seed=2**31 + 5, seconds=0.6):
    ctx = harness.Context(cell, seed=seed, seconds=seconds, trace=False,
                          t0=time.perf_counter())
    return ctx, cell.load_driver().run(ctx)


def replayed_fleet(cfg, record, ref):
    """The same data grown layer by layer through the public write and
    snapshot path."""
    n_live = cfg["live_clusters_per_disk"]
    spec = fleet_lib.FleetSpec(
        n_tenants=cfg["tenants"], n_pages=cfg["l2_entries_per_layer"],
        page_size=cfg["payload_words"], max_chain=cfg["chain_depth"],
        pool_capacity=cfg["tenants"] * n_live, lease_quantum=64,
        l2_per_table=cfg["l2_per_table"], slice_len=cfg["slice_len"])
    fl = fleet_lib.create(spec, scalable=jnp.asarray(record.scalable))
    tenants = np.arange(cfg["tenants"])[:, None]
    for layer in range(cfg["chain_depth"]):
        ids = record.clusters[:, record.owners == layer]
        bits = ref.payload_bits(record.seed, np.broadcast_to(tenants, ids.shape),
                                ids, cfg["payload_words"])
        fl = fleet_lib.write(fl, jnp.asarray(ids), jnp.asarray(bits.view(np.float32)))
        if layer < cfg["chain_depth"] - 1:
            fl = fleet_lib.snapshot(fl)
    return fl


@pytest.mark.parametrize("order", ["seeded_permutation", "newest_layer_first"])
def test_synthesized_fleet_reads_bit_for_bit_like_a_replayed_one(order):
    cell = tiny_cell()
    cfg = cell.config
    ref = cell.reference()
    drv = cell.load_driver()
    record = ref.Record(cfg, 2**32 + 9, order)
    synth = drv.build_fleet(cfg, record, ref)
    grown = replayed_fleet(cfg, record, ref)
    assert not np.asarray(grown.overflow).any()
    ids = jnp.broadcast_to(jnp.arange(cfg["l2_entries_per_layer"]),
                           (cfg["tenants"], cfg["l2_entries_per_layer"]))
    for method in ("auto", "vanilla", "direct"):
        (d1, r1), (d2, r2) = (fleet_lib.read(f, ids, method=method)
                              for f in (synth, grown))
        assert (np.asarray(d1).view(np.uint32) == np.asarray(d2).view(np.uint32)).all()
        for field in ("owner", "found", "zero", "lookups", "cold"):
            assert (np.asarray(getattr(r1, field)) == np.asarray(getattr(r2, field))).all(), field
    assert (np.asarray(synth.l1) == np.asarray(grown.l1)).all()
    assert (np.asarray(synth.length) == np.asarray(grown.length)).all()
    assert (np.asarray(synth.l2[..., 1]) == np.asarray(grown.l2[..., 1])).all()
    # the record's owners are what the walk finds
    owner = np.asarray(fleet_lib.read(grown, ids)[1].owner)
    assert (owner == record.owner_map()).all()


def test_every_seed_gets_the_same_layout_of_owners():
    cfg = dict(harness.load_json(harness.BENCH / "configs" / "rocksdb-d500.json"))
    ref = tiny_cell().reference()
    a = ref.rank_owners(cfg, "seeded_permutation")
    b = ref.rank_owners(cfg, "newest_layer_first")
    counts = np.bincount(a, minlength=cfg["chain_depth"])
    assert counts.max() - counts.min() <= 1
    assert sorted(a) == sorted(b) and (np.diff(b) <= 0).all()


def test_a_run_is_correct_and_reports_its_metrics():
    ctx, out = run_once(tiny_cell())
    assert [c.name for c in out.checks] == ["mismatched_pages"]
    assert all(c.ok for c in out.checks)
    assert out.attempted > 0 and out.failed == 0
    assert set(out.end_to_end) == {"pages_per_s", "io_p99_ms", "setup_s"}
    assert out.layer["needed_bytes"] > 0
    assert ctx.compiles_in_window[0] == []


def test_control_direct_reads_are_not_correct():
    """The program's direct method trusts the active layer on the vanilla
    disk too: its pages read as unwritten."""
    _, out = run_once(tiny_cell(method="direct"))
    assert out.checks[0].value > 0 and not out.checks[0].ok


def _faulty_read(fault):
    def read(fl, ids, *, method):
        data, res = fleet_lib.read(fl, ids, method=method)
        b = ids.shape[1]
        if fault == "altered":
            bits = jax.lax.bitcast_convert_type(data, jnp.uint32)
            data = jax.lax.bitcast_convert_type(bits.at[0, 0, 0].add(1),
                                                jnp.float32)
        elif fault == "half_batch":
            data = data.at[:, b // 2:].set(0)
            res = res._replace(found=res.found.at[:, b // 2:].set(False))
        return data, res
    return read


@pytest.mark.parametrize("fault", ["altered", "half_batch"])
def test_a_broken_read_path_is_not_correct(monkeypatch, fault):
    cell = tiny_cell()
    drv = cell.load_driver()
    monkeypatch.setattr(drv, "fleet_lib", types.SimpleNamespace(
        read=_faulty_read(fault), FleetSpec=fleet_lib.FleetSpec,
        ChainFleet=fleet_lib.ChainFleet))
    _, out = run_once(cell)
    assert not all(c.ok for c in out.checks)
