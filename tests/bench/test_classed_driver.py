"""The ``chains-1to1000`` cell's driver at a tiny size on the CPU (Pallas
kernels in interpret mode): the on-device synthesis of each depth class
against classes grown through ``fleet.write``/``fleet.snapshot``, the
reference, a whole run, the control, the faults that must turn
``correct`` false, and the ``deepest_class_ms`` reader."""

import time
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import program_trace as P
from repro.core import depth_classes
from repro.core import fleet as fleet_lib

CELL = "chains-1to1000.readrandom"
DEPTHS = [1, 1, 3, 3, 8, 8, 17, 17]
TINY = dict(depths=DEPTHS, tenants=len(DEPTHS),
            tenant_formats=["scalable", "vanilla"] * 4,
            l2_entries_per_layer=256, l2_per_table=64, slice_len=16,
            live_clusters_per_disk=64, payload_words=128)
DATA = Path(__file__).parent / "data"


def tiny_cell(**traffic):
    cell = harness.find_cell(CELL)
    cell.config.update(TINY)
    cell.file["traffic"] = dict(cell.traffic, **traffic)
    return cell


def run_once(cell, seed=2**31 + 11, seconds=0.6):
    ctx = harness.Context(cell, seed=seed, seconds=seconds, trace=False,
                          t0=time.perf_counter())
    return ctx, cell.load_driver().run(ctx)


def grown_classes(cfg, record, ref):
    """The same data grown layer by layer through the dense fleet's public
    write and snapshot path, one fleet per depth, then assembled."""
    drv = tiny_cell().load_driver()
    depths = np.asarray(cfg["depths"])
    classes, disks = [], []
    for depth in sorted(set(cfg["depths"])):
        ids = np.flatnonzero(depths == depth)
        fl = fleet_lib.create(drv.class_spec(cfg, ids.size, int(depth)),
                              scalable=jnp.asarray(record.scalable[ids]))
        for layer in range(depth):
            pages = np.stack([record.clusters[d][record.owners[d] == layer]
                              for d in ids])
            bits = ref.payload_bits(record.seed, ids[:, None], pages,
                                    cfg["payload_words"])
            fl = fleet_lib.write(fl, jnp.asarray(pages),
                                 jnp.asarray(bits.view(np.float32)))
            if layer < depth - 1:
                fl = fleet_lib.snapshot(fl)
        assert not np.asarray(fl.overflow).any()
        classes.append(fl)
        disks.append(ids.tolist())
    return depth_classes.assemble(classes, disks)


def test_synthesized_classes_read_bit_for_bit_like_grown_ones():
    cell = tiny_cell()
    cfg, ref = cell.config, cell.reference()
    record = ref.Record(cfg, 2**32 + 9, "seeded_permutation")
    synth = cell.load_driver().build_fleet(cfg, record, ref)
    grown = grown_classes(cfg, record, ref)
    assert synth.capacities == grown.capacities == (1, 3, 8, 17)
    assert synth.index_bytes() == sum(DEPTHS) * 256 * 8
    ids = jnp.broadcast_to(jnp.arange(256), (len(DEPTHS), 256))
    for method in ("auto", "vanilla", "direct"):
        (d1, r1), (d2, r2) = (depth_classes.read(f, ids, method=method)
                              for f in (synth, grown))
        assert (np.asarray(d1).view(np.uint32)
                == np.asarray(d2).view(np.uint32)).all()
        for field in ("owner", "found", "zero", "lookups", "cold"):
            assert (np.asarray(getattr(r1, field))
                    == np.asarray(getattr(r2, field))).all(), field
    # what the reference records is what the read finds
    owner = np.asarray(depth_classes.read(grown, ids)[1].owner)
    assert (owner == record.owner_map(range(len(DEPTHS)))).all()
    for a, b in zip(synth.classes, grown.classes):
        assert (np.asarray(a.l1) == np.asarray(b.l1)).all()
        assert (np.asarray(a.length) == np.asarray(b.length)).all()


def test_each_layer_of_a_disk_owns_as_many_clusters():
    cfg = dict(harness.load_json(harness.BENCH / "configs"
                                 / "chains-1to1000.json"))
    owners = tiny_cell().reference().rank_owners(cfg, "seeded_permutation")
    assert owners.shape == (10, cfg["live_clusters_per_disk"])
    for depth, row in zip(cfg["depths"], owners):
        counts = np.bincount(row, minlength=depth)
        assert counts.size == depth and counts.max() - counts.min() <= 1


def test_a_run_is_correct_and_reports_its_metrics():
    ctx, out = run_once(tiny_cell())
    assert [c.name for c in out.checks] == ["mismatched_pages"]
    assert all(c.ok for c in out.checks)
    assert out.attempted > 0 and out.failed == 0
    assert set(out.end_to_end) == {"pages_per_s", "io_p99_ms", "setup_s"}
    assert out.layer["needed_bytes"] > 0
    assert out.layer["index_bytes"] == sum(DEPTHS) * 256 * 8
    assert ctx.compiles_in_window[0] == []


def test_control_direct_reads_are_not_correct():
    """The program's direct method trusts the active layer on the vanilla
    disks too: their pages read as unwritten."""
    _, out = run_once(tiny_cell(method="direct"))
    assert out.checks[0].value > 0 and not out.checks[0].ok


def _faulty_read(fault):
    def read(fl, ids, *, method):
        data, res = depth_classes.read(fl, ids, method=method)
        if fault == "altered":
            bits = jax.lax.bitcast_convert_type(data, jnp.uint32)
            data = jax.lax.bitcast_convert_type(bits.at[0, 0, 0].add(1),
                                                jnp.float32)
        elif fault == "wrong_class":
            # the deepest vanilla disk's pages answered from a depth-1 disk
            swap = lambda a: a.at[7].set(a[1])
            data = swap(data)
            res = res._replace(found=swap(res.found), owner=swap(res.owner))
        return data, res
    return read


@pytest.mark.parametrize("fault", ["altered", "wrong_class"])
def test_a_broken_read_path_is_not_correct(monkeypatch, fault):
    cell = tiny_cell()
    drv = cell.load_driver()
    monkeypatch.setattr(drv, "depth_classes", types.SimpleNamespace(
        read=_faulty_read(fault), assemble=depth_classes.assemble,
        ClassedFleet=depth_classes.ClassedFleet))
    _, out = run_once(cell)
    assert not all(c.ok for c in out.checks)


# -- deepest_class_ms ---------------------------------------------------------


def reader():
    return harness.load_module(harness.BENCH / "metrics"
                               / "deepest_class_ms.py")


def test_deepest_class_ms_is_the_deepest_scope_per_read(monkeypatch):
    span = lambda name, start, dur: P.Span(name, start, dur, "t", {})
    win = span("bench.window", 0, 10_000_000)
    pt = P.ProgramTrace(
        spans=[win, span("snapstore.fleet.read", 100, 10),
               span("snapstore.fleet.read", 5_000_000, 10)],
        modules={}, ops={"d": [
            P.Op("a", 1_000, 2_000_000, "jit(read_classes)/fleet.class1/"
                 "fleet.resolve/a"),
            P.Op("b", 2_500_000, 3_000_000, "jit(read_classes)/fleet.class17/"
                 "fleet.gather/b"),
            P.Op("c", 6_000_000, 1_000_000, "jit(read_classes)/fleet.class17/"
                 "fleet.resolve/c")]})
    monkeypatch.setattr(P, "for_run", lambda run: pt)
    assert reader().read(object()) == pytest.approx(2.0)


def run_on(tmp_path, xplane):
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(root=tmp_path, name="cell"),
        trace=object())
    dst = P.trace_dir(run) / "plugins" / "profile" / "0"
    dst.mkdir(parents=True)
    (dst / "host.xplane.pb").write_bytes(xplane.read_bytes())
    return run


def test_deepest_class_ms_finds_nothing_in_a_dense_read(tmp_path):
    """A dense fleet's read (the recorded TPU trace of ``fleet.read``)
    has no class scope: the metric is left out, as on the parent."""
    assert reader().read(run_on(tmp_path, DATA / "tpu_read_trace.xplane.pb")
                         ) is None


def test_deepest_class_ms_reads_the_recorded_class_read(tmp_path):
    """On a TPU trace of ``depth_classes.read`` (made by
    ``make_tpu_classed_read_trace.py``): four reads, the deepest class's
    scope below the whole busy time per read."""
    run = run_on(tmp_path, DATA / "tpu_classed_read_trace.xplane.pb")
    deepest = reader().read(run)
    pt = P.for_run(run)
    win = pt.window()
    reads = P.starts_inside(pt.program_spans("fleet.read"), win)
    assert len(reads) == 4 and reads[0].args["classes"] == 3
    shallow = P.scope_ms_per_read(run, "fleet.class1")
    resolve = P.scope_ms_per_read(run, "fleet.resolve")
    gather = P.scope_ms_per_read(run, "fleet.gather")
    assert 0 < shallow < deepest < resolve + gather
