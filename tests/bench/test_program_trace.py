"""The program's spans and scopes in a trace (``bench/program_trace.py``),
the three readers built on it (``resolve_ms``, ``gather_ms``,
``read_entry_ms``) and ``bench/tools/gaps.py``: on synthetic events, on
the CPU trace (which holds no program spans) and on a small trace of
``fleet.read`` recorded on a TPU v5 lite
(``data/tpu_read_trace.xplane.pb``, made by ``make_tpu_read_trace.py``)."""

import shutil
import types
from pathlib import Path

import pytest

from bench import harness
from bench import program_trace as P
from bench import trace as T

DATA = Path(__file__).parent / "data"
CPU = DATA / "cpu_trace.xplane.pb"
TPU = DATA / "tpu_read_trace.xplane.pb"
READERS = ("resolve_ms", "gather_ms", "read_entry_ms")
READS = 4


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def run_on(tmp_path, xplane, traced=True):
    """A run view whose traced profile is ``xplane``, laid out where
    ``bench/run.py`` writes it."""
    run = types.SimpleNamespace(
        cell=types.SimpleNamespace(root=tmp_path, name="cell"),
        trace=object() if traced else None)
    if xplane is not None:
        dst = P.trace_dir(run) / "plugins" / "profile" / "0"
        dst.mkdir(parents=True)
        shutil.copy(xplane, dst / "host.xplane.pb")
    return run


def span(name, start, dur, thread="t", **args):
    return P.Span(name, start, dur, thread, args)


def test_self_time_leaves_out_program_spans_inside_on_the_same_thread():
    outer = span("snapstore.engine.step", 0, 100)
    spans = [outer, span("snapstore.kv.prepare", 10, 20),
             span("snapstore.kv.commit", 25, 10),        # overlaps prepare
             span("snapstore.engine.sample", 50, 30, thread="other"),
             span("bench.wait", 60, 10)]
    assert P.self_ns(outer, spans) == 100 - 25
    assert P.self_ns(spans[1], spans) == 20


def test_scope_is_a_whole_component_of_the_op_path():
    op = P.Op("slice", 0, 1, "jit(read)/fleet.resolve/jit(resolve_auto)/slice")
    assert P.in_scope(op, "fleet.resolve")
    assert not P.in_scope(op, "fleet")
    assert not P.in_scope(op, "fleet.gather")


def test_scope_busy_is_none_where_no_op_carries_a_scope():
    win = span("bench.window", 0, 100)
    bare = P.ProgramTrace(spans=[win], modules={},
                          ops={"d": [P.Op("copy", 10, 5, "")]})
    assert P.scope_busy_s(bare, "fleet.resolve", win) is None
    scoped = P.ProgramTrace(spans=[win], modules={}, ops={"d": [
        P.Op("a", 10, 5, "x/fleet.resolve/a"),
        P.Op("b", 12, 6, "x/fleet.resolve/b"),     # overlaps a: counted once
        P.Op("c", 95, 10, "x/fleet.resolve/c"),    # clipped at the window
        P.Op("d", 30, 7, "x/fleet.gather/d")]})
    assert P.scope_busy_s(scoped, "fleet.resolve", win) == pytest.approx(
        13e-9)


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_program_spans(tmp_path, name):
    assert reader(name).read(run_on(tmp_path, CPU)) is None


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_an_untraced_run(tmp_path, name):
    assert reader(name).read(run_on(tmp_path, None, traced=False)) is None


def test_cpu_trace_holds_benchmark_spans_and_no_program_spans():
    pt = P.load(CPU)
    assert pt.window() is not None
    assert pt.program_spans() == []
    assert sum(s.name == "bench.dispatch" for s in pt.spans) == 3
    assert all(not o.scope for ops in pt.ops.values() for o in ops)


@pytest.fixture(scope="module")
def tpu():
    pt = P.load(TPU)
    win = pt.window()
    (dev,) = pt.ops
    ops = T.clip(pt.ops[dev], win.start, win.end)
    return pt, win, dev, ops


def test_tpu_trace_has_one_read_span_per_dispatch_with_its_counts(tpu):
    pt, win, _, _ = tpu
    reads = P.starts_inside(pt.program_spans("fleet.read"), win)
    assert len(reads) == READS
    for r in reads:
        assert r.args == dict(pages=2 * 256, resolved=2 * 65536,
                              method="auto")
    dispatches = [s for s in pt.spans if s.name == "bench.dispatch"]
    assert [d.start <= r.start and r.end <= d.end
            for d, r in zip(dispatches, reads)] == [True] * READS


def test_tpu_trace_runs_each_read_program_after_its_span_opened(tpu):
    pt, win, dev, _ = tpu
    reads = P.starts_inside(pt.program_spans("fleet.read"), win)
    runs = [m for m in pt.modules[dev] if m.name == "jit_read"
            and win.start <= m.start < win.end]
    assert len(runs) == len(reads)
    for r, m in zip(reads, runs):
        assert r.start <= m.start


def test_tpu_trace_scopes_cover_the_busy_time(tpu):
    pt, win, _, ops = tpu
    busy = T.busy_ns(ops) / 1e9
    resolve = P.scope_busy_s(pt, "fleet.resolve", win)
    gather = P.scope_busy_s(pt, "fleet.gather", win)
    assert resolve > gather > 0
    assert resolve + gather >= 0.99 * busy
    assert resolve + gather <= busy * (1 + 1e-9)


def test_tpu_trace_readers(tmp_path, tpu):
    pt, win, _, ops = tpu
    run = run_on(tmp_path, TPU)
    got = {name: reader(name).read(run) for name in READERS}
    per_read_ms = 1e3 * T.busy_ns(ops) / 1e9 / READS
    assert got["resolve_ms"] + got["gather_ms"] == pytest.approx(
        per_read_ms, rel=0.01)
    reads = P.starts_inside(pt.program_spans("fleet.read"), win)
    assert got["read_entry_ms"] == pytest.approx(
        sum(r.dur for r in reads) / READS / 1e6)
    assert 0 < got["read_entry_ms"] < 1e3 * win.dur / 1e9 / READS


def test_gaps_are_named_by_the_innermost_span_of_either_kind(tpu):
    gaps_tool = harness.load_module(harness.BENCH / "tools" / "gaps.py")
    pt, win, _, ops = tpu
    gaps = gaps_tool.named_gaps(pt)
    assert sum(ns for _, _, ns in gaps) == win.dur - T.busy_ns(ops)
    names = {name for name, _, _ in gaps}
    assert names <= {"bench.dispatch", "snapstore.fleet.read", "(no span)"}
    assert "snapstore.fleet.read" in names
    index = gaps_tool.Spans([span("bench.window", 0, 1000),
                             span("bench.dispatch", 0, 100),
                             span("snapstore.fleet.read", 10, 20)])
    assert index.name_at(15) == "snapstore.fleet.read"
    assert index.name_at(50) == "bench.dispatch"
    assert index.name_at(150) == "(no span)"
    by_name = gaps_tool.summary(gaps, -1)
    assert sum(n for n, _, _ in by_name.values()) == len(gaps)
    split = gaps_tool.split_gaps(pt)
    assert sum(split.values()) == win.dur - T.busy_ns(ops)
    assert set(split) <= {
        "bench.dispatch, before snapstore.fleet.read",
        "snapstore.fleet.read",
        "bench.dispatch, after snapstore.fleet.read", "(no span)"}
    assert split["snapstore.fleet.read"] > 0


def test_gaps_are_cut_where_spans_open_and_close():
    gaps_tool = harness.load_module(harness.BENCH / "tools" / "gaps.py")
    win = span("bench.window", 0, 1000)
    pt = P.ProgramTrace(
        spans=[win, span("bench.dispatch", 100, 500),
               span("snapstore.fleet.read", 200, 100)],
        modules={}, ops={"d": [P.Op("a", 0, 150, ""), P.Op("b", 350, 400, "")]})
    assert gaps_tool.split_gaps(pt) == {
        "bench.dispatch, before snapstore.fleet.read": 50,
        "snapstore.fleet.read": 100,
        "bench.dispatch, after snapstore.fleet.read": 50,
        "(no span)": 250}
    assert [n for n, _, _ in gaps_tool.named_gaps(pt)] == [
        "snapstore.fleet.read", "(no span)"]
