"""The trace reduction (``bench/trace.py``): busy-time union, device time
per module and per kernel, and idle gaps attributed to host spans, on
synthetic events and on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``, made by ``make_cpu_trace.py``)."""

from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).parent / "data" / "cpu_trace.xplane.pb"


def ev(name, start, dur):
    return T.Event(name, start, dur)


def test_union_counts_overlapping_ops_once():
    ops = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 15, 1)]
    assert T.union(ops) == [(0, 16), (30, 35)]
    assert T.busy_ns(ops) == 21


def test_gaps_are_the_complement_of_busy_inside_the_window():
    ops = [ev("a", 0, 10), ev("b", 20, 10), ev("c", 95, 10)]
    assert T.gaps(ops, 5, 100) == [(10, 20), (30, 95)]
    assert T.gaps([], 0, 7) == [(0, 7)]


def test_clip_keeps_the_part_inside():
    assert T.clip([ev("a", 0, 10), ev("b", 20, 5)], 5, 22) == [
        ev("a", 5, 5), ev("b", 20, 2)]


def test_open_span_is_the_innermost():
    spans = [ev("bench.window", 0, 100), ev("bench.step", 10, 50),
             ev("bench.prep", 12, 5)]
    assert T.open_span(spans, 14) == "prep"
    assert T.open_span(spans, 30) == "step"
    assert T.open_span(spans, 80) == "(no span)"


def test_clock_shift_puts_no_program_before_its_enqueue():
    device = {"1": 100, "2": 300}
    host = {"1": 150, "2": 310, "3": 999}
    assert T.clock_shift(device, host) == 50
    assert T.clock_shift({"1": 200}, {"1": 150}) == 0


def test_kernel_and_module_names():
    assert T.op_name("%fusion.3 = f32[2]{0} fusion(%p)") == "fusion.3"
    assert T.kernel_name("gather_fleet_pallas.1") == "gather_fleet_pallas"
    assert T.module_name("jit_read(1655942)") == "jit_read"


@pytest.fixture(scope="module")
def cpu():
    tr = T.load(str(DATA))
    return tr, T.reduce(tr)


def test_cpu_trace_busy_is_the_union_of_its_ops(cpu):
    tr, red = cpu
    win = tr.window()
    ops = T.clip(tr.ops["/host:CPU:0"], win.start, win.end)
    assert len(ops) == 9
    assert red.busy_s == pytest.approx(T.busy_ns(ops) / 1e9)
    assert 0 < red.busy_s < red.window_s == pytest.approx(win.dur / 1e9)


def test_cpu_trace_device_time_per_module(cpu):
    tr, red = cpu
    mods = tr.modules["/host:CPU:0"]
    for name in ("jit_step", "jit_other"):
        hand = sum(m.dur for m in mods if m.name == name) / 1e9
        assert red.by_module_s[name] == pytest.approx(hand)
    assert sum(red.by_module_s.values()) == pytest.approx(
        sum(red.by_kernel_s.values()))
    assert red.by_kernel_s["dot_general"] > 0


def test_cpu_trace_idle_gaps_go_to_the_open_span(cpu):
    tr, red = cpu
    top = red.gaps[:3]
    assert [name for name, _ in top] == ["wait"] * 3
    assert all(s > 0.015 for _, s in top)
    hand = sum(t - s for s, t in T.gaps(tr.ops["/host:CPU:0"],
                                        tr.window().start, tr.window().end))
    assert red.idle_s == pytest.approx(hand / 1e9)
    assert red.idle_s + red.busy_s == pytest.approx(red.window_s)
    bd = T.breakdown(red, top=2)
    assert [k for k, _ in bd["idle_gaps"]] == ["wait", "wait"]
    assert len(bd["device_ops"]) == 2
