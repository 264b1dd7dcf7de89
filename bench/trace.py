"""Reduce a profiler trace (``.xplane.pb``) to the numbers the metrics read.

Three things come out of a trace:

- device busy time: the union of the intervals in which an operation ran
  on the device (overlapping ops count once);
- device time per XLA module and per kernel (op name without its ``.N``
  suffix: a Pallas kernel's op is named after its kernel function);
- idle gaps inside the traced window, each attributed to the benchmark
  span (``bench.*``) that was open on the host at the gap's midpoint.

On a TPU the ops are the events of the ``XLA Ops`` line of each
``/device:TPU:N`` plane and the modules those of its ``XLA Modules`` line.
The device clock is not the host's: every device event is shifted by the
least offset that puts no program's start before the host enqueued it
(matched by ``run_id``). On the CPU backend the ops are the host events
that carry an ``hlo_op`` stat, with their module in ``hlo_module``, and
the clocks agree.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Event:
    name: str
    start: int      # ns, on the host clock
    dur: int        # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """What the reduction reads from one trace, per device."""
    ops: dict[str, list[Event]]        # device -> ops (name = op name)
    modules: dict[str, list[Event]]    # device -> module executions
    spans: list[Event]                 # host benchmark spans

    def window(self, name: str = SPAN_PREFIX + "window") -> Event | None:
        found = [s for s in self.spans if s.name == name]
        return found[0] if found else None


def op_name(hlo_text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].strip().lstrip("%")


_SUFFIX = re.compile(r"\.\d+$")


def kernel_name(op: str) -> str:
    """``gather_fleet_pallas.1`` -> ``gather_fleet_pallas``."""
    return _SUFFIX.sub("", op)


def module_name(event_name: str) -> str:
    """``jit_read(1655942...)`` -> ``jit_read``."""
    return event_name.split("(", 1)[0]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or the log directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    spans: list[Event] = []
    enqueue: dict[str, int] = {}       # run_id -> host enqueue start
    dev_runs: dict[str, dict[str, int]] = {}  # device -> run_id -> start
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            for ev in line.events:
                e = Event(ev.name, int(ev.start_ns), int(ev.duration_ns))
                if device and line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).append(
                        dataclasses.replace(e, name=op_name(ev.name)))
                elif device and line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).append(e)
                    run = _stats(ev).get("run_id")
                    if run is not None:
                        dev_runs.setdefault(plane.name, {})[str(run)] = e.start
                elif not device and plane.name.startswith("/host:"):
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(e)
                        continue
                    st = _stats(ev)
                    if ev.name == "DoEnqueueProgram" and "run_id" in st:
                        enqueue.setdefault(str(st["run_id"]), e.start)
                    elif "hlo_op" in st and "hlo_module" in st and e.dur > 0:
                        dev = f"/host:CPU:{st.get('device_ordinal', 0)}"
                        ops.setdefault(dev, []).append(e)
                        modules.setdefault(dev, []).append(
                            Event(str(st["hlo_module"]), e.start, e.dur))
    for dev, runs in dev_runs.items():
        shift = clock_shift(runs, enqueue)
        ops[dev] = [dataclasses.replace(e, start=e.start + shift)
                    for e in ops.get(dev, [])]
        modules[dev] = [dataclasses.replace(e, start=e.start + shift)
                        for e in modules[dev]]
    for evs in (*ops.values(), *modules.values(), spans):
        evs.sort(key=lambda e: e.start)
    return Trace(ops=ops, modules=modules, spans=spans)


def clock_shift(device_starts: dict[str, int], enqueued: dict[str, int]) -> int:
    """The least shift (ns) of the device clock that puts no program's
    start before the host enqueued it; programs matched by ``run_id``."""
    lags = [enqueued[r] - s for r, s in device_starts.items() if r in enqueued]
    return max([0] + lags)


def clip(events: list[Event], lo: int, hi: int) -> list[Event]:
    """The parts of ``events`` that lie inside ``[lo, hi)``."""
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def union(events: list[Event]) -> list[tuple[int, int]]:
    """Merge intervals: the disjoint ``(start, end)`` pairs they cover."""
    merged: list[list[int]] = []
    for e in sorted(events, key=lambda e: e.start):
        if merged and e.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end)
        else:
            merged.append([e.start, e.end])
    return [(s, t) for s, t in merged]


def busy_ns(events: list[Event]) -> int:
    return sum(t - s for s, t in union(events))


def time_by(events: list[Event], key) -> dict[str, int]:
    """Summed duration of ``events`` grouped by ``key(event.name)``."""
    out: dict[str, int] = {}
    for e in events:
        k = key(e.name)
        out[k] = out.get(k, 0) + e.dur
    return out


def gaps(events: list[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals inside ``[lo, hi)``: the complement of the busy
    union there."""
    out, cur = [], lo
    for s, t in union(clip(events, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def open_span(spans: list[Event], t: int, *, skip=("window",)) -> str:
    """Innermost benchmark span open at host time ``t`` (the one that
    started last), without its prefix; ``"(no span)"`` where none is."""
    best = None
    for s in spans:
        if s.start <= t < s.end and s.name[len(SPAN_PREFIX):] not in skip:
            if best is None or s.start >= best.start:
                best = s
    return best.name[len(SPAN_PREFIX):] if best else "(no span)"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # averaged over devices
    idle_s: float                      # window_s - busy_s, per device
    by_module_s: dict[str, float]      # summed over devices
    by_kernel_s: dict[str, float]
    gaps: list[tuple[str, float]]      # the longest: (host span, seconds)
    n_devices: int


def reduce(trace: Trace, *, window: Event | None = None,
           top: int = 10) -> Reduced:
    """The numbers of one traced window. ``window`` defaults to the
    ``bench.window`` span; device events are clipped to it. The ``top``
    longest idle gaps are named by the host span open in them."""
    win = window or trace.window()
    if win is None:
        raise ValueError("the trace holds no bench.window span")
    lo, hi = win.start, win.end
    devices = sorted(trace.ops)
    busy, by_mod, by_ker, all_gaps = 0, {}, {}, []
    for dev in devices:
        ops = clip(trace.ops[dev], lo, hi)
        busy += busy_ns(ops)
        for k, v in time_by(ops, kernel_name).items():
            by_ker[k] = by_ker.get(k, 0) + v
        mods = clip(trace.modules.get(dev, []), lo, hi)
        for k, v in time_by(mods, module_name).items():
            by_mod[k] = by_mod.get(k, 0) + v
        all_gaps += gaps(ops, lo, hi)
    all_gaps.sort(key=lambda g: g[0] - g[1])
    n = max(1, len(devices))
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy / n / 1e9,
        idle_s=(hi - lo) / 1e9 - busy / n / 1e9,
        by_module_s={k: v / 1e9 for k, v in by_mod.items()},
        by_kernel_s={k: v / 1e9 for k, v in by_ker.items()},
        gaps=[(open_span(trace.spans, (s + t) // 2), (t - s) / 1e9)
              for s, t in all_gaps[:top]],
        n_devices=len(devices),
    )


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced result line: the device ops (by
    kernel) that took most time, and the longest idle gaps by host span."""
    ops = sorted(red.by_kernel_s.items(), key=lambda kv: -kv[1])[:top]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[k, v] for k, v in red.gaps[:top]])
