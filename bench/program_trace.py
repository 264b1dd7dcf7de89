"""What the program itself records in a traced window.

``bench/trace.py`` reduces a trace to what the benchmark needs from the
outside: its own ``bench.*`` spans and device time by op name. This
module reads what the program adds (``src/repro/obs.py``):

- host spans ``snapstore.*`` with their arguments, beside the
  benchmark's ``bench.*`` spans, each with the host thread it ran on;
- each device op with its named-scope path: the ``op_name`` metadata
  XLA keeps from ``jax.named_scope`` (``jit(read)/fleet.resolve/...``),
  which the TPU profile carries as the ``tf_op`` stat of the op's event
  metadata on the ``/device:TPU:N`` plane. Scopes survive the renaming
  and re-fusing of ops that op names do not. ``ProfileData`` shows an
  event's own stats but not its metadata's, so ``op_scopes`` reads that
  stat from the ``.xplane.pb`` itself. Ops that XLA adds after
  lowering (layout copies) carry no scope.

Device events are put on the host clock as ``bench/trace.py`` does
(``clock_shift``). A trace is read once per process; the per-layer
readers of one run share it. On the CPU backend the ops carry no scope,
and readers that need one find nothing.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from pathlib import Path

from bench.trace import (Event, busy_ns, clip, clock_shift, find_xplane,
                         module_name, op_name)

PROGRAM = "snapstore."
BENCH = "bench."
WINDOW = BENCH + "window"
SCOPE_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Span:
    """A host span: ``snapstore.*`` (the program's) or ``bench.*``."""
    name: str
    start: int          # ns, host clock
    dur: int
    thread: str         # host plane and line it ran on
    args: dict

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Op:
    """A device op with its named-scope path ("" where it has none)."""
    name: str
    start: int          # ns, shifted onto the host clock
    dur: int
    scope: str

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class ProgramTrace:
    spans: list[Span]                  # both kinds, by start
    ops: dict[str, list[Op]]           # device -> ops, by start
    modules: dict[str, list[Event]]    # device -> program executions

    def window(self) -> Span | None:
        found = [s for s in self.spans if s.name == WINDOW]
        return found[0] if found else None

    def program_spans(self, name: str | None = None) -> list[Span]:
        """The program's spans (``name`` without its prefix, or all)."""
        return [s for s in self.spans if s.name.startswith(PROGRAM)
                and (name is None or s.name == PROGRAM + name)]


def in_scope(op: Op, scope: str) -> bool:
    """True if ``scope`` is one of the components of the op's path."""
    return scope in op.scope.split("/")


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _varint(buf, i: int) -> tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(number, value)`` of each field of one protobuf message: an int
    for a varint, bytes (a view) for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5
_STAT_MD_ID, _STAT_MD_NAME = 1, 2
_STAT_KEY, _STAT_STR, _STAT_REF = 1, 5, 7
_MAP_VALUE = 2


def op_scopes(path: str) -> dict[str, str]:
    """Device op name (the event name ``ProfileData`` gives) -> the
    ``tf_op`` stat of its event metadata, over every TPU plane."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, str] = {}
    for num, plane in _fields(space):
        if num != _SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == _PLANE_NAME:
                name = bytes(v).decode()
            elif pnum == _PLANE_EVENT_MD:
                events += [m for k, m in _fields(v) if k == _MAP_VALUE]
            elif pnum == _PLANE_STAT_MD:
                for k, m in _fields(v):
                    if k == _MAP_VALUE:
                        md = dict(_fields(m))
                        stat_names[md.get(_STAT_MD_ID, 0)] = bytes(
                            md.get(_STAT_MD_NAME, b"")).decode()
        if not name.startswith("/device:TPU"):
            continue
        for md in events:
            ev_name, scope = "", None
            for k, v in _fields(md):
                if k == _EVENT_MD_NAME:
                    ev_name = bytes(v).decode()
                elif k == _EVENT_MD_STATS:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(_STAT_KEY)) == SCOPE_STAT:
                        scope = (bytes(st[_STAT_STR]).decode()
                                 if _STAT_STR in st
                                 else stat_names.get(st.get(_STAT_REF), ""))
            if scope is not None:
                out[ev_name] = scope
    return out


def _read(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    scopes = op_scopes(path)
    spans: list[Span] = []
    ops: dict[str, list[Op]] = {}
    modules: dict[str, list[Event]] = {}
    enqueue: dict[str, int] = {}
    dev_runs: dict[str, dict[str, int]] = {}
    for plane in pd.planes:
        device = plane.name.startswith("/device:TPU")
        host = plane.name.startswith("/host:")
        for line in plane.lines:
            for ev in line.events:
                start, dur = int(ev.start_ns), int(ev.duration_ns)
                if device and line.name == "XLA Ops":
                    ops.setdefault(plane.name, []).append(
                        Op(op_name(ev.name), start, dur,
                           scopes.get(ev.name, "")))
                elif device and line.name == "XLA Modules":
                    modules.setdefault(plane.name, []).append(
                        Event(module_name(ev.name), start, dur))
                    run = _stats(ev).get("run_id")
                    if run is not None:
                        dev_runs.setdefault(plane.name, {})[str(run)] = start
                elif host and ev.name.startswith((PROGRAM, BENCH)):
                    spans.append(Span(ev.name, start, dur,
                                      f"{plane.name}/{line.name}",
                                      _stats(ev)))
                elif host and ev.name == "DoEnqueueProgram":
                    run = _stats(ev).get("run_id")
                    if run is not None:
                        enqueue.setdefault(str(run), start)
    for dev, runs in dev_runs.items():
        shift = clock_shift(runs, enqueue)
        ops[dev] = [dataclasses.replace(o, start=o.start + shift)
                    for o in ops.get(dev, [])]
        modules[dev] = [dataclasses.replace(m, start=m.start + shift)
                        for m in modules[dev]]
    for evs in (*ops.values(), *modules.values(), spans):
        evs.sort(key=lambda e: e.start)
    return ProgramTrace(spans=spans, ops=ops, modules=modules)


@functools.lru_cache(maxsize=4)
def _load_file(path: str, _mtime_ns: int) -> ProgramTrace:
    return _read(path)


def load(path) -> ProgramTrace:
    """Read an ``.xplane.pb`` (or the log directory holding one), once
    per process for each file."""
    path = str(path)
    if os.path.isdir(path):
        path = find_xplane(path)
    return _load_file(path, os.stat(path).st_mtime_ns)


def trace_dir(run) -> Path:
    """Where ``bench/run.py`` writes a traced run's profile."""
    return Path(run.cell.root) / ".bench_trace" / run.cell.name


def for_run(run) -> ProgramTrace | None:
    """The run's trace, or ``None`` where the run was not traced."""
    if run.trace is None:
        return None
    try:
        return load(trace_dir(run))
    except FileNotFoundError:
        return None


def starts_inside(spans: list[Span], win: Span) -> list[Span]:
    return [s for s in spans if win.start <= s.start < win.end]


def scope_busy_s(pt: ProgramTrace, scope: str, win: Span) -> float | None:
    """Device time of the ops under ``scope`` inside the window (the union
    of their intervals, averaged over devices); ``None`` where no op in
    the window carries a scope at all."""
    total, scoped = 0, False
    for dev_ops in pt.ops.values():
        scoped = scoped or bool(clip([o for o in dev_ops if o.scope],
                                     win.start, win.end))
        total += busy_ns(clip([o for o in dev_ops if in_scope(o, scope)],
                              win.start, win.end))
    if not scoped:
        return None
    return total / len(pt.ops) / 1e9


def self_ns(span: Span, spans: list[Span]) -> int:
    """A span's own time: its length less that of the program spans
    directly or indirectly inside it on the same thread."""
    inner = [Event(s.name, s.start, s.dur) for s in spans
             if s is not span and s.thread == span.thread
             and s.name.startswith(PROGRAM)
             and span.start <= s.start and s.end <= span.end]
    return span.dur - busy_ns(inner)


def scope_ms_per_read(run, scope: str) -> float | None:
    """Device ms under ``scope`` per ``snapstore.fleet.read`` span that
    opened inside the window."""
    pt = for_run(run)
    win = pt.window() if pt else None
    if win is None:
        return None
    reads = starts_inside(pt.program_spans("fleet.read"), win)
    busy = scope_busy_s(pt, scope, win)
    if not reads or busy is None:
        return None
    return 1e3 * busy / len(reads)
