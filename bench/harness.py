"""The benchmark's harness: finds a cell's files by name, runs its driver
once, reduces the trace and prints the result line.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

- ``bench/workloads/<cell>.json``: the cell's configuration, driver and
  traffic parameters;
- ``bench/configs/<config>.json``: the configuration's sizes;
- ``bench/drivers/<driver>.py``: a load loop, ``run(ctx) -> Outcome``;
- ``bench/metrics/<metric>.py``: a per-layer reader, ``read(run)`` that
  returns a number or ``None`` when it finds nothing to read.

A new cell, configuration, driver or metric is new files and new entries
in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import sys
import time
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN = "bench."


def load_module(path: Path):
    """Import a file by path (names may hold dots and dashes)."""
    path = Path(path).resolve()
    name = "bench._loaded." + re.sub(r"\W", "_", str(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    file: dict           # bench/workloads/<name>.json
    config: dict         # bench/configs/<config>.json
    spec: dict           # the whole BENCHMARK.json
    root: Path = ROOT    # the checkout holding BENCHMARK.json

    @property
    def bench_dir(self) -> Path:
        return self.root / "bench"

    def reference(self):
        """The configuration's plain reference, beside its file."""
        return load_module(self.bench_dir / "configs"
                           / f"{self.entry['config']}.ref.py")

    def load_driver(self):
        return load_module(self.bench_dir / "drivers" / f"{self.driver}.py")

    @property
    def traffic(self) -> dict:
        return self.file["traffic"]

    @property
    def driver(self) -> str:
        return self.file["driver"]

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def find_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(spec_path)
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in {spec_path.name}; have "
                       f"{sorted(entries)}")
    entry = entries[name]
    root = Path(spec_path).resolve().parent
    configs = {c["name"]: c for c in spec["configs"]}
    cfile = load_json(root / configs[entry["config"]]["file"])
    wfile = load_json(root / "bench" / "workloads" / f"{name}.json")
    if wfile["config"] != entry["config"]:
        raise ValueError(f"{name}: workload file names config "
                         f"{wfile['config']!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    return Cell(name=name, entry=entry, file=wfile, config=cfile, spec=spec,
                root=root)


def cell_from_files(name: str, chips: int = 1) -> Cell:
    """A cell that ``BENCHMARK.json`` does not list yet, from its workload
    and configuration files alone: for the tools and tests of cells that
    a later benchmark change adds."""
    wfile = load_json(BENCH / "workloads" / f"{name}.json")
    cfile = load_json(BENCH / "configs" / f"{wfile['config']}.json")
    entry = dict(name=name, config=wfile["config"], chips=chips)
    return Cell(name=name, entry=entry, file=wfile, config=cfile,
                spec=load_json(ROOT / "BENCHMARK.json"))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown device is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" bench/peaks.json has {sorted(table)}")
    return table[device_kind]


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver returns from one run."""
    end_to_end: dict[str, float]
    attempted: int
    failed: int
    checks: list[Check]
    memory_peak_bytes: int
    layer: dict[str, Any] = dataclasses.field(default_factory=dict)
    notes: list[str] = dataclasses.field(default_factory=list)


class CompileCounter:
    """Counts programs lowered and compiled, through JAX's monitoring
    events: every new jit specialization (an eager op's too) lowers once,
    whether the compile cache then holds it or the backend compiles it."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.lowered: list[str] = []     # names of the programs lowered
        self.backend = 0

    def __call__(self, name, _duration, **kw):
        if name == self.LOWER:
            self.lowered.append(str(kw.get("fun_name", "?")))
        elif name == self.BACKEND:
            self.backend += 1


class Context:
    """What a driver gets: the cell, the run's arguments, spans and the
    measured window."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float,
                 trace: bool, t0: float, trace_dir: Path | None = None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = t0
        self.trace_dir = trace_dir
        self.spans: dict[str, list[float]] = {}
        self.compiles = CompileCounter()
        self.window_start: float | None = None
        self.window_end: float | None = None
        self.compiles_in_window: tuple[list[str], int] | None = None
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self.compiles)

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span: kept in memory, and in the profiler's trace when
        the run is traced."""
        t = time.perf_counter()
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation(SPAN + name):
                yield
        else:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    def setup_s(self) -> float:
        return self.window_start - self.t0

    @contextlib.contextmanager
    def window(self):
        """The measured window: set-up ends where it opens. In a traced
        run the profiler records exactly this window."""
        import jax
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        low0, back0 = len(self.compiles.lowered), self.compiles.backend
        self.window_start = time.perf_counter()
        try:
            if self.trace:
                with jax.profiler.TraceAnnotation(SPAN + "window"):
                    yield self
            else:
                yield self
        finally:
            self.window_end = time.perf_counter()
            self.compiles_in_window = (self.compiles.lowered[low0:],
                                       self.compiles.backend - back0)
            if self.trace:
                jax.profiler.stop_trace()

    def open(self) -> bool:
        """True while the window still takes new work."""
        return time.perf_counter() < self.window_start + self.seconds


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader sees."""
    cell: Cell
    outcome: Outcome
    trace: Any            # bench.trace.Reduced, or None
    peaks: dict
    spans: dict[str, list[float]]

    @property
    def layer(self) -> dict:
        return self.outcome.layer


def per_layer_values(cell: Cell, view: RunView) -> dict[str, dict]:
    out = {}
    for m in cell.per_layer():
        reader = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
        value = reader.read(view)
        if value is not None:
            out[m["name"]] = dict(value=value, unit=m["unit"])
    return out


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache`` in
    the checkout: a fixed path, so a later run finds what an earlier one
    compiled."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
