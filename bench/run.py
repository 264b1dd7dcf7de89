#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (data or weights from the seed, warm-up of every shape the cell
uses) runs first; then the cell's driver measures for ``--seconds``; then
what the timed path produced is compared with the benchmark's own
reference. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), then ``checks``: each compared number beside its limit.
The checks are also the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits nonzero and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench import harness  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    devices = jax.devices()
    chips = int(cell.entry["chips"])
    if devices[0].platform != "tpu":
        log(f"run: needs a TPU; JAX found {devices[0].platform!r}")
        return 2
    if len(devices) < chips:
        log(f"run: {args.workload} needs {chips} chips; JAX found "
            f"{len(devices)}")
        return 2
    kind = devices[0].device_kind
    peaks = harness.peaks_for(kind)
    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    trace_dir = None
    if args.trace:
        trace_dir = harness.ROOT / ".bench_trace" / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = harness.Context(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t0=T0, trace_dir=trace_dir)
    out = cell.load_driver().run(ctx)

    low, back = ctx.compiles_in_window
    log(f"run: set-up {ctx.setup_s():.3f} s; window "
        f"{ctx.window_end - ctx.window_start:.3f} s; compilations inside "
        f"the window: {len(low)} programs lowered, {back} compiled by the "
        f"backend{': ' + ', '.join(sorted(set(low))) if low else ''}")
    for note in out.notes:
        log(note)

    device = dict(platform=devices[0].platform, kind=kind,
                  count=len(devices), memory_peak_bytes=out.memory_peak_bytes)
    result: dict = dict(correct=all(c.ok for c in out.checks),
                        attempted=out.attempted, failed=out.failed)
    if args.trace:
        from bench import trace as trace_lib
        red = trace_lib.reduce(trace_lib.load(str(trace_dir)))
        if red.busy_s <= 0:
            log("run: the trace shows no operation on the device")
            return 3
        view = harness.RunView(cell=cell, outcome=out, trace=red,
                               peaks=peaks, spans=ctx.spans)
        result["metrics"] = harness.per_layer_values(cell, view)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["device"] = device
        result["breakdown"] = trace_lib.breakdown(red)
    else:
        result["metrics"] = {
            m["name"]: dict(value=out.end_to_end[m["name"]], unit=m["unit"])
            for m in cell.end_to_end()}
        result["device"] = device
    result["checks"] = {c.name: dict(value=c.value, limit=c.limit)
                        for c in out.checks}
    for c in out.checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
