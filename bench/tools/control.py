#!/usr/bin/env python3
"""Read a cell's comparison on several seeds, for the program and for the
control that has to fail it, in one process on the chip.

    python bench/tools/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,3

Serving cells: after a window at the cell's own load, the sampled
requests are compared with the float32 reference, and the same positions
with a float8 reference put in the program's place (the token it puts
first there, read by the float32 reference). Prints, per seed, the
widest gap of each. Fleet cells: the window's reads are made through the
program's own ``direct`` method, which trusts the active layer on every
disk and so skips the vanilla disk's walk: the guarantee that a read
returns the newest write is broken, and the mismatched pages are counted.
These readings set the limits in the drivers; the benchmark's own runs
never run a control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402


def serve_readings(ctx) -> dict:
    drv = ctx.cell.load_driver()
    state = drv.setup(ctx)
    reqs, rng = drv.requests_for(ctx, state)
    drv.serve_window(ctx, state, reqs)
    sample = drv.choose_sample(reqs, rng)
    gaps, ctl = drv.compare(ctx, state, sample, control=True)
    return dict(program=float(gaps.max()), control=float(ctl.max()),
                served_tokens=int(gaps.size), requests=len(sample))


def fleet_readings(ctx) -> dict:
    ctx.cell.file["traffic"] = dict(ctx.traffic, method="direct")
    out = ctx.cell.load_driver().run(ctx)
    return dict(control=out.checks[0].value)


def readings(ctx) -> dict:
    if ctx.cell.driver == "serve_open_loop":
        return serve_readings(ctx)
    return fleet_readings(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = harness.cell_from_files(args.workload)
        ctx = harness.Context(cell, seed=seed, seconds=args.seconds,
                              trace=False, t0=time.perf_counter())
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              **readings(ctx))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
