#!/usr/bin/env python3
"""Sweep the Poisson rate of a serving cell on the chip: one set-up, then
one window per rate, printing the tails and whether the queue grew.

    python bench/tools/knee.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,1.5,2,2.5

The knee is the highest rate whose queue of waiting requests does not
grow over the window. A cell offers load at a fixed rate below it, which
is written into its workload file by hand.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("knee: needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = harness.cell_from_files(args.workload)
    drv = cell.load_driver()
    ctx = harness.Context(cell, seed=args.seed, seconds=args.seconds,
                          trace=False, t0=T0)
    state = drv.setup(ctx)
    base = dict(cell.traffic)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.file["traffic"] = dict(base, rate_per_s=rate)
        rng = np.random.default_rng([args.seed, 100 + i])
        n = int(np.ceil(rate * args.seconds * 1.25)) + 1
        reqs = drv.make_requests(cell.traffic, cell.config["vocab_size"], rng,
                                 state.goldens, n)
        book = drv.serve_window(ctx, state, reqs)
        for sid in list(state.engine.active):
            state.engine.finish_request(sid)
        t0, end = ctx.window_start, ctx.window_start + args.seconds
        due = [r for r in reqs if r.arrival <= args.seconds]
        ttft = [(r.first if r.first is not None else end) - (t0 + r.arrival)
                for r in due]
        itl = [b - a for r in reqs for a, b in zip(r.times, r.times[1:])]
        d = np.array([q for _, q in book["depth"]], float)
        t = np.array([x for x, _ in book["depth"]], float)
        thirds = [d[(t >= k * args.seconds / 3) & (t < (k + 1) * args.seconds / 3)]
                  for k in range(3)]
        means = [float(x.mean()) if x.size else 0.0 for x in thirds]
        steps = book["steps"]
        print(json.dumps(dict(
            rate_per_s=rate, due=len(due),
            admitted=sum(r.first is not None for r in due),
            finished=sum(r.done for r in reqs),
            queue_mean_by_third=means, queued_at_close=int(d[-1]) if d.size else 0,
            grows=means[2] > means[1] + 1.0,
            ttft_p50_ms=1e3 * float(np.quantile(ttft, 0.5)),
            ttft_p90_ms=1e3 * float(np.quantile(ttft, 0.9)),
            itl_p50_ms=1e3 * float(np.quantile(itl, 0.5)),
            itl_p99_ms=1e3 * float(np.quantile(itl, 0.99)),
            decode_steps=len(steps),
            mean_batch=float(np.mean([len(s) for s in steps])) if steps else 0,
            compiles_in_window=len(ctx.compiles_in_window[0]),
            compiled=sorted(set(ctx.compiles_in_window[0])))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
