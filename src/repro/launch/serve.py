"""Serving launcher: continuous batching over the COW paged KV cache.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
        --requests 4 --forks 2 --tokens 8
"""

from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.launch import use_compile_cache
from repro.serve.engine import Engine, init_serving_params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--scale", choices=("smoke", "full"), default="smoke")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--forks", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--vanilla", action="store_true",
                    help="vanilla fork chains (walks) instead of direct")
    args = ap.parse_args()

    use_compile_cache(Path(__file__).resolve().parents[3] / ".jax_cache")
    cfg = get_config(args.arch) if args.scale == "full" else smoke_config(args.arch)
    params = init_serving_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, scalable=not args.vanilla, n_blocks=1024,
                 block_size=8, max_blocks_per_seq=64)

    rng = np.random.default_rng(0)
    roots = []
    for _ in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, args.prompt_len)
        roots.append(eng.add_request(prompt))
    for r in roots:
        for _ in range(args.forks):
            eng.fork_request(r)

    t0 = time.perf_counter()
    for _ in range(args.tokens):
        eng.step()
    dt = time.perf_counter() - t0
    st = eng.memory_stats()
    n_seqs = st["n_seqs"]
    print(f"{n_seqs} sequences ({args.requests} roots x {args.forks} forks), "
          f"{args.tokens} steps in {dt:.2f}s "
          f"({n_seqs*args.tokens/dt:.1f} tok/s)")
    print(f"blocks in use: {st['blocks_in_use']} "
          f"(independent copies would need ~"
          f"{n_seqs * (args.prompt_len // 8 + 2)}); "
          f"table lookups: {st['lookups']} "
          f"({'vanilla walk' if args.vanilla else 'direct'})")


if __name__ == "__main__":
    main()
