#!/usr/bin/env python3
"""Compile the cells' programs at their real sizes for a described TPU v5e
(no chip needed) and print what the compiler says about memory.

    JAX_PLATFORMS=cpu python bench/tools/rehearse.py [fleet] [serve]

A program the chip's compiler refuses (a block shape, or more memory
than the chip has) fails here. Nothing runs, so nothing here is a time.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness  # noqa: E402

GIB = 2 ** 30


def report(name: str, lowered) -> None:
    """Compile ``lowered`` and print its memory analysis."""
    m = lowered.compile().memory_analysis()
    kernel = "tpu_custom_call" in lowered.as_text()
    print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.3f} GiB, temporaries "
          f"{m.temp_size_in_bytes / GIB:.3f} GiB, aliased "
          f"{m.alias_size_in_bytes / GIB:.3f} GiB; Pallas kernel "
          f"{'present' if kernel else 'absent'}", flush=True)


def shapes(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=sharding),
                        tree)


def fleet(one_chip) -> None:
    from repro.core import fleet as fleet_lib
    cell = harness.find_cell("rocksdb-d500.readrandom")
    drv = cell.load_driver()
    cfg, tr = cell.config, cell.traffic
    spec = drv.fleet_spec(cfg)
    t, p = spec.n_tenants, spec.n_pages
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    args = (i32(t, p), i32(t, p), i32(spec.pool_capacity),
            i32(spec.pool_capacity),
            jax.ShapeDtypeStruct((t,), jnp.bool_, sharding=one_chip))
    lowered = drv.synthesize.lower(spec, *args, seed_words=(1, 2))
    report("fleet synthesize", lowered)
    fl = shapes(jax.eval_shape(lambda *a: drv.synthesize(
        spec, *a, seed_words=(1, 2)), *args), one_chip)
    ids = i32(t, tr["reads_per_dispatch_per_disk"])
    report(f"fleet.read(method={tr['method']!r})",
           fleet_lib.read.lower(fl, ids, method=tr["method"]))


class _AsTPU:
    """``jax`` as the kernels' dispatch sees it on a chip: the program
    picks the compiled Pallas kernels by ``jax.default_backend()``, which
    is the CPU here."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend() -> str:
        return "tpu"


def steer_kernels_to_tpu() -> None:
    from repro.kernels.chain_resolve import ops as cr_ops
    from repro.kernels.cow_gather import ops as cg_ops
    from repro.kernels.paged_attention import ops as pa_ops
    cr_ops._on_tpu = lambda: True
    cg_ops.jax = _AsTPU()
    pa_ops.jax = _AsTPU()


def main(argv) -> int:
    steer_kernels_to_tpu()
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    what = argv or ["fleet", "serve"]
    if "fleet" in what:
        fleet(one_chip)
    if "serve" in what:
        serve(one_chip)
    return 0


def serve(one_chip) -> None:
    from repro.serve import paged_decode as pd
    cell = harness.cell_from_files("qwen2.5-3b.golden-agent")
    drv = cell.load_driver()
    cfg, tr = cell.config, cell.traffic
    ref = cell.reference()
    mc = drv.program_config(cfg)
    k = ref.dims(cfg)
    w = shapes(jax.eval_shape(lambda key: ref._weights(
        key, (k["L"], k["d"], k["H"], k["Hkv"], k["hd"], k["ff"], k["V"])),
        jax.random.PRNGKey(0)), one_chip)
    e = cfg["engine"]
    pool = jax.ShapeDtypeStruct((mc.n_layers, e["n_blocks"], e["block_size"],
                                 mc.n_kv_heads, mc.hd), jnp.bfloat16,
                                sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    tenants, m = 64, e["max_blocks_per_seq"]
    l2 = jax.ShapeDtypeStruct((tenants, 1, m, 2), jnp.uint32, sharding=one_chip)
    b = tr["max_running"]
    report(f"paged_decode_step_fused, batch {b}", pd.paged_decode_step_fused.lower(
        mc, w, pool, pool, l2, i32(tenants), i32(b), i32(b), i32(b), i32(b, 1)))
    s = max(tr["suffix_lens"])
    report(f"paged_suffix_prefill, {s} tokens", pd.paged_suffix_prefill.lower(
        mc, w, pool, pool, i32(s, m), i32(s), i32(s), i32(s), i32(1, s)))
    from repro.models import get_model
    model = get_model(mc)
    n = tr["head_len"] + s
    report(f"prefill, {n} tokens", jax.jit(model.prefill).lower(
        w, dict(tokens=i32(1, n))))
    fn = ref.make_logits_fn(cfg)
    report(f"reference logits, {drv.REF_TOKENS} tokens",
           fn.lower(w, i32(drv.REF_TOKENS), i32(drv.REF_SERVED)))


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
