"""Record the small TPU trace of a depth-class read that
``test_classed_driver.py`` reads.

    python -c "import runpy; \
        runpy.run_path('tests/bench/make_tpu_classed_read_trace.py', \
        run_name='__main__')" [OUT]

Run it from the repository root on a host with one TPU. A small fleet of
the ``chains-1to1000`` layout is built by
``bench/drivers/fleet_classed_closed_loop.py``: six disks, one scalable
and one vanilla at each of the depths 1, 100 and 500 (three classes),
4 KiB payloads and 65,536 clusters per layer. Then, inside a
``bench.window`` span, four ``bench.dispatch`` spans each run one
``depth_classes.read(method="auto")`` of 256 pages per disk (one
``snapstore.fleet.read`` span, the ``fleet.class<C>`` scopes around each
class's ``fleet.resolve``/``fleet.gather``) and wait for it. The trace is
written to ``OUT``, by default
``tests/bench/data/tpu_classed_read_trace.xplane.pb``.
"""

from __future__ import annotations

import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT, ROOT / "src"):
    sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench import trace as trace_lib  # noqa: E402
from repro.core import depth_classes  # noqa: E402

SMALL = dict(depths=[1, 1, 100, 100, 500, 500], tenants=6,
             tenant_formats=["scalable", "vanilla"] * 3,
             l2_entries_per_layer=65536, live_clusters_per_disk=16384)
READS = 4
BATCH = 256


def main(out: Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("make_tpu_classed_read_trace: needs a TPU")
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}/"))
    cell = harness.find_cell("chains-1to1000.readrandom")
    cell.config.update(SMALL)
    ref = cell.reference()
    record = ref.Record(cell.config, 7, cell.traffic["key_order"])
    fl = cell.load_driver().build_fleet(cell.config, record, ref)
    rng = np.random.default_rng(7)
    batches = [np.take_along_axis(
        record.clusters, rng.integers(0, SMALL["live_clusters_per_disk"],
                                      (SMALL["tenants"], BATCH)), axis=1)
        for _ in range(READS)]
    jax.block_until_ready(depth_classes.read(fl, jnp.asarray(batches[0])))
    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for ids in batches:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                jax.block_until_ready(depth_classes.read(fl,
                                                         jnp.asarray(ids)))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_lib.find_xplane(str(tmp)), out)
    shutil.rmtree(tmp)
    print(f"make_tpu_classed_read_trace: wrote {out} "
          f"({out.stat().st_size} bytes)")


if __name__ == "__main__":
    main(Path(sys.argv[1]) if len(sys.argv) > 1
         else HERE / "data" / "tpu_classed_read_trace.xplane.pb")
