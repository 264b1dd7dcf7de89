"""Record the small TPU trace that ``test_program_trace.py`` reads.

    python -c "import runpy; \
        runpy.run_path('tests/bench/make_tpu_read_trace.py', run_name='__main__')" [OUT]

Run it from the repository root on a host with one TPU. The op metadata
it records names source files relative to the root (JAX's
``jax_hlo_source_file_canonicalization_regex``), not by the machine's
absolute paths. A small fleet of the ``rocksdb-d500`` layout is built by
``bench/drivers/fleet_closed_loop.py``: two
disks, one scalable and one vanilla, at the cell's depth (500) and
payload (4 KiB), with 65,536 clusters per layer (1/11 of the cell's), so
that the index work dominates a read as it does in the cell. Then,
inside a ``bench.window`` span, four ``bench.dispatch`` spans each run
one ``fleet.read(method="auto")`` of 256 pages per disk (the Pallas
kernel path, under the program's ``snapstore.fleet.read`` span and its
``fleet.resolve``/``fleet.gather`` scopes) and wait for it.
The trace is written to ``OUT``, by default
``tests/bench/data/tpu_read_trace.xplane.pb``.
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
from repro.core import fleet as fleet_lib  # noqa: E402

SMALL = dict(l2_entries_per_layer=65536, live_clusters_per_disk=16384)
READS = 4
BATCH = 256


def main(out: Path) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("make_tpu_read_trace: needs a TPU")
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{ROOT}/"))
    cell = harness.find_cell("rocksdb-d500.readrandom")
    cell.config.update(SMALL)
    ref = cell.reference()
    record = ref.Record(cell.config, 7, cell.traffic["key_order"])
    fl = jax.block_until_ready(
        cell.load_driver().build_fleet(cell.config, record, ref))
    rng = np.random.default_rng(7)
    batches = [np.take_along_axis(
        record.clusters, rng.integers(0, SMALL["live_clusters_per_disk"],
                                      (cell.config["tenants"], BATCH)), axis=1)
        for _ in range(READS)]
    jax.block_until_ready(fleet_lib.read(fl, jnp.asarray(batches[0])))
    tmp = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for ids in batches:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                jax.block_until_ready(fleet_lib.read(fl, jnp.asarray(ids)))
            time.sleep(0.002)
    jax.profiler.stop_trace()
    out.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace_lib.find_xplane(str(tmp)), out)
    shutil.rmtree(tmp)
    print(f"make_tpu_read_trace: wrote {out} ({out.stat().st_size} bytes)")


if __name__ == "__main__":
    main(Path(sys.argv[1]) if len(sys.argv) > 1
         else HERE / "data" / "tpu_read_trace.xplane.pb")
