"""Record the small CPU trace that ``test_trace.py`` reads.

    JAX_PLATFORMS=cpu python -c "import runpy; \
        runpy.run_path('tests/bench/make_cpu_trace.py', run_name='__main__')"

(run from the repository root this way, the trace records the script's
path relative to it, not the machine's absolute path).

Inside a ``bench.window`` span: three ``bench.dispatch`` spans, each
running the jitted ``step`` (two ops of module ``jit_step``), each
followed by a ``bench.wait`` span in which the device is idle; then one
call of the jitted ``other``. The trace is written to
``tests/bench/data/cpu_trace.xplane.pb``.
"""

from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace as trace_lib  # noqa: E402


@jax.jit
def step(x):
    return jnp.sin(x) @ x


@jax.jit
def other(x):
    return jnp.cos(x).sum()


def main() -> None:
    x = jnp.ones((384, 384))
    jax.block_until_ready((step(x), other(x)))
    out = Path(tempfile.mkdtemp())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                jax.block_until_ready(step(x))
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
        jax.block_until_ready(other(x))
    jax.profiler.stop_trace()
    shutil.copy(trace_lib.find_xplane(str(out)),
                HERE / "data" / "cpu_trace.xplane.pb")
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
