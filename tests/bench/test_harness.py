"""The harness: it refuses to run without a TPU, its contract file is well
formed, and a new cell, configuration, driver and per-layer metric are
new files plus new entries in ``BENCHMARK.json``, with no edit to any
file the benchmark already has."""

import filecmp
import json
import os
import shutil
import subprocess
import sys
import time

from bench import harness

ROOT = harness.ROOT


def test_run_exits_nonzero_and_prints_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "rocksdb-d500.readrandom", "--seed", str(2**31 + 1), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_every_cell_finds_its_files_and_metrics():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert cell.load_driver().run
        assert {m["name"] for m in cell.end_to_end()} >= {"setup_s"}
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()
        for m in cell.per_layer():
            assert (cell.bench_dir / "metrics" / f"{m['name']}.py").exists()
            assert m["moves"] in {e["name"] for e in cell.end_to_end()}
        assert (cell.bench_dir / "configs"
                / f"{w['config']}.ref.py").exists()


DUMMY_DRIVER = '''
from bench import harness


def run(ctx):
    with ctx.window() as win:
        n = 0
        while win.open():
            with ctx.span("tick"):
                n += ctx.traffic["step"]
    return harness.Outcome(
        end_to_end=dict(ticks_per_s=n / ctx.seconds, setup_s=ctx.setup_s()),
        attempted=n, failed=0,
        checks=[harness.Check("wrong_ticks", n % ctx.traffic["step"], 0)],
        memory_peak_bytes=0, layer=dict(ticks=n))
'''

DUMMY_METRIC = '''
def read(run):
    spans = run.spans.get("tick")
    return 1e3 * sum(spans) / len(spans) if spans else None
'''


def test_a_dummy_cell_is_new_files_and_new_entries_only(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    b = tmp_path / "bench"
    (b / "configs" / "dummy.json").write_text(json.dumps({"name": "dummy"}))
    (b / "configs" / "dummy.ref.py").write_text("ANSWER = 0\n")
    (b / "workloads" / "dummy.ticks.json").write_text(json.dumps(dict(
        config="dummy", driver="dummy_loop", traffic=dict(step=3),
        why="a cell made of new files")))
    (b / "drivers" / "dummy_loop.py").write_text(DUMMY_DRIVER)
    (b / "metrics" / "tick_ms.py").write_text(DUMMY_METRIC)
    spec["configs"].append(dict(name="dummy", source="a test",
                                file="bench/configs/dummy.json", reduced=[]))
    spec["workloads"].append(dict(name="dummy.ticks", config="dummy",
                                  traffic="ticks", chips=1, why="a test"))
    spec["end_to_end"].append(dict(name="ticks_per_s", unit="1/s",
                                   better="higher", bound=0.25,
                                   source="host_clock",
                                   workloads=["dummy.ticks"]))
    spec["per_layer"].append(dict(name="tick_ms", unit="ms", better="lower",
                                  source="program_span", layer="loop",
                                  moves="ticks_per_s",
                                  workloads=["dummy.ticks"]))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell("dummy.ticks", tmp_path / "BENCHMARK.json")
    assert {m["name"] for m in cell.end_to_end()} == {"ticks_per_s", "setup_s"}
    ctx = harness.Context(cell, seed=1, seconds=0.05, trace=False,
                          t0=time.perf_counter())
    out = cell.load_driver().run(ctx)
    assert all(c.ok for c in out.checks) and out.attempted > 0
    view = harness.RunView(cell=cell, outcome=out, trace=None, peaks={},
                           spans=ctx.spans)
    assert set(harness.per_layer_values(cell, view)) == {"tick_ms"}
    assert cell.reference().ANSWER == 0

    # every file the benchmark already had is unchanged
    cmp = filecmp.dircmp(ROOT / "bench", b, ignore=["__pycache__"])

    def changed(c):
        return c.diff_files + c.left_only + [
            f for sub in c.subdirs.values() for f in changed(sub)]
    assert changed(cmp) == []
