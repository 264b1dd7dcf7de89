"""``chip_smoke.py`` at a tiny size on the CPU.

The script's phases take their sizes as arguments, so the same fleet and
serving checks run here in seconds: a later change that breaks the
bring-up test's control flow, or makes a variant disagree with the dense
reference, fails here before it costs chip time. The script's own entry
point must refuse to run without a TPU.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleet_phase_tiny(smoke):
    out = smoke.fleet_phase(0, n_tenants=4, n_pages=128, depth=6,
                            writes_per_layer=4, page_size=128, batch=32)
    for method in ("pallas_vanilla", "pallas_direct", "auto"):
        assert 0.0 < out[method]["found"] <= 1.0


def test_serving_phase_tiny(smoke):
    out = smoke.serving_phase(smoke_config("qwen2.5-3b"), 0, n_prompts=2,
                              prompt_len=24, golden_len=16, n_suffix=2,
                              steps=3, n_blocks=64, block_size=4,
                              max_blocks=128)
    assert sorted(out) == ["scalable/fused", "scalable/tables",
                           "vanilla/fused", "vanilla/tables"]
    for r in out.values():
        assert r["rel_max"] <= smoke.LOGITS_REL_TOL


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
