"""The serving cells' driver at a tiny size on the CPU: whole runs of both
traffic mixes, the float8 control, and the faults that must turn
``correct`` false. The tiny model's limit is its own (its logits are
smaller than the full model's), set as the full size's is: between what
sound runs read and what the control and the faults read."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from repro.configs import get_config
from repro.serve import engine as engine_lib

#: The tiny model's limit on the widest gap.
TINY_GAP_LIMIT = 0.02
SMALL = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=512, served_logit_gap_limit=TINY_GAP_LIMIT,
             engine=dict(scalable=True, n_blocks=512, block_size=4,
                         max_blocks_per_seq=128))
TRAFFIC = dict(head_len=16, suffix_lens=[4, 8], suffix_weights=[0.6, 0.4],
               output_len_min=3, output_len_max=6, max_running=4,
               rate_per_s=10.0)


@pytest.fixture
def driver(monkeypatch):
    cell = harness.cell_from_files("qwen2.5-3b.golden-agent")
    drv = cell.load_driver()
    small = dataclasses.replace(
        get_config("qwen2.5-3b"), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab_size=512)
    monkeypatch.setattr(drv, "program_config", lambda cfg: small)
    monkeypatch.setattr(drv, "REF_TOKENS", 64)
    monkeypatch.setattr(drv, "REF_SERVED", 8)
    return drv


def tiny_ctx(name, seed=2**31 + 3, seconds=2.0):
    cell = harness.cell_from_files(name)
    cell.config.update(SMALL)
    cell.file["traffic"] = dict(cell.traffic, **TRAFFIC)
    return harness.Context(cell, seed=seed, seconds=seconds, trace=False,
                           t0=time.perf_counter())


@pytest.mark.parametrize("name", ["qwen2.5-3b.golden-agent",
                                  "qwen2.5-3b.unshared-agent"])
def test_a_run_is_correct_and_reports_its_metrics(driver, name):
    ctx = tiny_ctx(name)
    out = driver.run(ctx)
    assert [c.name for c in out.checks] == ["served_logit_gap"]
    assert out.checks[0].value < TINY_GAP_LIMIT / 4
    assert set(out.end_to_end) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert out.attempted > 5 and out.layer["decode_steps"] > 0
    assert out.layer["decode_flops"] > 0 and out.layer["decode_kv_bytes"] > 0
    assert ctx.compiles_in_window[0] == []
    assert {"admit", "prep", "step"} <= set(ctx.spans)


def test_every_seed_gets_the_same_schedule(driver):
    tr = dict(harness.cell_from_files("qwen2.5-3b.golden-agent").traffic)
    goldens = [np.arange(1024, dtype=np.int32) + k for k in range(4)]
    runs = [driver.make_requests(tr, 151936, np.random.default_rng(seed),
                                 goldens, 200) for seed in (1, 2**31 + 7)]
    a, b = runs
    assert [(r.arrival, r.prompt.size, r.out_len) for r in a] == [
        (r.arrival, r.prompt.size, r.out_len) for r in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    sizes = sorted(r.prompt.size for r in a)
    assert sizes.count(1024 + 64) == 80 and max(r.out_len for r in a) == 128


def test_the_float8_control_reads_far_above_the_program(driver):
    ctx = tiny_ctx("qwen2.5-3b.golden-agent")
    state = driver.setup(ctx)
    reqs, rng = driver.requests_for(ctx, state)
    driver.serve_window(ctx, state, reqs)
    gaps, ctl = driver.compare(ctx, state, driver.choose_sample(reqs, rng),
                               control=True)
    assert gaps.size == ctl.size > 10
    assert ctl.max() > TINY_GAP_LIMIT > 3 * gaps.max()


def _token_altered(orig):
    def decode(self, sids, last_tokens):
        return {s: (t + 1) % self.cfg.vocab_size
                for s, t in orig(self, sids, last_tokens).items()}
    return decode


def _half_batch(orig):
    def step(cfg, *args):
        logits, pk, pv = orig(cfg, *args)
        b = logits.shape[0]
        return logits.at[b // 2:].set(0.0), pk, pv
    return step


def _state_unchanged(orig):
    def step(cfg, params, pool_k, pool_v, *args):
        logits, _, _ = orig(cfg, params, pool_k, pool_v, *args)
        return logits, pool_k, pool_v
    return step


@pytest.mark.parametrize("fault", ["token_altered", "half_batch",
                                   "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(driver, monkeypatch, fault):
    if fault == "token_altered":
        monkeypatch.setattr(engine_lib.Engine, "_decode",
                            _token_altered(engine_lib.Engine._decode))
    else:
        wrap = _half_batch if fault == "half_batch" else _state_unchanged
        monkeypatch.setattr(engine_lib, "paged_decode_step_fused",
                            wrap(engine_lib.paged_decode_step_fused))
    out = driver.run(tiny_ctx("qwen2.5-3b.golden-agent"))
    assert not out.checks[0].ok, out.checks[0]


def test_weights_are_bf16_in_the_engines_layout():
    cell = harness.cell_from_files("qwen2.5-3b.golden-agent")
    cfg = dict(cell.config, **{k: v for k, v in SMALL.items() if k != "engine"})
    w = cell.reference().init_weights(cfg, 2**31 + 1)
    assert {str(a.dtype) for a in jax.tree.leaves(w)} == {"bfloat16"}
    assert w["layers"]["attn"]["wq"].shape == (2, 64, 64)
    assert float(jnp.abs(w["layers"]["attn"]["bk"]).max()) > 0
