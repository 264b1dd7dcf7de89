"""Work counts (``bench/work.py``) against hand counts."""

import numpy as np
import pytest

from bench import harness, work

TINY = dict(hidden_size=4, head_dim=2, num_attention_heads=2,
            num_key_value_heads=1, intermediate_size=3, num_hidden_layers=2,
            vocab_size=10)


def test_walk_layers_vanilla_walks_to_the_owner_or_the_whole_chain():
    owner = np.array([0, 3, 4, -1])
    assert work.walk_layers(owner, 5, False).tolist() == [5, 2, 1, 5]
    assert work.walk_layers(owner, 5, True).tolist() == [1, 1, 1, 1]


def test_fleet_read_bytes_by_hand():
    owner = np.array([[0, 4], [0, 4]])
    # scalable disk: 2 entries + 2 payloads read and written (2 * 2 * 16)
    # vanilla disk, chain of 5: 5 + 1 entries + the same payload bytes
    want = (8 * 2 + 64) + (8 * 6 + 64)
    got = work.fleet_read_bytes(owner, [5, 5], [True, False], 16)
    assert got == want == 192
    # an unwritten page costs its walk and no payload
    assert work.fleet_read_bytes(np.array([[-1]]), [5], [False], 16) == 40


def test_matmul_params_by_hand():
    # q 4*2*2, k and v 2*(4*1*2), o 2*2*4, gated MLP 3*4*3, per layer
    assert work.matmul_params(TINY) == 2 * (16 + 16 + 16 + 36) == 168


def test_decode_flops_and_kv_bytes_by_hand():
    ctx = [3, 5]
    dense = 2 * (168 + 4 * 10)                 # per row, head included
    attn = 4 * 2 * 2 * 2 * (3 + 5)             # QK^T and PV, all layers
    assert work.decode_flops(TINY, ctx) == 2 * dense + attn == 1088
    # K and V, 2 layers, 1 kv head of 2, bf16: 16 bytes a token
    assert work.decode_kv_bytes(TINY, ctx) == 16 * 8 == 128


def test_qwen_matmul_params_match_the_published_size():
    cfg = harness.load_json(harness.BENCH / "configs" / "qwen2.5-3b.json")
    from repro.configs import get_config
    mc = get_config(cfg["program_config"])
    embed = cfg["hidden_size"] * cfg["vocab_size"]
    assert work.matmul_params(cfg) + embed == mc.param_count()
    assert work.decode_kv_bytes(cfg, [1]) == 36 * 1024


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks_for("TPU v9 imaginary")
