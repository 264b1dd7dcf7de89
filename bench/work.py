"""Work counts that rooflines and MFU divide.

Each count is the work a request needs, computed from shapes and from
the benchmark's own record of the data; never what the current kernel
happens to touch. A program that resolves only the requested pages, or
skips a kernel, then reads a higher share, not an impossible one.
"""

from __future__ import annotations

import numpy as np

ENTRY_BYTES = 8        # one qcow2 L2 entry (two u32 words)
KV_BYTES = 2           # bf16 K and V


def walk_layers(owner: np.ndarray, length: int, scalable: bool) -> np.ndarray:
    """L2 entries a read of each page must consult. A scalable image
    reads one entry (the active layer's, which names the owner); a
    vanilla image walks from the active layer down to the owner, or the
    whole chain where no layer holds the page (``owner`` -1)."""
    owner = np.asarray(owner)
    if scalable:
        return np.ones(owner.shape, np.int64)
    return np.where(owner >= 0, length - owner, length).astype(np.int64)


def fleet_read_bytes(owner: np.ndarray, lengths, scalable,
                     payload_bytes: int) -> int:
    """HBM bytes one fleet read dispatch needs: the L2 entries each page's
    resolution consults, plus each found page's payload read from the
    pool and written to the output. ``owner``: (T, B) owning layer of
    each requested page (-1 = unwritten); ``lengths``/``scalable``:
    (T,) per disk."""
    total = 0
    for t in range(owner.shape[0]):
        total += ENTRY_BYTES * int(walk_layers(owner[t], int(lengths[t]),
                                               bool(scalable[t])).sum())
        total += 2 * payload_bytes * int((owner[t] >= 0).sum())
    return total


def matmul_params(cfg: dict) -> int:
    """Weights a token multiplies through in one pass of the decoder
    layers (q, k, v, o and the gated MLP), without the embedding."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d
    per_layer += 3 * d * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * per_layer


def decode_flops(cfg: dict, context_lens) -> int:
    """Model FLOPs of one decode step over its real rows: two per weight
    per row through the layers and the output head, and QK^T plus PV over
    each row's attended context (its new token included)."""
    ctx = np.asarray(context_lens, np.int64)
    rows = ctx.size
    dense = 2 * (matmul_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"])
    attn = (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * int(ctx.sum()))
    return dense * rows + attn


def decode_kv_bytes(cfg: dict, context_lens) -> int:
    """K and V bytes the attention of one decode step must read: every
    layer's cached K and V over each real row's attended context."""
    ctx = np.asarray(context_lens, np.int64)
    per_token = (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                 * cfg["head_dim"] * KV_BYTES)
    return per_token * int(ctx.sum())

