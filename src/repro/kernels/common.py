"""Shared kernel-plane helpers.

Every Pallas kernel in this package tiles its page axis over the TPU's
128-wide lane dimension, so each ``ops`` wrapper needs the same
pad-to-lane-multiple step before the ``pallas_call`` and the same
un-pad slice after it. ``pad_lanes`` is that one helper; the per-kernel
wrappers (``chain_resolve``, ``cow_gather``, ``paged_attention``) all
share it instead of carrying private copies. ``first_hit_walk`` is the
one in-kernel chain walk that the resolve, merge and fused-attention
kernels all run.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: TPU vector lane width — the tiling unit of every kernel's page axis.
LANES = 128
#: TPU sublane count (32-bit): a block's second-minor dim must be a
#: multiple of this or the array's whole extent.
SUBLANES = 8


def first_hit_walk(length, n_layers: int, row_at, payload0):
    """In-kernel first-hit scan down a chain of ``n_layers`` stacked rows.

    Walks layer ``length - 1`` down to 0; ``row_at(idx)`` loads layer
    ``idx`` and returns ``(alloc (1, W) bool, payload (1, W))``. Returns
    ``(owner (1, W) int32 [-1 where no layer hit], payload)`` where
    ``payload`` holds the owning layer's value (``payload0`` on a miss).

    The carry is updated with whole-row ``jnp.where`` selects — Mosaic
    lowers no scatter, so ``x.at[0].set(...)`` on an in-kernel value is
    refused — and the row index is clamped into the ref, because a TPU
    VMEM load is not bounds-checked.
    """
    owner0 = jnp.full(payload0.shape, -1, jnp.int32)

    def body(i, carry):
        owner, payload = carry
        layer = length - 1 - i
        alloc, value = row_at(jnp.clip(layer, 0, n_layers - 1))
        first = alloc & (layer >= 0) & (layer < n_layers) & (owner < 0)
        return (jnp.where(first, layer, owner),
                jnp.where(first, value, payload))

    return jax.lax.fori_loop(0, n_layers, body, (owner0, payload0))


def pad_lanes(x, axis: int = -1, multiple: int = LANES):
    """Zero-pad ``axis`` of ``x`` up to a multiple of ``multiple``.

    Returns ``(padded, original_size)`` so callers can slice the kernel
    output back to the caller-visible extent. Zero padding is safe for
    every kernel here: a zero L2 word has ``FLAG_ALLOCATED`` unset (the
    walk skips it), and padded pool/output lanes are sliced away.
    """
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis % x.ndim] = (0, pad)
        x = jnp.pad(x, widths)
    return x, n
