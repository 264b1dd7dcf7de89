"""Jitted wrappers for chain resolution.

Single-chain wrappers dispatch Pallas on TPU and the jnp oracle elsewhere.
The fleet (``*_fleet``) wrappers *always* run the Pallas kernel — compiled
on TPU, interpret mode elsewhere — so CPU CI exercises the exact kernel
code path (the oracles in ``ref.py`` stay the independent pin the test
suite compares against).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.chain_resolve import ref
from repro.kernels.chain_resolve.chain_resolve import (
    resolve_batch_fleet_pallas,
    resolve_direct_fleet_pallas,
    resolve_direct_pallas,
    resolve_vanilla_fleet_pallas,
    resolve_vanilla_pallas,
)
from repro.kernels.common import pad_lanes as _pad_pages


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_vanilla(alloc, ptrs, length):
    """(C, N) chain walk. Dispatches Pallas (TPU) / interpret-validated ref."""
    if _on_tpu():
        alloc_p, n = _pad_pages(alloc)
        ptrs_p, _ = _pad_pages(ptrs)
        owner, ptr = resolve_vanilla_pallas(alloc_p, ptrs_p, length,
                                            interpret=False)
        return owner[:n], ptr[:n]
    return ref.resolve_vanilla_ref(alloc, ptrs, length)


def resolve_direct(alloc_active, bfi_active, ptrs_active):
    if _on_tpu():
        a, n = _pad_pages(alloc_active)
        b, _ = _pad_pages(bfi_active)
        p, _ = _pad_pages(ptrs_active)
        owner, ptr = resolve_direct_pallas(a, b, p, interpret=False)
        return owner[:n], ptr[:n]
    return ref.resolve_direct_ref(alloc_active, bfi_active, ptrs_active)


def resolve_vanilla_fleet(w0, lengths):
    """Stacked (T, C, P) chain walk. Always the Pallas kernel (interpret
    off-TPU); pads the page axis to a 128-lane multiple."""
    w0_p, n = _pad_pages(w0)
    owner, hit = resolve_vanilla_fleet_pallas(w0_p, lengths,
                                              interpret=not _on_tpu())
    return owner[:, :n], hit[:, :n]


def resolve_direct_fleet(w0, w1, lengths):
    """Stacked (T, C, P) direct lookup of each tenant's active layer.
    Always the Pallas kernel (interpret off-TPU); pads the page axis."""
    w0_p, n = _pad_pages(w0)
    w1_p, _ = _pad_pages(w1)
    owner, h0, h1 = resolve_direct_fleet_pallas(w0_p, w1_p, lengths,
                                                interpret=not _on_tpu())
    return owner[:, :n], h0[:, :n], h1[:, :n]


def resolve_batch_fleet(l2, lengths, ids):
    """Walk and direct results of the (T, B) pages ``ids`` only, read from
    the packed (T, C, P, 2) index in place. Always the Pallas kernel
    (interpret off-TPU); P must be a multiple of 128."""
    return resolve_batch_fleet_pallas(jnp.swapaxes(l2, 2, 3), lengths, ids,
                                      interpret=not _on_tpu())
