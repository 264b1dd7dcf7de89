"""Production meshes (TPU v5e pods) — functions only, no import-time jax
device-state side effects."""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh over real host devices (tests)."""
    n = len(jax.devices())
    data = min(data, max(1, n // model)) if n >= model else 1
    model = min(model, n)
    return _mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (per chip) for the roofline terms.
HW = dict(
    peak_flops_bf16=197e12,   # FLOP/s
    hbm_bw=819e9,             # B/s
    ici_bw_per_link=50e9,     # B/s per link (~)
)
