"""Compile every Pallas kernel and the serving decode step for a described
TPU v5e, with no chip attached.

The TPU compiler is installed beside the CPU backend, so a program can be
lowered and compiled for a ``v5e:2x2`` topology described in software.
That catches what interpret mode cannot: block shapes the tiling refuses,
ops Mosaic does not lower (scatter), scoped-VMEM overruns, and a step
program that does not fit the chip's memory. Nothing runs, so these tests
say nothing about results or speed.

Only one process at a time may load the TPU library, so the topology is
described inside a module-scoped fixture (never at import), and every
test that needs it lives in this one file.
"""

from __future__ import annotations

import math
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.serve import engine as engine_lib  # first: imports repro.core
from repro.core import depth_classes
from repro.core import fleet as fleet_lib
from repro.kernels.chain_resolve import ops as cr_ops
from repro.serve.paged_decode import paged_decode_step, paged_decode_step_fused
from repro.kernels.chain_resolve.chain_resolve import (
    resolve_batch_fleet_pallas, resolve_direct_fleet_pallas,
    resolve_direct_pallas, resolve_vanilla_fleet_pallas,
    resolve_vanilla_pallas)
from repro.kernels.cow_gather import ops as cg_ops
from repro.kernels.cow_gather.cow_gather import gather_fleet_pallas, gather_pallas
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.paged_attention import (
    fused_chain_attention_pallas, paged_attention_pallas)
from repro.kernels.stream_merge.stream_merge import merge_pallas

#: Usable HBM of one v5e chip as XLA reports it ("of 15.75G hbm").
V5E_HBM_BYTES = int(15.75 * 2**30)

# The serving plane's widths (Qwen2.5-3B: 2 KV heads of 128, 16 heads)
# and the fleet plane's: 64 tenants, 2048 lane-aligned pages, chains of
# 1 (scalable) or 500 (the paper's deep vanilla regime).
T, P, HKV, H, D, BS, NB = 64, 2048, 2, 16, 128, 16, 2048
U32, I32 = jnp.uint32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: a TPU executable written here could not be read back on a
    host without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _ops_at_least(hlo: str, n: int) -> set:
    """The kinds of the compiled program's ops whose result holds at
    least ``n`` elements."""
    big = set()
    for m in re.finditer(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* (\S+?)\(",
                         hlo, re.M):
        if math.prod(int(d) for d in m[1].split(",") if d) >= n:
            big.add(m[2])
    return big


@pytest.mark.parametrize("c", [1, 500])
def test_fleet_resolve_kernels_compile(one_chip, c):
    w0 = ((T, c, P), U32)
    van = _compile(one_chip, lambda w, ln: resolve_vanilla_fleet_pallas(
        w, ln, interpret=False), w0, ((T,), I32))
    direct = _compile(one_chip, lambda a, b, ln: resolve_direct_fleet_pallas(
        a, b, ln, interpret=False), w0, w0, ((T,), I32))
    batch = _compile(one_chip, lambda l2t, ln, ids: resolve_batch_fleet_pallas(
        l2t, ln, ids, interpret=False), ((T, c, 2, P), U32), ((T,), I32),
        ((T, 16), I32))
    assert _has_kernel(van) and _has_kernel(direct) and _has_kernel(batch)


def test_fleet_gather_kernel_compiles(one_chip):
    """The gather stages 8-row groups of the pool through its bitcast
    (R // 8, 8, P) view: no op but the parameter and that bitcast holds
    the pool, and nothing is copied to feed the kernel."""
    rows = T * 4096
    compiled = _compile(
        one_chip, lambda pool, r, f: gather_fleet_pallas(
            pool, r, f, interpret=False),
        ((rows, 128), jnp.float32), ((T, 512), I32), ((T, 512), jnp.bool_))
    assert _has_kernel(compiled)
    assert _ops_at_least(compiled.as_text(), rows * 128) == {"parameter",
                                                             "bitcast"}
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


@pytest.mark.parametrize("c", [1, 500])
def test_fused_chain_attention_compiles(one_chip, c):
    compiled = _compile(
        one_chip, lambda *a: fused_chain_attention_pallas(*a, interpret=False),
        ((16, H, D), jnp.bfloat16), ((NB, BS, HKV, D), jnp.bfloat16),
        ((NB, BS, HKV, D), jnp.bfloat16), ((T, c, P), U32), ((T,), I32),
        ((16,), I32), ((16,), I32))
    assert _has_kernel(compiled)


def test_paged_attention_compiles(one_chip):
    compiled = _compile(
        one_chip, lambda *a: paged_attention_pallas(*a, interpret=False),
        ((16, H, D), jnp.bfloat16), ((NB, BS, HKV, D), jnp.bfloat16),
        ((NB, BS, HKV, D), jnp.bfloat16), ((16, P), I32), ((16,), I32))
    assert _has_kernel(compiled)


@pytest.mark.parametrize("kernel", ["resolve_vanilla", "resolve_direct",
                                    "gather", "merge"])
def test_single_chain_kernels_compile(one_chip, kernel):
    c, n = 500, P
    fn, shapes = {
        "resolve_vanilla": (
            lambda a, p, ln: resolve_vanilla_pallas(a, p, ln, interpret=False),
            [((c, n), U32), ((c, n), U32), ((), I32)]),
        "resolve_direct": (
            lambda a, b, p: resolve_direct_pallas(a, b, p, interpret=False),
            [((n,), U32)] * 3),
        "gather": (
            lambda pool, r, f: gather_pallas(pool, r, f, interpret=False),
            [((4096, 128), jnp.float32), ((512,), I32), ((512,), jnp.bool_)]),
        "merge": (
            lambda a, p: merge_pallas(a, p, interpret=False),
            [((30, n), U32), ((30, n), U32)]),
    }[kernel]
    assert _has_kernel(_compile(one_chip, fn, *shapes))


@pytest.fixture
def tpu_fleet_kernels(monkeypatch):
    """Steer the fleet read's kernel wrappers to their compiled branch:
    under ``JAX_PLATFORMS=cpu`` they run the kernels in interpret mode."""
    monkeypatch.setattr(cr_ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(cg_ops, "jax",
                        types.SimpleNamespace(default_backend=lambda: "tpu"))


def test_deep_chain_read_resolves_the_index_in_place(one_chip,
                                                     tpu_fleet_kernels):
    """``fleet.read(method="auto")`` at the rocksdb-d500 shapes (two disks,
    500 layers, 720,896 pages, 256 reads per disk) compiles with the batch
    kernel, touches the 5.37 GiB index and the 1.375 GiB pool only as
    parameters and bitcasts to the kernels' (T, C, 2, P) and
    (R // 8, 8, P) views — no slice, copy or relayout of either or of a
    word plane — and needs under 64 MiB of temporaries (the whole-table
    path needed 8.1 GiB, the pool's row view 1.375 GiB)."""
    spec = fleet_lib.FleetSpec(
        n_tenants=2, n_pages=720_896, page_size=1024, max_chain=500,
        pool_capacity=2 * 180_224, lease_quantum=64, l2_per_table=8192,
        slice_len=512)
    fl = jax.eval_shape(lambda s: fleet_lib.create(spec, scalable=s),
                        jax.ShapeDtypeStruct((2,), jnp.bool_))
    fl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one_chip), fl)
    ids = jax.ShapeDtypeStruct((2, 256), I32, sharding=one_chip)
    compiled = fleet_lib.read.lower(fl, ids, method="auto").compile()
    hlo = compiled.as_text()
    assert _has_kernel(compiled)
    assert "resolve_batch_fleet_pallas" in " ".join(
        re.findall(r'op_name="([^"]*)"', hlo))
    pool = spec.pool_capacity * spec.page_size     # less than the index
    big = _ops_at_least(hlo, pool)
    assert big == {"parameter", "bitcast"}, big
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 64 * 2**20, f"{temp / 2**20:.1f} MiB of temporaries"


def test_batch_resolve_kernel_compiles_at_depth_1000(one_chip):
    """The batch kernel stages a (1000, 2, 128) tile column per page for
    the deepest class of ``chains-1to1000`` (two disks of 262,144 pages):
    1 MB a block in the index's (2, 128) tiling, 2 MB double buffered,
    inside the default scoped VMEM."""
    batch = _compile(one_chip, lambda l2t, ln, ids: resolve_batch_fleet_pallas(
        l2t, ln, ids, interpret=False), ((2, 1000, 2, 262_144), U32),
        ((2,), I32), ((2, 256), I32))
    assert _has_kernel(batch)


def test_depth_sweep_read_fits_one_v5e(one_chip, tpu_fleet_kernels):
    """``depth_classes.read`` at the ``chains-1to1000`` shapes (ten disks
    at depths 1, 50, 100, 500 and 1,000, 262,144 pages, 65,536 live
    clusters of 4 KiB each, 256 reads per disk) compiles as one program
    with the batch kernel in every class, holds 3,302 layers of index
    (a dense stack would hold 10,000), reads each class's index and pool
    in place, and fits the chip with room to spare."""
    classes = []
    for depth in (1, 50, 100, 500, 1000):
        spec = fleet_lib.FleetSpec(
            n_tenants=2, n_pages=262_144, page_size=1024, max_chain=depth,
            pool_capacity=2 * 65_536, lease_quantum=64, l2_per_table=8192,
            slice_len=512)
        fl = jax.eval_shape(lambda s: fleet_lib.create(spec, scalable=s),
                            jax.ShapeDtypeStruct((2,), jnp.bool_))
        classes.append(jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), fl))
    classed = depth_classes.assemble(classes)
    assert classed.index_bytes() == 3302 * 262_144 * 8
    ids = jax.ShapeDtypeStruct((10, 256), I32, sharding=one_chip)
    compiled = depth_classes.read.lower(classed, ids, method="auto").compile()
    hlo = compiled.as_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    for depth in (1, 50, 100, 500, 1000):
        assert f"fleet.class{depth}/fleet.resolve/" in names
    assert names.count("resolve_batch_fleet_pallas") >= 5
    pool = 2 * 65_536 * 1024       # each class's; less than any index
    big = _ops_at_least(hlo, pool)
    assert big == {"parameter", "bitcast"}, big
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20, (
        f"{mem.temp_size_in_bytes / 2**20:.1f} MiB of temporaries")
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES - 2**30, f"{used / 2**30:.2f} GiB"


@pytest.fixture
def tpu_attention(monkeypatch):
    """Steer the attention dispatch to its TPU branch: under
    ``JAX_PLATFORMS=cpu`` the backend reports CPU, and the decode step
    would trace the jnp oracle instead of the kernel."""
    monkeypatch.setattr(pa_ops, "jax",
                        types.SimpleNamespace(default_backend=lambda: "tpu"))


@pytest.mark.parametrize("path", ["tables", "fused"])
def test_qwen2_5_3b_decode_step_fits_one_v5e(one_chip, tpu_attention, path):
    """The serving decode step of Qwen2.5-3B at full width, bf16 weights,
    a 2048-block pool of 16-token blocks and 128 blocks per sequence,
    compiles for one v5e with the kernel in the program, and its
    arguments, outputs and temporaries fit the chip's memory."""
    cfg = get_config("qwen2.5-3b")
    b, mb = 16, 128
    params = jax.eval_shape(
        lambda k: engine_lib.init_serving_params(cfg, k),
        jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    assert {a.dtype for a in jax.tree.leaves(params)} == {jnp.dtype(jnp.bfloat16)}
    spec = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pool = spec((cfg.n_layers, NB, BS, cfg.n_kv_heads, cfg.hd), jnp.bfloat16)
    vec = spec((b,), I32)
    tokens = spec((b, 1), I32)
    if path == "tables":
        lowered = paged_decode_step.lower(cfg, params, pool, pool,
                                          spec((b, mb), I32), vec, tokens)
    else:
        lowered = paged_decode_step_fused.lower(
            cfg, params, pool, pool, spec((64, 8, mb, 2), U32),
            spec((64,), I32), vec, vec, vec, tokens)
    compiled = lowered.compile()
    assert _has_kernel(compiled)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= V5E_HBM_BYTES, (
        f"decode step needs {used / 2**30:.2f} GiB of {V5E_HBM_BYTES / 2**30} GiB")
