"""Sharding rules, HLO analysis, compression, cache simulator behaviour."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.launch import hlo_analysis


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: rule resolution needs only axis names/sizes, so tests
    # exercise the production 16x16 geometry without 256 devices.
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_rules_divisibility_guard(mesh):
    rules = sh.make_rules(mesh)
    # a dim not divisible by the axis size stays unsharded
    model_size = mesh.shape["model"]
    spec = rules.spec(("heads",), (model_size + 1,))
    assert spec == P(None)
    spec2 = rules.spec(("heads",), (model_size * 4,))
    assert spec2 == P("model")


def test_rules_duplicate_axis_dedup(mesh):
    rules = sh.make_rules(mesh)
    ms = mesh.shape["model"]
    spec = rules.spec(("kv_seq", "kv_heads"), (ms * 2, ms * 2))
    # both map to "model"; only the first may keep it
    assert spec[0] == "model" and spec[1] is None


def test_param_specs_name_rules(mesh):
    rules = sh.make_rules(mesh)
    params = dict(
        layers=dict(attn=dict(
            wq=jax.ShapeDtypeStruct((4, 64, 64), jnp.float32))),
        embed=jax.ShapeDtypeStruct((128, 64), jnp.float32),
        ln=jax.ShapeDtypeStruct((64,), jnp.float32),
    )
    specs = sh.param_specs(params, rules)
    assert specs["ln"] == P(None)
    assert len(specs["layers"]["attn"]["wq"]) == 3  # stacked rank respected


def test_weighted_costs_exact_on_known_scan():
    """flops of a scanned matmul == 2*M*N*K*trips exactly."""

    @jax.jit
    def f(a, b):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, a, b)
        return x

    m = n = k = 64
    trips = 7
    comp = f.lower(
        jax.ShapeDtypeStruct((m, k), jnp.float32),
        jax.ShapeDtypeStruct((trips, k, n), jnp.float32),
    ).compile()
    wc = hlo_analysis.weighted_costs(comp.as_text())
    assert wc["flops"] == 2.0 * m * n * k * trips


def test_compressed_psum_error_feedback():
    """Error feedback: accumulated compressed transmissions converge to the
    true mean (the property that keeps SGD convergence intact)."""
    from repro.distributed import compression as comp

    x = jnp.asarray(np.random.default_rng(0).standard_normal(256) * 3,
                    jnp.float32)
    err = jnp.zeros_like(x)
    total = jnp.zeros_like(x)
    for _ in range(50):
        y = x + err
        q, scale = comp.quantize_int8(y)
        deq = q.astype(jnp.float32) * scale
        err = y - deq
        total = total + deq
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(x),
                               atol=3e-3)
    # single-shot error is bounded by the quantization step
    q, scale = comp.quantize_int8(x)
    assert float(jnp.max(jnp.abs(x - q.astype(jnp.float32) * scale))) <= float(scale)


def test_compression_wire_bytes():
    from repro.distributed import compression as comp

    tree = dict(a=jnp.zeros((100,)), b=jnp.zeros((28,)))
    assert comp.wire_bytes(tree, compressed=False) == 512
    assert comp.wire_bytes(tree, compressed=True) == 128 + 8


@pytest.mark.slow
def test_cache_sim_vanilla_grows_unified_flat():
    """The paper's core low-level claim, on the simulator (Fig 13)."""
    from repro.core import cache, store

    def build(length, scalable):
        ch = store.create(n_pages=128, page_size=4, max_chain=32,
                          scalable=scalable, pool_capacity=4096)
        key = jax.random.PRNGKey(0)
        for i in range(length - 1):
            ids = jax.random.choice(jax.random.fold_in(key, i), 128, (16,),
                                    replace=False).astype(jnp.int32)
            ch = store.write(ch, ids, jnp.ones((16, 4)))
            ch = store.snapshot(ch)
        return ch

    reqs = jnp.arange(128, dtype=jnp.int32)
    v_short = cache.summarize(cache.simulate_vanilla(build(4, False), reqs, 8))
    v_long = cache.summarize(cache.simulate_vanilla(build(24, False), reqs, 8))
    u_short = cache.summarize(cache.simulate_unified(build(4, True), reqs, 8))
    u_long = cache.summarize(cache.simulate_unified(build(24, True), reqs, 8))
    # vanilla: unallocated-hit events grow with chain length
    assert v_long["hit_unallocated"] > 2 * max(v_short["hit_unallocated"], 1)
    # unified: probes stay one-per-request; unallocated events ~flat
    assert u_long["probes"] == u_short["probes"] == 128
    assert u_long["hit_unallocated"] <= u_short["hit_unallocated"] + 8


def test_cache_memory_model_fig12_shape():
    from repro.core.cache import cache_memory_bytes
    from repro.core.chain import ChainSpec

    spec = ChainSpec(n_pages=1024, page_size=16, max_chain=1024,
                     pool_capacity=2048)
    v = [cache_memory_bytes(spec, 64, n, unified=False) for n in (1, 500, 1000)]
    u = [cache_memory_bytes(spec, 64, n, unified=True) for n in (1, 500, 1000)]
    assert v[2] > 100 * v[0]            # vanilla grows linearly
    assert v[1] / u[1] > 10             # paper: 15.2x at length 500
    # the cache itself is chain-length independent; only the residual
    # per-snapshot driver structures grow (paper §6.2 observes the same)
    flat = [cache_memory_bytes(spec, 64, n, unified=True,
                               per_snapshot_overhead=0) for n in (1, 1000)]
    assert flat[0] == flat[1]
