"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import format as fmt
from repro.core import resolve as resolve_lib
from repro.kernels.chain_resolve import ref as cr_ref
from repro.kernels.chain_resolve.chain_resolve import (
    resolve_batch_fleet_pallas, resolve_direct_fleet_pallas,
    resolve_direct_pallas, resolve_vanilla_fleet_pallas,
    resolve_vanilla_pallas)
from repro.kernels.cow_gather import ref as cg_ref
from repro.kernels.cow_gather.cow_gather import (
    gather_fleet_pallas, gather_form, gather_pallas)
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention import ref as pa_ref
from repro.kernels.paged_attention.paged_attention import (
    fused_chain_attention_pallas, paged_attention_pallas)
from repro.kernels.stream_merge import ref as sm_ref
from repro.kernels.stream_merge.stream_merge import merge_pallas

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("c,n", [(1, 128), (4, 256), (16, 640), (64, 128)])
@pytest.mark.parametrize("density", [0.05, 0.5, 1.0])
def test_chain_resolve_vanilla_sweep(c, n, density):
    alloc = (jax.random.uniform(jax.random.fold_in(KEY, c * n), (c, n))
             < density).astype(jnp.uint32)
    ptrs = jax.random.randint(KEY, (c, n), 0, 10_000).astype(jnp.uint32)
    for length in {1, c // 2 or 1, c}:
        o1, p1 = cr_ref.resolve_vanilla_ref(alloc, ptrs, length)
        o2, p2 = resolve_vanilla_pallas(alloc, ptrs, length, interpret=True)
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("n", [128, 384, 1024])
def test_chain_resolve_direct_sweep(n):
    alloc = (jax.random.uniform(KEY, (n,)) < 0.6).astype(jnp.uint32)
    bfi = jax.random.randint(KEY, (n,), 0, 500).astype(jnp.uint32)
    ptrs = jax.random.randint(KEY, (n,), 0, 10_000).astype(jnp.uint32)
    o1, p1 = cr_ref.resolve_direct_ref(alloc, bfi, ptrs)
    o2, p2 = resolve_direct_pallas(alloc, bfi, ptrs, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def _packed_fleet_words(key, t, c, p, density):
    """Random stacked L2 word pairs in the real ``core.format`` layout."""
    ks = [jax.random.fold_in(key, i) for i in range(5)]
    entries = fmt.pack_entry(
        jax.random.randint(ks[0], (t, c, p), 0, 10_000).astype(jnp.uint32),
        jax.random.randint(ks[1], (t, c, p), 0, c).astype(jnp.uint32),
        allocated=jax.random.uniform(ks[2], (t, c, p)) < density,
        bfi_valid=jax.random.uniform(ks[3], (t, c, p)) < 0.7,
        zero=jax.random.uniform(ks[4], (t, c, p)) < 0.1,
    )
    return entries[..., 0], entries[..., 1]


@pytest.mark.parametrize("t,c,p", [(1, 1, 128), (3, 7, 256), (5, 16, 640),
                                   (2, 64, 128)])
@pytest.mark.parametrize("density", [0.05, 0.5, 1.0])
def test_chain_resolve_vanilla_fleet_sweep(t, c, p, density):
    key = jax.random.fold_in(KEY, t * c * p)
    w0, _ = _packed_fleet_words(key, t, c, p, density)
    # ragged lengths, including the length-1 (nothing-below-active) tenant
    lengths = jax.random.randint(jax.random.fold_in(key, 9), (t,), 1, c + 1)
    o1, h1 = cr_ref.resolve_vanilla_fleet_ref(w0, lengths)
    o2, h2 = resolve_vanilla_fleet_pallas(w0, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))


@pytest.mark.parametrize("t,c,p", [(1, 1, 128), (4, 9, 256), (3, 32, 640)])
def test_chain_resolve_direct_fleet_sweep(t, c, p):
    key = jax.random.fold_in(KEY, t * c * p + 1)
    w0, w1 = _packed_fleet_words(key, t, c, p, 0.6)
    lengths = jax.random.randint(jax.random.fold_in(key, 9), (t,), 1, c + 1)
    r1 = cr_ref.resolve_direct_fleet_ref(w0, w1, lengths)
    r2 = resolve_direct_fleet_pallas(w0, w1, lengths, interpret=True)
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _batch_ids(key, kind, t, b, p):
    """(T, B) page ids of one kind: random (duplicates likely), several
    (and repeated) ids inside one 128-page tile, or all in the last tile."""
    if kind == "random":
        return jax.random.randint(key, (t, b), 0, p)
    if kind == "one_tile":
        ids = 256 % p + jax.random.randint(key, (t, b), 0, 16)
        return ids.at[:, -1].set(ids[:, 0])
    return jax.random.randint(key, (t, b), p - 128, p)


@pytest.mark.parametrize("t,c,p,b", [(1, 1, 128, 1), (3, 7, 256, 8),
                                     (2, 16, 640, 5), (4, 9, 384, 16)])
@pytest.mark.parametrize("kind", ["random", "one_tile", "last_tile"])
@pytest.mark.parametrize("density", [0.05, 0.6])
def test_chain_resolve_batch_fleet_sweep(t, c, p, b, kind, density):
    key = jax.random.fold_in(KEY, t * c * p + b)
    w0, w1 = _packed_fleet_words(key, t, c, p, density)
    ids = _batch_ids(jax.random.fold_in(key, 7), kind, t, b, p)
    # some requested pages unwritten in every layer
    unwritten = jax.random.uniform(jax.random.fold_in(key, 8), (t, p)) < 0.3
    unwritten = unwritten & jnp.zeros((t, p), bool).at[
        jnp.arange(t)[:, None], ids].set(True)
    w0 = jnp.where(unwritten[:, None], jnp.uint32(0), w0)
    w1 = jnp.where(unwritten[:, None], jnp.uint32(0), w1)
    # ragged lengths below max_chain, a length-1 tenant among them
    lengths = jax.random.randint(jax.random.fold_in(key, 9), (t,), 1, c + 1)
    lengths = lengths.at[0].set(1)
    owner, hit = cr_ref.resolve_vanilla_fleet_ref(w0, lengths)
    _, h0, h1 = cr_ref.resolve_direct_fleet_ref(w0, w1, lengths)
    l2t = jnp.stack([w0, w1], axis=2)                     # (T, C, 2, P)
    got = resolve_batch_fleet_pallas(l2t, lengths, ids, interpret=True)
    for name, g, full in zip(("owner", "hit", "h0", "h1"), got,
                             (owner, hit, h0, h1)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(jnp.take_along_axis(full, ids, axis=1)),
            err_msg=name)


@pytest.mark.parametrize("formats", ["scalable", "vanilla", "mixed"])
def test_resolve_auto_stacked_batch_path(formats):
    """The batch path (B * 128 < P) of the stacked auto resolver against
    the vmapped jnp auto resolver, field for field: scalable tenants carry
    BFI_VALID words, vanilla ones an all-zero word1, and a scalable
    tenant's unallocated active entry falls back to the walk."""
    t, c, p, b = 4, 6, 512, 3
    key = jax.random.fold_in(KEY, len(formats))
    w0, w1 = _packed_fleet_words(key, t, c, p, 0.5)
    scalable = {"scalable": [True] * t, "vanilla": [False] * t,
                "mixed": [True, False, True, False]}[formats]
    w1 = jnp.where(jnp.asarray(scalable)[:, None, None], w1, jnp.uint32(0))
    lengths = jnp.asarray([c, 3, 1, c], jnp.int32)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (t, b), 0, p)
    # the first page asked of tenant 0 has no active entry: walk it
    w0 = w0.at[0, c - 1, ids[0, 0]].set(0)
    w1 = w1.at[0, c - 1, ids[0, 0]].set(0)
    l2 = jnp.stack([w0, w1], axis=-1)
    assert resolve_lib.batch_path_ok(p, b)
    got = resolve_lib.resolve_auto_stacked(l2, lengths, ids)
    want = jax.vmap(resolve_lib.get_table_resolver("auto"))(l2, lengths, ids)
    for field in got._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,page,t,b", [(16, 128, 2, 8), (64, 256, 5, 17)])
def test_cow_gather_fleet_sweep(dtype, rows, page, t, b):
    pool = jax.random.normal(KEY, (rows, page)).astype(dtype)
    idx = jax.random.randint(KEY, (t, b), 0, rows)
    found = jax.random.uniform(jax.random.fold_in(KEY, 1), (t, b)) < 0.8
    o1 = cg_ref.gather_fleet_ref(pool, idx, found)
    o2 = gather_fleet_pallas(pool, idx, found, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rows,page", [(16, 128), (64, 256), (200, 512)])
def test_cow_gather_sweep(dtype, rows, page):
    pool = jax.random.normal(KEY, (rows, page)).astype(dtype)
    b = min(rows, 32)
    idx = jax.random.randint(KEY, (b,), 0, rows)
    found = jax.random.uniform(jax.random.fold_in(KEY, 1), (b,)) < 0.8
    o1 = cg_ref.gather_ref(pool, idx, found)
    o2 = gather_pallas(pool, idx, found, interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


def _bit_pool(rows, page, dtype):
    """A pool of arbitrary bit patterns (NaNs and -0.0 among them), so a
    gather that rounds or canonicalises a payload reads wrong."""
    bits = jnp.dtype(dtype).itemsize * 8
    raw = jax.random.bits(KEY, (rows, page), getattr(jnp, f"uint{bits}"))
    return jax.lax.bitcast_convert_type(raw, dtype)


def _bits(x):
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


# (pool rows, requested rows, found; None = all found, or the pool rows
# that read as unfound). A 32-bit pool of 64 rows is 8 sublane groups.
_GATHER_CASES = {
    # every offset 0-7 of a middle group, then the pool's last group
    "offsets": (64, [24, 25, 26, 27, 28, 29, 30, 31,
                     56, 57, 58, 59, 60, 61, 62, 63], None),
    # repeated rows; unfound requests, one of them past the pool's end
    "repeats": (64, [5, 5, 13, 5, 63, 63, 0, 0,
                     13, 9999, 7, 7, 40, 41, 40, 5],
                [0, 1, 3, 9]),
    # 3 x 13 requests: a batch that is no multiple of 8
    "odd_batch": (64, list(range(63, 24, -1)), [2]),
    # 37 rows: no whole sublane groups, so the (R, 1, P) row view
    "ragged_pool": (37, [36, 0, 35, 8, 7, 36, 1, 16,
                         31, 32, 33, 34, 2, 3, 4, 5], [5]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(_GATHER_CASES))
@pytest.mark.parametrize("fleet", [True, False], ids=["fleet", "chain"])
def test_cow_gather_reads_every_row_bit_for_bit(fleet, case, dtype):
    """Both gathers against their oracles, bit for bit, whichever view of
    the pool they stage: a 32-bit pool of whole 8-row groups is read
    through the group view, any other through the row view."""
    n_rows, wanted, unfound = _GATHER_CASES[case]
    pool = _bit_pool(n_rows, 256, dtype)
    rows = jnp.asarray(wanted, jnp.int32)
    found = jnp.ones(rows.shape, bool)
    if unfound is not None:
        found = found.at[jnp.asarray(unfound)].set(False)
    wide = jnp.dtype(dtype).itemsize == 4
    want_form = "group" if wide and n_rows % 8 == 0 else "row"
    assert gather_form(n_rows, dtype) == want_form
    if fleet:
        t = 3 if case == "odd_batch" else 2
        rows, found = rows.reshape(t, -1), found.reshape(t, -1)
        want = cg_ref.gather_fleet_ref(pool, rows, found)
        got = gather_fleet_pallas(pool, rows, found, interpret=True)
    else:
        want = cg_ref.gather_ref(pool, rows, found)
        got = gather_pallas(pool, rows, found, interpret=True)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert not _bits(got)[~np.asarray(found)].any()


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("h,hkv,d,bs,m", [
    (8, 2, 64, 16, 4),    # GQA 4:1
    (4, 4, 128, 32, 2),   # MHA
    (16, 1, 64, 8, 8),    # MQA
])
def test_paged_attention_sweep(dtype, tol, h, hkv, d, bs, m):
    b, nb = 3, 64
    q = jax.random.normal(KEY, (b, h, d)).astype(dtype)
    pk = jax.random.normal(jax.random.fold_in(KEY, 1), (nb, bs, hkv, d)).astype(dtype)
    pv = jax.random.normal(jax.random.fold_in(KEY, 2), (nb, bs, hkv, d)).astype(dtype)
    lengths = jnp.array([1, bs * m // 2 + 1, bs * m], jnp.int32)
    tables = jnp.where(
        jnp.arange(m)[None, :] * bs < lengths[:, None],
        jax.random.randint(jax.random.fold_in(KEY, 3), (b, m), 0, nb), -1
    ).astype(jnp.int32)
    o1 = pa_ref.paged_attention_ref(q, pk, pv, tables, lengths)
    o2 = paged_attention_pallas(q, pk, pv, tables, lengths, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=tol, atol=tol,
    )


def _fused_attn_case(key, t, c, p, b, nb, bs, h, hkv, d, dtype,
                     density=0.55):
    """A random fused-attention problem: a packed (T, C, P) index whose
    ptrs address a real KV pool, ragged chain lengths, a batch drawn
    from a subset of tenants (repeats allowed, some tenants inactive),
    and ragged kv lengths."""
    ks = [jax.random.fold_in(key, i) for i in range(9)]
    w0 = fmt.pack_entry(
        jax.random.randint(ks[0], (t, c, p), 0, nb).astype(jnp.uint32),
        jax.random.randint(ks[1], (t, c, p), 0, c).astype(jnp.uint32),
        allocated=jax.random.uniform(ks[2], (t, c, p)) < density,
        bfi_valid=jax.random.uniform(ks[3], (t, c, p)) < 0.7,
        zero=jax.random.uniform(ks[4], (t, c, p)) < 0.1,
    )[..., 0]
    chain_lengths = jax.random.randint(ks[5], (t,), 1, c + 1)
    tenants = jax.random.randint(ks[6], (b,), 0, t)
    kv_lengths = jax.random.randint(ks[7], (b,), 1, p * bs + 1)
    q = jax.random.normal(ks[8], (b, h, d)).astype(dtype)
    pk = jax.random.normal(ks[0], (nb, bs, hkv, d)).astype(dtype)
    pv = jax.random.normal(ks[1], (nb, bs, hkv, d)).astype(dtype)
    return q, pk, pv, w0, chain_lengths, tenants, kv_lengths


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("t,c,p,h,hkv,d,bs", [
    (4, 6, 128, 8, 2, 64, 8),     # GQA 4:1, multi-layer chains
    (3, 1, 128, 4, 4, 32, 4),     # MHA, C=1: direct-path degeneration
    (5, 9, 256, 16, 1, 64, 8),    # MQA, two lane tiles
])
def test_fused_chain_attention_sweep(dtype, tol, t, c, p, h, hkv, d, bs):
    b, nb = 3, 32
    key = jax.random.fold_in(KEY, t * c * p + h)
    q, pk, pv, w0, cl, tn, kl = _fused_attn_case(
        key, t, c, p, b, nb, bs, h, hkv, d, dtype)
    o1 = pa_ref.fused_chain_attention_ref(q, pk, pv, w0, cl, tn, kl)
    o2 = fused_chain_attention_pallas(q, pk, pv, w0, cl, tn, kl,
                                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(o1, np.float32), np.asarray(o2, np.float32),
        rtol=tol, atol=tol,
    )


def test_fused_chain_attention_all_masked_row():
    """A batch row whose entire chain misses (nothing allocated below its
    length) must come out all-zero from kernel and oracle alike."""
    t, c, p, b, nb, bs, h, hkv, d = 2, 3, 128, 2, 16, 4, 4, 2, 32
    q, pk, pv, w0, cl, tn, kl = _fused_attn_case(
        jax.random.fold_in(KEY, 77), t, c, p, b, nb, bs, h, hkv, d,
        jnp.float32)
    w0 = w0.at[1].set(0)          # tenant 1 owns nothing anywhere
    tn = jnp.array([0, 1], jnp.int32)
    o1 = pa_ref.fused_chain_attention_ref(q, pk, pv, w0, cl, tn, kl)
    o2 = fused_chain_attention_pallas(q, pk, pv, w0, cl, tn, kl,
                                      interpret=True)
    np.testing.assert_array_equal(np.asarray(o2[1]), 0.0)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_fused_chain_attention_wrapper_pads_nonaligned_pages():
    """The always-kernel wrapper pads a non-lane-aligned page axis; the
    padded lanes are unallocated words the walk resolves to holes, so
    outputs match the unpadded oracle exactly."""
    t, c, p, b, nb, bs, h, hkv, d = 2, 3, 40, 2, 16, 4, 4, 2, 32
    q, pk, pv, w0, cl, tn, kl = _fused_attn_case(
        jax.random.fold_in(KEY, 40), t, c, p, b, nb, bs, h, hkv, d,
        jnp.float32)
    o1 = pa_ref.fused_chain_attention_ref(q, pk, pv, w0, cl, tn, kl)
    o2 = pa_ops.fused_chain_attention(q, pk, pv, w0, cl, tn, kl)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,n", [(2, 128), (8, 256), (30, 640)])
def test_stream_merge_sweep(k, n):
    alloc = (jax.random.uniform(jax.random.fold_in(KEY, k), (k, n)) < 0.3
             ).astype(jnp.uint32)
    ptrs = jax.random.randint(KEY, (k, n), 0, 10_000).astype(jnp.uint32)
    f1, p1, s1 = sm_ref.merge_ref(alloc, ptrs, None)
    f2, p2, s2 = merge_pallas(alloc, ptrs, interpret=True)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


def test_paged_attention_matches_dense_attention():
    """Paged attention over a contiguous table == ordinary decode attention."""
    from repro.models import layers as L

    b, h, hkv, d, bs, m = 2, 8, 4, 32, 8, 4
    s = bs * m
    q = jax.random.normal(KEY, (b, 1, h, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d), jnp.float32)
    kv_len = 19
    dense = L.decode_attention_ref(q, k, v, kv_len)[:, 0]
    # lay K/V into per-sequence contiguous pool blocks
    pool_k = k.reshape(b * m, bs, hkv, d)
    pool_v = v.reshape(b * m, bs, hkv, d)
    tables = jnp.arange(b * m, dtype=jnp.int32).reshape(b, m)
    lengths = jnp.full((b,), kv_len, jnp.int32)
    paged = pa_ref.paged_attention_ref(q[:, 0], pool_k, pool_v, tables, lengths)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(paged),
                               rtol=2e-5, atol=2e-5)
