"""Pallas TPU kernels: decode attention over a paged KV pool.

``paged_attention_pallas`` is the serving-side payoff of the paper's
*direct access* principle: the block table handed to it is the flattened
(copy-forward) table, so each grid step DMAs exactly one physical KV
block HBM→VMEM via the scalar-prefetched index map — no fork-chain
walking anywhere near the attention inner loop. It requires that table
to have been materialized (resolved, synced, assembled, re-shipped) by
the host first.

``fused_chain_attention_pallas`` removes that materialization step: the
kernel receives the *stacked fleet index itself* — the packed L2 word0
stacks of ``core.fleet`` plus per-tenant chain lengths — and performs
the first-hit chain walk of ``chain_resolve`` inside the attention grid,
then DMAs each KV block straight out of the shared pool through the
resolved row id. A tenant with ``max_chain == 1`` (the scalable/sQEMU
format) degenerates to the O(1) active-layer direct lookup; deeper
stacks pay the paper's O(chain) walk once per batch row, amortized over
every page lane at once. See ``docs/kernels.md`` for the cost model.

Grid: (batch, kv_blocks); the kv-block axis is innermost and sequential on
a TPU core, so the online-softmax running state (m, l, acc) lives in VMEM
scratch across iterations. f32 accumulation, bf16 I/O.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import format as fmt
from repro.kernels.common import first_hit_walk


def _paged_attn_kernel(tables_ref, lengths_ref, q_ref, k_ref, v_ref, out_ref,
                       m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)

    bs = k_ref.shape[1]
    hkv = k_ref.shape[2]
    d = q_ref.shape[2]
    h = q_ref.shape[1]
    g = h // hkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    # tables entries are -1 only past ceil(length/bs), so the length mask
    # alone is sufficient (entries were clamped to 0 for the DMA index map)
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    valid = pos < length                                  # (1,1,bs)

    q = q_ref[0].astype(jnp.float32).reshape(hkv, g, d)
    k = k_ref[0].astype(jnp.float32)                      # (bs, Hkv, D)
    v = v_ref[0].astype(jnp.float32)
    scores = jnp.einsum("hgd,shd->hgs", q, k)             # (Hkv,G,bs)
    scores = scores / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(valid.reshape(1, 1, bs), scores, -jnp.inf)

    m_prev = m_ref[...]                                   # (Hkv,G,1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.where(jnp.isfinite(scores), jnp.exp(scores - m_safe), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = (
        acc_ref[...] * alpha
        + jnp.einsum("hgs,shd->hgd", p, v)
    )
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = (acc_ref[...] / denom).reshape(1, h, d).astype(
            out_ref.dtype
        )


@partial(jax.jit, static_argnames=("interpret",))
def paged_attention_pallas(q, pool_k, pool_v, tables, lengths, *,
                           interpret: bool = True):
    """q: (B, H, D); pool_k/v: (nb, bs, Hkv, D); tables: (B, M); lengths (B,)."""
    b, h, d = q.shape
    nb, bs, hkv, _ = pool_k.shape
    m_blocks = tables.shape[1]
    g = h // hkv
    safe_tables = jnp.maximum(tables, 0).astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, m_blocks),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b, j, t, ln: (b, 0, 0)),
            pl.BlockSpec((1, bs, hkv, d), lambda b, j, t, ln: (t[b, j], 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, d), lambda b, j, t, ln: (t[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b, j, t, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _paged_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(safe_tables, lengths.astype(jnp.int32), q,
      pool_k.reshape(nb, bs, hkv, d), pool_v.reshape(nb, bs, hkv, d))


# -- fused chain-resolve attention -------------------------------------------


def _fused_chain_attn_kernel(tenants_ref, chain_len_ref, kvlen_ref,
                             q_ref, w0_ref, kp_ref, vp_ref, out_ref,
                             rows_ref, m_ref, l_ref, acc_ref,
                             k_buf, v_buf, sem_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    c, p = w0_ref.shape
    bs, hkv, d = k_buf.shape
    h = q_ref.shape[1]
    g = h // hkv

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the fused chain walk: one vectorized first-hit scan over this
        # batch row's tenant stack resolves every page lane at once and
        # parks the pool rows in VMEM scratch for the whole kv sweep.
        # C == 1 (scalable tenants) makes this the O(1) direct lookup.
        def row_at(idx):
            w = w0_ref[pl.ds(idx, 1), :]
            return ((w & jnp.uint32(fmt.FLAG_ALLOCATED)) != 0,
                    (w & jnp.uint32(fmt.PTR_MASK)).astype(jnp.int32))

        owner, rows = first_hit_walk(chain_len_ref[tenants_ref[b]], c,
                                     row_at, jnp.zeros((1, p), jnp.int32))
        rows_ref[...] = jnp.where(owner >= 0, rows, -1)

    # this block's resolved pool row: a masked reduce over the parked walk
    # result (VMEM has no dynamic scalar lane indexing)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, p), 1)
    rows = rows_ref[...]
    row = jnp.sum(jnp.where(lane == j, rows, 0))
    hole = row < 0
    row_safe = jnp.maximum(row, 0)

    # KV pages come straight from the shared pool through the resolved
    # row id — the pool stays in HBM (ANY) and each grid step DMAs one
    # block; no host-materialized table anywhere on this path
    ck = pltpu.make_async_copy(kp_ref.at[row_safe], k_buf, sem_ref.at[0])
    cv = pltpu.make_async_copy(vp_ref.at[row_safe], v_buf, sem_ref.at[1])
    ck.start()
    cv.start()
    ck.wait()
    cv.wait()

    kvlen = kvlen_ref[b]
    pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    valid = (pos < kvlen) & jnp.logical_not(hole)      # (1,1,bs)

    q = q_ref[0].astype(jnp.float32).reshape(hkv, g, d)
    k = k_buf[...].astype(jnp.float32)                 # (bs, Hkv, D)
    v = v_buf[...].astype(jnp.float32)
    scores = jnp.einsum("hgd,shd->hgs", q, k)          # (Hkv,G,bs)
    scores = scores / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(valid.reshape(1, 1, bs), scores, -jnp.inf)

    m_prev = m_ref[...]                                # (Hkv,G,1)
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    pmat = jnp.where(jnp.isfinite(scores), jnp.exp(scores - m_safe), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pmat, axis=-1, keepdims=True)
    acc_ref[...] = (
        acc_ref[...] * alpha
        + jnp.einsum("hgs,shd->hgd", pmat, v)
    )
    m_ref[...] = m_new

    @pl.when(j == n_blocks - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[...] = (acc_ref[...] / denom).reshape(1, h, d).astype(
            out_ref.dtype
        )


@partial(jax.jit, static_argnames=("interpret",))
def fused_chain_attention_pallas(q, pool_k, pool_v, w0, chain_lengths,
                                 tenants, kv_lengths, *,
                                 interpret: bool = True):
    """Decode attention that walks the snapshot chain inside the kernel.

    ``q``: (B, H, D); ``pool_k``/``pool_v``: (nb, bs, Hkv, D) shared KV
    pool; ``w0``: (T, C, P) uint32 — the stacked fleet index's packed L2
    word0 (``core.format`` layout), P a multiple of 128
    (``ops.fused_chain_attention`` pads); ``chain_lengths``: (T,) int32
    per-tenant chain length; ``tenants``: (B,) int32 batch-row → tenant
    row; ``kv_lengths``: (B,) int32 tokens to attend over. Returns
    (B, H, D) in q.dtype.

    Unallocated pages (first-hit miss) contribute nothing; a batch row
    whose tenant resolves no pages within ``kv_lengths`` outputs zeros.
    """
    b, h, d = q.shape
    nb, bs, hkv, _ = pool_k.shape
    t, c, p = w0.shape
    g = h // hkv
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, p),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda b, j, tn, cl, kl: (b, 0, 0)),
            pl.BlockSpec((None, c, p), lambda b, j, tn, cl, kl: (tn[b], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda b, j, tn, cl, kl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, p), jnp.int32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, 1), jnp.float32),
            pltpu.VMEM((hkv, g, d), jnp.float32),
            pltpu.VMEM((bs, hkv, d), pool_k.dtype),
            pltpu.VMEM((bs, hkv, d), pool_v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _fused_chain_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(tenants.astype(jnp.int32), chain_lengths.astype(jnp.int32),
      kv_lengths.astype(jnp.int32), q, w0.astype(jnp.uint32),
      pool_k.reshape(nb, bs, hkv, d), pool_v.reshape(nb, bs, hkv, d))
