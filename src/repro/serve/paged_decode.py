"""Paged decode step for dense/MoE transformers (continuous batching).

Unlike ``transformer.decode_step`` (dense per-request cache, used by the
dry-run serve cells), this path reads K/V through *direct block tables*
from a shared paged pool — the serving integration of the paper's
direct-access principle. Per-sequence positions come from ``lengths``
(sequences in a continuous batch are at different positions).

The attention inner loop is ``kernels/paged_attention`` (Pallas on TPU,
oracle on CPU). Pool writes happen in-step at (table[len // bs], len % bs).

Block tables must be fully **device-resident**: every id in ``tables``
must address live pool data. Host-tier promotion of spilled (cold)
blocks happens strictly before this step, inside
``PagedKVCache.prepare_step`` — by the time a table reaches this jitted
function there are no cold positions left (see ``docs/memory.md``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models.transformer import output_matrix


@partial(jax.jit, static_argnames=("cfg",))
def paged_decode_step(cfg: ModelConfig, params, pool_k, pool_v, tables,
                      lengths, tokens):
    """One decode step for B sequences.

    pool_k/pool_v: (L, nb, bs, Hkv, D); tables: (B, M) int32 (direct);
    lengths: (B,) int32 (tokens already in each sequence);
    tokens: (B, 1) int32. Returns (logits (B, V), new_pool_k, new_pool_v).
    """
    b = tokens.shape[0]
    bs = pool_k.shape[2]
    x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]      # (B,1,d)
    positions = lengths[:, None]                             # (B,1)

    blk = jnp.take_along_axis(tables, (lengths // bs)[:, None], axis=1)[:, 0]
    off = lengths % bs

    def body(x, inputs):
        p, pk, pv = inputs
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                             cfg.hd, positions, rope_theta=cfg.rope_theta,
                             use_rope=cfg.use_rope)
        pk = pk.at[blk, off].set(k[:, 0].astype(pk.dtype))
        pv = pv.at[blk, off].set(v[:, 0].astype(pv.dtype))
        attn = pa_ops.paged_attention(
            q[:, 0].astype(L.COMPUTE_DTYPE), pk, pv, tables, lengths + 1
        )
        x = x + attn.reshape(b, 1, -1).astype(x.dtype) @ p["attn"]["wo"].astype(x.dtype)
        h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            ff, _ = moe_lib.moe_apply(cfg, p["ff"], h2)
        else:
            ff = L.mlp_apply(p["ff"], h2, cfg.activation)
        return x + ff, (pk, pv)

    x, (pk, pv) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = (x[:, 0] @ output_matrix(cfg, params).astype(x.dtype)).astype(
        jnp.float32
    )
    return logits, pk, pv


@partial(jax.jit, static_argnames=("cfg",))
def paged_suffix_prefill(cfg: ModelConfig, params, pool_k, pool_v, tables,
                         slots_blk, slots_off, attn_lens, tokens):
    """Prefill S suffix tokens of ONE sequence whose first tokens already
    sit in the paged pool — the golden-fork admission step.

    A suffix chunk is ordinary causal prefill against a paged prefix:
    per layer, every suffix position's K/V is computed from the same
    input hidden states, scattered into its COW-prepared pool slot, and
    attention then runs the suffix positions as a *batch of S queries*
    over the shared block table with per-position lengths — position i
    sees the prefix plus suffix tokens ``<= i``, exactly causal. ONE
    device dispatch replaces S per-token decode steps.

    pool_k/pool_v: (L, nb, bs, Hkv, D); tables: (S, M) int32 (the
    sequence's table broadcast per position); slots_blk/slots_off: (S,)
    int32 pool slot of each suffix position (padded positions point at a
    reserved scratch block); attn_lens: (S,) int32 — prefix + i + 1 for
    real positions (1 for padded rows, whose outputs are discarded);
    tokens: (1, S) int32. Returns (logits (S, V), new_pool_k,
    new_pool_v) — the caller reads the last *real* row.
    """
    s = tokens.shape[1]
    x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]      # (1,S,d)
    positions = (attn_lens - 1)[None, :]                     # (1,S)

    def body(x, inputs):
        p, pk, pv = inputs
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                             cfg.hd, positions, rope_theta=cfg.rope_theta,
                             use_rope=cfg.use_rope)
        pk = pk.at[slots_blk, slots_off].set(k[0].astype(pk.dtype))
        pv = pv.at[slots_blk, slots_off].set(v[0].astype(pv.dtype))
        attn = pa_ops.paged_attention(
            q[0].astype(L.COMPUTE_DTYPE), pk, pv, tables, attn_lens
        )
        x = x + attn.reshape(1, s, -1).astype(x.dtype) @ p["attn"]["wo"].astype(x.dtype)
        h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            ff, _ = moe_lib.moe_apply(cfg, p["ff"], h2)
        else:
            ff = L.mlp_apply(p["ff"], h2, cfg.activation)
        return x + ff, (pk, pv)

    x, (pk, pv) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = (x[0] @ output_matrix(cfg, params).astype(x.dtype)).astype(
        jnp.float32
    )
    return logits, pk, pv


@partial(jax.jit, static_argnames=("cfg",))
def paged_decode_step_fused(cfg: ModelConfig, params, pool_k, pool_v, l2,
                            chain_lengths, tenants, lengths, write_blocks,
                            tokens):
    """One decode step reading K/V *through the stacked fleet index*.

    The fused counterpart of ``paged_decode_step``: no block tables are
    materialized anywhere — the attention plane receives the packed L2
    word0 stacks (``l2[..., 0]``), per-tenant ``chain_lengths`` and the
    batch's ``tenants`` mapping, and resolves each KV block by walking
    the chain in-grid (``kernels/paged_attention``). The in-step K/V
    scatter lands in ``write_blocks`` — the COW-prepared slots
    ``PagedKVCache.prepare_step_fused`` stamped into the index before
    this jit, so the walk resolves the write block too.

    Backend split (hot-path policy, ``docs/kernels.md``,
    ``pa_ops.fused_attention``): on TPU every layer runs the compiled
    fused kernel; elsewhere the composed jnp oracle — still zero
    host-side materialization, transfer or sync.

    pool_k/pool_v: (L, nb, bs, Hkv, D); l2: (T, C, P, 2) uint32;
    chain_lengths: (T,); tenants/lengths/write_blocks: (B,) int32;
    tokens: (B, 1) int32. Returns (logits (B, V), new_pool_k, new_pool_v).
    """
    b = tokens.shape[0]
    bs = pool_k.shape[2]
    x = params["embed"].astype(L.COMPUTE_DTYPE)[tokens]      # (B,1,d)
    positions = lengths[:, None]                             # (B,1)
    w0 = l2[..., 0]
    off = lengths % bs

    def body(x, inputs):
        p, pk, pv = inputs
        h = L.rmsnorm(x, p["ln1"], cfg.norm_eps)
        q, k, v = L.attn_qkv(p["attn"], h, cfg.n_heads, cfg.n_kv_heads,
                             cfg.hd, positions, rope_theta=cfg.rope_theta,
                             use_rope=cfg.use_rope)
        pk = pk.at[write_blocks, off].set(k[:, 0].astype(pk.dtype))
        pv = pv.at[write_blocks, off].set(v[:, 0].astype(pv.dtype))
        attn = pa_ops.fused_attention(q[:, 0].astype(L.COMPUTE_DTYPE), pk,
                                      pv, w0, chain_lengths, tenants,
                                      lengths + 1)
        x = x + attn.reshape(b, 1, -1).astype(x.dtype) @ p["attn"]["wo"].astype(x.dtype)
        h2 = L.rmsnorm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe:
            ff, _ = moe_lib.moe_apply(cfg, p["ff"], h2)
        else:
            ff = L.mlp_apply(p["ff"], h2, cfg.activation)
        return x + ff, (pk, pv)

    x, (pk, pv) = jax.lax.scan(body, x, (params["layers"], pool_k, pool_v))
    x = L.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = (x[:, 0] @ output_matrix(cfg, params).astype(x.dtype)).astype(
        jnp.float32
    )
    return logits, pk, pv
