"""Serving engine: continuous batching over the fleet-backed KV cache.

Request lifecycle: ``add_request(prompt)`` prefills through the model and
streams the K/V into the paged pool (one bulk fleet write, not a
per-token loop); ``fork_request`` COW-forks a sequence (shared system
prompts / beam candidates) — with the scalable cache this clones the
resolved tenant row forward (sQEMU snapshotting), with the vanilla cache
the fork becomes a new fleet tenant whose chain pays the walk on every
table materialization; ``step()`` decodes one token for every active
sequence through ``paged_decode_step``; ``finish_request`` releases a
sequence's blocks back to the pool (tombstoned while forks are live) and
retires its fleet tenant row (``fleet.free_tenant``).

``step()`` performs **zero per-sequence host-side chain walks**. Two
decode paths exist (``decode_path`` ctor arg, default ``"auto"``):

- ``"tables"`` — the COW-prepare mask and the attention block tables
  both come from ONE stacked fleet resolve (``PagedKVCache.prepare_step``)
  — the Pallas kernel plane on lane-aligned pools, the vmapped gather
  otherwise — and the stacked tables ship to the device in one transfer
  per step.
- ``"fused"`` — no padded block tables are materialized at all: a
  *narrow* resolve (``PagedKVCache.prepare_step_fused``, only the
  batch's write columns) stamps the COW slots, then
  ``paged_decode_step_fused`` reads K/V straight through the packed
  (T, C, P) fleet index — the chain walk happens inside the attention
  plane (``kernels/paged_attention``). Auto-selected iff the page axis
  is lane-aligned (``core.fleet.fused_layout_ok``); see
  ``docs/kernels.md`` for the cost model.

The engine can also drive a fleet maintenance plane: pass a
``core.scheduler.MaintenanceScheduler`` and each decode step ends with one
budgeted maintenance tick — background streaming/GC running *beside* the
serving path instead of stopping the world (paper §6.4).

Tiering: ``park_request`` pulls a sequence out of the decode batch and
spills its exclusively-owned KV blocks to host memory
(``PagedKVCache.demote_seq``), freeing device pool blocks for admissions;
``resume_request`` just re-activates it — promotion is *lazy*, paid by
the first ``step()`` whose batch includes the sequence (the decode path's
``prepare_step`` promotes before resolving tables). See
``docs/memory.md`` for the full residency lifecycle.

Golden prefixes: ``register_golden(prompt)`` prefills a prompt once and
freezes it as a shared base; an ``add_request`` whose prompt extends a
registered base (radix-trie probe on token ids) COW-forks the base and
prefills only the suffix — ONE chunked dispatch against the forked
paged prefix (``paged_suffix_prefill``) — so shared-prefix prefill
becomes a fork, costing zero fresh pool blocks and zero prefill FLOPs
for the shared span (``docs/architecture.md``).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import fleet as fleet_lib
from repro.core.golden import PrefixTrie
from repro.kvcache.paged import PagedKVCache, PagedKVConfig
from repro.models import layers as L
from repro.models.api import get_model
from repro.serve.paged_decode import (
    paged_decode_step,
    paged_decode_step_fused,
    paged_suffix_prefill,
)


def serving_params(params):
    """Serving weights: every floating leaf in ``COMPUTE_DTYPE`` (bf16).

    Every layer casts its weights to the compute dtype at use, so storing
    them in bf16 changes no result; it halves their bytes, and inside the
    decode step's layer scan there is no f32 stack for XLA to cast (and
    keep) whole."""
    return jax.tree.map(
        lambda a: (a.astype(L.COMPUTE_DTYPE)
                   if jnp.issubdtype(a.dtype, jnp.floating) else a),
        params)


def init_serving_params(cfg: ModelConfig, key):
    """Random serving weights from ``key``, built in bf16 inside one jit,
    so the float32 initialisation never lands whole on the device."""
    model = get_model(cfg)
    return jax.jit(lambda k: serving_params(model.init(k)))(key)


class Engine:
    def __init__(self, cfg: ModelConfig, params, *, scalable: bool = True,
                 n_blocks: int = 512, block_size: int = 16,
                 max_blocks_per_seq: int = 64, scheduler=None,
                 resolver: str = "auto", decode_path: str = "auto"):
        if cfg.family not in ("dense", "moe"):
            raise ValueError("paged serving engine supports attention LMs")
        if decode_path not in ("auto", "fused", "tables"):
            raise ValueError(f"unknown decode_path {decode_path!r}")
        if decode_path == "auto":
            decode_path = ("fused"
                           if fleet_lib.fused_layout_ok(max_blocks_per_seq)
                           else "tables")
        elif decode_path == "fused" and not fleet_lib.fused_layout_ok(
                max_blocks_per_seq):
            raise ValueError(
                "decode_path='fused' needs a lane-aligned page axis "
                f"(max_blocks_per_seq % 128 == 0, got {max_blocks_per_seq})"
            )
        self.decode_path = decode_path
        self.cfg = cfg
        self.params = serving_params(params)
        self.model = get_model(cfg)
        self.kv = PagedKVCache(
            PagedKVConfig(
                n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.hd, block_size=block_size, n_blocks=n_blocks,
                max_blocks_per_seq=max_blocks_per_seq,
                dtype=L.COMPUTE_DTYPE,
            ),
            scalable=scalable,
            resolver=resolver,
        )
        self.active: dict[int, list[int]] = {}  # sid -> generated tokens
        self.parked: dict[int, list[int]] = {}  # sid -> tokens, off-batch
        # prefill is jitted ONCE per engine: re-wrapping per request would
        # re-trace (and re-lower) the whole prefill on every admission
        self._jit_prefill = jax.jit(self.model.prefill)
        # golden-prefix registry: the admission-time dedup plane. The trie
        # maps registered prompt token ids -> golden sid; _golden_info
        # keeps each base's prompt (for trie removal) and its predicted
        # first token (an exact-match admission skips the model entirely).
        self._trie = PrefixTrie()
        self._golden_info: dict[int, tuple[tuple[int, ...], int]] = {}
        self.golden_hits = 0   # admissions served by forking a base
        # Scratch block absorbing the in-step pool writes of padded batch
        # rows, so a padded decode can never touch a live sequence's blocks.
        self._pad_block = self.kv.reserve_block()
        # Optional MaintenanceScheduler (core.scheduler) ticked between
        # decode steps — the background half of the serving loop.
        self.scheduler = scheduler
        self.last_maintenance: dict | None = None
        # (padded batch, vocab) f32 logits of the last decode dispatch,
        # left on the device: what a caller compares with a reference
        self.last_logits: jax.Array | None = None

    def _prefill_seq(self, prompt_tokens) -> tuple[int, int]:
        """Full-prompt prefill into a fresh sequence: one model prefill,
        one bulk KV append. Returns ``(sid, first_token)``."""
        toks = jnp.asarray(prompt_tokens, jnp.int32)[None]
        logits, cache = self._jit_prefill(self.params, dict(tokens=toks))
        sid = self.kv.new_seq()
        # cache k/v: (L, 1, S, Hkv, D) → (L, S, Hkv, D)
        self.kv.append_prefill(sid, cache["k"][:, 0], cache["v"][:, 0])
        return sid, int(jnp.argmax(logits[0]))

    def add_request(self, prompt_tokens: np.ndarray) -> int:
        """Admit a prompt; returns the sequence id.

        Admission probes the golden-prefix trie first: when a registered
        base's prompt is a prefix of this one, the base is COW-forked —
        the shared prefix contributes ZERO fresh pool blocks and zero
        prefill FLOPs — and only the suffix runs through one chunked
        suffix-prefill dispatch. An exact match skips the model entirely
        (the base's first token was recorded at registration). Without a
        trie hit this is the ordinary full prefill.
        """
        toks = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        with obs.span("engine.admit", tokens=len(toks)) as span:
            depth, gsid = self._trie.longest_prefix(toks)
            if gsid is not None:
                self.golden_hits += 1
                sid = self.kv.fork(gsid)
                span.set_metadata(sid=sid, path="golden")
                suffix = toks[depth:]
                nxt = (self._suffix_prefill(sid, suffix) if suffix
                       else self._golden_info[gsid][1])
                self.active[sid] = [nxt]
                return sid
            sid, first = self._prefill_seq(prompt_tokens)
            span.set_metadata(sid=sid, path="full")
            self.active[sid] = [first]
            return sid

    def _suffix_prefill(self, sid: int, tokens) -> int:
        """Push a prompt suffix through ONE chunked device dispatch
        against the sequence's paged prefix (``paged_suffix_prefill``)
        and return the first generated token. The chunk is padded to a
        power-of-two bucket — padded rows scatter into the reserved
        scratch block and their outputs are discarded — so admission
        compiles once per bucket, not once per suffix length."""
        s = len(tokens)
        pad = self._bucket(s)
        start = self.kv.seq_length(sid)
        table, blks, offs = self.kv.prepare_span(sid, s)
        fill = self._pad_block
        tbl = np.where(table >= 0, table, fill).astype(np.int32)
        tables = np.broadcast_to(tbl, (pad, tbl.size))
        sb = np.full(pad, fill, np.int32)
        sb[:s] = blks
        so = np.zeros(pad, np.int32)
        so[:s] = offs
        attn_lens = np.ones(pad, np.int32)
        attn_lens[:s] = start + 1 + np.arange(s)
        tok_row = np.zeros((1, pad), np.int32)
        tok_row[0, :s] = tokens
        logits, pk, pv = paged_suffix_prefill(
            self.cfg, self.params, self.kv.pool_k, self.kv.pool_v,
            jnp.asarray(tables), jnp.asarray(sb), jnp.asarray(so),
            jnp.asarray(attn_lens), jnp.asarray(tok_row),
        )
        self.kv.commit_pools(pk, pv)
        self.kv.advance_span(sid, s)
        return int(jnp.argmax(logits[s - 1]))

    def register_golden(self, prompt_tokens: np.ndarray) -> int:
        """Prefill a prompt and freeze it as a golden shared-prefix base.

        The base never joins the decode batch: it exists to be forked by
        later ``add_request`` admissions whose prompts extend its token
        ids. Its KV blocks are frozen device-resident
        (``PagedKVCache.register_golden``) until ``release_golden``.
        Returns the base's sid.
        """
        toks = [int(t) for t in np.asarray(prompt_tokens).reshape(-1)]
        sid, first = self._prefill_seq(prompt_tokens)
        self.kv.register_golden(sid)
        self._trie.insert(toks, sid)
        self._golden_info[sid] = (tuple(toks), first)
        return sid

    def release_golden(self, sid: int) -> None:
        """Retire a golden base: unregister it from the trie and the KV
        plane, then free it. Live forks keep their shared blocks through
        the usual refcounts (the base is tombstoned until the last fork
        frees)."""
        toks, _ = self._golden_info.pop(sid)
        self._trie.remove(list(toks))
        self.kv.release_golden(sid)
        self.kv.free_seq(sid)

    def fork_request(self, sid: int) -> int:
        child = self.kv.fork(sid)   # promotes a parked parent first
        tokens = self.active.get(sid) or self.parked.get(sid) or []
        self.active[child] = list(tokens)
        return child

    def finish_request(self, sid: int) -> None:
        """Retire a finished sequence and release its blocks to the pool.

        Safe with live forks: the cache tombstones the parent until the
        last descendant is freed (``PagedKVCache.free_seq``). Parked
        sequences may finish too — their host-tier spill is dropped with
        them, never promoted.
        """
        if sid in self.active:
            del self.active[sid]
        else:
            del self.parked[sid]
        self.kv.free_seq(sid)

    def park_request(self, sid: int) -> int:
        """Suspend a sequence: drop it from the decode batch and spill its
        exclusively-owned KV blocks to the host tier, freeing device pool
        blocks for other admissions. Shared blocks (live forks, common
        prefixes) stay hot and stay shared. Returns the number of blocks
        spilled (0 is fine — parking is always legal, spilling is
        best-effort)."""
        self.parked[sid] = self.active.pop(sid)
        return self.kv.demote_seq(sid)

    def resume_request(self, sid: int) -> None:
        """Re-activate a parked sequence. Promotion is deliberately NOT
        done here: the first ``step()`` including the sequence promotes
        it inside ``prepare_step``, so a resume costs nothing until the
        sequence actually decodes."""
        self.active[sid] = self.parked.pop(sid)

    def migrate_request_to(self, dst: "Engine", sid: int) -> int:
        """Live-migrate a sequence to another engine; returns its sid
        there.

        The sequence's resolved KV state is exported from this engine's
        cache, imported into ``dst`` as a fresh root (the fork topology
        stays behind — ancestors keep serving their own descendants
        here), bit-verified against the export, and only then retired on
        the source via ``finish_request`` — which tombstones/reaps
        exactly as a normal finish would, so migrating a forked child
        exercises the same cascade. A parked sequence migrates too (its
        host-tier spill is read, never promoted) and lands *active* on
        the destination. Raises ``RuntimeError`` — with the destination
        copy rolled back — if a decode step landed on the source
        mid-migration (stale export) or the landed bytes differ.
        """
        blob = self.kv.export_seq(sid)
        tokens = list(self.active.get(sid) or self.parked.get(sid) or [])
        new_sid = dst.kv.import_seq(blob)
        k, v = dst.kv.gather(new_sid)
        landed_ok = (
            np.asarray(k).view(np.uint8) == blob["k"].view(np.uint8)
        ).all() and (
            np.asarray(v).view(np.uint8) == blob["v"].view(np.uint8)
        ).all()
        stale = self.kv.seq_fingerprint(sid) != blob["fingerprint"]
        if stale or not landed_ok:
            dst.kv.free_seq(new_sid)
            raise RuntimeError(
                f"migration of sid {sid} aborted "
                + ("(source sequence changed mid-migration)" if stale
                   else "(destination KV not bit-identical)")
                + "; source left intact"
            )
        dst.active[new_sid] = tokens
        self.finish_request(sid)
        return new_sid

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power of two: the decode step is compiled once per bucket,
        not once per active-set size (fleet batching, no per-chain re-jit)."""
        b = 1
        while b < n:
            b *= 2
        return b

    def _decode(self, sids, last_tokens) -> dict[int, int]:
        """ONE fleet-batched decode dispatch: COW-prepare, attention,
        pool commit, advance — for ``sids`` feeding ``last_tokens``.
        Returns ``{sid: next_token}``. The device core of ``step()``,
        shared with golden suffix admission (``add_request`` on a trie
        hit), so both paths run the identical compiled step."""
        pad_to = self._bucket(len(sids))
        tok_col = np.zeros((pad_to, 1), np.int32)
        tok_col[: len(sids), 0] = last_tokens
        if self.decode_path == "fused":
            # No table materialization: the narrow COW-prepare resolve
            # stamps this step's write slots, then the decode step reads
            # K/V straight through the stacked fleet index (the chain
            # walk runs inside the attention plane).
            with obs.span("kv.prepare", rows=len(sids)):
                plan = self.kv.prepare_step_fused(
                    sids, pad_to=pad_to, pad_block=self._pad_block
                )
            logits, pk, pv = paged_decode_step_fused(
                self.cfg, self.params, self.kv.pool_k, self.kv.pool_v,
                plan.l2, plan.chain_lengths, plan.tenants, plan.lengths,
                plan.write_blocks, jnp.asarray(tok_col),
            )
        else:
            # ONE stacked fleet resolve serves both the COW-prepare mask
            # (the slots the decode step's in-place scatter will hit) and
            # the attention block tables; the sids→tenant-rows mapping
            # ships once. A lone sequence (suffix admission) takes the
            # narrow single-row resolve — O(C·P), not O(T·C·P) — so
            # admission latency stays flat as the fleet fills.
            with obs.span("kv.prepare", rows=len(sids)):
                if len(sids) == 1:
                    tables, lengths = self.kv.prepare_step_single(
                        sids[0], pad_to=pad_to, pad_block=self._pad_block
                    )
                else:
                    tables, lengths = self.kv.prepare_step(
                        sids, pad_to=pad_to, pad_block=self._pad_block
                    )
            logits, pk, pv = paged_decode_step(
                self.cfg, self.params, self.kv.pool_k, self.kv.pool_v,
                tables, lengths, jnp.asarray(tok_col),
            )
        with obs.span("kv.commit"):
            self.kv.commit_pools(pk, pv)
        self.last_logits = logits
        out = {}
        # the sampling boundary: greedy argmax must reach the host to
        # extend python-side sequences — the one designed sync in step()
        with obs.span("engine.sample", rows=len(sids)):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))  # fleetlint: disable=FL002
        for i, sid in enumerate(sids):
            self.kv.advance(sid)
            out[sid] = int(nxt[i])
        return out

    def step(self) -> dict[int, int]:
        """Decode one token for every active sequence — one fleet-batched
        device dispatch: stacked block tables, padded to a size bucket."""
        sids = sorted(self.active)
        with obs.span("engine.step", rows=len(sids),
                      padded_rows=self._bucket(len(sids)) if sids else 0):
            if not sids:
                # an idle engine is the cheapest time for background
                # work — keep draining the maintenance backlog while
                # polling
                self._maintain()
                return {}
            out = self._decode(sids, [self.active[s][-1] for s in sids])
            for sid, tok in out.items():
                self.active[sid].append(tok)
            self._maintain()
            return out

    def _maintain(self) -> None:
        """One budgeted maintenance slice between decode steps: stream/GC
        a few cold tenants instead of ever stopping the world."""
        if self.scheduler is not None:
            self.last_maintenance = self.scheduler.tick()

    def memory_stats(self) -> dict:
        stats = dict(
            blocks_in_use=self.kv.blocks_in_use(),
            host_blocks=self.kv.host_blocks_in_use(),
            lookups=self.kv.lookup_count,
            n_seqs=len(self.active),
            n_parked=len(self.parked),
            golden_hits=self.golden_hits,
            **self.kv.golden_stats(),
        )
        if self.scheduler is not None:
            stats["maintenance"] = self.scheduler.stats()
        return stats
