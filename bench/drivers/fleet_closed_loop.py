"""Closed-loop page reads over a deep-chain fleet.

Set-up builds the fleet on the device in one jitted program from the
benchmark's record (``bench/configs/<config>.ref.py``): every layer of
every chain, as a fleet grown through ``fleet.write``/``fleet.snapshot``
would hold it, without replaying the chain. One client per disk then
keeps ``reads_per_dispatch_per_disk`` reads outstanding: each dispatch is
one ``fleet.read`` of every disk's batch, issued when the previous one
completed. Keys are Zipfian over the disk's live clusters in the order
the traffic names.

Reported: ``pages_per_s`` (cluster reads completed over the window's
seconds) and ``io_p99_ms`` (each disk's batch in each dispatch is one
request, timed from its issue, the previous completion, to
``block_until_ready``). A sample of the dispatches, drawn from the seed,
is kept and compared bit for bit with the record once the window closes.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, work
from repro.core import fleet as fleet_lib
from repro.core import format as fmt


def fleet_spec(cfg: dict) -> fleet_lib.FleetSpec:
    t, n_live = cfg["tenants"], cfg["live_clusters_per_disk"]
    return fleet_lib.FleetSpec(
        n_tenants=t, n_pages=cfg["l2_entries_per_layer"],
        page_size=cfg["payload_words"], max_chain=cfg["chain_depth"],
        pool_capacity=t * n_live, lease_quantum=64,
        l2_per_table=cfg["l2_per_table"], slice_len=cfg["slice_len"])


def _fmix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def payload(seed_words, tenant, page, n_words: int):
    """On-device twin of the reference's ``payload_bits``, as float32."""
    s0, s1 = (jnp.uint32(w) for w in seed_words)
    t = tenant.astype(jnp.uint32)[:, None]
    p = page.astype(jnp.uint32)[:, None]
    j = jnp.arange(n_words, dtype=jnp.uint32)[None, :]
    h = (s0 ^ (t * jnp.uint32(0x9E3779B1)) ^ (p * jnp.uint32(0x85EBCA77))
         ^ (j * jnp.uint32(0xC2B2AE3D)) ^ (s1 * jnp.uint32(0x27D4EB2F)))
    bits = (_fmix32(h) >> 9) | jnp.uint32(0x3F800000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@partial(jax.jit, static_argnames=("spec", "seed_words"))
def synthesize(spec: fleet_lib.FleetSpec, owner, row, row_tenant, row_page,
               scalable, *, seed_words):
    """The fleet a write/snapshot replay would leave, built directly.

    ``owner``/``row``: (T, P) int32 owning layer (-1 = unwritten) and
    pool row of each cluster. Each cluster was written once, by its
    owner: a vanilla layer holds only its own writes; a scalable layer
    holds, by copy-forward, every cluster owned at or below it. Each entry
    carries its owner as backing-file index, marked valid on scalable
    images.
    """
    t, c, p = spec.n_tenants, spec.max_chain, spec.n_pages
    layer = jnp.arange(c, dtype=jnp.int32)[None, :, None]
    own = owner[:, None, :]
    scal = scalable[:, None, None]
    alloc = jnp.where(scal, (own >= 0) & (own <= layer), own == layer)
    w0 = jnp.where(alloc, jnp.uint32(fmt.FLAG_ALLOCATED)
                   | row[:, None, :].astype(jnp.uint32), jnp.uint32(0))
    # the writing layer's index rides in word 1 on both formats; only a
    # scalable image marks it valid (what ``fleet.write`` stamps)
    w1 = jnp.where(alloc, jnp.where(scal, jnp.uint32(fmt.FLAG_BFI_VALID),
                                    jnp.uint32(0)) | own.astype(jnp.uint32),
                   jnp.uint32(0))
    l2 = jnp.stack([w0, w1], axis=-1)
    l1 = alloc.reshape(t, c, spec.n_l1, spec.l2_per_table).any(axis=-1)
    nq = spec.n_quanta
    return fleet_lib.ChainFleet(
        spec=spec,
        l1=l1.astype(jnp.uint32),
        l2=l2,
        pool=payload(seed_words, row_tenant, row_page,
                     spec.page_size).astype(spec.dtype),
        lease_owner=jnp.full((nq,), -1, jnp.int32),
        lease_index=jnp.full((t, nq), -1, jnp.int32),
        lease_count=jnp.zeros((t,), jnp.int32),
        alloc_count=jnp.sum(owner >= 0, axis=1, dtype=jnp.int32),
        length=jnp.full((t,), c, jnp.int32),
        scalable=scalable,
        overflow=jnp.zeros((t,), bool),
        snap_dropped=jnp.zeros((t,), bool),
        cold_count=jnp.zeros((t,), jnp.int32),
    )


def build_fleet(cfg: dict, record, ref) -> fleet_lib.ChainFleet:
    """Upload the record's layout and synthesize the fleet from it."""
    spec = fleet_spec(cfg)
    n_live = cfg["live_clusters_per_disk"]
    owner = record.owner_map()
    row = np.zeros_like(owner)
    row_tenant = np.repeat(np.arange(spec.n_tenants, dtype=np.int32), n_live)
    row_page = record.clusters.reshape(-1)
    for i in range(spec.n_tenants):
        row[i, record.clusters[i]] = i * n_live + np.arange(n_live)
    return synthesize(spec, jnp.asarray(owner), jnp.asarray(row),
                      jnp.asarray(row_tenant), jnp.asarray(row_page),
                      jnp.asarray(record.scalable),
                      seed_words=ref.seed_words(record.seed))


def _landed(sample):
    ranks, out = sample
    return ranks, tuple(np.asarray(a) for a in out)


class Keys:
    """Zipfian ranks (YCSB's zipfian, theta from the traffic) per disk."""

    def __init__(self, n: int, theta: float, batch: int, tenants: int,
                 rng: np.random.Generator):
        w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** theta
        self.cdf = np.cumsum(w) / w.sum()
        self.n, self.batch, self.tenants, self.rng = n, batch, tenants, rng

    def draw(self) -> np.ndarray:
        u = self.rng.random((self.tenants, self.batch))
        return np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          self.n - 1).astype(np.int64)


def run(ctx) -> harness.Outcome:
    cfg, tr = ctx.config, ctx.traffic
    ref = ctx.cell.reference()
    record = ref.Record(cfg, ctx.seed, tr["key_order"])
    fl = jax.block_until_ready(build_fleet(cfg, record, ref))
    rng = np.random.default_rng([ctx.seed % (1 << 64), 11])
    keys = Keys(cfg["live_clusters_per_disk"], tr["zipf_theta"],
                tr["reads_per_dispatch_per_disk"], cfg["tenants"], rng)
    method = tr["method"]
    read = partial(fleet_lib.read, method=method)

    def dispatch(ranks):
        ids = np.take_along_axis(record.clusters, ranks, axis=1)
        data, res = read(fl, jnp.asarray(ids))
        return jax.block_until_ready((data, res.found, res.owner))

    for _ in range(2):          # compile, then one warm call
        dispatch(keys.draw())

    lengths = np.full(cfg["tenants"], cfg["chain_depth"])
    pay = 4 * cfg["payload_words"]
    lat, drawn, kept = [], [], []
    pending = None      # the last sampled read, on its way to the host
    pages = 0
    with ctx.window() as win:
        prev = time.perf_counter()
        while win.open():
            ranks = keys.draw()
            check = not kept or (rng.random() < tr["checked_dispatch_share"]
                                 and len(kept) < tr["checked_dispatches_max"])
            with ctx.span("dispatch"):
                out = dispatch(ranks)
            now = time.perf_counter()
            lat.append(now - prev)
            prev = now
            pages += ranks.size
            drawn.append(ranks.astype(np.int32))
            if check:
                # land each sample on the host at the next one, so that no
                # more than one sampled output stays on the nearly full
                # device between the read programs' temporaries
                for a in out:
                    a.copy_to_host_async()
                if pending is not None:
                    kept.append(_landed(pending))
                pending = (ranks, out)
        last = prev
    if pending is not None:
        kept.append(_landed(pending))
    seconds = last - ctx.window_start
    peak = harness.memory_peak_bytes(jax.devices()[:ctx.cell.entry["chips"]])

    bad = checked = 0
    for ranks, (data, found, owner) in kept:
        bad += ref.mismatched_pages(record, ranks, data, found, owner)
        checked += ranks.size
    del fl, kept
    med = float(np.median(lat))
    slow = sorted(lat)[-3:]
    requests = [x for x in lat for _ in range(cfg["tenants"])]
    return harness.Outcome(
        end_to_end=dict(
            pages_per_s=pages / seconds,
            io_p99_ms=1e3 * float(np.quantile(requests, 0.99)),
            setup_s=ctx.setup_s()),
        attempted=len(requests), failed=0,
        checks=[harness.Check("mismatched_pages", bad, 0)],
        memory_peak_bytes=peak,
        layer=dict(dispatches=len(lat), needed_bytes=sum(
            work.fleet_read_bytes(record.owners[r], lengths, record.scalable,
                                  pay) for r in drawn)),
        notes=[f"fleet: {len(lat)} dispatches of {cfg['tenants']} x "
               f"{tr['reads_per_dispatch_per_disk']} reads in {seconds:.3f} s;"
               f" {checked} sampled pages checked, {bad} mismatched; "
               f"{sum(x > 1.5 * med for x in lat)} dispatches over 1.5x the "
               f"median {1e3 * med:.2f} ms, slowest "
               f"{', '.join(f'{1e3 * x:.1f}' for x in slow)} ms"],
    )
