"""Closed-loop page reads over a fleet whose disks sit at different chain
depths, held in depth classes (``repro.core.depth_classes``).

Set-up builds one dense fleet per depth in the configuration's
``depths`` (a class holds the disks of its depth, each chain exactly
that deep), each on the device in one jitted program from the
benchmark's record (``bench/configs/<config>.ref.py``) by the rocksdb
driver's ``synthesize``, deepest first, and assembles them. One client
per disk then keeps ``reads_per_dispatch_per_disk`` reads outstanding:
each dispatch is one ``depth_classes.read`` of every disk's batch, every
class in one program, issued when the previous one completed. Keys are
Zipfian over the disk's live clusters in the order the traffic names.

Reported: ``pages_per_s`` (cluster reads completed over the window's
seconds, to the last completion) and ``io_p99_ms``: each disk's batch in
each dispatch is one request, timed from its issue (drawing its keys
included) to ``block_until_ready``. Unlike ``fleet_closed_loop``, whose
requests run from the previous completion, the time the benchmark takes
to land a sampled read on the host lies between two requests: with ten
disks a sample is 10 MB and 1.4% of the dispatches are sampled, so
counted inside the next request it would set the 99th percentile. The
samples, drawn from the seed, are compared bit for bit with the record
once the window closes.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, work
from bench.drivers.fleet_closed_loop import Keys, _landed, synthesize
from repro.core import depth_classes
from repro.core import fleet as fleet_lib


def class_spec(cfg: dict, n_disks: int, depth: int) -> fleet_lib.FleetSpec:
    n_live = cfg["live_clusters_per_disk"]
    return fleet_lib.FleetSpec(
        n_tenants=n_disks, n_pages=cfg["l2_entries_per_layer"],
        page_size=cfg["payload_words"], max_chain=depth,
        pool_capacity=n_disks * n_live, lease_quantum=64,
        l2_per_table=cfg["l2_per_table"], slice_len=cfg["slice_len"])


def build_fleet(cfg: dict, record, ref) -> depth_classes.ClassedFleet:
    """Synthesize each depth's class from the record, deepest first (its
    synthesis needs the most room), and assemble them. A disk's pool
    rows hold its payloads under its own disk id."""
    n_live = cfg["live_clusters_per_disk"]
    depths = np.asarray(cfg["depths"])
    classes, disks = [], []
    for depth in sorted(set(cfg["depths"]), reverse=True):
        ids = np.flatnonzero(depths == depth)
        row = np.zeros((ids.size, cfg["l2_entries_per_layer"]), np.int32)
        for i, d in enumerate(ids):
            row[i, record.clusters[d]] = i * n_live + np.arange(n_live)
        fl = synthesize(class_spec(cfg, ids.size, int(depth)),
                        jnp.asarray(record.owner_map(ids)), jnp.asarray(row),
                        jnp.asarray(np.repeat(ids, n_live).astype(np.int32)),
                        jnp.asarray(record.clusters[ids].reshape(-1)),
                        jnp.asarray(record.scalable[ids]),
                        seed_words=ref.seed_words(record.seed))
        classes.append(jax.block_until_ready(fl))
        disks.append(ids.tolist())
    return depth_classes.assemble(classes, disks)


def run(ctx) -> harness.Outcome:
    cfg, tr = ctx.config, ctx.traffic
    n_disks = len(cfg["depths"])
    ref = ctx.cell.reference()
    record = ref.Record(cfg, ctx.seed, tr["key_order"])
    fl = build_fleet(cfg, record, ref)
    rng = np.random.default_rng([ctx.seed % (1 << 64), 11])
    keys = Keys(cfg["live_clusters_per_disk"], tr["zipf_theta"],
                tr["reads_per_dispatch_per_disk"], n_disks, rng)
    read = partial(depth_classes.read, method=tr["method"])

    def dispatch(ranks):
        ids = np.take_along_axis(record.clusters, ranks, axis=1)
        data, res = read(fl, jnp.asarray(ids))
        return jax.block_until_ready((data, res.found, res.owner))

    for _ in range(2):          # compile, then one warm call
        dispatch(keys.draw())

    pay = 4 * cfg["payload_words"]
    lat, drawn, kept = [], [], []
    pending = None      # the last sampled read, on its way to the host
    pages = 0
    with ctx.window() as win:
        while win.open():
            issued = time.perf_counter()
            ranks = keys.draw()
            check = not kept or (rng.random() < tr["checked_dispatch_share"]
                                 and len(kept) < tr["checked_dispatches_max"])
            with ctx.span("dispatch"):
                out = dispatch(ranks)
            last = time.perf_counter()
            lat.append(last - issued)
            pages += ranks.size
            drawn.append(ranks.astype(np.int32))
            if check:
                # land each sample on the host at the next one (as
                # fleet_closed_loop does), between requests: the landing
                # is the benchmark's work, not the next request's
                for a in out:
                    a.copy_to_host_async()
                if pending is not None:
                    kept.append(_landed(pending))
                pending = (ranks, out)
    if pending is not None:
        kept.append(_landed(pending))
    seconds = last - ctx.window_start
    peak = harness.memory_peak_bytes(jax.devices()[:ctx.cell.entry["chips"]])

    bad = checked = 0
    for ranks, (data, found, owner) in kept:
        bad += ref.mismatched_pages(record, ranks, data, found, owner)
        checked += ranks.size
    index_bytes = fl.index_bytes()
    del fl, kept
    med = float(np.median(lat))
    slow = sorted(lat)[-3:]
    requests = [x for x in lat for _ in range(n_disks)]
    return harness.Outcome(
        end_to_end=dict(
            pages_per_s=pages / seconds,
            io_p99_ms=1e3 * float(np.quantile(requests, 0.99)),
            setup_s=ctx.setup_s()),
        attempted=len(requests), failed=0,
        checks=[harness.Check("mismatched_pages", bad, 0)],
        memory_peak_bytes=peak,
        layer=dict(dispatches=len(lat), index_bytes=index_bytes,
                   needed_bytes=sum(
                       work.fleet_read_bytes(
                           np.take_along_axis(record.owners, r, axis=1),
                           record.depths, record.scalable, pay)
                       for r in drawn)),
        notes=[f"fleet: {len(lat)} dispatches of {n_disks} x "
               f"{tr['reads_per_dispatch_per_disk']} reads over classes "
               f"{sorted(set(cfg['depths']))} ({index_bytes} bytes of L2 "
               f"index) in {seconds:.3f} s; {checked} sampled pages "
               f"checked, {bad} mismatched; "
               f"{sum(x > 1.5 * med for x in lat)} dispatches over 1.5x the "
               f"median {1e3 * med:.2f} ms, slowest "
               f"{', '.join(f'{1e3 * x:.1f}' for x in slow)} ms"],
    )
