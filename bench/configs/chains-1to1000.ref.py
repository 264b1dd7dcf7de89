"""Plain reference of the ``chains-1to1000`` deployment: the benchmark's
own record of which layer of which disk's chain wrote which cluster with
what payload, and what a read of any cluster must return. Imports
nothing of the program.

Each disk has a chain of its own depth (``depths``). The layout of the
data is the same for every seed: a disk's live clusters are spread
evenly over its own layers (the rank ``i`` owned by layer ``i % depth``,
under one fixed permutation of the ranks for every disk). The seed
chooses which cluster ids hold the ranks, the payloads and the request
stream. So every seed asks the same work of a resolver whose cost
follows the owning layer.
"""

from __future__ import annotations

import numpy as np

#: Seed of the rank -> owning-layer layout, the same for every run.
LAYOUT_SEED = 20052206


def seed_words(seed: int) -> tuple[int, int]:
    s = int(seed) % (1 << 64)
    return s & 0xFFFFFFFF, s >> 32


def _fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def payload_bits(seed: int, tenant, page, n_words: int) -> np.ndarray:
    """The uint32 bit patterns of the payload of ``(disk, page)``:
    (..., n_words), each a float32 in [1, 2)."""
    s0, s1 = seed_words(seed)
    t = np.asarray(tenant, np.uint32)[..., None]
    p = np.asarray(page, np.uint32)[..., None]
    j = np.arange(n_words, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = (np.uint32(s0) ^ (t * np.uint32(0x9E3779B1))
             ^ (p * np.uint32(0x85EBCA77)) ^ (j * np.uint32(0xC2B2AE3D))
             ^ (np.uint32(s1) * np.uint32(0x27D4EB2F)))
        h = _fmix32(h)
    return (h >> np.uint32(9)) | np.uint32(0x3F800000)


def rank_owners(cfg: dict, key_order: str) -> np.ndarray:
    """(disks, L) owning layer of each disk's live cluster at each
    popularity rank (rank 0 is the hottest). Each layer of a disk owns
    ``live / depth`` of its clusters (to within one)."""
    if key_order != "seeded_permutation":
        raise ValueError(f"unknown key_order {key_order!r}")
    n_live = cfg["live_clusters_per_disk"]
    perm = np.random.default_rng(LAYOUT_SEED).permutation(n_live)
    ranks = np.arange(n_live, dtype=np.int32)
    return np.stack([(ranks % d)[perm] for d in cfg["depths"]])


class Record:
    """Which cluster each rank is, per disk, and who wrote it."""

    def __init__(self, cfg: dict, seed: int, key_order: str):
        t, p = len(cfg["depths"]), cfg["l2_entries_per_layer"]
        n_live = cfg["live_clusters_per_disk"]
        rng = np.random.default_rng([int(seed) % (1 << 64), 7])
        self.cfg = cfg
        self.seed = int(seed)
        self.depths = np.asarray(cfg["depths"], np.int32)
        self.owners = rank_owners(cfg, key_order)                 # (T, L)
        self.clusters = np.stack([rng.permutation(p)[:n_live]
                                  for _ in range(t)]).astype(np.int32)
        self.scalable = np.array([f == "scalable"
                                  for f in cfg["tenant_formats"]])

    def owner_map(self, disks) -> np.ndarray:
        """(len(disks), P) int32: owning layer of every cluster of each of
        ``disks``, -1 if unwritten."""
        out = np.full((len(disks), self.cfg["l2_entries_per_layer"]), -1,
                      np.int32)
        for i, d in enumerate(disks):
            out[i, self.clusters[d]] = self.owners[d]
        return out

    def expected(self, ranks: np.ndarray):
        """What a read of the clusters at ``ranks`` ((T, B)) returns:
        ``(ids, owner, payload bits)``, each page found."""
        ids = np.take_along_axis(self.clusters, ranks, axis=1)
        owner = np.take_along_axis(self.owners, ranks, axis=1)
        disk = np.broadcast_to(np.arange(ranks.shape[0])[:, None],
                               ranks.shape)
        bits = payload_bits(self.seed, disk, ids, self.cfg["payload_words"])
        return ids, owner, bits


def mismatched_pages(record: Record, ranks: np.ndarray, data: np.ndarray,
                     found: np.ndarray, owner: np.ndarray) -> int:
    """Pages of one read whose data, ``found`` or owner differ from the
    record: payload compared bit for bit."""
    _, want_owner, want_bits = record.expected(ranks)
    got = np.ascontiguousarray(data, np.float32).view(np.uint32)
    bad = (got != want_bits).any(axis=-1) | ~found.astype(bool)
    bad |= owner != want_owner
    return int(bad.sum())
