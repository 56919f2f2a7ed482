"""The one traffic generator. A mix (``traffic/<mix>.json``) is a set of
parameters for it; the seed and the tenant count come from the run.

A pool of ``pool_events`` events is built once, vectorised, in set-up:

* tenant popularity ranks are Zipf(``zipf_s``) over the K ranks; every
  ``burst_every``-th arrival chunk is a flash crowd in which ``burst_frac``
  of the events go to ``burst_tenants`` random ranks;
* ranks become sparse 64-bit tenant ids through a seeded bijection
  (split into uint32 lo/hi words, as the key directory takes them);
* element ids index an id pool of ``id_pool_frac`` x pool_events random
  uint32 ids, so duplicates are common and dedup does real work; the weight
  belongs to the pool entry (weight is a function of the element):
  lognormal flow bytes clipped to a range, or gamma;

The window replays the pool for as long as it lasts. Replay cycle c xors
every element id with a 32-bit salt hashed from (seed, c): the ids within a
cycle keep their duplicates, while each cycle brings new distinct elements,
so dedup and register work stay the same over the whole window. Events are
handed out in ``chunk``-event arrival chunks (the host arrival granularity).
"""

from __future__ import annotations

import dataclasses

import numpy as np

M64 = (1 << 64) - 1


def splitmix64(x: np.ndarray) -> np.ndarray:
    """The splitmix64 finalizer over uint64 (a bijection)."""
    x = x.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def cycle_salt(seed: int, cycle: int) -> np.uint32:
    """32-bit salt of replay cycle ``cycle``."""
    x = np.array([((seed * 0xD1B54A32D192ED03) ^ (cycle * 0x9E3779B97F4A7C15)) & M64], np.uint64)
    return np.uint32(int(splitmix64(x)[0]) & 0xFFFFFFFF)


@dataclasses.dataclass
class Chunk:
    t_lo: np.ndarray  # uint32 tenant id, low word
    t_hi: np.ndarray  # uint32 tenant id, high word
    ids: np.ndarray  # uint32 element ids
    w: np.ndarray  # float32 weights
    rank: np.ndarray  # int32 tenant popularity rank (for the reference)


class Stream:
    """Seeded replayable event stream of one mix over ``n_ranks`` tenants."""

    def __init__(self, mix: dict, n_ranks: int, seed: int):
        self.mix, self.n_ranks, self.seed = mix, int(n_ranks), int(seed)
        self.chunk_len = int(mix["chunk"])
        p = int(mix["pool_events"])
        if p % self.chunk_len:
            raise ValueError("pool_events must be a multiple of chunk")
        self.pool_events = p
        rng = np.random.default_rng(self.seed)
        pz = 1.0 / np.arange(1, self.n_ranks + 1, dtype=np.float64) ** float(mix["zipf_s"])
        cdf = np.cumsum(pz / pz.sum())
        rank = np.minimum(np.searchsorted(cdf, rng.random(p)), self.n_ranks - 1)
        every = int(mix.get("burst_every", 0))
        if every:
            n_chunks = p // self.chunk_len
            bursts = np.arange(every - 1, n_chunks, every)
            nb = int(self.chunk_len * float(mix["burst_frac"]))
            hot = rng.integers(0, self.n_ranks, (len(bursts), int(mix["burst_tenants"])))
            pick = rng.integers(0, hot.shape[1], (len(bursts), nb))
            pos = bursts[:, None] * self.chunk_len + np.arange(nb)[None, :]
            rank[pos] = np.take_along_axis(hot, pick, axis=1)
        self.rank = rank.astype(np.int32)
        n_ids = max(int(p * float(mix["id_pool_frac"])), 16)
        self.id_pool = rng.integers(0, 2**32, n_ids, dtype=np.uint32)
        self.w_pool = _weights(mix["weights"], rng, n_ids)
        self.id_idx = rng.integers(0, n_ids, p).astype(np.int32)
        tid = self.tenant_ids(self.rank)
        self.t_lo = (tid & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        self.t_hi = (tid >> np.uint64(32)).astype(np.uint32)

    def tenant_ids(self, ranks: np.ndarray) -> np.ndarray:
        """uint64 tenant id of each rank (a seeded bijection)."""
        key = np.uint64((self.seed * 0x632BE59BD9B4E019) & M64)
        return splitmix64(np.asarray(ranks).astype(np.uint64) ^ key)

    def chunk(self, c: int) -> Chunk:
        """Arrival chunk ``c`` (events c*chunk .. (c+1)*chunk-1)."""
        return self.events(c * self.chunk_len, (c + 1) * self.chunk_len)

    def events(self, start: int, stop: int) -> Chunk:
        """Events [start, stop) of the replayed stream."""
        idx = np.arange(start, stop, dtype=np.int64)
        pos = idx % self.pool_events
        cyc = idx // self.pool_events
        salts = np.array([cycle_salt(self.seed, c) for c in range(int(cyc[0]), int(cyc[-1]) + 1)],
                         np.uint32) if len(idx) else np.zeros(0, np.uint32)
        k = self.id_idx[pos]
        ids = self.id_pool[k] ^ salts[cyc - (cyc[0] if len(idx) else 0)]
        return Chunk(self.t_lo[pos], self.t_hi[pos], ids, self.w_pool[k], self.rank[pos])


def _weights(spec: dict, rng, n: int) -> np.ndarray:
    model = spec["model"]
    if model == "lognormal":
        lo, hi = spec["clip"]
        w = np.clip(rng.lognormal(float(spec["mu"]), float(spec["sigma"]), n), lo, hi)
    elif model == "gamma":
        w = rng.gamma(float(spec["shape"]), float(spec["scale"]), n) + float(spec.get("offset", 0.0))
    else:
        raise ValueError(f"unknown weight model {model!r}")
    return w.astype(np.float32)
