"""The traffic generator: seeded, replayable, salted per replay cycle."""

import numpy as np
import pytest

import loadgen
from bench_fixtures import MIX, GAMMA

BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("mix", [MIX, GAMMA], ids=["lognormal", "gamma"])
def test_same_seed_same_events(mix):
    a, b = loadgen.Stream(mix, 1024, BIG_SEED), loadgen.Stream(mix, 1024, BIG_SEED)
    for c in (0, 5, 40):
        x, y = a.chunk(c), b.chunk(c)
        for f in ("t_lo", "t_hi", "ids", "w", "rank"):
            np.testing.assert_array_equal(getattr(x, f), getattr(y, f))
    other = loadgen.Stream(mix, 1024, BIG_SEED + 1).chunk(0)
    assert not np.array_equal(other.ids, a.chunk(0).ids)


def test_replay_salts_ids_and_keeps_tenants_and_weights():
    s = loadgen.Stream(MIX, 1024, 7)
    p = s.pool_events
    first, again = s.events(0, 512), s.events(p, p + 512)
    np.testing.assert_array_equal(first.t_lo, again.t_lo)
    np.testing.assert_array_equal(first.w, again.w)
    salt = first.ids ^ again.ids
    assert len(np.unique(salt)) == 1 and salt[0] != 0
    # duplicates within a cycle stay duplicates after salting
    assert len(np.unique(first.ids)) == len(np.unique(again.ids))


def test_events_span_cycles_like_chunks():
    s = loadgen.Stream(MIX, 1024, 3)
    c = s.chunk_len
    span = s.events(s.pool_events - c, s.pool_events + c)
    joined = np.concatenate([s.chunk(s.pool_events // c - 1).ids, s.chunk(s.pool_events // c).ids])
    np.testing.assert_array_equal(span.ids, joined)


def test_zipf_skew_and_bursts():
    s = loadgen.Stream(MIX, 1024, 11)
    counts = np.bincount(s.rank, minlength=1024)
    assert counts[0] > 10 * np.median(counts[counts > 0])
    c = s.chunk(MIX["burst_every"] - 1)
    head = c.rank[: int(s.chunk_len * MIX["burst_frac"])]
    assert len(np.unique(head)) <= MIX["burst_tenants"]


def test_weight_is_a_function_of_the_pool_element():
    s = loadgen.Stream(MIX, 1024, 5)
    ev = s.events(0, s.pool_events)
    for i in np.unique(ev.ids)[:50]:
        assert len(np.unique(ev.w[ev.ids == i])) == 1
    assert ev.w.min() >= 40 and ev.w.max() <= 65535
