"""Scan planner: mode selection, sentinel retry, match enumeration, LRU.

The retry contract (-1 overflow / -2 saturated always re-executed through
an exact path) is tested here single-device with an injected faulty routed
executor, and again on a real 8-device mesh in test_distributed.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codec, query as Q
from repro.core.planner import (MODE_BROADCAST, MODE_ROUTED, MODE_SINGLE,
                                ScanPlanner)
from repro.core.query import MatchResult
from repro.core.tablet import build_tablet_store

TEXT_N = 20_000


@pytest.fixture(scope="module")
def store():
    return build_tablet_store(codec.random_dna(TEXT_N, seed=0), is_dna=True)


@pytest.fixture(scope="module")
def text_codes():
    return codec.random_dna(TEXT_N, seed=0).astype(np.int32)


def test_plan_single_device(store):
    planner = ScanPlanner(store)
    plan = planner.plan(4096)
    assert plan.mode == MODE_SINGLE
    assert planner.num_tablets == 1


def test_exact_counts_and_first_pos(store, text_codes):
    planner = ScanPlanner(store)
    pats = Q.random_patterns(48, 1, 12, seed=3)
    out = planner.scan(pats)
    for i, p in enumerate(pats):
        want, first = Q.brute_force_count(text_codes, codec.encode_dna(p))
        assert int(out.count[i]) == want, p
        assert bool(out.found[i]) == (want > 0)
        if want:
            fp = int(out.first_pos[i])
            assert (text_codes[fp:fp + len(p)]
                    == codec.encode_dna(p)).all()


def test_locate_round_trips_through_oracle(store, text_codes):
    """Every position returned by locate() is a genuine occurrence; when
    count <= top_k the returned set IS the brute-force set."""
    planner = ScanPlanner(store)
    pats = Q.random_patterns(32, 2, 10, seed=5)
    k = 16
    out = planner.scan(pats, top_k=k)
    for i, p in enumerate(pats):
        pc = codec.encode_dna(p).astype(np.int32)
        oracle = {j for j in range(TEXT_N - len(p) + 1)
                  if (text_codes[j:j + len(p)] == pc).all()}
        got = {int(x) for x in out.positions[i] if x >= 0}
        assert got <= oracle, p
        assert len(got) == min(len(oracle), k), p
        if len(oracle) <= k:
            assert got == oracle, p


def test_retry_restores_exact_counts(store, text_codes):
    """Inject a faulty routed executor that stamps -1/-2 sentinels; the
    planner must transparently re-execute those through the exact path."""
    planner = ScanPlanner(store)
    real = planner._executor(MODE_SINGLE)

    def faulty_routed(patt, plen):
        res = real(patt, plen)
        count = np.asarray(res.count).copy()
        rank = np.asarray(res.first_rank).copy()
        count[0::3] = -1          # dispatch overflow
        count[1::3] = -2          # saturated run
        rank[2::3] = -1           # exact count but unusable rank
        return MatchResult(found=jnp.asarray(count > 0),
                           count=jnp.asarray(count),
                           first_rank=jnp.asarray(rank),
                           first_pos=res.first_pos)

    planner._executors[MODE_ROUTED] = faulty_routed
    pats = Q.random_patterns(30, 1, 10, seed=9)
    _, pp, pl = Q.encode_patterns(pats, 112)
    ref = planner._executor(MODE_SINGLE)(pp, pl)
    res = planner.scan_encoded(pp, pl, mode=MODE_ROUTED)
    for i, p in enumerate(pats):
        want, _ = Q.brute_force_count(text_codes, codec.encode_dna(p))
        assert int(res.count[i]) == want, p
        assert int(res.first_rank[i]) == int(ref.first_rank[i]), p
    assert planner.stats.retried_overflow == 10
    assert planner.stats.retried_saturated == 10
    n_rank_bad = sum(1 for i in range(2, 30, 3)
                     if int(ref.count[i]) > 0)
    assert planner.stats.retried_inexact_rank == n_rank_bad
    # without retry the sentinels must survive untouched (bench contract)
    raw = planner.scan_encoded(pp, pl, mode=MODE_ROUTED, retry=False)
    assert (np.asarray(raw.count)[0::3] == -1).all()
    assert (np.asarray(raw.count)[1::3] == -2).all()


def test_lru_cache_hits_and_eviction(store):
    planner = ScanPlanner(store, cache_size=2)
    a, b, c = "ACGT", "GGT", "TTA"
    planner.scan([a]); planner.scan([b])
    assert planner.stats.cache_misses == 2
    planner.scan([a])                      # hit, refreshes a
    assert planner.stats.cache_hits == 1
    planner.scan([c])                      # evicts b (LRU)
    planner.scan([b])                      # miss again
    assert planner.stats.cache_misses == 4
    # cached result equals fresh result
    fresh = ScanPlanner(store, cache_size=0).scan([a])
    again = planner.scan([a])
    assert int(again.count[0]) == int(fresh.count[0])


def test_cache_is_topk_aware(store, text_codes):
    """One cache entry per pattern: a (pattern, top_k=8) entry serves any
    request with top_k <= 8 by slicing, and any top_k at all once the
    position set is complete (count <= k_stored) — no duplicate entries
    per (pattern, top_k) key."""
    planner = ScanPlanner(store)
    # a pattern with a healthy occurrence count
    p = "".join("ACGT"[c] for c in text_codes[100:103])
    full = planner.scan([p], top_k=8)
    n = int(full.count[0])
    assert n > 8, "fixture text too small for this test"
    assert planner.stats.cache_misses == 1
    # smaller top_k: served by slicing the k=8 entry
    out4 = planner.scan([p], top_k=4)
    assert planner.stats.cache_hits == 1
    assert (out4.positions[0] == full.positions[0][:4]).all()
    # count-only: also a hit
    out0 = planner.scan([p])
    assert planner.stats.cache_hits == 2
    assert int(out0.count[0]) == n
    # larger top_k than stored (and count > stored): honest miss,
    # entry upgraded in place
    out16 = planner.scan([p], top_k=16)
    assert planner.stats.cache_misses == 2
    assert (out16.positions[0][:8] == full.positions[0]).all()
    assert len(planner._cache) == 1
    # re-request smaller k after the upgrade: still a hit
    planner.scan([p], top_k=8)
    assert planner.stats.cache_hits == 3
    # a zero-count pattern is complete at any k: top_k request hits
    miss_pat = "ACGT" * 8                     # long pattern, almost surely 0
    if int(planner.scan([miss_pat]).count[0]) == 0:
        planner.scan([miss_pat], top_k=8)
        assert planner.stats.cache_hits == 4


def test_cached_batch_and_empty_batch(store):
    """A fully cache-served batch triggers the empty-encode path."""
    planner = ScanPlanner(store)
    pats = ["ACGTAC", "TGCA"]
    first = planner.scan(pats, top_k=4)
    second = planner.scan(pats, top_k=4)
    assert planner.stats.cache_hits == 2
    assert (first.count == second.count).all()
    assert (first.positions == second.positions).all()
    empty = planner.scan([])
    assert empty.count.shape == (0,)


def test_token_corpus_goes_through_planner():
    """Non-DNA stores use the generic code path (and must never route)."""
    rng = np.random.default_rng(0)
    corpus = rng.integers(0, 50_000, 3000).astype(np.int32)
    corpus[1000:1010] = corpus[2000:2010]
    store = build_tablet_store(corpus, is_dna=False)
    planner = ScanPlanner(store)
    w = jnp.asarray(corpus[2000:2010][None, :])
    res = planner.scan_encoded(w, jnp.asarray([10]))
    assert int(res.count[0]) == 2
    pos = planner.positions_from_result(res, top_k=4)
    assert sorted(int(x) for x in pos[0] if x >= 0) == [1000, 2000]


class _FourTablets:
    """Stands in for a 4-device tablet mesh: the planner reads only its
    shape to count tablets, and every executor here is injected."""
    shape = {"tablets": 4}
    axis_names = ("tablets",)


@pytest.fixture()
def mesh_planner():
    """A planner over a 4-tablet store whose broadcast and routed
    executors are the single-device search (routed stamps overflow
    sentinels on the rows listed in ``overflow``)."""
    store4 = build_tablet_store(codec.random_dna(TEXT_N, seed=0),
                                is_dna=True, num_tablets=4)
    planner = ScanPlanner(store4)
    planner.mesh = _FourTablets()
    real = planner._executor(MODE_SINGLE)
    planner.overflow = []

    def routed(patt, plen):
        res = real(patt, plen)
        count = np.asarray(res.count).copy()
        count[planner.overflow] = -1
        return MatchResult(found=jnp.asarray(count > 0),
                           count=jnp.asarray(count),
                           first_rank=res.first_rank,
                           first_pos=res.first_pos)

    planner._executors[MODE_BROADCAST] = real
    planner._executors[MODE_ROUTED] = routed
    return planner


def _batch(n, seed):
    _, pp, pl = Q.encode_patterns(Q.random_patterns(n, 1, 10, seed=seed),
                                  112)
    return pp, pl


def test_tablet_searches_of_a_broadcast_batch_are_b_times_p(mesh_planner):
    pp, pl = _batch(12, 21)
    assert mesh_planner.num_tablets == 4
    mesh_planner.scan_encoded(pp, pl)            # 12 < 64: broadcast
    assert mesh_planner.stats.mode_counts[MODE_BROADCAST] == 1
    assert mesh_planner.stats.tablet_searches == 12 * 4
    # bucket padding slots are not queries and are not counted
    mesh_planner.scan_encoded(pp, pl, n_real=9)
    assert mesh_planner.stats.tablet_searches == 12 * 4 + 9 * 4
    assert mesh_planner.stats.as_dict()["tablet_searches"] == 84


def test_a_bucketed_batch_is_planned_by_its_real_queries(mesh_planner):
    pp, pl = _batch(64, 24)
    mesh_planner.scan_encoded(pp, pl, n_real=40)   # 64 slots, 40 real
    mesh_planner.scan_encoded(pp, pl, n_real=64)
    assert mesh_planner.stats.mode_counts[MODE_BROADCAST] == 1
    assert mesh_planner.stats.mode_counts[MODE_ROUTED] == 1
    assert mesh_planner.stats.tablet_searches == 40 * 4 + 64


def test_tablet_searches_of_a_routed_batch_are_b(mesh_planner):
    pp, pl = _batch(64, 22)
    mesh_planner.scan_encoded(pp, pl)            # 64 >= 64: routed
    assert mesh_planner.stats.mode_counts[MODE_ROUTED] == 1
    assert mesh_planner.stats.retried_overflow == 0
    assert mesh_planner.stats.tablet_searches == 64


def test_tablet_searches_count_p_per_retried_row(mesh_planner):
    pp, pl = _batch(64, 23)
    mesh_planner.overflow = [0, 5, 17, 63]
    res = mesh_planner.scan_encoded(pp, pl, mode=MODE_ROUTED)
    assert (np.asarray(res.count) >= 0).all()
    assert mesh_planner.stats.retried_overflow == 4
    assert mesh_planner.stats.tablet_searches == 64 + 4 * 4
