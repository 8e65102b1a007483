"""repro.api: SuffixTable lifecycle, the memtable write path, the catalog.

The load-bearing property: after any sequence of appends, merged reads
(count / first_pos / positions) exactly match a from-scratch
``build_tablet_store`` oracle over the concatenated text — including
patterns straddling the base/append boundary — before AND after
``compact()``, and again after ``open()`` in a fresh runtime.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    from _hypothesis_compat import given, settings, strategies as st

from repro.api import Catalog, SuffixTable
from repro.core import codec, query as Q
from repro.core.tablet import build_tablet_store
from repro.serving import HedgedScanService


def _oracle(codes: np.ndarray, pattern: str):
    """(count, first_pos=smallest position, all positions) by Algorithm 1."""
    cc = np.asarray(codes).astype(np.int32)
    pc = codec.encode_dna(pattern).astype(np.int32)
    k = len(pc)
    pos = [i for i in range(len(cc) - k + 1)
           if (cc[i:i + k] == pc).all()]
    return len(pos), (pos[0] if pos else -1), pos


def _check_vs_oracle(table, combined, patterns, top_k=16):
    out = table.scan(patterns, top_k=top_k)
    for i, p in enumerate(patterns):
        want, first, pos = _oracle(combined, p)
        assert int(out.count[i]) == want, (p, int(out.count[i]), want)
        assert bool(out.found[i]) == (want > 0)
        assert int(out.first_pos[i]) == first, (p, "first_pos")
        got = [int(x) for x in out.positions[i] if x >= 0]
        # text-order semantics: the top_k smallest positions, ascending —
        # the complete occurrence set whenever count <= top_k
        assert got == pos[:top_k], p


# ---------------------------------------------------------------------------
# persistence round trip + catalog
# ---------------------------------------------------------------------------
def test_create_open_round_trip(tmp_path):
    codes = codec.random_dna(4000, seed=0)
    pats = Q.random_patterns(24, 1, 10, seed=1)
    t = SuffixTable.create("dna", codes, root=str(tmp_path))
    assert t.version == 1 and t.is_persistent
    before = t.scan(pats, top_k=8)
    t2 = SuffixTable.open("dna", root=str(tmp_path))
    after = t2.scan(pats, top_k=8)
    assert (before.count == after.count).all()
    assert (before.first_pos == after.first_pos).all()
    assert (before.positions == after.positions).all()
    _check_vs_oracle(t2, codes, pats[:8])


def test_create_refuses_duplicates(tmp_path):
    codes = codec.random_dna(200, seed=0)
    SuffixTable.create("t", codes, root=str(tmp_path))
    with pytest.raises(FileExistsError):
        SuffixTable.create("t", codes, root=str(tmp_path))
    t = SuffixTable.create("t", codes[:100], root=str(tmp_path),
                           overwrite=True)
    assert t.n_base == 100
    with pytest.raises(FileNotFoundError):
        SuffixTable.open("nope", root=str(tmp_path))
    # a failed open must not litter the root with empty table dirs
    assert not (tmp_path / "nope").exists()
    for bad in ("bad/name", ".", "..", ".hidden", "catalog.json", ""):
        with pytest.raises(ValueError):
            SuffixTable.create(bad, codes, root=str(tmp_path))


def test_overwrite_drops_stale_snapshots(tmp_path):
    """Regression: overwrite=True used to leave the old table's higher-
    numbered snapshots in place, so open() restored the OLD data (or the
    keep_n GC deleted the fresh version-1 save)."""
    old = codec.random_dna(300, seed=1)
    t = SuffixTable.create("t", old, root=str(tmp_path))
    for i in range(4):                         # versions 2..5 (keep_n=3)
        t.append(codec.random_dna(50, seed=2 + i))
        t.compact()
    assert t.version == 5
    new = codec.random_dna(120, seed=9)
    SuffixTable.create("t", new, root=str(tmp_path), overwrite=True)
    t2 = SuffixTable.open("t", root=str(tmp_path))
    assert t2.version == 1 and t2.n_base == 120
    assert (np.asarray(t2.store.text_codes[:120])
            == new.astype(np.int32)).all()


def test_flush_raises_on_in_memory_table():
    t = SuffixTable.from_codes(codec.random_dna(100, seed=0))
    t.append("ACGT")
    with pytest.raises(RuntimeError, match="non-persistent"):
        t.flush()


def test_catalog_manages_mixed_tables(tmp_path):
    """DNA + token corpora as named tables in one root (METADATA analogue)."""
    cat = Catalog(str(tmp_path))
    cat.create_table("dna", codec.random_dna(500, seed=1), is_dna=True)
    tokens = np.random.default_rng(0).integers(0, 50_000, 600).astype(np.int32)
    cat.create_table("tokens", tokens, is_dna=False, max_query_len=32)
    assert cat.list_tables() == ["dna", "tokens"]
    assert "dna" in cat and "missing" not in cat
    assert cat.table_meta("tokens")["is_dna"] is False
    tok = cat.open_table("tokens")
    assert not tok.is_dna and tok.max_query_len == 32
    import jax.numpy as jnp
    res = tok.scan_encoded(jnp.asarray(tokens[100:108][None]),
                           jnp.asarray([8]))
    assert int(res.count[0]) >= 1
    cat.drop_table("dna")
    assert cat.list_tables() == ["tokens"]
    with pytest.raises(KeyError):
        cat.drop_table("dna")
    cat.drop_table("dna", missing_ok=True)


# ---------------------------------------------------------------------------
# the write path: append / merged reads / compact
# ---------------------------------------------------------------------------
def test_append_merged_reads_match_oracle_through_compact():
    base = codec.random_dna(3000, seed=2)
    t = SuffixTable.from_codes(base, is_dna=True)
    combined = base
    rng = np.random.default_rng(3)
    for step in range(3):                      # several appends stack up
        app = codec.random_dna(200 + 50 * step, seed=10 + step)
        t.append(app)
        n_before = len(combined)
        combined = np.concatenate([combined, app])
        # patterns: random, planted-in-append, straddling the boundary
        pats = Q.random_patterns(12, 1, 8, seed=20 + step)
        pats.append(codec.decode_dna(combined[n_before + 3:n_before + 11]))
        for off in (1, 4, 7):                  # straddle old end-of-text
            lo = n_before - off
            pats.append(codec.decode_dna(combined[lo:lo + off + 5]))
        short = int(rng.integers(1, 3))        # high-count short patterns
        pats.append(codec.decode_dna(combined[:short]))
        _check_vs_oracle(t, combined, pats)
    assert t.memtable.size == len(combined) - 3000
    # merged counts == a from-scratch store built over the same text
    patt, plen = t.planner.encode(pats)
    fresh = build_tablet_store(combined, is_dna=True)
    ref = Q.query(fresh, patt, plen)
    res = t.scan_encoded(patt, plen)
    assert (np.asarray(res.count) == np.asarray(ref.count)).all()
    v = t.compact()
    assert v == 1 and t.memtable.size == 0 and t.n_base == len(combined)
    _check_vs_oracle(t, combined, pats)
    res2 = t.scan_encoded(patt, plen)       # post-compact: base-only path
    assert (np.asarray(res2.count) == np.asarray(ref.count)).all()
    assert (np.asarray(res2.first_pos) == np.asarray(ref.first_pos)).all()


def test_append_beyond_paper_boundary_window_is_exact():
    """A pattern of exactly max_query_len straddling by one symbol is the
    overlap window's worst case; counts must stay exact."""
    base = codec.random_dna(600, seed=4)
    t = SuffixTable.from_codes(base, is_dna=True, max_query_len=32)
    app = codec.random_dna(100, seed=5)
    t.append(app)
    combined = np.concatenate([base, app])
    edge = [codec.decode_dna(combined[600 - 31:600 - 31 + 32]),   # 1 in new
            codec.decode_dna(combined[600 - 1:600 - 1 + 32]),     # 31 in new
            codec.decode_dna(combined[600 - 16:600 - 16 + 32])]
    _check_vs_oracle(t, combined, edge, top_k=4)


@given(st.integers(0, 10_000), st.integers(1, 3), st.integers(10, 200))
@settings(max_examples=4, deadline=None)
def test_append_property_counts_and_positions(seed, n_appends, chunk):
    """Property: append+query == brute-force oracle, any seed/shape."""
    rng = np.random.default_rng(seed)
    base = codec.random_dna(int(rng.integers(300, 900)), seed=seed)
    t = SuffixTable.from_codes(base, is_dna=True)
    combined = base
    for a in range(n_appends):
        app = codec.random_dna(chunk, seed=seed * 7 + a)
        t.append(app)
        combined = np.concatenate([combined, app])
    n_base = len(base)
    pats = Q.random_patterns(8, 1, 9, seed=seed + 1)
    pats.append(codec.decode_dna(combined[n_base - 2:n_base + 4]))
    out = t.scan(pats, top_k=8)
    for i, p in enumerate(pats):
        want, first, _pos = _oracle(combined, p)
        assert int(out.count[i]) == want, (p, int(out.count[i]), want)
        assert int(out.first_pos[i]) == first, p
        for q in out.positions[i]:
            if q >= 0:
                got = codec.decode_dna(combined[int(q):int(q) + len(p)])
                assert got == p


def test_flush_persists_memtable(tmp_path):
    base = codec.random_dna(800, seed=6)
    t = SuffixTable.create("t", base, root=str(tmp_path))
    t.append("GATTACAGATTACA")
    t.flush()                                  # durable without compaction
    t2 = SuffixTable.open("t", root=str(tmp_path))
    assert t2.version == 1 and t2.memtable.size == 14
    assert int(t2.count(["GATTACAGATTACA"])[0]) >= 1
    combined = np.concatenate([base, codec.encode_dna("GATTACAGATTACA")])
    _check_vs_oracle(t2, combined, ["GATTACA", "ACGT"])


def test_compact_bumps_version_and_reopens(tmp_path):
    base = codec.random_dna(700, seed=7)
    t = SuffixTable.create("t", base, root=str(tmp_path))
    t.append(codec.random_dna(300, seed=8))
    assert t.compact() == 2
    assert t.compact() == 2                    # empty memtable: no-op
    t2 = SuffixTable.open("t", root=str(tmp_path))
    assert t2.version == 2 and t2.n_base == 1000 and t2.memtable.size == 0


def test_memtable_limit_seals_runs_and_max_runs_majors():
    """``memtable_limit`` now triggers MINOR compaction (seal to an
    immutable run, base untouched); ``max_runs`` triggers the major fold."""
    t = SuffixTable.from_codes(codec.random_dna(500, seed=9), is_dna=True,
                               memtable_limit=100, max_runs=2)
    t.append(codec.random_dna(60, seed=1))
    assert t.memtable.size == 60 and t.version == 0 and not t.runs
    t.append(codec.random_dna(60, seed=2))     # crosses the limit: seal
    assert t.memtable.size == 0 and len(t.runs) == 1
    assert t.version == 0 and t.n_base == 500  # minor: base untouched
    assert len(t) == 620
    t.append(codec.random_dna(120, seed=3))    # second seal hits max_runs
    assert t.memtable.size == 0 and not t.runs
    assert t.version == 1 and t.n_base == 740  # major: folded into base


def test_token_table_append_and_encoded_reads():
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 50_000, 2000).astype(np.int32)
    t = SuffixTable.from_codes(tokens, is_dna=False, max_query_len=64)
    extra = rng.integers(0, 50_000, 300).astype(np.int32)
    t.append(extra)
    combined = np.concatenate([tokens, extra])
    import jax.numpy as jnp
    # window straddling the boundary + window inside the append
    for lo in (1995, 2100):
        w = combined[lo:lo + 10]
        res = t.scan_encoded(jnp.asarray(w[None]), jnp.asarray([10]))
        assert int(res.count[0]) >= 1, lo
        assert int(res.first_pos[0]) == lo
    with pytest.raises(TypeError):
        t.append("ACGT")                       # strings are DNA-only


def test_pattern_longer_than_cap_raises():
    t = SuffixTable.from_codes(codec.random_dna(400, seed=0), is_dna=True,
                               max_query_len=16)
    with pytest.raises(ValueError, match="max_pattern_len"):
        t.scan(["A" * 17])
    with pytest.raises(ValueError, match="max_pattern_len"):
        t.planner.scan(["A" * 17])
    # encoded path validates too (would otherwise silently truncate)
    import jax.numpy as jnp
    _, pp, pl = Q.encode_patterns(["A" * 17], 32)
    with pytest.raises(ValueError, match="max_pattern_len"):
        t.planner.scan_encoded(pp, pl)
    assert int(t.count(["A" * 16])[0]) >= 0    # at the cap: fine


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------
def test_hedged_service_accepts_table_and_store_shim():
    codes = codec.random_dna(5000, seed=1)
    table = SuffixTable.from_codes(codes, is_dna=True)
    svc_t = HedgedScanService(table, seed=3)
    store = build_tablet_store(codes, is_dna=True)
    svc_s = HedgedScanService(store, seed=3)   # deprecation shim
    assert svc_s.store is store and svc_t.store is table.store
    a = svc_t.run_workload(200, batch=100, seed=1)
    b = svc_s.run_workload(200, batch=100, seed=1)
    assert a["hit_rate"] == b["hit_rate"]
    assert a["mean_ms"] == b["mean_ms"]        # same rng stream, same seed


def test_hedged_service_rng_is_reproducible_not_mutating():
    """Regression: scan() used to mutate self.seed per call, so equal-value
    services diverged and the dataclass compared unequal to itself."""
    store = build_tablet_store(codec.random_dna(2000, seed=0), is_dna=True)
    s1 = HedgedScanService(store, seed=7)
    s2 = HedgedScanService(store, seed=7)
    r1 = s1.run_workload(300, batch=100, seed=2)
    r2 = s2.run_workload(300, batch=100, seed=2)
    assert r1 == r2                            # identical latency stream
    assert s1.seed == 7 and s2.seed == 7       # field never mutated
    # a service also sees appends through the table (merged serving reads)
    table = SuffixTable.from_codes(codec.random_dna(2000, seed=0))
    svc = HedgedScanService(table)
    probe = "GATTACA" * 3
    _, pp, pl = Q.encode_patterns([probe], 32)
    base_count = int(svc.scan(pp, pl, hedged=False)[0].count[0])
    table.append(probe)
    assert int(svc.scan(pp, pl, hedged=False)[0].count[0]) == base_count + 1


# ---------------------------------------------------------------------------
# elastic persistence: 1 <-> 8 device meshes (subprocess, weekly tier)
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_save_open_round_trip_across_device_counts(multidevice, tmp_path):
    """create on 1 device -> open/append/compact on an 8-tablet mesh ->
    open on 1 device again; counts stay oracle-exact throughout."""
    common = f"""
import json, numpy as np
from repro.api import SuffixTable
from repro.core import codec, query as Q
ROOT = r'{tmp_path}'
pats = Q.random_patterns(48, 1, 10, seed=3) + ['A', 'ACGT']
"""
    multidevice(common + """
codes = codec.random_dna(4096, seed=5)
t = SuffixTable.create('elastic', codes, root=ROOT)
out = t.scan(pats, top_k=8)
json.dump({'count': out.count.tolist(),
           'first': out.first_pos.tolist()},
          open(ROOT + '/expect.json', 'w'))
print('OK')
""", n_devices=1)
    multidevice(common + """
t = SuffixTable.open('elastic', root=ROOT)
assert t.planner.num_tablets == 8 and t.mesh is not None
want = json.load(open(ROOT + '/expect.json'))
out = t.scan(pats, top_k=8)
assert out.count.tolist() == want['count']
assert out.first_pos.tolist() == want['first']
# big encoded batch takes the routed path on the mesh; still exact
patt, plen = t.planner.encode(pats * 4)
assert t.planner.plan(len(pats) * 4).mode == 'routed'
res = t.scan_encoded(patt, plen)
assert np.asarray(res.count).tolist() == want['count'] * 4
app = codec.random_dna(512, seed=6)
t.append(app)
t.compact()                       # distributed rebuild + persist v2
combined = np.concatenate([codec.random_dna(4096, seed=5), app])
cc = combined.astype(np.int32)
out3 = t.scan(pats)
for i, p in enumerate(pats):
    want_c, _ = Q.brute_force_count(cc, codec.encode_dna(p).astype(np.int32))
    assert int(out3.count[i]) == want_c, p
json.dump({'count': out3.count.tolist()}, open(ROOT + '/expect2.json', 'w'))
print('OK')
""", n_devices=8)
    multidevice(common + """
t = SuffixTable.open('elastic', root=ROOT)
assert t.version == 2 and t.planner.num_tablets == 1
want = json.load(open(ROOT + '/expect2.json'))
assert t.scan(pats).count.tolist() == want['count']
print('OK')
""", n_devices=1)


@pytest.mark.parametrize("n,block,k", [(1, 4, 3), (100, 8, 1),
                                       (5000, 64, 8), (5000, 7, 20)])
def test_rangemin_matches_brute_force(n, block, k):
    """Block-minimum range reads (the base tier's text-order first_pos and
    top-k) equal a full gather of every row in the range."""
    from repro.core.rangemin import block_minima, range_min, range_smallest
    rng = np.random.default_rng(n + block)
    vals = rng.permutation(n).astype(np.int32)
    bmin = block_minima(vals, block)
    lo = rng.integers(0, n, size=60)
    count = np.minimum(rng.integers(0, n + 1, size=60), n - lo)
    count[:3] = 0
    got = range_min(vals.__getitem__, bmin, lo, count, block)
    for i in range(lo.size):
        seg = vals[lo[i]:lo[i] + count[i]]
        assert got[i] == (seg.min() if seg.size else -1)
        np.testing.assert_array_equal(
            range_smallest(vals.__getitem__, bmin, lo[i], count[i], k,
                           block),
            np.sort(seg)[:k])


@pytest.mark.parametrize("append", [False, True])
def test_frozen_first_pos_matches_live_and_counts_device_settled(append):
    """A frozen table's text-order ``first_pos`` — settled in the FM
    launch or, past its lane budget, by host LF walks — equals the live
    table's, also with delta tiers after an append; the counter sees
    real patterns only."""
    codes = codec.random_dna(2500, seed=21)
    live = SuffixTable.from_codes(codes, is_dna=True)
    froz = SuffixTable.from_codes(codes, is_dna=True)
    froz.freeze()
    text = codec.decode_dna(codes)
    if append:
        extra = text[100:140] + "GATTACA" * 3
        live.append(extra)
        froz.append(extra)
        text += extra
    rng = np.random.default_rng(5)
    # 20-mers of the text occur once: 5 real rows in a bucket of 8
    once = [text[s:s + 20] for s in rng.integers(0, 2000, 5)]
    heavy = ["A", "C", "G", "T", "AC"]          # past the 3 x 8 lanes
    for pats in (once, heavy, once + heavy + ["GATTACA", "CA"]):
        a, b = live.scan(pats), froz.scan(pats)
        assert np.array_equal(a.count, b.count)
        assert np.array_equal(a.first_pos, b.first_pos)
        assert [int(x) for x in b.first_pos] == [text.find(p) for p in pats]
    froz.clear_cache()
    froz.planner.reset_stats()
    patt, plen = froz.planner.encode(once)
    froz.scan_batch(patt, plen)
    assert froz.planner.stats.device_min_settled == len(once)
    froz.planner.reset_stats()
    patt, plen = froz.planner.encode(heavy)
    froz.scan_batch(patt, plen)
    assert froz.planner.stats.device_min_settled < len(heavy)
    assert live.planner.stats.device_min_settled == 0
