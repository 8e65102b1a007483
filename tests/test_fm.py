"""repro.api.fm: the frozen FM-index tier vs its live SA twin.

The load-bearing property: a frozen table is **bit-identical** to a live
twin built over the same text on every read — count / found /
first_rank / first_pos / positions — over random DNA and small-vocab
token corpora, through freeze -> append -> minor_compact -> compact
schedules (frozen is sticky across major compaction), and across a
save/open round trip on a different device count.
"""
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    from _hypothesis_compat import given, settings, strategies as st

from repro.api import Catalog, Database, Query, SuffixTable
from repro.api.fm import FMIndex, MAX_VOCAB, sa_is_fully_sorted
from repro.core import codec, query as Q


PATS = ["A", "ACGT", "GATTACA", "TTTT", "CCGG", "A" * 24, "ACGT" * 6]


def _twins(codes, **kw):
    """(live, frozen) tables over the same text."""
    live = SuffixTable.from_codes(codes, is_dna=True, **kw)
    froz = SuffixTable.from_codes(codes, is_dna=True, **kw)
    froz.freeze()
    return live, froz


def _assert_reads_identical(live, froz, pats, top_k=5):
    a, b = live.scan(pats, top_k=top_k), froz.scan(pats, top_k=top_k)
    assert np.array_equal(a.found, b.found)
    assert np.array_equal(a.count, b.count)
    assert np.array_equal(a.first_pos, b.first_pos)
    assert np.array_equal(a.positions, b.positions)
    assert np.array_equal(live.locate(pats, top_k=top_k),
                          froz.locate(pats, top_k=top_k))


# ---------------------------------------------------------------------------
# bit-identity: frozen vs live, random DNA (property test)
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(st.sampled_from([3, 33, 256, 701]), st.integers(0, 2**16))
def test_fm_dna_bit_identical_to_sa_path(n, seed):
    codes = codec.random_dna(n, seed=seed)
    live, froz = _twins(codes)
    assert froz.is_frozen and not live.is_frozen
    # planted substrings guarantee hits; PATS mixes hits and misses
    text = codec.decode_dna(codes)
    rng = np.random.default_rng(seed)
    pats = [p for p in PATS if len(p) <= n]
    for _ in range(3):
        lo = int(rng.integers(0, n))
        pats.append(text[lo:lo + int(rng.integers(1, 12))])
    _assert_reads_identical(live, froz, pats)
    # base-path identity below the merged layer too: found / count /
    # first_rank (the planner's suffix-rank contract) must agree exactly
    patt, plen = live.planner.encode(pats)
    ra = live.planner.scan_encoded(patt, plen)
    rb = froz.planner.scan_encoded(patt, plen)
    for f in ("found", "count", "first_rank", "first_pos"):
        assert np.array_equal(np.asarray(getattr(ra, f)),
                              np.asarray(getattr(rb, f))), f


# ---------------------------------------------------------------------------
# bit-identity: small-vocab token corpora (encoded-batch API — the string
# encoder is DNA-only)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vocab", [2, 5, 40])
def test_fm_token_corpus_bit_identical(vocab):
    rng = np.random.default_rng(vocab)
    tokens = rng.integers(0, vocab, 1200).astype(np.int32)
    live = SuffixTable.from_codes(tokens, is_dna=False, max_query_len=32)
    froz = SuffixTable.from_codes(tokens, is_dna=False, max_query_len=32)
    froz.freeze()
    # windows of the text (hits) + random junk (mostly misses) + one
    # pattern with an out-of-vocab symbol (must report zero, not garbage)
    W = 8
    patt = np.zeros((10, W), np.int32)
    plen = np.zeros((10,), np.int32)
    for i in range(8):
        lo = int(rng.integers(0, 1200 - W))
        k = int(rng.integers(1, W + 1))
        patt[i, :k] = tokens[lo:lo + k]
        plen[i] = k
    patt[8, :4] = rng.integers(0, vocab, 4)
    plen[8] = 4
    patt[9, :2] = [vocab + 7, 0]
    plen[9] = 2
    a = live.scan_batch(patt, plen, top_k=4)
    b = froz.scan_batch(patt, plen, top_k=4)
    assert np.array_equal(a.count, b.count)
    assert np.array_equal(a.first_pos, b.first_pos)
    assert np.array_equal(a.positions, b.positions)
    assert int(b.count[9]) == 0                 # out-of-vocab symbol


# ---------------------------------------------------------------------------
# lifecycle: freeze -> append -> minor_compact -> compact stays identical,
# and frozen is sticky across major compaction
# ---------------------------------------------------------------------------
def test_freeze_append_compact_schedule():
    codes = codec.random_dna(2000, seed=4)
    live, froz = _twins(codes)
    extra = "GATTACA" * 2 + codec.decode_dna(codec.random_dna(400, seed=5))
    live.append(extra)
    froz.append(extra)                          # boundary-straddling reads
    _assert_reads_identical(live, froz, PATS)
    live.minor_compact()
    froz.minor_compact()                        # sealed-run tier
    _assert_reads_identical(live, froz, PATS)
    v = froz.compact()
    live.compact()
    assert v == froz.version and froz.is_frozen, \
        "frozen is a sticky tier state across major compaction"
    assert froz.stats()["tiers"]["resident_bytes"]["base_sa"] == 0
    _assert_reads_identical(live, froz, PATS)


def test_freeze_adversarial_repeats_counts_exact():
    """Repetitive text exercises the deepest backward-search intervals
    AND the full-order validity check: after a merge-fold compaction the
    stored SA order is only exact to the compare depth, so freeze() must
    detect that and re-derive a true suffix array before taking the BWT.
    """
    codes = codec.encode_dna("ACGT" * 120 + "A" * 160 + "ACGT" * 40)
    t = SuffixTable.from_codes(codes, is_dna=True, max_query_len=64)
    t.append("A" * 90 + "ACGTACGT")
    t.compact()                                 # merge-fold (depth-capped)
    t.freeze()
    cc = np.concatenate([codes, codec.encode_dna("A" * 90 + "ACGTACGT")])
    for p in ["A" * 40, "ACGT" * 10, "AAACGT", "T", "CA"]:
        want, _ = Q.brute_force_count(cc.astype(np.int32),
                                      codec.encode_dna(p).astype(np.int32))
        assert int(t.count([p])[0]) == want, p


def test_sa_is_fully_sorted_detects_depth_capped_order():
    codes = codec.encode_dna("A" * 64)
    n = codes.size
    true_sa = np.arange(n - 1, -1, -1).astype(np.int64)  # shortest-first
    assert sa_is_fully_sorted(codes, true_sa)
    assert not sa_is_fully_sorted(codes, true_sa[::-1].copy())
    assert not sa_is_fully_sorted(codes, np.zeros(n, np.int64))  # not a perm


# ---------------------------------------------------------------------------
# memory + policy
# ---------------------------------------------------------------------------
def test_frozen_resident_bytes_under_quarter_of_sa():
    codes = codec.random_dna(20_000, seed=6)
    live, froz = _twins(codes)
    la = live.stats()["tiers"]
    fa = froz.stats()["tiers"]
    assert la["frozen"] is False and fa["frozen"] is True
    assert fa["resident_bytes"]["base_sa"] == 0
    assert 0 < fa["resident_bytes"]["fm"] <= la["resident_bytes"]["base_sa"] / 4
    for k in ("base_sa", "fm", "text_device", "runs", "memtable",
              "text_host"):
        assert k in fa["resident_bytes"]


def test_fm_threshold_policy_and_vocab_cap():
    # below threshold: stays live; crossing it via compact(): freezes
    t = SuffixTable.from_codes(codec.random_dna(500, seed=7), is_dna=True,
                               fm_threshold=600)
    assert not t.is_frozen
    t.append(codec.decode_dna(codec.random_dna(200, seed=8)))
    assert not t.is_frozen                      # memtable doesn't count
    t.compact()
    assert t.is_frozen                          # base grew past threshold
    # the policy is a no-op on a big-vocab token table...
    big = np.random.default_rng(0).integers(0, 50_000, 300).astype(np.int32)
    tb = SuffixTable.from_codes(big, is_dna=False, max_query_len=16,
                                fm_threshold=10)
    assert not tb.is_frozen
    # ...but an explicit freeze() states why it can't
    with pytest.raises(ValueError, match="vocab"):
        tb.freeze()
    with pytest.raises(ValueError, match="vocab"):
        FMIndex.build(np.arange(MAX_VOCAB + 1, dtype=np.int32), None,
                      is_dna=False)


def test_database_freeze_passthrough():
    db = Database(None)
    db.attach("x", SuffixTable.from_codes(codec.random_dna(1500, seed=9),
                                          is_dna=True))
    tiers = db.freeze("x")
    assert tiers["frozen"] and tiers["resident_bytes"]["fm"] > 0
    ref = SuffixTable.from_codes(codec.random_dna(1500, seed=9), is_dna=True)
    out = db.query(Query.scan("x", PATS, top_k=3))
    want = ref.scan(PATS, top_k=3)
    assert np.array_equal(np.asarray(out.count), want.count)
    assert np.array_equal(np.asarray(out.positions), want.positions)
    db.close()


# ---------------------------------------------------------------------------
# persistence: auto-freeze at create, reopen, artifact lifecycle
# ---------------------------------------------------------------------------
def test_persistent_freeze_reopen_and_drop(tmp_path):
    cat = Catalog(str(tmp_path))
    codes = codec.random_dna(3000, seed=10)
    t = cat.create_table("frz", codes, fm_threshold=1000)
    assert t.is_frozen and os.path.isdir(cat.fm_dir("frz"))
    t.append("GATTACA" * 3)
    t.flush()
    want = t.scan(PATS, top_k=4)
    t.close()
    t2 = cat.open_table("frz")                  # artifact reload, no rebuild
    assert t2.is_frozen
    got = t2.scan(PATS, top_k=4)
    assert np.array_equal(got.count, want.count)
    assert np.array_equal(got.positions, want.positions)
    t2.close()
    # drop removes the per-table auxiliary dirs (fm/, wal/) with the table
    cat.drop_table("frz")
    assert not os.path.isdir(os.path.join(str(tmp_path), "frz"))
    # orphan-dir reconcile: an unregistered name whose dir survived a
    # crashed create/drop (holding a frozen artifact) is removed too
    orphan_fm = cat.fm_dir("ghost")
    os.makedirs(orphan_fm)
    with open(os.path.join(orphan_fm, "junk.bin"), "wb") as f:
        f.write(b"x")
    assert "ghost" not in cat
    cat.drop_table("ghost")
    assert not os.path.isdir(os.path.join(str(tmp_path), "ghost"))
    with pytest.raises(KeyError):
        cat.drop_table("ghost")                 # now truly absent


def test_corrupt_fm_artifact_falls_back_to_rebuild(tmp_path):
    cat = Catalog(str(tmp_path))
    t = cat.create_table("rb", codec.random_dna(1200, seed=11),
                         fm_threshold=100)
    want = t.count(PATS)
    t.close()
    import shutil
    shutil.rmtree(cat.fm_dir("rb"))             # artifact lost, not the table
    t2 = cat.open_table("rb")
    assert t2.is_frozen                         # rebuilt from saved codes
    assert np.array_equal(t2.count(PATS), want)
    t2.close()


# ---------------------------------------------------------------------------
# elastic open: frozen artifact round-trips onto a different device count
# (subprocess, weekly tier)
# ---------------------------------------------------------------------------
@pytest.mark.multidevice
def test_frozen_open_across_device_counts(multidevice, tmp_path):
    common = f"""
import json, numpy as np
from repro.api import SuffixTable
from repro.core import codec
ROOT = r'{tmp_path}'
pats = ['A', 'ACGT', 'GATTACA', 'TTTT', 'ACGT' * 6]
"""
    multidevice(common + """
t = SuffixTable.create('fmx', codec.random_dna(4096, seed=12), root=ROOT,
                       fm_threshold=1000)
assert t.is_frozen
out = t.scan(pats, top_k=6)
json.dump({'count': out.count.tolist(),
           'pos': out.positions.tolist()}, open(ROOT + '/want.json', 'w'))
print('OK')
""", n_devices=1)
    multidevice(common + """
t = SuffixTable.open('fmx', root=ROOT)
assert t.is_frozen and t.mesh is None        # frozen serves single-replica
want = json.load(open(ROOT + '/want.json'))
out = t.scan(pats, top_k=6)
assert out.count.tolist() == want['count']
assert out.positions.tolist() == want['pos']
print('OK')
""", n_devices=8)


# ---------------------------------------------------------------------------
# the launch's text-order minimum (``text_min``) vs brute force and the
# host LF walks it replaces
# ---------------------------------------------------------------------------
def _windows(text, rng, num, lo, hi):
    starts = rng.integers(0, len(text) - hi, num)
    return [text[s:s + int(rng.integers(lo, hi + 1))] for s in starts]


def _text_min_case(kind):
    """(text codes, patterns as the batch sends them, real rows)."""
    rng = np.random.default_rng(31)
    if kind == "repeats":
        codes = codec.encode_dna("ACGT" * 120 + "A" * 160 + "ACGT" * 40)
        text = codec.decode_dna(codes)
        pats = ["ACGT" * 2, "AAAA", "CA", "ACGT" * 10, "A" * 40, "TA"]
        return codes, pats + _windows(text, rng, 10, 4, 30), 16
    codes = codec.random_dna(1500, seed=11)
    text = codec.decode_dna(codes)
    if kind == "random":
        return codes, _windows(text, rng, 32, 4, 12), 32
    if kind == "overflow":                      # 3B = 24 lanes, few fit
        return codes, ["A", "C", "G", "T", "AC", "GT", "ACG", "TTA"], 8
    if kind == "missing":
        miss = [codec.decode_dna(codec.random_dna(20, seed=s))
                for s in range(6)]
        return codes, miss + _windows(text, rng, 2, 8, 12), 8
    assert kind == "padded"                     # 5 real rows, row 0 x3
    pats = _windows(text, rng, 5, 5, 9)
    return codes, pats + [pats[0]] * 3, 5


@pytest.mark.parametrize("kind", ["random", "repeats", "overflow",
                                  "missing", "padded"])
def test_fm_search_text_min_matches_brute_force_and_host_walks(kind):
    from repro.core.rangemin import range_min
    from repro.core.suffix_array import build_suffix_array
    from repro.kernels import ops

    codes, pats, n_real = _text_min_case(kind)
    text = codec.decode_dna(codes)
    fm = FMIndex.build(codes, is_dna=True)
    patt, plen = codec.encode_pattern_batch(pats, 64, packed=True)
    res = ops.fm_search(fm.arrays, patt, plen)
    count = np.asarray(res.count).astype(np.int64)
    rank = np.asarray(res.first_rank).astype(np.int64)
    min_pos, settled = np.asarray(res.text_min)
    settled = settled.astype(bool)

    sa = np.asarray(build_suffix_array(codes))
    brute = np.array([min((int(s) for s in sa if text.startswith(p, s)),
                          default=-1) for p in pats])
    host = range_min(fm.ranks_to_positions, fm.row_min, rank + 1,
                     np.where(rank >= 0, count, 0))
    assert np.array_equal(host, brute)
    assert np.array_equal(min_pos[settled], brute[settled])
    assert np.all(min_pos[~settled] == -1)
    # settled = the range fits the 3B extra lanes, in batch order, a
    # range longer than all of them skipped
    budget, used, want = 3 * len(pats), 0, []
    for c in count:
        extra = max(int(c) - 1, 0)
        if extra <= budget:
            used += extra
        want.append(extra == 0 or (extra <= budget and used <= budget))
    assert np.array_equal(settled, want)
    assert np.all(settled[count <= 1])
    # the table's merge: the host walks only the unsettled rows
    rest = range_min(fm.ranks_to_positions, fm.row_min, rank + 1,
                     np.where(settled | (rank < 0), 0, count))
    assert np.array_equal(np.where(settled, min_pos, rest)[:n_real],
                          brute[:n_real])
    if kind in ("repeats", "overflow"):       # both paths are exercised
        assert settled.any() and not settled.all()
    else:
        assert settled[:n_real].all()
