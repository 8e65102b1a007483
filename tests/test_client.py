"""repro.api.client: typed queries, coalescing, paged streaming, caches.

Load-bearing properties:

* coalesced dispatch (inline waves AND the scheduler thread) returns
  results bit-identical to per-call dispatch and to the table itself;
* concatenating every ``ReadSession`` page reproduces the one-shot
  ``locate`` enumeration for random append/seal schedules, and a cursor
  taken mid-stream resumes exactly — including after a minor compaction
  moves the data under it;
* no cached count/top-k from before a write is ever served (the
  generation-stamped ``TopKCache``), even through planner references
  captured before a major compaction.
"""
import threading

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                    # pragma: no cover
    from _hypothesis_compat import given, settings, strategies as st

from repro.api import Database, Query, SuffixTable
from repro.core import codec, query as Q
from repro.core.planner import TopKCache
from repro.serving import HedgedScanService


def _db_over(codes, name="dna", **kw):
    db = Database.in_memory()
    table = db.attach(name, SuffixTable.from_codes(codes, is_dna=True, **kw))
    return db, table


def _oracle_positions(codes, pattern):
    cc = np.asarray(codes).astype(np.int32)
    pc = codec.encode_dna(pattern).astype(np.int32)
    k = len(pc)
    return [i for i in range(len(cc) - k + 1) if (cc[i:i + k] == pc).all()]


# ---------------------------------------------------------------------------
# typed request validation + routing
# ---------------------------------------------------------------------------
def test_query_validation():
    with pytest.raises(ValueError, match="kind"):
        Query(table="t", kind="explode", patterns=("A",))
    with pytest.raises(ValueError, match="exactly one"):
        Query(table="t", patterns=("A",), codes=np.zeros((1, 4)),
              lens=np.array([1]))
    with pytest.raises(ValueError, match="exactly one"):
        Query(table="t")
    with pytest.raises(ValueError, match="lens"):
        Query(table="t", codes=np.zeros((1, 4), np.int32))
    with pytest.raises(ValueError, match="max_len"):
        Query.count("t", ["ACGTACGT"], max_len=4)
    with pytest.raises(TypeError):
        Query(table="t", patterns=(b"ACGT",))
    q = Query.locate("t", ["ACGT"])            # locate defaults top_k to 8
    assert q.top_k == 8 and q.num_patterns == 1
    with pytest.raises(ValueError, match="top_k"):
        Query.locate("t", ["ACGT"], top_k=-5)  # rejected, not coerced to 8
    assert Query.count("t", ["AC", "GT"]).num_patterns == 2


def test_database_routes_and_lifecycle(tmp_path):
    db = Database(str(tmp_path))
    db.create_table("dna", codec.random_dna(500, seed=0), is_dna=True)
    mem = SuffixTable.from_codes(codec.random_dna(300, seed=1), is_dna=True)
    db.attach("scratch", mem)
    assert db.list_tables() == ["dna", "scratch"]
    assert "dna" in db and "scratch" in db and "nope" not in db
    with pytest.raises(ValueError, match="already attached"):
        db.attach("scratch", mem)
    # a second handle over the same root lazily opens the persisted table
    db2 = Database(str(tmp_path))
    assert int(db2.query(Query.count("dna", ["A"])).value[0]) == \
        int(db.query(Query.count("dna", ["A"])).value[0])
    with pytest.raises(KeyError):
        Database.in_memory().table("anything")
    # ensure_attached reuses registrations and dodges name clashes
    assert db.ensure_attached(mem) == "scratch"
    other = SuffixTable.from_codes(codec.random_dna(100, seed=2))
    alt = db.ensure_attached(other, name="dna")    # 'dna' is taken on disk
    assert alt != "dna" and db.table(alt) is other
    # drop_table honors missing_ok on BOTH backends
    mdb = Database.in_memory()
    mdb.attach("t", mem)
    mdb.drop_table("t")
    mdb.drop_table("t", missing_ok=True)           # quiet, like the catalog
    with pytest.raises(KeyError):
        mdb.drop_table("t")
    # a FAILED drop (attached name, not in the catalog) must leave the
    # attached table routed and serving — not detached-and-closed
    with pytest.raises(KeyError):
        db.drop_table("scratch")
    assert int(db.query(Query.count("scratch", ["A"])).value[0]) >= 0
    db.close(), db2.close(), mdb.close()


def test_kinds_payload_and_errors():
    db, table = _db_over(codec.random_dna(2000, seed=3))
    pats = ["ACGT", "TTTTTTTTTTTTTTTTTTTTTTTTTTTTTTTT"]
    want = table.scan(pats, top_k=8)
    assert (db.query(Query.count("dna", pats)).value == want.count).all()
    assert (db.query(Query.contains("dna", pats)).value == want.found).all()
    assert (db.query(Query.locate("dna", pats, top_k=8)).value
            == want.positions).all()
    full = db.query(Query.scan("dna", pats, top_k=8)).value
    assert (full.first_pos == want.first_pos).all()
    # execution failures surface as error results, and .value raises
    bad = db.query(Query.count("nope", ["A"]))
    assert not bad.ok and "KeyError" in bad.error
    with pytest.raises(RuntimeError, match="query failed"):
        bad.value
    toolong = db.query(Query.count("dna", ["A" * 200]))
    assert not toolong.ok and "max_pattern_len" in toolong.error
    db.close()


# ---------------------------------------------------------------------------
# coalescing: bit-identical to per-call, across tables and callers
# ---------------------------------------------------------------------------
def _assert_same(res_a, res_b):
    assert res_a.kind == res_b.kind
    assert (res_a.count == res_b.count).all()
    assert (res_a.found == res_b.found).all()
    assert (res_a.first_pos == res_b.first_pos).all()
    assert (res_a.positions is None) == (res_b.positions is None)
    if res_a.positions is not None:
        assert (res_a.positions == res_b.positions).all()


def test_query_many_coalesces_bit_identical_across_tables():
    db, t1 = _db_over(codec.random_dna(3000, seed=4))
    t2 = db.attach("dna2", SuffixTable.from_codes(
        codec.random_dna(1500, seed=5), is_dna=True))
    t2.append("GATTACA")                      # delta tier on one table
    rng = np.random.default_rng(6)
    queries = []
    for i in range(40):
        name = "dna" if i % 2 == 0 else "dna2"
        pats = Q.random_patterns(int(rng.integers(1, 4)), 1, 9,
                                 seed=100 + i)
        queries.append(Query.scan(name, pats, top_k=int(rng.integers(0, 6))))
    coalesced = db.query_many(queries)
    for q, got in zip(queries, coalesced):
        t1.clear_cache(), t2.clear_cache()
        _assert_same(got, db.query(q))
    # mixed-table wave -> one dispatch per table, not per query
    assert all(r.ok for r in coalesced)
    assert any(r.batch_size > q.num_patterns
               for q, r in zip(queries, coalesced))
    db.close()


def test_scheduler_coalesces_concurrent_callers():
    db, table = _db_over(codec.random_dna(4000, seed=7))
    pats = Q.random_patterns(32, 1, 10, seed=8)
    want = table.scan(pats, top_k=4)
    table.clear_cache()
    results = [None] * len(pats)

    def caller(i):
        results[i] = db.submit(
            Query.scan("dna", [pats[i]], top_k=4)).result(timeout=30.0)

    threads = [threading.Thread(target=caller, args=(i,))
               for i in range(len(pats))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    for i, res in enumerate(results):
        assert res is not None and res.ok
        assert int(res.count[0]) == int(want.count[i])
        assert (res.positions[0] == want.positions[i]).all()
    s = db.scheduler.stats
    assert s.submitted == 32 and s.executed == 32
    assert s.batches < s.submitted          # some coalescing happened
    assert s.coalesced_queries > 0 and s.max_batch_patterns > 1
    db.close()
    with pytest.raises(RuntimeError, match="closed"):
        db.submit(Query.count("dna", ["A"]))


def test_inline_callers_race_scheduler_worker_on_shared_cache():
    """Inline db.query on caller threads races the scheduler worker on
    the SAME hot pattern while writes bump the cache generation — the
    locked TopKCache and serialized group execution must never produce
    an error result or a stale count."""
    db, table = _db_over(codec.random_dna(1500, seed=20))
    probe = "GATTACA"
    floor = int(table.count([probe])[0])       # appends only add matches
    errors: list[str] = []

    def inline_caller():
        for _ in range(12):
            res = db.query(Query.count("dna", [probe]))
            if not res.ok:
                errors.append(res.error)
            elif int(res.count[0]) < floor:
                errors.append(f"stale count {int(res.count[0])} < {floor}")

    def writer():
        for i in range(6):        # client writes serialize against reads
            db.append("dna", codec.random_dna(20, seed=30 + i))

    futs = [db.submit(Query.count("dna", [probe])) for _ in range(8)]
    threads = [threading.Thread(target=inline_caller) for _ in range(3)]
    threads.append(threading.Thread(target=writer))
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    for f in futs:
        res = f.result(timeout=30.0)
        assert res.ok and int(res.count[0]) >= floor
    assert errors == [], errors
    # after the dust settles: exact, and the cache serves the final text
    want = int(table.count([probe])[0])
    assert int(db.query(Query.count("dna", [probe])).count[0]) == want
    db.close()


def test_deadline_is_enforced_not_silently_dropped():
    db, _ = _db_over(codec.random_dna(500, seed=9))
    ok = db.query(Query.count("dna", ["ACGT"], deadline_ms=60_000.0))
    assert ok.ok
    expired = db.query(Query.count("dna", ["ACGT"], deadline_ms=0.0))
    assert not expired.ok and "deadline exceeded" in expired.error
    assert db.scheduler.stats.deadline_expired == 1
    # an expired query in a wave must not poison its neighbours
    wave = db.query_many([Query.count("dna", ["ACGT"], deadline_ms=0.0),
                          Query.count("dna", ["ACGT"])])
    assert not wave[0].ok and wave[1].ok
    db.close()


# ---------------------------------------------------------------------------
# paged streaming (ReadSession)
# ---------------------------------------------------------------------------
def test_read_session_pages_and_cursor_resume():
    db, table = _db_over(codec.random_dna(3000, seed=10))
    probe = "AC"                                # plenty of occurrences
    want = _oracle_positions(table._codes, probe)
    assert len(want) > 30
    pages = list(db.read_rows("dna", probe, page_size=7).pages())
    got = [int(x) for p in pages for x in p.positions]
    assert got == want
    assert pages[-1].is_last and not any(p.is_last for p in pages[:-1])
    assert all(len(p.positions) <= 7 for p in pages)
    # resume from a serialized mid-stream cursor (fresh session object)
    sess = db.read_rows("dna", probe, page_size=7)
    first = sess.next_page()
    rest = [int(x) for x in db.resume_read(first.cursor).positions()]
    assert [int(x) for x in first.positions] + rest == want
    # a pattern with zero matches yields exactly one empty terminal page
    none = list(db.read_rows("dna", "A" * 40, page_size=5).pages())
    assert len(none) == 1 and none[0].is_last \
        and none[0].positions.size == 0
    with pytest.raises(ValueError):
        db.read_rows("dna", probe, page_size=0)
    db.close()


@given(st.integers(0, 10_000), st.integers(1, 3), st.integers(40, 160),
       st.integers(1, 17))
@settings(max_examples=4, deadline=None)
def test_read_session_property_pages_equal_one_shot(seed, n_appends, chunk,
                                                    page_size):
    """Property: for random append/seal schedules, page concatenation ==
    the one-shot locate enumeration == the brute-force oracle; and a
    cursor taken mid-stream resumes exactly after a minor compaction
    reshapes the tiers under it."""
    rng = np.random.default_rng(seed)
    base = codec.random_dna(int(rng.integers(200, 600)), seed=seed)
    db, table = _db_over(base)
    combined = base
    for a in range(n_appends):
        app = codec.random_dna(chunk, seed=seed * 11 + a)
        table.append(app)
        combined = np.concatenate([combined, app])
        if rng.random() < 0.5:
            table.minor_compact()              # seal into a run mid-schedule
    probe = codec.decode_dna(combined[:int(rng.integers(1, 3))])
    want = _oracle_positions(combined, probe)
    one_shot = [int(x) for x in table.locate_range(probe, limit=10**6)]
    assert one_shot == want
    got = [int(x)
           for x in db.read_rows("dna", probe, page_size=page_size)
           .positions()]
    assert got == want

    # resume-from-cursor across a minor compaction AND a fresh append:
    # rows behind the cursor never resurface, rows ahead (old and new) all
    # arrive exactly once
    sess = db.read_rows("dna", probe, page_size=page_size)
    first = sess.next_page()
    cursor = first.cursor
    head = [int(x) for x in first.positions]
    app = codec.random_dna(60, seed=seed + 999)
    table.append(app)
    combined = np.concatenate([combined, app])
    table.minor_compact()
    tail = [int(x) for x in db.resume_read(cursor).positions()]
    new_want = _oracle_positions(combined, probe)
    cut = head[-1] if head else -1
    assert tail == [p for p in new_want if p > cut]
    assert head == [p for p in new_want if p <= cut]
    db.close()


# ---------------------------------------------------------------------------
# cache staleness (the version-stamp bugfix) + stats schema
# ---------------------------------------------------------------------------
def test_topk_cache_generation_stamping():
    c = TopKCache(8)
    c.put("p", 3, 7, 0, None)
    assert c.get("p", 0) == (3, 7, None)
    gen = c.bump()
    assert gen == 1 and c.get("p", 0) is None      # pre-bump entry unservable
    c.put("p", 4, 7, 0, None)
    assert c.get("p", 0) == (4, 7, None)
    # a stale richer entry must not block a fresh poorer one
    c.put("q", 5, 1, 8, np.arange(8))
    c.bump()
    c.put("q", 6, 2, 0, None)
    assert c.get("q", 0) == (6, 2, None)
    assert c.hits == 3 and c.misses == 1


def test_no_stale_counts_after_write_through_any_surface():
    """Regression: a count cached before a write could be served after
    the logical text changed — through the table, through a captured
    planner reference, or through a serving engine built before a major
    compaction replaced the planner's store."""
    table = SuffixTable.from_codes(codec.random_dna(1200, seed=11),
                                   is_dna=True)
    svc = HedgedScanService(table, seed=0)     # captures table.planner
    planner = table.planner
    probe = "GATTACA" * 2
    base = int(table.count([probe])[0])
    planner.scan([probe])                       # populate the planner cache

    table.append(probe)                         # write #1: memtable
    assert int(table.count([probe])[0]) == base + 1
    table.minor_compact()                       # write #2: sealed run
    assert int(table.count([probe])[0]) == base + 1
    table.compact()                             # write #3: new base store
    assert int(table.count([probe])[0]) == base + 1
    # the captured planner was re-bound in place, not replaced: it serves
    # the post-compaction text and was never left pointing at the old SA
    assert planner is table.planner
    assert int(planner.scan([probe]).count[0]) == base + 1
    # and the service keeps serving exact counts through the client
    _, pp, pl = Q.encode_patterns([probe], 128)
    assert int(svc.scan(pp, pl, hedged=False)[0].count[0]) == base + 1


def test_stats_schema_is_stable_and_documented():
    db, table = _db_over(codec.random_dna(800, seed=12),
                         memtable_limit=200)
    db.query(Query.count("dna", ["ACGT"]))
    db.query(Query.count("dna", ["ACGT"]))      # second hit is cached
    table.append(codec.random_dna(250, seed=13))   # triggers a seal
    s = table.stats()
    assert set(s) == {"name", "version", "is_dna", "max_query_len",
                      "tiers", "cache", "build", "planner", "wal",
                      "latency"}
    # latency = tracing-span histograms (docs/observability.md); every
    # span exposes the same quantile schema
    assert "total" in s["latency"]
    assert set(s["latency"]["total"]) == {"p50_ms", "p95_ms", "p99_ms",
                                          "n", "total", "sum_ms"}
    assert set(s["build"]) == {"mode", "n_bases", "rounds", "n_chunks",
                               "chunk_rows", "peak_device_bytes",
                               "spill_bytes", "elapsed_s", "bases_per_s",
                               "compile_s", "devices"}
    assert s["build"]["mode"] == "in_memory"    # from_codes: one sort
    assert s["build"]["n_bases"] == 800
    assert set(s["tiers"]) == {"base_rows", "run_count", "run_rows",
                               "memtable_rows", "frozen", "resident_bytes"}
    assert s["tiers"]["frozen"] is False       # no freeze() here
    assert set(s["tiers"]["resident_bytes"]) == {
        "base_sa", "fm", "text_device", "runs", "memtable", "text_host"}
    assert set(s["cache"]) == {"entries", "hits", "misses", "generation"}
    assert set(s["wal"]) == {"enabled", "seq", "log", "recovery"}
    assert s["wal"]["enabled"] is False      # in-memory table: no log
    assert s["tiers"]["base_rows"] == 800 and s["tiers"]["run_count"] == 1
    assert s["cache"]["hits"] >= 1 and s["cache"]["generation"] >= 1
    for key in ("batches", "queries", "bucketed_batches",
                "bucketed_queries", "pad_slots", "mode_counts",
                "tablet_searches"):
        assert key in s["planner"], key
    dbs = db.stats()
    assert set(dbs) == {"scheduler", "tables"}
    assert "dna" in dbs["tables"]
    assert dbs["scheduler"]["submitted"] >= 2
    db.close()


def test_scan_batch_bucket_padding_accounts_slots():
    table = SuffixTable.from_codes(codec.random_dna(600, seed=14),
                                   is_dna=True)
    pats = Q.random_patterns(5, 1, 8, seed=15)
    patt, plen = table.planner.encode(pats)
    before = table.planner.stats.as_dict()
    out = table.scan_batch(patt, plen, top_k=4)
    after = table.planner.stats
    assert out.count.shape == (5,) and out.positions.shape == (5, 4)
    assert after.queries - before["queries"] == 5       # real queries only
    assert after.pad_slots - before["pad_slots"] == 3    # 5 -> bucket of 8
    assert after.bucketed_batches - before["bucketed_batches"] == 1
    # identical to the unbucketed string path
    want = table.scan(pats, top_k=4)
    assert (out.count == want.count).all()
    assert (out.positions == want.positions).all()
