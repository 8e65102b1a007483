"""The read batch's staging: patterns stay host arrays from the strings
until the launch, so a host-encoded batch is never copied back from the
device to pad it or check its lengths (``PlannerStats.input_copybacks``
stays 0); device-array inputs keep working, are counted once a batch,
and get the same answers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import Database, Query, SuffixTable
from repro.core import codec

PATS = ["A", "ACGT", "GATTACA", "TTTT", "CCGG", "A" * 24, "ACGT" * 6,
        "gattaca"]


def _db(kind):
    """A Database with one table ``t``: live, live with a memtable tier
    (the fused multi-tier launch), or frozen (the FM executor)."""
    db = Database(None)
    db.attach("t", SuffixTable.from_codes(codec.random_dna(3000, seed=11),
                                          is_dna=True))
    if kind == "frozen":
        db.freeze("t")
    elif kind == "memtable":
        db.append("t", codec.random_dna(200, seed=12))
    return db


@pytest.mark.parametrize("kind", ["live", "memtable", "frozen"])
def test_host_batches_are_never_copied_back(kind):
    db = _db(kind)
    t = db.table("t")
    before = t.stats()["planner"]
    t.scan(PATS, top_k=4)
    t.clear_cache()
    db.query(Query.count("t", PATS))
    t.clear_cache()
    db.submit(Query.scan("t", PATS[::-1], top_k=3)).result()
    t.locate_range("ACGT")
    t.planner.scan(PATS)
    after = t.stats()["planner"]
    assert after["batches"] > before["batches"]
    assert after["input_copybacks"] == 0
    db.close()


@pytest.mark.parametrize("kind", ["live", "memtable", "frozen"])
def test_device_inputs_count_one_copyback_a_batch(kind):
    db = _db(kind)
    t = db.table("t")
    patt, plen = t.planner.encode(PATS)
    assert isinstance(patt, np.ndarray) and isinstance(plen, np.ndarray)
    host = t.scan_batch(patt, plen, top_k=4)
    assert t.planner.stats.input_copybacks == 0
    for n, args in enumerate([(jnp.asarray(patt), jnp.asarray(plen)),
                              (jnp.asarray(patt), plen),
                              (patt, jnp.asarray(plen))], start=1):
        assert any(isinstance(a, jax.Array) for a in args)
        dev = t.scan_batch(*args, top_k=4)
        assert t.planner.stats.input_copybacks == n
        for f in ("found", "count", "first_pos", "positions"):
            assert np.array_equal(getattr(dev, f), getattr(host, f)), f
    db.close()


def test_planner_checks_device_lengths_once():
    t = _db("live").table("t")
    patt, plen = t.planner.encode(PATS)
    want = t.planner.scan_encoded(patt, plen)
    assert t.planner.stats.input_copybacks == 0
    got = t.planner.scan_encoded(jnp.asarray(patt), jnp.asarray(plen))
    assert t.planner.stats.input_copybacks == 1
    assert np.array_equal(np.asarray(got.count), np.asarray(want.count))
    with pytest.raises(ValueError, match="exceeds max_pattern_len"):
        t.planner.scan_encoded(jnp.asarray(patt),
                               jnp.full_like(jnp.asarray(plen), 10_000))
    assert t.planner.stats.input_copybacks == 2
