"""Staged out-of-core builds (repro.core.build_pipeline + the streamed
persist protocol): bit-identity with the in-memory builder, spill modes,
shard streaming, crash reconcile, and the build stats schema.

docs/build_pipeline.md documents the pipeline; the contract tested here
is that every configuration — chunk size, spill mode, device count,
corpus kind — produces the SAME array the single-sort builder does.
"""
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                               # pragma: no cover
    from _hypothesis_compat import given, settings, strategies as st

from repro.api.catalog import Catalog
from repro.api.table import SuffixTable
from repro.checkpoint.manager import CheckpointManager, ShardedSave
from repro.core import codec
from repro.core.build_pipeline import (BYTES_PER_ROW, DEFAULT_CHUNK_ROWS,
                                       MIN_CHUNK_ROWS, BuildStats,
                                       chunk_rows_for_budget,
                                       staged_suffix_array)
from repro.core.dsort import merge_sorted_runs
from repro.core.suffix_array import build_suffix_array, \
    build_suffix_array_staged


def _ref(codes):
    return np.asarray(build_suffix_array(np.asarray(codes, np.int32)))


# --------------------------------------------------------------------------
# merge_sorted_runs
# --------------------------------------------------------------------------
class _ArrRun:
    def __init__(self, key, idx):
        self.n = len(key)
        self._k, self._i = key, idx

    def read_block(self, lo, hi):
        return self._k[lo:hi], self._i[lo:hi]


def test_merge_sorted_runs_matches_lexsort():
    rng = np.random.default_rng(0)
    n, k = 5000, 7
    key = rng.integers(0, 50, size=n).astype(np.int64)   # heavy key ties
    idx = rng.permutation(n).astype(np.int32)            # unique tiebreak
    order = np.lexsort((idx, key))
    cuts = np.sort(rng.choice(np.arange(1, n), size=k - 1, replace=False))
    runs = []
    for lo, hi in zip(np.r_[0, cuts], np.r_[cuts, n]):
        seg = np.lexsort((idx[lo:hi], key[lo:hi]))
        runs.append(_ArrRun(key[lo:hi][seg], idx[lo:hi][seg]))
    got_k, got_i = [], []
    for kb, ib in merge_sorted_runs(runs, block_rows=64):
        assert len(kb) == len(ib)
        got_k.append(kb)
        got_i.append(ib)
    assert np.array_equal(np.concatenate(got_k), key[order])
    assert np.array_equal(np.concatenate(got_i), idx[order])


def test_merge_single_and_empty_runs():
    key = np.arange(100, dtype=np.int64)
    idx = np.arange(100, dtype=np.int32)
    blocks = list(merge_sorted_runs(
        [_ArrRun(key, idx), _ArrRun(key[:0], idx[:0])], block_rows=17))
    assert np.array_equal(np.concatenate([b for b, _ in blocks]), key)
    assert list(merge_sorted_runs([_ArrRun(key[:0], idx[:0])])) == []


# --------------------------------------------------------------------------
# bit-identity property: chunk sizes x spill x corpus kind
# --------------------------------------------------------------------------
@settings(max_examples=12, deadline=None)
@given(st.integers(2, 4000), st.integers(MIN_CHUNK_ROWS, 2048),
       st.booleans(), st.integers(0, 2 ** 31 - 1))
def test_staged_bit_identical_property(n, chunk_rows, dna, seed):
    rng = np.random.default_rng(seed)
    if dna:
        codes = codec.random_dna(n, seed=seed)
    else:
        codes = rng.integers(0, 1 + int(rng.integers(1, 5000)),
                             size=n).astype(np.int32)
    sa, stats = staged_suffix_array(codes, chunk_rows=chunk_rows)
    assert np.array_equal(sa, _ref(codes))
    assert stats.n_chunks == -(-n // max(chunk_rows, MIN_CHUNK_ROWS))
    assert stats.rounds >= 1 and stats.spill_bytes == 0


def test_staged_spill_to_disk_identical_and_cleaned(tmp_path):
    codes = codec.random_dna(20_000, seed=1)
    spill = tmp_path / "spill"
    sa, stats = staged_suffix_array(codes, chunk_rows=777,
                                    spill_dir=str(spill))
    assert np.array_equal(sa, _ref(codes))
    assert stats.spill_bytes > 0
    # every run/rank/sa/scat spill artifact is deleted on completion
    assert [f for f in os.listdir(spill)] == []


def test_staged_emit_shard_streaming():
    codes = codec.random_dna(5000, seed=2)
    shards = []
    sa, stats = staged_suffix_array(
        codes, chunk_rows=512, shard_rows=900,
        emit_shard=lambda i, blk: shards.append((i, blk.copy())))
    assert sa is None
    assert [i for i, _ in shards] == list(range(len(shards)))
    sizes = [len(b) for _, b in shards]
    assert all(s == 900 for s in sizes[:-1]) and sizes[-1] == 5000 % 900
    assert np.array_equal(np.concatenate([b for _, b in shards]),
                          _ref(codes))


def test_staged_edge_sizes():
    for n in (0, 1, 2, 3, MIN_CHUNK_ROWS, MIN_CHUNK_ROWS + 1):
        codes = codec.random_dna(n, seed=n)
        sa, _ = staged_suffix_array(codes, chunk_rows=MIN_CHUNK_ROWS)
        assert np.array_equal(sa, _ref(codes)), n
    # constant text: maximal ties, saturation only at the last round
    const = np.zeros(1000, np.uint8)
    sa, stats = staged_suffix_array(const, chunk_rows=MIN_CHUNK_ROWS)
    assert np.array_equal(sa, _ref(const))
    # wrapper spelling
    assert np.array_equal(
        build_suffix_array_staged(const, chunk_rows=MIN_CHUNK_ROWS), sa)


def test_budget_math():
    assert chunk_rows_for_budget(None) == DEFAULT_CHUNK_ROWS
    assert chunk_rows_for_budget(10 * BYTES_PER_ROW) == MIN_CHUNK_ROWS
    assert chunk_rows_for_budget(100_000) == 100_000 // BYTES_PER_ROW
    _, stats = staged_suffix_array(codec.random_dna(4000, seed=3),
                                   max_device_bytes=MIN_CHUNK_ROWS
                                   * BYTES_PER_ROW)
    assert stats.chunk_rows == MIN_CHUNK_ROWS
    assert stats.peak_device_bytes == MIN_CHUNK_ROWS * BYTES_PER_ROW


# --------------------------------------------------------------------------
# staged create -> open -> stats
# --------------------------------------------------------------------------
def test_staged_create_bit_identical_and_stats(tmp_path):
    codes = codec.random_dna(12_000, seed=4)
    t = SuffixTable.create("g", codes, root=str(tmp_path),
                           build_chunk_rows=1024,
                           spill_dir=str(tmp_path / "spill"))
    ref = _ref(codes)
    assert np.array_equal(
        np.asarray(t.store.sa)[t.store.pad_count:], ref)
    b = t.stats()["build"]
    assert b["mode"] == "staged" and b["spill_bytes"] > 0
    assert set(b) == {"mode", "n_bases", "rounds", "n_chunks", "chunk_rows",
                      "peak_device_bytes", "spill_bytes", "elapsed_s",
                      "bases_per_s", "compile_s", "devices"}
    assert b["bases_per_s"] > 0
    # the snapshot on disk is the streamed-shard kind
    mgr = CheckpointManager(str(tmp_path / "g"))
    step = mgr.latest_step()
    step_dir = os.path.join(str(tmp_path / "g"), f"step_{step:010d}")
    assert any(f.startswith("shard_sa_real_")
               for f in os.listdir(step_dir))
    # reads + writes behave like a normal table
    assert int(t.count(["ACGT"])[0]) == int(
        SuffixTable.from_codes(codes, is_dna=True).count(["ACGT"])[0])
    t.append("GATTACA")
    assert int(t.count(["GATTACA"])[0]) >= 1
    t.close()
    # reopen restores the identical SA and the persisted build stats
    t2 = SuffixTable.open("g", root=str(tmp_path))
    assert np.array_equal(
        np.asarray(t2.store.sa)[t2.store.pad_count:], ref)
    b2 = t2.stats()["build"]
    assert b2["mode"] == "staged" and b2["rounds"] == b["rounds"]
    assert BuildStats.from_dict(b2).n_bases == 12_000
    t2.close()


def test_staged_create_token_corpus(tmp_path):
    rng = np.random.default_rng(5)
    codes = rng.integers(0, 30_000, size=6000).astype(np.int32)
    t = SuffixTable.create("tok", codes, root=str(tmp_path),
                           max_device_bytes=512 * BYTES_PER_ROW)
    assert not t.is_dna
    assert np.array_equal(np.asarray(t.store.sa)[t.store.pad_count:],
                          _ref(codes))
    assert t.stats()["build"]["chunk_rows"] == 512
    t.close()


# --------------------------------------------------------------------------
# crash at every shard boundary + reconcile
# --------------------------------------------------------------------------
def test_kill_at_every_shard_boundary(tmp_path, monkeypatch):
    """A create killed after ANY number of streamed shards (abort never
    runs — a hard kill) leaves no published snapshot; the next catalog
    open garbage-collects the remnant and a re-create succeeds and is
    bit-identical."""
    codes = codec.random_dna(4000, seed=6)
    ref = _ref(codes)
    n_shards = -(-4000 // 512)

    class _Kill(BaseException):
        pass

    orig_add = ShardedSave.add_shard
    orig_commit = ShardedSave.commit
    monkeypatch.setattr(ShardedSave, "abort", lambda self: None)
    for die_at in range(n_shards + 1):        # +1: die at commit instead
        root = tmp_path / f"r{die_at}"
        seen = {"n": 0}

        def add(self, name, i, arr, _die=die_at, _seen=seen):
            if _seen["n"] == _die:
                raise _Kill()
            _seen["n"] += 1
            return orig_add(self, name, i, arr)

        monkeypatch.setattr(ShardedSave, "add_shard", add)
        if die_at == n_shards:
            monkeypatch.setattr(
                ShardedSave, "commit",
                lambda self, state, extra=None: (_ for _ in ()).throw(
                    _Kill()))
        with pytest.raises(_Kill):
            SuffixTable.create("t", codes, root=str(root),
                               build_chunk_rows=512, shard_rows=512)
        monkeypatch.setattr(ShardedSave, "add_shard", orig_add)
        monkeypatch.setattr(ShardedSave, "commit", orig_commit)
        # the kill left a registered entry + partial stream, no snapshot
        cat = Catalog(str(root), reconcile=False)
        assert "t" in cat
        with pytest.raises(FileNotFoundError):
            SuffixTable.open("t", root=str(root))
        Catalog(str(root))                    # open-time auto-reconcile
        assert "t" not in Catalog(str(root)).list_tables()
        assert not os.path.isdir(root / "t")
        t = SuffixTable.create("t", codes, root=str(root),
                               build_chunk_rows=512, shard_rows=512)
        assert np.array_equal(
            np.asarray(t.store.sa)[t.store.pad_count:], ref)
        t.close()


def test_reconcile_cases(tmp_path):
    codes = codec.random_dna(600, seed=7)
    t = SuffixTable.create("keep", codes, root=str(tmp_path))
    t.close()
    # 1. stale .tmp stage inside a healthy table (crashed re-publish)
    os.makedirs(tmp_path / "keep" / "step_0000000099.tmp")
    # 2. unregistered remnant: only table machinery inside
    os.makedirs(tmp_path / "ghost" / "step_0000000001.tmp")
    os.makedirs(tmp_path / "ghost" / "wal")
    # 3. unregistered dir holding USER data: must never be touched
    os.makedirs(tmp_path / "userdata")
    (tmp_path / "userdata" / "notes.txt").write_text("keep me")
    removed = Catalog(str(tmp_path), reconcile=False).reconcile()
    assert removed == ["ghost"]
    assert not (tmp_path / "keep" / "step_0000000099.tmp").exists()
    assert (tmp_path / "userdata" / "notes.txt").exists()
    # the healthy table still opens with its data intact
    t2 = SuffixTable.open("keep", root=str(tmp_path))
    assert np.array_equal(np.asarray(t2.store.sa)[t2.store.pad_count:],
                          _ref(codes))
    t2.close()
    # 4. a data-bearing orphan (crashed drop: unregistered, HAS snapshot)
    #    is preserved for drop_table, not GC'd
    cat = Catalog(str(tmp_path), reconcile=False)
    data = cat.load()
    del data["tables"]["keep"]
    cat._write(data)
    assert cat.reconcile() == []
    assert (tmp_path / "keep").is_dir()
    cat.drop_table("keep")                    # finishes the drop
    assert not (tmp_path / "keep").exists()


def test_sharded_save_protocol(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    stage = mgr.stage_sharded(1)
    stage.add_shard("sa_real", 0, np.arange(5, dtype=np.int32))
    with pytest.raises(ValueError, match="out of order"):
        stage.add_shard("sa_real", 2, np.arange(3, dtype=np.int32))
    stage.add_shard("sa_real", 1, np.arange(5, 8, dtype=np.int32))
    assert mgr.latest_step() is None          # nothing visible pre-commit
    stage.commit({"codes": np.zeros(8, np.uint8)}, {"v": 1})
    arrays, extra = mgr.restore_arrays(1)
    got = {k.strip("[']"): v for k, v in arrays.items()}
    assert np.array_equal(got["sa_real"], np.arange(8))
    assert got["sa_real"].dtype == np.int32 and extra == {"v": 1}
    with pytest.raises(RuntimeError, match="already"):
        stage.add_shard("sa_real", 2, np.zeros(1, np.int32))
    # abort leaves nothing behind
    stage2 = mgr.stage_sharded(2)
    stage2.add_shard("x", 0, np.ones(4))
    stage2.abort()
    assert mgr.latest_step() == 1
    assert not os.path.exists(stage2.tmp)


# --------------------------------------------------------------------------
# device-count portability: 1 -> 8 -> 1
# --------------------------------------------------------------------------
@pytest.mark.multidevice
def test_staged_build_8dev_bit_identical(multidevice):
    """The mesh super-chunk path (8 devices) produces the same SA and the
    same persisted table as the single-device staged build; reopening on
    1 device serves it unchanged."""
    out = multidevice("""
import numpy as np, tempfile
import jax
from repro.api.table import SuffixTable
from repro.core import codec
from repro.core.build_pipeline import staged_suffix_array
from repro.core.suffix_array import build_suffix_array
from repro.launch.mesh import make_tablet_mesh

assert len(jax.devices()) == 8
codes = codec.random_dna(15_000, seed=11)
ref = np.asarray(build_suffix_array(codes.astype(np.int32)))
mesh = make_tablet_mesh(8)
sa, stats = staged_suffix_array(codes, chunk_rows=256, mesh=mesh,
                                axis_name="tablets")
assert np.array_equal(sa, ref)
assert stats.peak_device_bytes == 256 * 24
with tempfile.TemporaryDirectory() as root:
    t = SuffixTable.create("g8", codes, root=root, build_chunk_rows=256)
    assert np.array_equal(np.asarray(t.store.sa)[t.store.pad_count:], ref)
    assert t.stats()["build"]["mode"] == "staged"
    t.close()
print("SA8_OK")
""")
    assert "SA8_OK" in out
    # and a table persisted under 8 devices reopens identically under 1
    out = multidevice("""
import numpy as np, tempfile, subprocess, sys, os
from repro.api.table import SuffixTable
from repro.core import codec
root = tempfile.mkdtemp()
codes = codec.random_dna(8000, seed=12)
t = SuffixTable.create("port", codes, root=root, build_chunk_rows=512)
sa = np.asarray(t.store.sa)[t.store.pad_count:]
np.save(os.path.join(root, "ref.npy"), sa)
t.close()
print(root)
""")
    root = out.strip().splitlines()[-1]
    import subprocess
    import sys

    from conftest import SRC
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    code = f"""
import numpy as np, os
from repro.api.table import SuffixTable
root = {root!r}
t = SuffixTable.open("port", root=root)
ref = np.load(os.path.join(root, "ref.npy"))
assert np.array_equal(np.asarray(t.store.sa)[t.store.pad_count:], ref)
assert t.stats()["build"]["mode"] == "staged"
print("REOPEN1_OK")
"""
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "REOPEN1_OK" in proc.stdout
