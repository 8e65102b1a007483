#!/usr/bin/env python3
"""Smoke run of the paper's deployment on a TPU, end to end.

The deployment is ``configs/dna_suffix.py``: a 250 Mbp DNA text
(generated from ``--seed``), patterns up to ``max_query_len`` 112,
batches of ``query_batch`` 1024.  Everything goes through the normal
``Database`` / ``SuffixTable`` entry points, in one process:

* build — ``SuffixTable.create`` under a temporary root (WAL live).  The
  one-shot build is compiled for the device first; when its
  ``memory_analysis()`` does not fit the device's ``bytes_limit``, the
  build runs the staged pipeline with ``max_device_bytes`` = that limit;
* base — ``Query.count`` and ``Query.scan(top_k=8)`` batches of 1024
  patterns, lengths 1-100 (half cut from the text, half random);
* tiers — append, ``minor_compact``, append: the fused read runs over
  T >= 2 delta tiers (the Pallas tier scan on a TPU);
* compact — major compaction, then the same reads;
* frozen — ``db.freeze``, then ``count`` and ``scan(top_k=8)`` from the
  FM tier.

Every phase is checked against a host reference that shares no code with
the store: numpy brute-force counts and smallest positions over the
generated text for a sample of >= 64 patterns per batch, plus the SA
order on a random sample of adjacent rows.  Each read is timed on the
host around materialised results, after one warm-up batch of its shape.

``--chips 4`` runs only the 4-tablet mesh path: the same text built and
served over the mesh, routed and broadcast batches, and overflow retries
(a low ``capacity_factor``).

The last line of standard output is ``{"ok": true, "device": {...}}``;
it is printed only when a TPU ran every phase and every answer matched.
Without a TPU the script exits non-zero before doing any work.

    python3 chip_smoke.py [--chips 4] [--text-len N] [--seed S]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

TOP_K = 8
SAMPLE = 64             # patterns per batch checked by the reference
KMER = 12               # reference prefix length (24 bits of uint32)
ALPHABET = np.frombuffer(b"ACGT", np.uint8)


def log(*a) -> None:
    print(*a, flush=True)


# ---------------------------------------------------------------------------
# host reference: numpy only, independent of the store
# ---------------------------------------------------------------------------
class Reference:
    """Brute-force counts and smallest positions over one text."""

    def __init__(self, text: np.ndarray):
        self.text = np.asarray(text, np.uint8)
        n = self.text.size
        padded = np.concatenate([self.text, np.zeros(KMER, np.uint8)])
        km = np.zeros(n, np.uint32)
        for j in range(KMER):               # km[i] = codes i..i+KMER-1
            km = (km << np.uint32(2)) | padded[j:j + n]
        self.kmer = km

    def answers(self, patterns: list[np.ndarray], k: int):
        """(count (P,), smallest k positions (P, k) -1 padded)."""
        n = self.text.size
        count = np.zeros(len(patterns), np.int64)
        first = np.full((len(patterns), k), -1, np.int64)
        longs = []
        for i, p in enumerate(patterns):
            m = p.size
            if m > KMER:
                longs.append(i)
                continue
            hit = (self.kmer >> np.uint32(2 * (KMER - m))) == _code(p)
            hit[n - m + 1:] = False
            pos = np.flatnonzero(hit)
            count[i] = pos.size
            first[i, :min(k, pos.size)] = pos[:k]
        if longs:
            codes = np.array([_code(patterns[i][:KMER]) for i in longs],
                             np.uint32)
            cand = np.flatnonzero(np.isin(self.kmer, codes))
            ckm = self.kmer[cand]
            for i, c in zip(longs, codes):
                p = patterns[i]
                pos = [int(s) for s in cand[ckm == c]
                       if s + p.size <= n
                       and np.array_equal(self.text[s:s + p.size], p)]
                count[i] = len(pos)
                first[i, :min(k, len(pos))] = pos[:k]
        return count, first


def _code(p: np.ndarray) -> int:
    code = 0
    for c in p:
        code = code * 4 + int(c)
    return code


def sa_in_order(text: np.ndarray, sa: np.ndarray, rng, samples: int = 4096
                ) -> bool:
    """Every sampled adjacent pair ``sa[i] < sa[i+1]`` compares strictly
    increasing as suffixes (a shorter suffix sorts first on a tie), and
    ``sa`` is a permutation of the text positions."""
    n = text.size
    if sa.size != n:
        return False
    seen = np.zeros(n, bool)
    seen[sa] = True
    if not seen.all():
        return False
    if n < 2:
        return True
    i = rng.integers(0, n - 1, size=min(samples, n - 1))
    a = sa[i].astype(np.int64)
    b = sa[i + 1].astype(np.int64)
    ext = np.concatenate([text.astype(np.int16), np.full(64, -1, np.int16)])
    undecided = np.ones(i.size, bool)
    ok = np.ones(i.size, bool)
    off = 0
    while undecided.any() and off <= n:
        u = np.flatnonzero(undecided)
        cols = off + np.arange(64)
        wa = ext[np.minimum(a[u, None] + cols, n)]
        wb = ext[np.minimum(b[u, None] + cols, n)]
        diff = wa != wb
        has = diff.any(axis=1)
        j = np.argmax(diff, axis=1)
        r = np.arange(u.size)
        ok[u[has]] = wa[r, j][has] < wb[r, j][has]
        undecided[u[has]] = False
        off += 64
    return bool(ok.all() and not undecided.any())


# ---------------------------------------------------------------------------
# workload
# ---------------------------------------------------------------------------
def make_patterns(text: np.ndarray, batch: int, rng, max_len: int = 100):
    """``batch`` patterns of lengths 1..max_len: half cut from the text
    (they occur), half uniform random (long ones do not)."""
    lens = rng.integers(1, max_len + 1, size=batch)
    out = []
    for j, m in enumerate(lens):
        if j % 2 == 0 and text.size >= m:
            s = int(rng.integers(0, text.size - m + 1))
            out.append(np.array(text[s:s + m], np.uint8))
        else:
            out.append(rng.integers(0, 4, size=m, dtype=np.uint8))
    return out


def to_str(p: np.ndarray) -> str:
    return ALPHABET[p].tobytes().decode()


def pick_sample(patterns, rng, size: int) -> np.ndarray:
    """``size`` random indices plus the first few short patterns (the
    heaviest match sets)."""
    idx = set(rng.choice(len(patterns), size=min(size, len(patterns)),
                         replace=False).tolist())
    short = [i for i, p in enumerate(patterns) if p.size <= 3][:4]
    return np.array(sorted(idx | set(short)), np.int64)


def custom_call_in(fn, *args) -> bool:
    """Does the program the device runs for this read hold a Pallas
    kernel (``tpu_custom_call``)?"""
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def run_phases(text_len: int, *, seed: int = 0, batch: int = 1024,
               root: str, chips: int = 1,
               capacity_factor: float = 2.0, out=log) -> list[dict]:
    """Build the table under ``root`` and run every phase; returns one
    record per read with ``match`` set when every sampled answer agreed
    with the host reference.  Raises on any failure inside a phase."""
    import jax
    import jax.numpy as jnp

    from repro.api import Database, Query
    from repro.configs.dna_suffix import CONFIG
    from repro.core import codec, planner
    from repro.core import suffix_array as sa_mod
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    t_run = time.perf_counter()
    mql = CONFIG.max_query_len
    text = codec.random_dna(text_len, seed=seed)
    out(f"text_len {text_len}")
    records: list[dict] = []

    # -- build --------------------------------------------------------------
    kw = {"max_query_len": mql, "capacity_factor": capacity_factor}
    if chips == 1:
        steps = max(1, int(np.ceil(np.log2(max(text_len, 2)))))
        t0 = time.perf_counter()
        compiled = sa_mod._build_jit.lower(
            jax.ShapeDtypeStruct((text_len,), jnp.int32), steps).compile()
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes) if mem is not None else 0
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        out(f"build_compile_s {time.perf_counter() - t0:.3f} "
            f"build_hbm_bytes {need} bytes_limit {limit}")
        if limit is not None and need > limit:
            kw["max_device_bytes"] = int(limit)
            out("build_mode staged (one-shot build exceeds HBM)")
        else:
            out("build_mode one-shot")
    db = Database(root)
    name = "dna"
    t0 = time.perf_counter()
    table = db.create_table(name, text, **kw)
    build_s = time.perf_counter() - t0
    out(f"build_s {build_s:.3f} bases_per_s {text_len / build_s:.0f}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    out(f"peak_bytes_in_use {peak}")
    store = table.store
    sa_real = np.asarray(store.sa)[store.pad_count:]
    ordered = sa_in_order(text, sa_real, rng)
    out(f"[build ] sa_in_order {ordered}")
    records.append({"phase": "build", "match": ordered})
    del sa_real

    logical = text

    refs: list = []                    # the reference of the latest text

    def read_phase(phase: str, kinds, batches=(batch,)):
        """Per batch size one pattern set and one sampled host reference;
        per kind a warm-up then a timed batch (string cache cleared
        before each), both checked."""
        out(f"[{phase:6s}] elapsed_s {time.perf_counter() - t_run:.3f}")
        if not refs or refs[0].text.size != logical.size:
            refs[:] = [Reference(logical)]
        for size in batches:
            pats = make_patterns(logical, size, rng)
            strs = [to_str(p) for p in pats]
            idx = pick_sample(pats, rng, SAMPLE)
            want_c, want_p = refs[0].answers([pats[i] for i in idx], TOP_K)
            for kind in kinds:
                q = (Query.count(name, strs) if kind == "count"
                     else Query.scan(name, strs, top_k=TOP_K))
                for label in ("warmup", "timed"):
                    db.table(name).clear_cache()
                    t0 = time.perf_counter()
                    res = db.query(q)
                    dt = time.perf_counter() - t0
                    if not res.ok:
                        raise RuntimeError(f"{phase}/{kind}: {res.error}")
                    ok = bool(np.array_equal(res.count[idx], want_c)
                              and np.array_equal(res.first_pos[idx],
                                                 want_p[:, 0]))
                    if kind == "scan":
                        ok &= bool(np.array_equal(res.positions[idx],
                                                  want_p))
                    out(f"[{phase:6s}] {kind:5s} B={size} {label} "
                        f"s_per_batch {dt:.6f} checked {idx.size} "
                        f"match {ok}")
                    records.append({"phase": phase, "kind": kind,
                                    "batch": size, "label": label,
                                    "s": dt, "match": ok})

    def report_kernel(phase: str, fn, *args) -> None:
        found = custom_call_in(fn, *args)
        out(f"[{phase:6s}] tpu_custom_call {found}")

    pl = table.planner
    enc = pl.encode([to_str(p) for p in make_patterns(logical, batch, rng)])

    if chips > 1:
        # the mesh path: routed (B >= routed_min_batch) and broadcast
        # (small B) base scans with sentinel retries
        read_phase("mesh", ("count", "scan"), batches=(batch, 32))
        st = table.stats()["planner"]
        out(f"[plan  ] batches={st['batches']} modes={st['mode_counts']} "
            f"retried={st['retried_overflow']}/{st['retried_saturated']}"
            f"/{st['retried_inexact_rank']}")
        modes = st["mode_counts"]
        records.append({"phase": "plan",
                        "match": bool(modes["routed"] > 0
                                      and modes["broadcast"] > 0
                                      and st["retried_overflow"] > 0)})
        return records

    report_kernel("base", planner._query_single, table.store, *enc)
    read_phase("base", ("count", "scan"))

    # -- T >= 2 delta tiers -------------------------------------------------
    # both memtables pad to the same power-of-two text bucket (overlap
    # window + appends), so their index builds share one compilation
    n_add = min(1 << 15, max(1024, text_len // 8)) - (mql - 1)
    add_a = codec.random_dna(n_add, seed=seed + 1)
    add_b = codec.random_dna(n_add // 2, seed=seed + 2)
    db.append(name, add_a)
    db.table(name).minor_compact()
    db.append(name, add_b)
    logical = np.concatenate([text, add_a, add_b])
    ts = table._tierset()
    out(f"[tiers ] num_tiers {ts.num_tiers}")
    records.append({"phase": "tiers", "match": ts.num_tiers >= 2})
    report_kernel("tiers", ops.fused_single, table.store, ts.stack, *enc)
    read_phase("tiers", ("count", "scan"))

    # -- major compaction ---------------------------------------------------
    t0 = time.perf_counter()
    db.compact(name)
    out(f"[compact] compact_s {time.perf_counter() - t0:.3f}")
    report_kernel("compact", planner._query_single, table.store,
                  *enc)
    read_phase("compact", ("count", "scan"))

    # -- frozen FM tier -----------------------------------------------------
    t0 = time.perf_counter()
    db.freeze(name)
    out(f"[frozen] freeze_s {time.perf_counter() - t0:.3f}")
    report_kernel("frozen", ops.fm_search, table.fm.arrays, *enc)
    read_phase("frozen", ("count", "scan"))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    out(f"peak_bytes_in_use {peak}")
    out(f"elapsed_s {time.perf_counter() - t_run:.3f}")
    db.close()
    return records


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--text-len", type=int, default=None,
                    help="bases (default: configs/dna_suffix.py text_len)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"platform {dev.platform} device_kind {dev.device_kind} "
        f"device_count {len(devices)}")
    if dev.platform != "tpu":
        log(f"FAIL: no TPU (platform {dev.platform!r})")
        return 2
    if len(devices) != args.chips:
        log(f"FAIL: {len(devices)} chips visible, --chips {args.chips}")
        return 2

    from repro.configs.dna_suffix import CONFIG
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile_cache {enable_compile_cache()}")
    text_len = args.text_len or CONFIG.text_len
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as root:
        records = run_phases(
            text_len, seed=args.seed, batch=CONFIG.query_batch,
            root=root, chips=args.chips,
            capacity_factor=0.5 if args.chips > 1 else 2.0)
    bad = [r for r in records if not r["match"]]
    if bad:
        log(f"FAIL: {len(bad)} reads differ from the host reference: {bad}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
