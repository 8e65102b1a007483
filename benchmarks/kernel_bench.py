"""Micro-benchmarks for the scan-path compute (XLA path on CPU; the Pallas
kernels target TPU and are validated in interpret mode by tests)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, query as Q
from repro.core.codec import random_dna
from repro.core.planner import ScanPlanner
from repro.core.tablet import build_tablet_store


def _time(fn, *args, reps=5):
    fn(*args)                                # compile+warm
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_pattern_compare(B=4096, W=7):
    codes = random_dna(100_000, seed=0)
    packed = codec.pack_2bit(codes)
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.integers(0, 100_000, B), jnp.int32)
    pats = Q.random_patterns(B, 1, 100, seed=1)
    _, pp, pl = Q.encode_patterns(pats, W * 16)

    f = jax.jit(lambda p: Q.compare_packed(packed, 100_000, p, pp, pl))
    dt = _time(f, pos)
    return dt / B * 1e6, {"compares_per_s": round(B / dt), "batch": B}


def bench_binary_search(B=1024):
    store = build_tablet_store(random_dna(1_000_000, seed=2), is_dna=True)
    pats = Q.random_patterns(B, 1, 100, seed=3)
    _, pp, pl = Q.encode_patterns(pats, 112)
    f = jax.jit(lambda a, b: Q.query(store, a, b))
    dt = _time(f, pp, pl)
    return dt / B * 1e6, {"scans_per_s": round(B / dt),
                          "rows": store.n_pad}


def bench_planner_scan(B=1024):
    """Planner entry point (single-device executor, jitted) — the path the
    serving engine now takes; comparable to bench_binary_search."""
    store = build_tablet_store(random_dna(1_000_000, seed=2), is_dna=True)
    planner = ScanPlanner(store)
    pats = Q.random_patterns(B, 1, 100, seed=3)
    _, pp, pl = Q.encode_patterns(pats, 112)
    dt = _time(lambda a, b: planner.scan_encoded(a, b), pp, pl)
    return dt / B * 1e6, {"scans_per_s": round(B / dt),
                          "rows": store.n_pad,
                          "mode": planner.plan(B).mode}


def bench_pack_throughput(n=4_000_000, reps=5):
    """Host pack (``codec.pack_2bit`` is numpy; no device work to sync)."""
    codes = random_dna(n, seed=4)
    codec.pack_2bit(codes)
    t0 = time.perf_counter()
    for _ in range(reps):
        codec.pack_2bit(codes)
    dt = (time.perf_counter() - t0) / reps
    return dt / n * 1e6, {"mbase_per_s": round(n / dt / 1e6, 1)}
