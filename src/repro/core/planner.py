"""Scan planner — the single entry point for all pattern lookups.

The store exposes three scan implementations (`repro.core.query`):

* ``query``          — single-device batched binary search;
* ``query_sharded``  — broadcast fan-out: every tablet searches its local
  rows for every query, bounds are psum'd (paper-faithful Accumulo scan);
* ``query_routed``   — each query travels to its owner tablet through a
  fixed-capacity all_to_all (MoE-dispatch shape).  Cheaper per device but
  *partial*: it returns sentinel counts that callers must handle.

Sentinel semantics (``MatchResult.count`` from the routed path):

====== =====================================================================
value  meaning
====== =====================================================================
``>0``   exact occurrence count
``0``    exact: no match
``-1``   dispatch overflow — a hot tablet received more queries than its
         capacity slots; the query was never executed.  ``found`` is False
         but unreliable.
``-2``   saturated run — the match run spans more than two tablets (very
         short pattern); ``found``/``first_pos`` are exact, the count is not.
====== =====================================================================

The planner makes those sentinels invisible: any query coming back with a
negative count is transparently re-executed through an exact path
(broadcast when a mesh is live, single-device otherwise), so **callers
always get exact counts**.  This is the retry guarantee tested against
``brute_force_count`` in ``tests/test_planner.py`` and
``tests/test_distributed.py``.

On top of the exact scan the planner adds:

* :meth:`ScanPlanner.locate` — match *enumeration*: up to ``top_k``
  occurrence positions per query, gathered from the SA slice ``[lb, ub)``
  (previously only ``first_pos`` was exposed);
* an LRU result cache for repeated hot patterns (string-level API);
* :meth:`ScanPlanner.plan` — mode selection from mesh shape and batch
  size, overridable per call for benchmarking.

See ``docs/scan_planner.md`` for the full contract.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import OrderedDict
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.core import codec
from repro.core import query as Q
from repro.core.query import FMMatchResult, MatchResult
from repro.core.rangemin import block_minima
from repro.core.tablet import TabletStore
from repro.serving.trace import Tracer

MODE_SINGLE = "single"
MODE_BROADCAST = "broadcast"
MODE_ROUTED = "routed"
MODE_FM = "fm"            # frozen tier: FM-index backward search


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """One planning decision: which executor a batch will run through."""
    mode: str      # MODE_SINGLE | MODE_BROADCAST | MODE_ROUTED | MODE_FM
    reason: str
    batch: int


@dataclasses.dataclass
class PlannerStats:
    """Counters for observability; reset with :meth:`ScanPlanner.reset_stats`."""
    batches: int = 0
    queries: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    retried_overflow: int = 0     # -1 sentinels re-executed
    retried_saturated: int = 0    # -2 sentinels re-executed
    retried_inexact_rank: int = 0  # found but first_rank < 0 (defensive)
    # batch-slot accounting for the client's bucket-padded batches: a
    # batch submitted with n_real carries B - n_real padding slots
    # (shape bucketing); ``queries`` above counts only the real ones.
    # (True cross-caller coalescing is counted by SchedulerStats in
    # repro.api.client — these count slot usage per dispatch.)
    bucketed_batches: int = 0
    bucketed_queries: int = 0
    pad_slots: int = 0
    mode_counts: dict = dataclasses.field(
        default_factory=lambda: {MODE_SINGLE: 0, MODE_BROADCAST: 0,
                                 MODE_ROUTED: 0, MODE_FM: 0})
    # fused read-path counters (docs/read_path.md): ``fused_batches``
    # crossed the device boundary ONCE for base + all delta tiers;
    # ``base_only_batches`` took the no-delta fast path.  ``tier_reads``
    # counts logical tier visits per kind — under the old fan-out each
    # visit was its own dispatch, so (runs + memtable) / fused_batches
    # is the dispatch count a batch no longer pays.
    fused_batches: int = 0
    base_only_batches: int = 0
    tier_reads: dict = dataclasses.field(
        default_factory=lambda: {"base": 0, "runs": 0, "memtable": 0})
    # batches whose patterns arrived as device arrays and had to be
    # copied back to the host to pad or check them (host-encoded
    # batches, what ``encode`` returns, cross to the device once)
    input_copybacks: int = 0
    # tablet-level searches of the real queries: p per query for a
    # broadcast batch (every tablet searches it), 1 for a routed or
    # single-device one, plus the exact mode's count per retried row —
    # the work the mesh multiplies (``tablet_searches / queries``)
    tablet_searches: int = 0
    # real queries of frozen ``SuffixTable`` reads whose text-order
    # smallest position the FM launch settled (``host_text_min``): the
    # rest take the host LF walks (``SuffixTable._base_min_positions``)
    device_min_settled: int = 0

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mode_counts"] = dict(self.mode_counts)
        d["tier_reads"] = dict(self.tier_reads)
        return d


@dataclasses.dataclass(frozen=True)
class TierScanResult:
    """The fused tier scan's per-tier outputs ((T, B) int32 each; tier
    order = the TierSet's).  ``less``/``matches`` delimit each tier's
    raw prefix-match run in its own suffix array — enough for the table
    to enumerate owned positions by pure host slicing.  Fields are
    still-async device handles; count-only callers never force the
    sync, enumeration converts with ``np.asarray`` when it slices."""
    count: "np.ndarray"    # occurrences the tier owns (bounds applied)
    less: "np.ndarray"     # rows strictly before the pattern (slice lb)
    matches: "np.ndarray"  # raw prefix-match run length (no bounds)
    first_g: "np.ndarray"  # min owned global position (2**30 if none)


class TopKCache:
    """LRU over pattern strings, top_k-aware and generation-stamped.

    One entry per pattern holds ``(generation, count, first_pos,
    k_stored, row)``.  An entry cached with ``k_stored`` positions
    serves ANY request with ``top_k <= k_stored`` by slicing, and any
    ``top_k`` at all when the cached position set is complete
    (``count <= k_stored``) — instead of storing duplicate entries per
    ``(pattern, top_k)`` key.  A request needing more positions than
    stored is a miss and its result overwrites the entry (never with
    fewer positions than it had).

    Every entry is stamped with the cache's ``generation`` at put time;
    :meth:`bump` advances the generation, lazily invalidating every
    older entry in O(1) — the write path (``append`` /
    ``minor_compact`` / ``compact``) bumps instead of serving counts
    from before the logical text changed.  Shared by
    :class:`ScanPlanner` and ``repro.api.SuffixTable``.
    """

    def __init__(self, size: int):
        self.size = int(size)
        self.generation = 0
        self.hits = 0
        self.misses = 0
        self._d: OrderedDict[str, tuple] = OrderedDict()
        # the client's scheduler worker and inline callers share this
        # cache across threads; every mutating path is check-then-act on
        # the OrderedDict, so each method holds the lock
        self._lock = threading.Lock()

    def get(self, pattern: str, top_k: int):
        """(count, first_pos, positions (top_k,) | None) or None on miss."""
        if self.size <= 0:
            return None
        with self._lock:
            ent = self._d.get(pattern)
            if ent is not None and ent[0] != self.generation:
                del self._d[pattern]         # stamped before the last write
                ent = None
            if ent is None:
                self.misses += 1
                return None
            _gen, count, first_pos, k_stored, row = ent
            if top_k > 0 and k_stored < top_k and count > k_stored:
                self.misses += 1
                return None        # not enough positions cached
            self._d.move_to_end(pattern)
            self.hits += 1
        if top_k <= 0:
            return count, first_pos, None
        out = np.full(top_k, -1, np.int64)
        if row is not None:
            take = np.asarray(row)[:top_k]
            out[:take.shape[0]] = take
        return count, first_pos, out

    def put(self, pattern: str, count: int, first_pos: int,
            k_stored: int, row) -> None:
        if self.size <= 0:
            return
        with self._lock:
            old = self._d.get(pattern)
            if (old is not None and old[0] == self.generation
                    and old[3] > k_stored):
                self._d.move_to_end(pattern)  # keep the richer live entry
                return
            self._d[pattern] = (self.generation, int(count), int(first_pos),
                                int(k_stored),
                                None if row is None else np.asarray(row))
            self._d.move_to_end(pattern)
            while len(self._d) > self.size:
                self._d.popitem(last=False)

    def bump(self) -> int:
        """Invalidate every current entry (O(1)): stale entries are
        dropped lazily on their next lookup.  Returns the new
        generation — ``repro.api.SuffixTable`` stamps this into its
        :meth:`~repro.api.SuffixTable.stats` so staleness is observable."""
        with self._lock:
            self.generation += 1
            return self.generation

    def clear(self) -> None:
        with self._lock:
            self._d.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._d)


@dataclasses.dataclass(frozen=True)
class ScanOutcome:
    """Host-side result of a string-level scan: exact counts always.

    ``positions`` is present when ``top_k > 0``: shape (B, top_k) int64,
    row i holding up to ``min(count[i], top_k)`` occurrence positions in
    suffix-rank order (lexicographically smallest matching suffix first),
    padded with -1.  (``SuffixTable.scan`` fills the same shape in
    text order instead — smallest positions first.)
    """
    found: np.ndarray        # (B,)  bool
    count: np.ndarray        # (B,)  int64
    first_pos: np.ndarray    # (B,)  int64
    positions: Optional[np.ndarray] = None   # (B, top_k) int64 | None


_query_single = jax.jit(Q.query)


class ScanPlanner:
    """Plans, executes, retries, and caches pattern scans over a store.

    Parameters
    ----------
    store:
        The tablet store (full replicated SA + text).
    mesh, axis_name:
        Optional 1-D jax mesh over tablets.  When absent (or 1 device),
        every scan runs the single-device path.
    capacity_factor:
        Dispatch capacity for the routed path (MoE-style); lower values
        save bandwidth but overflow hot tablets more often — overflow is
        corrected by the retry pass, trading latency for exactness.
    routed_min_batch:
        Batches of at least this many real queries prefer the routed
        path (per-device work O(B/p log m) instead of O(B log m));
        smaller batches broadcast, whatever bucket their padding fills.
        The routed path also requires a DNA store and a batch divisible
        into the mesh (the planner pads internally).
    cache_size:
        LRU entries for the string-level API (0 disables caching).
    """

    def __init__(self, store: TabletStore, *, mesh=None,
                 axis_name: str = "tablets", capacity_factor: float = 2.0,
                 routed_min_batch: int = 64, cache_size: int = 4096,
                 max_pattern_len: Optional[int] = None, fm=None,
                 tracer: Optional[Tracer] = None):
        self.store = store
        self.mesh = mesh if fm is None else None   # frozen = single-replica
        self.fm = fm
        mesh = self.mesh
        self.axis_name = axis_name
        if mesh is not None:
            p = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
            if store.n_pad % p != 0:
                raise ValueError(
                    f"store.n_pad={store.n_pad} is not divisible by the "
                    f"mesh's {p} tablets — rebuild the store with "
                    f"num_tablets={p} (build_tablet_store)")
        self.capacity_factor = float(capacity_factor)
        self.routed_min_batch = int(routed_min_batch)
        self.cache_size = int(cache_size)
        self.max_pattern_len = int(max_pattern_len or store.max_query_len)
        self.stats = PlannerStats()
        # shared with the owning table so span histograms survive
        # rebind/recreation across freeze and compaction
        self.tracer = tracer if tracer is not None else Tracer("table")
        self._cache = TopKCache(self.cache_size)
        self._sa_host: Optional[np.ndarray] = None
        self._sa_bmin: Optional[np.ndarray] = None
        # executors are built lazily and injectable for tests: each maps
        # (patt, plen) -> MatchResult
        self._executors: dict[str, Callable] = {}
        self._compiled_ahead: set[int] = set()   # routed batch sizes

    def rebind(self, store: TabletStore, *, fm=None) -> None:
        """Swap the underlying store in place (major compaction publishes
        a new base).  Captured planner references — the serving engine
        holds one — keep serving the NEW text instead of going silently
        stale: jitted executors are rebuilt lazily against the new store,
        the host SA copy is dropped, and the string-result cache is
        generation-bumped.  Accumulated stats survive the rebind.

        ``fm`` swaps the table onto (or off) the frozen tier: base reads
        route through the FM-index instead of ``store.sa``.  Frozen
        tables serve single-replica, so a live mesh is dropped (the
        store's divisibility constraint goes with it)."""
        self.fm = fm
        if fm is not None:
            self.mesh = None
        if self.mesh is not None:
            p = self.num_tablets
            if store.n_pad % p != 0:
                raise ValueError(
                    f"store.n_pad={store.n_pad} is not divisible by the "
                    f"mesh's {p} tablets — rebuild the store with "
                    f"num_tablets={p} (build_tablet_store)")
        self.store = store
        self.max_pattern_len = int(store.max_query_len)
        self._executors.clear()
        self._compiled_ahead.clear()
        self._sa_host = None
        self._sa_bmin = None
        self._cache.bump()

    def invalidate_cache(self) -> int:
        """Generation-bump the string-result cache: every cached
        count/top-k from before this call becomes unservable.  The table
        write path calls this on ``append`` / ``minor_compact`` /
        ``compact`` so no read can observe pre-write results."""
        return self._cache.bump()

    # -- planning -----------------------------------------------------------
    @property
    def num_tablets(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))

    def plan(self, batch: int) -> ScanPlan:
        """Pick the executor for a batch of ``batch`` queries (the real
        ones: a bucketed batch is planned by its ``n_real``)."""
        if self.fm is not None:
            return ScanPlan(MODE_FM,
                            "frozen table: FM backward search", batch)
        p = self.num_tablets
        if p <= 1:
            return ScanPlan(MODE_SINGLE, "no mesh / single device", batch)
        if (self.store.is_dna and batch >= max(self.routed_min_batch, p)):
            return ScanPlan(
                MODE_ROUTED,
                f"batch {batch} >= {self.routed_min_batch} on {p} tablets: "
                f"route queries to owners", batch)
        return ScanPlan(MODE_BROADCAST,
                        f"small batch ({batch}) or non-DNA store: "
                        f"broadcast to all {p} tablets", batch)

    # -- executors ----------------------------------------------------------
    def _executor(self, mode: str) -> Callable:
        fn = self._executors.get(mode)
        if fn is None:
            fn = self._build_executor(mode)
            self._executors[mode] = fn
        return fn

    def _build_executor(self, mode: str) -> Callable:
        store = self.store
        if mode == MODE_SINGLE:
            # the store is an ARGUMENT, not a closure: a jit closure over
            # device arrays embeds them in the program as constants (the
            # whole SA and text at genome scale)
            return lambda patt, plen: _query_single(store, patt, plen)
        if mode == MODE_FM:
            if self.fm is None:
                raise ValueError("mode 'fm' requires a frozen table "
                                 "(planner has no FM-index bound)")
            from repro.kernels import ops
            fmarr = self.fm.arrays
            return lambda patt, plen: ops.fm_search(fmarr, patt, plen)

        from jax.sharding import PartitionSpec as P
        ax = self.axis_name
        # the sharded scans read the text through ``meta`` (replicated,
        # an argument — see MODE_SINGLE) and their own tablet of the SA
        meta = dataclasses.replace(store, sa=jnp.zeros((0,), jnp.int32))
        if mode == MODE_BROADCAST:
            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=(P(ax), P(), P(), P()), out_specs=P())
            def broadcast(sa_local, meta, patt, plen):
                return Q.query_sharded(sa_local, meta, patt, plen, ax)

            def run(patt, plen):
                return broadcast(store.sa, meta, patt, plen)

            run.compile_ahead = lambda patt, plen: broadcast.lower(
                store.sa, meta, patt, plen).compile()
            return run

        if mode == MODE_ROUTED:
            cf = self.capacity_factor

            @jax.jit
            @partial(shard_map, mesh=self.mesh,
                     in_specs=(P(ax), P(), P(ax), P(ax)), out_specs=P(ax))
            def routed(sa_local, meta, patt, plen):
                return Q.query_routed(sa_local, meta, patt, plen, ax,
                                      capacity_factor=cf)

            def run(patt, plen):
                # routed shards the query batch: pad B to a multiple of p
                p = self.num_tablets
                B = patt.shape[0]
                pad = (-B) % p
                if pad:
                    patt = jnp.concatenate(
                        [patt, jnp.zeros((pad,) + patt.shape[1:],
                                         patt.dtype)])
                    plen = jnp.concatenate(
                        [plen, jnp.ones((pad,), plen.dtype)])
                res = routed(store.sa, meta, patt, plen)
                if pad:
                    # trimmed on the host: the outputs are split over an
                    # Explicit mesh axis, where a device slice to B rows
                    # (not a multiple of p) has no unambiguous sharding
                    res = MatchResult(
                        found=np.asarray(res.found)[:B],
                        count=np.asarray(res.count)[:B],
                        first_rank=np.asarray(res.first_rank)[:B],
                        first_pos=np.asarray(res.first_pos)[:B])
                return res

            return run

        raise ValueError(f"unknown scan mode {mode!r}")

    def _exact_mode(self) -> str:
        return MODE_SINGLE if self.num_tablets <= 1 else MODE_BROADCAST

    # -- encoded-batch API --------------------------------------------------
    def _check_plen(self, plen, B: int,
                    n_real: Optional[int] = None) -> None:
        if n_real is not None and not 0 <= n_real <= B:
            raise ValueError(f"n_real={n_real} out of range for batch {B}")
        if B:
            # a leaf span: the maximum of a host ``plen``; a device one
            # from a caller that did not use ``encode`` is copied back
            with self.tracer.span("plen_check"):
                if isinstance(plen, jax.Array):
                    self.stats.input_copybacks += 1
                max_plen = int(np.max(np.asarray(plen)))
            if max_plen > self.max_pattern_len:
                raise ValueError(
                    f"pattern length {max_plen} exceeds max_pattern_len="
                    f"{self.max_pattern_len}; compares are depth-capped, so "
                    f"longer patterns would be silently truncated — rebuild "
                    f"the store with a larger max_query_len")

    def _account(self, chosen: str, B: int,
                 n_real: Optional[int]) -> None:
        self.stats.batches += 1
        if n_real is None:
            self.stats.queries += B
        else:
            self.stats.queries += n_real
            self.stats.bucketed_batches += 1
            self.stats.bucketed_queries += n_real
            self.stats.pad_slots += B - n_real
        self.stats.mode_counts[chosen] += 1
        fan_out = self.num_tablets if chosen == MODE_BROADCAST else 1
        self.stats.tablet_searches += fan_out * (B if n_real is None
                                                 else n_real)

    def scan_encoded(self, patt, plen, *, mode: Optional[str] = None,
                     retry: bool = True,
                     n_real: Optional[int] = None) -> MatchResult:
        """Exact scan of an encoded batch (packed uint32 DNA or int32 codes).

        Selects the executor via :meth:`plan` (or ``mode`` when forced),
        then re-executes any query whose routed count came back negative
        (-1 overflow / -2 saturated) through the exact path.  With
        ``retry=False`` the raw sentinels are returned (benchmarks only).

        ``n_real`` is the client's batch-slot accounting: the trailing
        ``B - n_real`` rows are shape-bucketing padding whose results
        the caller discards.  Stats then attribute only the real queries
        to ``queries`` (and record the batch under ``bucketed_batches``
        / ``pad_slots``); execution is unchanged — padding rows still
        run, which is the point of bucketing.

        ``patt``/``plen`` may be host numpy arrays (what :meth:`encode`
        returns; they cross to the device in the launch) or device
        arrays.
        """
        B = int(patt.shape[0])
        self._check_plen(plen, B, n_real)
        return self._scan_checked(patt, plen, B, mode, retry, n_real)

    def _scan_checked(self, patt, plen, B: int, mode: Optional[str],
                      retry: bool, n_real: Optional[int]) -> MatchResult:
        """:meth:`scan_encoded` after its length check."""
        chosen = mode or self.plan(B if n_real is None else n_real).mode
        if chosen not in (MODE_SINGLE, MODE_BROADCAST, MODE_ROUTED,
                          MODE_FM):
            raise ValueError(f"unknown scan mode {chosen!r}")
        if (chosen not in (MODE_SINGLE, MODE_FM) and self.mesh is None
                and chosen not in self._executors):  # injected fakes are ok
            raise ValueError(
                f"mode {chosen!r} requires a mesh; this planner has none")
        self._account(chosen, B, n_real)
        self.stats.tier_reads["base"] += 1
        if B == 0:
            z = jnp.zeros((0,), jnp.int32)
            return MatchResult(found=z.astype(bool), count=z,
                               first_rank=z, first_pos=z)
        if chosen == MODE_ROUTED and B not in self._compiled_ahead:
            # a bucket that reaches routed_min_batch also holds fewer real
            # queries, which broadcast: compile that program for it now,
            # where the routed one first runs, not on first use
            self._compiled_ahead.add(B)
            ahead = getattr(self._executor(self._exact_mode()),
                            "compile_ahead", None)
            if ahead is not None:
                ahead(patt, plen)
        # NOTE jax dispatch is async: this span is the launch (enqueue +
        # any host work the executor does); the table's "wait" span pays
        # the device's work when it first forces the result
        with self.tracer.span("dispatch_" + chosen):
            res = self._executor(chosen)(patt, plen)
        if chosen != MODE_ROUTED or not retry:
            return res

        count = np.asarray(res.count)
        # retry negative sentinels, plus any row claiming a match without a
        # usable rank (defensive: rank feeds locate()'s SA-slice gather);
        # bucket padding rows (past ``n_real``) are discarded by the
        # caller, so they are never retried
        rank_bad = (count > 0) & (np.asarray(res.first_rank) < 0)
        if n_real is not None:
            rank_bad[n_real:] = False
            count = count.copy()
            count[n_real:] = np.maximum(count[n_real:], 0)
        bad = np.flatnonzero((count < 0) | rank_bad)
        if bad.size == 0:
            return res
        self.stats.retried_overflow += int((count[bad] == -1).sum())
        self.stats.retried_saturated += int((count[bad] == -2).sum())
        self.stats.retried_inexact_rank += int(rank_bad.sum())
        self.stats.tablet_searches += int(bad.size) * self.num_tablets
        # pad the retry batch to a power-of-two bucket: its size varies
        # per batch, and the jitted exact executor recompiles per shape —
        # bucketing bounds that to log2(B) compilations
        n_bad = int(bad.size)
        bucket = 1 << (n_bad - 1).bit_length() if n_bad > 1 else 1
        take = np.concatenate(
            [bad, np.full(bucket - n_bad, bad[0], bad.dtype)])
        sub = self._executor(self._exact_mode())(
            jnp.asarray(np.asarray(patt)[take]),
            jnp.asarray(np.asarray(plen)[take]))
        found = np.asarray(res.found).copy()
        first_rank = np.asarray(res.first_rank).copy()
        first_pos = np.asarray(res.first_pos).copy()
        count = count.copy()
        # sliced on the host: a device slice would compile once per n_bad
        found[bad] = np.asarray(sub.found)[:n_bad]
        count[bad] = np.asarray(sub.count)[:n_bad]
        first_rank[bad] = np.asarray(sub.first_rank)[:n_bad]
        first_pos[bad] = np.asarray(sub.first_pos)[:n_bad]
        return MatchResult(found=jnp.asarray(found), count=jnp.asarray(count),
                           first_rank=jnp.asarray(first_rank),
                           first_pos=jnp.asarray(first_pos))

    # -- fused multi-tier scan ----------------------------------------------
    def scan_tiers(self, tierset, patt, plen, *,
                   mode: Optional[str] = None, retry: bool = True,
                   n_real: Optional[int] = None
                   ) -> tuple[MatchResult, Optional[TierScanResult]]:
        """Merged read over base + every delta tier of ``tierset`` (a
        ``repro.api.runs.TierSet`` or None).  Returns the MERGED
        MatchResult — exact total counts, text-minimum ``first_pos``,
        base-only ``first_rank`` (docs/table_api.md) — plus the per-tier
        :class:`TierScanResult` for enumeration (None when the base-only
        fast path ran).

        Single-device batches fuse end to end: base binary search, all
        tier scans, straddle masks, and the merge ride ONE jitted launch
        (``kernels.ops.fused_single``).  Mesh batches keep their exact
        sharded base dispatch — with its sentinel retries — and add one
        fused launch for all delta tiers.  Either way a batch crosses
        the layer boundary once, not once per tier.
        """
        B = int(patt.shape[0])
        if tierset is None or tierset.num_tiers == 0 or B == 0:
            res = self.scan_encoded(patt, plen, mode=mode, retry=retry,
                                    n_real=n_real)
            self.stats.base_only_batches += 1
            return res, None
        self._check_plen(plen, B, n_real)
        n_runs = sum(1 for k in tierset.kinds if k == "run")
        chosen = mode or self.plan(B if n_real is None else n_real).mode
        from repro.kernels import ops

        if chosen == MODE_SINGLE:
            self._account(chosen, B, n_real)
            self.stats.tier_reads["base"] += 1
            with self.tracer.span("dispatch_fused"):
                merged, _base, tiers = ops.fused_single(
                    self.store, tierset.stack, patt, plen)
        else:
            # mesh base scan keeps its own dispatch (and sentinel
            # retries) and does the accounting for it; the lengths are
            # checked above
            base = self._scan_checked(patt, plen, B, chosen, retry, n_real)
            with self.tracer.span("dispatch_fused"):
                tiers = ops.fused_tiers(tierset.stack, patt, plen)
            from repro.kernels.tier_scan import merge_tier_results
            merged = merge_tier_results(
                MatchResult(found=jnp.asarray(base.found),
                            count=jnp.asarray(base.count, jnp.int32),
                            first_rank=jnp.asarray(base.first_rank,
                                                   jnp.int32),
                            first_pos=jnp.asarray(base.first_pos,
                                                  jnp.int32)),
                tiers[0], tiers[3])
            if isinstance(base, FMMatchResult):
                # the frozen base's own text-order minimum rides along
                merged = FMMatchResult(**vars(merged),
                                       text_min=base.text_min)
        self.stats.fused_batches += 1
        self.stats.tier_reads["runs"] += n_runs
        self.stats.tier_reads["memtable"] += tierset.num_tiers - n_runs
        # handles stay on device: the count-only path (scan_encoded)
        # never pays the host sync; enumeration converts lazily
        tres = TierScanResult(count=tiers[0], less=tiers[1],
                              matches=tiers[2], first_g=tiers[3])
        return merged, tres

    def host_text_min(self, res: MatchResult,
                      n_real: int) -> Optional[np.ndarray]:
        """The frozen base's text-order minimum of a :meth:`scan_tiers`
        result as a host ``(2, n_real)`` array (``FMMatchResult.
        text_min``), its settled real queries counted in
        ``device_min_settled``; None when another base answered."""
        if not isinstance(res, FMMatchResult):
            return None
        text_min = np.asarray(res.text_min)[:, :n_real]
        self.stats.device_min_settled += int(text_min[1].sum())
        return text_min

    # -- match enumeration --------------------------------------------------
    def _sa(self) -> np.ndarray:
        if self._sa_host is None:
            self._sa_host = np.asarray(self.store.sa)
        return self._sa_host

    def _sa_block_min(self) -> np.ndarray:
        """Block minima of the host SA mirror (``core.rangemin``)."""
        if self._sa_bmin is None:
            self._sa_bmin = block_minima(self._sa())
        return self._sa_bmin

    def locate_encoded(self, patt, plen, top_k: int = 8,
                       *, mode: Optional[str] = None) -> np.ndarray:
        """Up to ``top_k`` occurrence positions per query, (B, top_k) int.

        Positions come from the SA slice ``[lb, lb + min(count, top_k))``
        — suffix-rank order, so position j is the start of the (j+1)-th
        lexicographically smallest matching suffix.  Rows are padded with
        -1 past ``count``.
        """
        res = self.scan_encoded(patt, plen, mode=mode)
        return self.positions_from_result(res, top_k)

    def positions_from_result(self, res: MatchResult,
                              top_k: int = 8) -> np.ndarray:
        """Enumerate positions for an already-exact MatchResult."""
        count = np.asarray(res.count)
        found = np.asarray(res.found)
        first_rank = np.asarray(res.first_rank)
        if self.fm is not None:
            # frozen tier: no SA to slice — LF-walk the SA$ rows
            # [lo, lo + min(count, top_k)) back to text positions
            k = np.arange(max(int(top_k), 1))[None, :]
            rows = first_rank[:, None] + 1 + k           # SA$ row = rank + 1
            valid = ((found & (first_rank >= 0))[:, None]
                     & (k < count[:, None]))
            rows = np.clip(rows, 1, self.fm.n)
            pos = self.fm.ranks_to_positions(
                rows.reshape(-1)).reshape(rows.shape)
            return np.where(valid, pos, -1)[:, :top_k].astype(np.int64)
        sa = self._sa()
        lb = first_rank + self.store.pad_count        # global SA row of lb
        k = np.arange(max(int(top_k), 1))[None, :]
        idx = lb[:, None] + k
        # a row without a usable rank cannot be enumerated — never emit
        # garbage SA gathers (scan_encoded's retry makes this unreachable
        # for its callers, but the method is public)
        valid = (found & (first_rank >= 0))[:, None] & (k < count[:, None])
        idx = np.clip(idx, 0, sa.shape[0] - 1)
        return np.where(valid, sa[idx], -1)[:, :top_k].astype(np.int64)

    # -- string-level API with LRU cache ------------------------------------
    def encode(self, patterns: list[str]):
        """Encode pattern strings for :meth:`scan_encoded`: (patt, plen)
        as host numpy arrays — the batch crosses to the device once, where
        it is dispatched.

        Packed uint32 words for DNA stores (word-packing rounds the width
        up to a 16-base multiple), exact-width int32 codes otherwise.
        Raises on any pattern longer than ``max_pattern_len`` — compares
        are depth-capped, so a longer pattern would silently match on its
        truncated prefix.
        """
        return codec.encode_pattern_batch(patterns, self.max_pattern_len,
                                          packed=self.store.is_dna)

    # back-compat alias (pre-api_redesign name)
    _encode = encode

    def scan(self, patterns: list[str], top_k: int = 0) -> ScanOutcome:
        """Scan a batch of pattern strings; exact counts, optional
        enumeration, LRU-cached per pattern (top_k-aware: see
        :class:`TopKCache`)."""
        B = len(patterns)
        count = np.full(B, -1, np.int64)
        first_pos = np.full(B, -1, np.int64)
        positions = (np.full((B, top_k), -1, np.int64) if top_k else None)
        miss_idx: list[int] = []
        for i, pat in enumerate(patterns):
            hit = self._cache.get(pat, top_k)
            if hit is not None:
                count[i], first_pos[i] = hit[0], hit[1]
                if top_k:
                    positions[i] = hit[2]
            else:
                miss_idx.append(i)
        self.stats.cache_hits += B - len(miss_idx)
        self.stats.cache_misses += len(miss_idx)

        if miss_idx:
            patt, plen = self.encode([patterns[i] for i in miss_idx])
            res = self.scan_encoded(patt, plen)
            sub_count = np.asarray(res.count)
            sub_first = np.asarray(res.first_pos)
            sub_pos = (self.positions_from_result(res, top_k)
                       if top_k else None)
            for j, i in enumerate(miss_idx):
                count[i] = sub_count[j]
                first_pos[i] = sub_first[j]
                row = sub_pos[j] if top_k else None
                if top_k:
                    positions[i] = row
                self._cache.put(patterns[i], int(sub_count[j]),
                                int(sub_first[j]), top_k, row)
        return ScanOutcome(found=count > 0, count=count,
                           first_pos=first_pos, positions=positions)

    def locate(self, patterns: list[str], top_k: int = 8) -> np.ndarray:
        """String-level enumeration: (B, top_k) positions, -1 padded."""
        return self.scan(patterns, top_k=top_k).positions

    def clear_cache(self) -> None:
        self._cache.clear()

    def reset_stats(self) -> None:
        self.stats = PlannerStats()
