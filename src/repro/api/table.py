"""``SuffixTable`` — the Bigtable-style table facade over the whole store.

The paper's deliverable is not a function but a *table*: a durable, named
suffix index you open, scan, and mutate (Accumulo gives Randazzo & Rombo
and Wu et al. the same thing).  This module is that single public entry
point; callers no longer hand-wire ``build_tablet_store`` + ``ScanPlanner``
+ mesh plumbing:

* :meth:`SuffixTable.create` builds the suffix array (distributed over the
  local mesh when more than one device is visible) and persists it through
  ``CheckpointManager``-style atomic versioned files;
* :meth:`SuffixTable.open` restores a table on **any** device count — the
  persisted real-row suffix array is re-padded for the local tablet count
  and the right mesh/planner are constructed internally;
* reads (:meth:`count` / :meth:`contains` / :meth:`scan` / :meth:`locate`)
  delegate to the :class:`~repro.core.planner.ScanPlanner` for the base
  index and merge in the LSM delta tiers (below);
* the write path is a real LSM stack **paired with a commit log**
  (:mod:`repro.api.wal`, Bigtable's memtable+log discipline): every
  :meth:`append` on a persistent table is CRC-framed, fsync'd, and only
  then acked, so acknowledged writes survive crashes — :meth:`open`
  replays the live log tail through the normal memtable path and
  reports a recovery summary in :meth:`stats`; :meth:`append` lands
  codes in a single-device :class:`~repro.api.memtable.Memtable`;
  :meth:`minor_compact` seals the memtable into an immutable, persisted
  :class:`~repro.api.runs.Run` (automatic at ``memtable_limit``); reads
  fan out to base + runs + memtable and merge exact counts and positions,
  each tier owning the occurrences that END in its region (the per-run
  generalization of the ``g + plen > n_base`` straddle rule —
  docs/table_api.md); :meth:`compact` (major compaction) folds runs and
  memtable into the base SA **by merging** — prefix doubling over only
  the dirty suffix range plus a batched window-compare merge
  (:mod:`repro.api.compaction`), never a from-scratch rebuild — and bumps
  the persisted version; :meth:`flush` makes un-compacted state durable.

Multiple named tables live in one root directory under a
:class:`~repro.api.catalog.Catalog` (Accumulo's METADATA analogue).
"""
from __future__ import annotations

import os
import re
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

import time

from repro.api.compaction import merge_delta_sa
from repro.api.memtable import Memtable
from repro.api.runs import Run, TierSet, logical_tail
from repro.api.wal import WriteAheadLog
from repro.checkpoint.manager import CheckpointManager
from repro.core import codec
from repro.core.build_pipeline import BuildStats, chunk_rows_for_budget, \
    distributed_build_stats, in_memory_build_stats, staged_suffix_array
from repro.core.dsa import build_suffix_array_distributed
from repro.core.planner import ScanOutcome, ScanPlanner, TopKCache
from repro.core.query import MatchResult
from repro.core.rangemin import range_min, range_smallest
from repro.serving.metrics import MetricsEmitter, table_record
from repro.serving.trace import Tracer
from repro.core.suffix_array import build_suffix_array
from repro.core.tablet import TabletStore, place_on_mesh, store_from_arrays
from repro.launch.mesh import make_tablet_mesh

# no leading dot: forbids '.', '..' (path traversal — drop_table rmtree's
# the name under root) and hidden-file collisions; 'catalog.json' is the
# catalog's own metadata file
_NAME_RE = re.compile(r"(?!\.)[A-Za-z0-9._-]{1,128}")
_RESERVED_NAMES = frozenset({"catalog.json"})


def default_root() -> str:
    """Root directory for persisted tables (``REPRO_TABLE_ROOT`` env var,
    falling back to ``./repro_tables``)."""
    return os.environ.get("REPRO_TABLE_ROOT", "repro_tables")


def _check_name(name: str) -> str:
    if not _NAME_RE.fullmatch(name or "") or name in _RESERVED_NAMES:
        raise ValueError(f"table name {name!r} must match "
                         f"{_NAME_RE.pattern} and not be reserved "
                         f"(it becomes a directory under the root)")
    return name


def _as_codes(codes, is_dna: Optional[bool]):
    """Normalize input text: DNA strings/bytes become uint8 codes.

    DNA is only *inferred* for uint8 arrays (what ``codec.encode_dna`` /
    ``random_dna`` produce).  Any other integer dtype defaults to the
    generic token path — a small-vocab token corpus must not silently
    take the packed 2-bit codec; pass ``is_dna=True`` explicitly to opt
    a non-uint8 code array into it."""
    if isinstance(codes, (str, bytes, bytearray)):
        return codec.encode_dna(codes), True
    codes = np.asarray(codes)
    if is_dna is None:
        is_dna = bool(codes.size > 0 and codes.dtype == np.uint8
                      and codes.max() < 4)
    return codes, bool(is_dna)


def _named_arrays(arrays: dict) -> dict:
    """Strip ``_flatten`` path decoration: ``"['codes']"`` -> ``"codes"``."""
    return {re.sub(r"[^0-9A-Za-z_]", "", k): v for k, v in arrays.items()}


class SuffixTable:
    """A named, versioned, mutable suffix-array table.

    Construct through :meth:`create` / :meth:`open` (persistent) or
    :meth:`from_codes` / :meth:`from_store` (in-memory); the constructor
    itself wires the runtime (store + mesh + planner) for the *current*
    device count from host arrays.
    """

    def __init__(self, codes: np.ndarray, sa_real: np.ndarray, *,
                 is_dna: bool, max_query_len: int = 128,
                 name: Optional[str] = None, root: Optional[str] = None,
                 version: int = 0, cache_size: int = 4096, keep_n: int = 3,
                 capacity_factor: float = 2.0, routed_min_batch: int = 64,
                 memtable_limit: Optional[int] = None,
                 max_runs: Optional[int] = None,
                 distributed_build: Optional[bool] = None,
                 wal: Optional[bool] = None,
                 group_commit_ms: float = 0.0,
                 fm_threshold: Optional[int] = None,
                 _store: Optional[TabletStore] = None,
                 _planner: Optional[ScanPlanner] = None,
                 _fm=None, _tracer: Optional[Tracer] = None):
        self.name = name
        self.root = root
        self.version = int(version)
        self.is_dna = bool(is_dna)
        self.max_query_len = int(max_query_len)
        self.keep_n = int(keep_n)
        self.capacity_factor = float(capacity_factor)
        self.routed_min_batch = int(routed_min_batch)
        self.cache_size = int(cache_size)
        self.memtable_limit = memtable_limit
        self.max_runs = max_runs
        self.fm_threshold = fm_threshold
        self.fm = None
        self.runs: list[Run] = []
        self._codes = np.asarray(codes)
        # span histograms (stats()["latency"]): created before the
        # planner so freeze/compaction rebinds keep one shared tracer;
        # create() passes the one its build spans went to
        self.tracer = _tracer if _tracer is not None else Tracer("table")
        self._metrics: Optional[MetricsEmitter] = None

        if _store is not None:                       # from_store: adopt as-is
            self.mesh = _planner.mesh if _planner is not None else None
            self.store = _store
            self.planner = _planner or ScanPlanner(
                _store, cache_size=cache_size,
                capacity_factor=capacity_factor,
                routed_min_batch=routed_min_batch, tracer=self.tracer)
            if _planner is not None:
                self.tracer = _planner.tracer        # adopt its histograms
        elif _fm is not None:                        # open(): frozen tier
            self.mesh = None
            self._attach_frozen(_fm)
        else:
            n_dev = len(jax.devices())
            self.mesh = make_tablet_mesh(n_dev) if n_dev > 1 else None
            self._attach(self._codes, np.asarray(sa_real, np.int32))
        self._distributed_build = (self.mesh is not None
                                   if distributed_build is None
                                   else bool(distributed_build))
        self.memtable = Memtable(self._codes, is_dna=self.is_dna,
                                 max_query_len=self.max_query_len)
        # cached TierSet snapshot for the fused read path; rebuilt lazily
        # after any write changes the tier population (docs/read_path.md)
        self._tiers: Optional[TierSet] = None
        self._tiers_valid = False
        self._cache = TopKCache(cache_size)
        self._manager: Optional[CheckpointManager] = None
        if self.root is not None and self.name is not None:
            self._manager = CheckpointManager(
                os.path.join(self.root, self.name), keep_n=self.keep_n)
        # commit log (repro.api.wal): defaults ON for persistent tables;
        # attached by create()/open() — after the snapshot exists, so the
        # log only ever covers appends the snapshot does not
        if wal and self._manager is None:
            raise ValueError("wal=True needs a persistent table (create/"
                             "open with a root); in-memory tables have "
                             "nothing to recover into")
        self._wal_on = (self._manager is not None) if wal is None else wal
        self.group_commit_ms = float(group_commit_ms)
        self._wal: Optional[WriteAheadLog] = None
        self._wal_seq = 0            # seq of the last logged/applied append
        self._recovery: Optional[dict] = None
        self._replaying = False
        # construction telemetry (stats()["build"]); set by create()/
        # from_codes()/open(), persisted across versions
        self._build: Optional[BuildStats] = None

    # -- construction --------------------------------------------------------
    @classmethod
    def from_codes(cls, codes, *, is_dna: Optional[bool] = None,
                   max_query_len: int = 128, **kw) -> "SuffixTable":
        """In-memory table (no persistence): build over ``codes`` now,
        distributed over the local mesh when >1 device is visible."""
        codes, is_dna = _as_codes(codes, is_dna)
        tracer = Tracer("table")
        sa, build = cls._build_sa_for(codes, tracer)
        table = cls(codes, sa, is_dna=is_dna,
                    max_query_len=max_query_len, _tracer=tracer, **kw)
        table._build = build
        table._maybe_freeze()
        return table

    @classmethod
    def from_store(cls, store: TabletStore, *,
                   planner: Optional[ScanPlanner] = None,
                   **kw) -> "SuffixTable":
        """Wrap an existing :class:`TabletStore` (deprecation shim for
        pre-table callers).  The store and optional planner are adopted
        unchanged; appends and merged reads work, persistence needs
        :meth:`create`."""
        codes = np.asarray(store.text_codes[:store.n_real])
        if store.is_dna:
            codes = codes.astype(np.uint8)
        return cls(codes, None, is_dna=store.is_dna,
                   max_query_len=store.max_query_len,
                   _store=store, _planner=planner, **kw)

    @classmethod
    def create(cls, name: str, codes, *, root: Optional[str] = None,
               is_dna: Optional[bool] = None, max_query_len: int = 128,
               overwrite: bool = False, staged: Optional[bool] = None,
               max_device_bytes: Optional[int] = None,
               spill_dir: Optional[str] = None,
               build_chunk_rows: Optional[int] = None,
               shard_rows: Optional[int] = None, **kw) -> "SuffixTable":
        """Build AND persist version 1 of a named table under ``root``,
        registering it in the root's :class:`Catalog`.

        Two build paths, bit-identical results (docs/build_pipeline.md):
        the default in-memory builder, and — when ``staged=True`` or any
        of ``max_device_bytes`` / ``spill_dir`` / ``build_chunk_rows`` is
        given — the out-of-core staged pipeline, which sorts in
        device-budgeted chunks, spills working state to host RAM or
        ``spill_dir``, and streams finished SA shards of ``shard_rows``
        rows straight into the snapshot (register -> stream shards ->
        publish atomically), so the full array is never resident during
        construction.

        Crash-safe ordering: the catalog entry is written BEFORE the
        snapshot, so a create that dies mid-persist (or mid-shard-stream)
        leaves a *visible* registered-but-empty table rather than an
        invisible orphan directory; ``Catalog.reconcile`` (run on every
        catalog open) and a later ``create`` of the same name both
        garbage-collect such remnants (no published snapshot) instead of
        refusing."""
        import shutil
        from repro.api.catalog import Catalog
        _check_name(name)
        root = root or default_root()
        catalog = Catalog(root)
        table_dir = os.path.join(root, name)
        if name in catalog or os.path.isdir(table_dir):
            # only a PUBLISHED snapshot makes the table real; a bare dir
            # or catalog entry is a crashed create's remnant — reconcile
            has_snapshot = (os.path.isdir(table_dir) and
                            CheckpointManager(table_dir).latest_step()
                            is not None)
            if has_snapshot and not overwrite:
                raise FileExistsError(
                    f"table {name!r} already exists in {root!r} — "
                    f"SuffixTable.open() it, or pass overwrite=True")
            # drop stale snapshots: a survivor with a higher step would
            # shadow (or GC) the fresh version-1 save below
            shutil.rmtree(table_dir, ignore_errors=True)
        codes, is_dna = _as_codes(codes, is_dna)
        if staged is None:
            staged = (max_device_bytes is not None or spill_dir is not None
                      or build_chunk_rows is not None)
        if staged:
            return cls._create_staged(
                name, codes, root=root, catalog=catalog, is_dna=is_dna,
                max_query_len=max_query_len,
                max_device_bytes=max_device_bytes, spill_dir=spill_dir,
                build_chunk_rows=build_chunk_rows, shard_rows=shard_rows,
                **kw)
        tracer = Tracer("table")
        sa, build = cls._build_sa_for(codes, tracer)
        table = cls(codes, sa, is_dna=is_dna, max_query_len=max_query_len,
                    name=name, root=root, version=1, _tracer=tracer, **kw)
        table._build = build
        catalog.register(name, {"is_dna": table.is_dna,
                                "max_query_len": table.max_query_len})
        table._persist()
        table._maybe_freeze()       # fm_threshold policy; re-persists frozen
        table._open_wal(fresh=True)
        return table

    @classmethod
    def _create_staged(cls, name: str, codes: np.ndarray, *, root: str,
                       catalog, is_dna: bool, max_query_len: int,
                       max_device_bytes: Optional[int],
                       spill_dir: Optional[str],
                       build_chunk_rows: Optional[int],
                       shard_rows: Optional[int], **kw) -> "SuffixTable":
        """The out-of-core create: staged chunked build
        (``core.build_pipeline``) with SA shards streamed into a
        :class:`~repro.checkpoint.manager.ShardedSave` as they finish,
        published atomically, then reopened through the normal
        :meth:`open` path (which re-attaches wal/fm policy)."""
        chunk_rows = (int(build_chunk_rows) if build_chunk_rows
                      else chunk_rows_for_budget(max_device_bytes))
        if shard_rows is None:
            shard_rows = chunk_rows
        n_dev = len(jax.devices())
        mesh = make_tablet_mesh(n_dev) if n_dev > 1 else None
        mgr = CheckpointManager(os.path.join(root, name),
                                keep_n=int(kw.get("keep_n", 3)))
        catalog.register(name, {"is_dna": is_dna,
                                "max_query_len": max_query_len})
        stage = mgr.stage_sharded(1)
        try:
            _, stats = staged_suffix_array(
                codes, chunk_rows=chunk_rows,
                max_device_bytes=max_device_bytes, spill_dir=spill_dir,
                mesh=mesh, axis_name="tablets", shard_rows=shard_rows,
                emit_shard=lambda i, blk: stage.add_shard("sa_real", i,
                                                          blk))
            if "sa_real" not in stage._shards:   # empty corpus: no shards
                stage.add_shard("sa_real", 0, np.zeros((0,), np.int32))
            state = {"codes": codes,
                     "mem_codes": np.zeros((0,), codes.dtype)}
            extra = {"kind": "suffix_table", "name": name, "version": 1,
                     "is_dna": is_dna, "max_query_len": max_query_len,
                     "n_base": int(len(codes)), "runs": [], "mem_len": 0,
                     "wal_seq": 0, "frozen": False, "fm_sample_rate": None,
                     "build": stats.to_dict()}
            stage.commit(state, extra)
        except BaseException:
            stage.abort()
            raise
        return cls.open(name, root=root, **kw)

    @classmethod
    def open(cls, name: str, *, root: Optional[str] = None,
             **kw) -> "SuffixTable":
        """Restore the latest persisted version of ``name`` on the current
        device count (the saved SA is re-padded; no rebuild).  Sealed runs
        and un-compacted appends saved by :meth:`flush` /
        :meth:`minor_compact` are restored too — run indexes come back
        frozen from disk, never re-sorted."""
        _check_name(name)
        root = root or default_root()
        table_dir = os.path.join(root, name)
        if not os.path.isdir(table_dir):        # before CheckpointManager:
            raise FileNotFoundError(            # its ctor mkdirs the path
                f"no table {name!r} under {root!r}")
        mgr = CheckpointManager(table_dir)
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"no persisted version of table {name!r} under {root!r}")
        arrays, extra = mgr.restore_arrays(step)
        arrays = _named_arrays(arrays)
        fm = None
        if extra.get("frozen"):
            from repro.api.catalog import table_fm_dir
            from repro.api.fm import FMIndex
            fm = FMIndex.load(table_fm_dir(root, name))
            if fm is None or fm.n != int(arrays["codes"].shape[0]):
                # artifact missing/stale (partial copy, old format):
                # rebuild from codes — freeze state survives, bit-exactly
                fm = FMIndex.build(
                    arrays["codes"], None, is_dna=bool(extra["is_dna"]),
                    sample_rate=int(extra.get("fm_sample_rate") or 32))
        table = cls(arrays["codes"], arrays["sa_real"],
                    is_dna=bool(extra["is_dna"]),
                    max_query_len=int(extra["max_query_len"]),
                    name=name, root=root, version=int(extra["version"]),
                    _fm=fm, **kw)
        if extra.get("build"):
            table._build = BuildStats.from_dict(extra["build"])
        for i, rm in enumerate(extra.get("runs", [])):
            table.runs.append(Run.restore(
                arrays[f"run{i}_tail"], arrays[f"run{i}_codes"],
                arrays.get(f"run{i}_sa"), start=int(rm["start"]),
                is_dna=table.is_dna, max_query_len=table.max_query_len))
        if table.runs:
            table._reset_memtable()
        mem = arrays.get("mem_codes")
        if mem is not None and mem.size:
            table.memtable.append(mem)
        # crash recovery: replay the commit-log tail (appends acked after
        # this snapshot was published) through the normal memtable path
        table._wal_seq = int(extra.get("wal_seq", 0))
        table._open_wal(fresh=False)
        table._maybe_freeze()       # threshold may be new on this open
        return table

    @staticmethod
    def _build_sa_for(codes: np.ndarray, tracer: Optional[Tracer] = None
                      ) -> tuple[np.ndarray, BuildStats]:
        """Real-row SA over ``codes`` and what building it took —
        distributed over the local mesh when >1 device is visible (its
        compile and rounds timed as ``tracer``'s spans), single-device
        otherwise."""
        t0 = time.perf_counter()
        n_dev = len(jax.devices())
        if n_dev > 1:
            sa, pad, run = build_suffix_array_distributed(
                codes, make_tablet_mesh(n_dev), "tablets", tracer=tracer)
            sa = np.asarray(sa)[pad:]
            return sa, distributed_build_stats(
                len(codes), time.perf_counter() - t0, run)
        sa = np.asarray(build_suffix_array(codes.astype(np.int32)))
        return sa, in_memory_build_stats(len(codes),
                                         time.perf_counter() - t0)

    def _attach(self, codes: np.ndarray, sa_real: np.ndarray) -> None:
        """(Re)build the runtime store for the current mesh.  An existing
        planner is re-bound IN PLACE (not replaced): captured references
        — the serving engine holds one — keep serving the post-compaction
        text, and accumulated planner stats survive."""
        from repro.distributed.sharding import mesh_axis_size
        p = mesh_axis_size(self.mesh)
        self.store = store_from_arrays(
            codes, sa_real, is_dna=self.is_dna,
            max_query_len=self.max_query_len, num_tablets=p)
        if self.mesh is not None:
            self.store = place_on_mesh(self.store, self.mesh)
        planner = getattr(self, "planner", None)
        if planner is None:
            self.planner = ScanPlanner(
                self.store, mesh=self.mesh, cache_size=self.cache_size,
                capacity_factor=self.capacity_factor,
                routed_min_batch=self.routed_min_batch,
                tracer=self.tracer)
        else:
            planner.rebind(self.store)          # also drops any FM binding
        self.fm = None

    def _attach_frozen(self, fm) -> None:
        """Swap the base tier onto the FM-index: base reads route through
        the backward-search kernel and the raw SA (device array + host
        mirror + packed text) is DROPPED — that is the footprint win.  A
        metadata-only store keeps the shape facts (``n_real``/``n_pad``/
        codecs) the planner and delta tiers read; the raw host codes stay
        (memtable overlap windows, compaction, persistence all need
        them).  Frozen tables serve single-replica — an active mesh is
        released."""
        if fm.n != self.n_base or fm.is_dna != self.is_dna:
            raise ValueError(
                f"FM-index (n={fm.n}, is_dna={fm.is_dna}) does not match "
                f"the table (n={self.n_base}, is_dna={self.is_dna})")
        self.fm = fm
        self.mesh = None
        self.store = TabletStore(
            text_packed=None, text_codes=None,
            sa=jnp.zeros((0,), jnp.int32),
            n_real=self.n_base, n_pad=self.n_base,
            is_dna=self.is_dna, max_query_len=self.max_query_len)
        planner = getattr(self, "planner", None)
        if planner is None:
            self.planner = ScanPlanner(
                self.store, cache_size=self.cache_size,
                capacity_factor=self.capacity_factor,
                routed_min_batch=self.routed_min_batch, fm=fm,
                tracer=self.tracer)
        else:
            planner.rebind(self.store, fm=fm)

    # -- introspection -------------------------------------------------------
    def __len__(self) -> int:
        """Total indexed symbols: base + sealed runs + memtable."""
        return self.n_logical + self.memtable.size

    @property
    def n_base(self) -> int:
        return int(self._codes.shape[0])

    @property
    def n_logical(self) -> int:
        """Symbols covered by the immutable tiers (base + sealed runs) —
        the memtable's boundary."""
        return self.n_base + sum(r.length for r in self.runs)

    @property
    def is_persistent(self) -> bool:
        return self._manager is not None

    @property
    def is_frozen(self) -> bool:
        """True when the base tier serves from the FM-index."""
        return self.fm is not None

    @property
    def write_generation(self) -> int:
        """Monotone counter bumped by every write (``append`` /
        ``minor_compact`` / ``compact``) — the staleness stamp for
        cached results (``ReadSession`` re-enumerates only when this
        moves)."""
        return self._cache.generation

    def stats(self) -> dict:
        """Observability snapshot with a STABLE schema (docs/client_api.md
        documents every key; serve.py prints it):

        * ``name`` / ``version`` / ``is_dna`` / ``max_query_len`` —
          identity;
        * ``tiers`` — ``base_rows``, ``run_count``, ``run_rows``,
          ``memtable_rows`` (the LSM stack, in symbols);
        * ``cache`` — the table-level string-result cache: ``entries``,
          ``hits``, ``misses``, ``generation`` (bumped by every write);
        * ``planner`` — ``PlannerStats.as_dict()``: batches, queries,
          mode counts, retry counters, and the bucketed-batch slot
          accounting (``bucketed_batches`` / ``bucketed_queries`` /
          ``pad_slots``) fed by the client frontend, plus the fused
          read-path counters ``fused_batches`` / ``base_only_batches``
          / ``tier_reads`` (docs/read_path.md).  (True cross-caller
          coalescing counters live in ``Database.stats()["scheduler"]``.)
        * ``build`` — how the base index was constructed (``None`` for
          adopted stores): ``mode`` (``"staged"``/``"in_memory"``/
          ``"distributed"``), ``rounds``, ``n_chunks``, ``chunk_rows``,
          ``peak_device_bytes``, ``spill_bytes``, ``elapsed_s``,
          ``bases_per_s``, ``compile_s``, ``devices`` — the
          :class:`~repro.core.build_pipeline.BuildStats` schema,
          persisted with the table
          (docs/build_pipeline.md);
        * ``latency`` — rolling span histograms from the table's
          :class:`~repro.serving.trace.Tracer` (``encode`` /
          ``dispatch`` / ``merge`` / ``total`` plus the planner's
          ``dispatch_*`` modes), each ``{p50_ms, p95_ms, p99_ms, n,
          total, sum_ms}`` — docs/observability.md defines every span;
        * ``wal`` — durability: ``enabled``, ``seq`` (last append's
          commit sequence), ``log`` (appends/fsyncs/seals counters, or
          ``None`` with no log), and ``recovery`` — ``None`` on a clean
          open, otherwise the last recovery summary
          (``records_replayed`` / ``records_skipped`` / ``torn_bytes`` /
          ``reason`` — docs/table_api.md gives the full schema).

        New keys may be added; existing keys keep their meaning."""
        return {
            "name": self.name,
            "version": self.version,
            "is_dna": self.is_dna,
            "max_query_len": self.max_query_len,
            "tiers": {
                "base_rows": self.n_base,
                "run_count": len(self.runs),
                "run_rows": self.n_logical - self.n_base,
                "memtable_rows": self.memtable.size,
                "frozen": self.fm is not None,
                "resident_bytes": self._resident_bytes(),
            },
            "cache": {
                "entries": len(self._cache),
                "hits": self._cache.hits,
                "misses": self._cache.misses,
                "generation": self._cache.generation,
            },
            "build": (self._build.to_dict() if self._build is not None
                      else None),
            "planner": self.planner.stats.as_dict(),
            "latency": self.tracer.snapshot(),
            "wal": {
                "enabled": self._wal is not None,
                "seq": self._wal_seq,
                "log": (self._wal.stats() if self._wal is not None
                        else None),
                "recovery": self._recovery,
            },
        }

    def _resident_bytes(self) -> dict:
        """Per-tier index footprint in bytes (the ``stats()["tiers"]
        ["resident_bytes"]`` schema, docs/storage_tiers.md).  ``base_sa``
        counts the device SA plus the lazily-materialized host mirror;
        ``text_device`` the packed/padded device text; both drop to 0 on
        a frozen table, where ``fm`` carries the compressed index
        instead.  ``text_host`` (the raw 1 B/sym code array every table
        keeps for compaction and memtable overlap) is reported separately
        so the index-vs-index comparison stays clean."""
        base_sa = int(self.store.sa.size) * 4
        if self.planner._sa_host is not None:
            base_sa += int(self.planner._sa_host.nbytes)
        text_dev = 0
        if self.store.text_packed is not None:
            text_dev += int(self.store.text_packed.size) * 4
        if self.store.text_codes is not None:
            text_dev += int(self.store.text_codes.size) * 4
        run_bytes = 0
        for r in self.runs:
            run_bytes += int(np.asarray(r.tail).nbytes)
            run_bytes += int(np.asarray(r.codes).nbytes)
            sa_p = getattr(r, "sa_padded", None)
            if sa_p is not None:
                run_bytes += int(np.asarray(sa_p).nbytes)
        return {
            "base_sa": base_sa,
            "fm": self.fm.resident_bytes() if self.fm is not None else 0,
            "text_device": text_dev,
            "runs": run_bytes,
            "memtable": int(self.memtable.size),
            "text_host": int(self._codes.nbytes),
        }

    def _invalidate_caches(self) -> None:
        """Generation-bump the table AND planner string-result caches —
        the logical text just changed, so any cached count/top-k from
        before this write must never be served again (previously the
        planner's own cache was left stale across table writes)."""
        self._cache.bump()
        self.planner.invalidate_cache()
        self._tiers = None
        self._tiers_valid = False

    def clear_cache(self) -> None:
        """Drop all cached string-scan results (benchmarks use this to
        time cold reads)."""
        self._cache.clear()
        self.planner.clear_cache()

    def _reset_memtable(self) -> None:
        """Fresh empty memtable whose overlap window is the tail of the
        current logical text (base + sealed runs)."""
        if not self.runs:
            self.memtable = Memtable(self._codes, is_dna=self.is_dna,
                                     max_query_len=self.max_query_len)
            self._tiers = None
            self._tiers_valid = False
            return
        n = self.n_logical
        tail = logical_tail([self._codes] + [r.codes for r in self.runs],
                            min(self.max_query_len - 1, n))
        self.memtable = Memtable(tail.astype(self._codes.dtype, copy=False),
                                 is_dna=self.is_dna,
                                 max_query_len=self.max_query_len, n_base=n)
        self._tiers = None
        self._tiers_valid = False

    def _sa(self) -> np.ndarray:
        # the planner already caches a host copy of the same store.sa —
        # don't materialize a second one per table
        return self.planner._sa()

    # -- read path -----------------------------------------------------------
    def _tierset(self) -> Optional[TierSet]:
        """The cached delta-tier snapshot for the fused read path — None
        when there are no delta tiers (the base-only fast path).
        Rebuilt lazily after any write that changes the tier population
        (append / seal / compaction / restore all invalidate it)."""
        if not self._tiers_valid:
            self._tiers = TierSet.build(self.runs, self.memtable)
            self._tiers_valid = True
        return self._tiers

    def _scan_tiers(self, patt, plen, *, mode=None, n_real=None):
        """One fused merged dispatch and the wait for its result, as host
        arrays of the first ``n_real`` rows: (merged count, base-SA
        ``first_rank``, base-only count, the delta tiers' ``(less,
        matches)`` | None — see :meth:`_delta` —, the frozen base's
        ``text_min`` | None — see :meth:`_base_min_positions`)."""
        merged, tres = self.planner.scan_tiers(
            self._tierset(), patt, plen, mode=mode, n_real=n_real)
        B = len(plen) if n_real is None else int(n_real)
        # the first host conversions force the launch: the device's work
        # and the copy back land in this span
        with self.tracer.span("wait"):
            count = np.asarray(merged.count).astype(np.int64)[:B]
            base_rank = np.asarray(merged.first_rank)[:B]
            text_min = self.planner.host_text_min(merged, B)
            if tres is None:
                return count, base_rank, count, None, text_min
            tcount = np.asarray(tres.count)
            tiers = (np.asarray(tres.less), np.asarray(tres.matches))
        base_count = count - tcount[:, :B].astype(np.int64).sum(axis=0)
        return count, base_rank, base_count, tiers, text_min

    def _delta(self, tiers, plen, B: int):
        """Per query, the ascending delta-tier positions (None without
        delta tiers) from :meth:`_scan_tiers`'s ``(less, matches)``."""
        if tiers is None:
            return None
        return self._tiers.delta_positions(*tiers, plen, n_real=B)

    def _base_rows(self):
        """(row -> text position getter, its block minima, row offset of
        real-SA rank 0) for the base tier — the host SA mirror, or on a
        frozen table LF walks over ``SA$`` (real rank r is row r + 1)."""
        if self.fm is not None:
            return self.fm.ranks_to_positions, self.fm.row_min, 1
        sa = self._sa()
        return sa.__getitem__, self.planner._sa_block_min(), \
            self.store.pad_count

    def _base_min_positions(self, base_count, base_rank,
                            text_min=None) -> np.ndarray:
        """Per query, the smallest BASE text position among its base-tier
        matches (-1 when none) — the text-order ``first_pos`` reduction
        over the rows ``[lb, lb + count)``, bounded by block minima
        (``core.rangemin``) instead of gathering every match.  On a
        frozen table the FM launch already settled most queries
        (``text_min``, :meth:`ScanPlanner.host_text_min`); only the rest
        take the host's LF walks."""
        get, bmin, off = self._base_rows()
        count = np.where(base_rank >= 0, base_count, 0)
        if text_min is None:
            return range_min(get, bmin, off + base_rank.astype(np.int64),
                             count)
        settled = text_min[1] != 0
        rest = range_min(get, bmin, off + base_rank.astype(np.int64),
                         np.where(settled, 0, count))
        return np.where(settled, text_min[0].astype(np.int64), rest)

    def scan_encoded(self, patt, plen, *, mode: Optional[str] = None
                     ) -> MatchResult:
        """Exact merged scan of an encoded batch (see ``ScanPlanner.
        scan_encoded`` for encodings).  With no runs and an empty memtable
        this is a pure base delegation; otherwise the fused tier scan
        (``ScanPlanner.scan_tiers``) adds the run/memtable-only
        occurrences in the same launch and ``first_pos`` is the smallest
        of the base's reported position and every delta-tier occurrence
        position.  ``first_rank`` always refers to the BASE suffix array
        (−1 when the only matches are in the delta tiers) — do not feed a
        merged result to ``planner.positions_from_result``, use
        :meth:`scan`/:meth:`locate` for merged enumeration."""
        merged, _tres = self.planner.scan_tiers(self._tierset(), patt,
                                                plen, mode=mode)
        return merged

    def _base_smallest(self, base_count, base_rank, i, k) -> np.ndarray:
        """The ``k`` smallest base-tier text positions of row ``i``'s
        matches, ascending."""
        if base_rank[i] < 0:
            return np.zeros((0,), np.int64)
        get, bmin, off = self._base_rows()
        return range_smallest(get, bmin, off + int(base_rank[i]),
                              int(base_count[i]), k)

    def _base_slice(self, base_count, base_rank, i) -> np.ndarray:
        """Base-tier SA slice of row ``i``'s matches (text positions,
        unsorted — suffix-rank order)."""
        cb = int(base_count[i])
        if cb <= 0 or base_rank[i] < 0:
            return np.zeros((0,), np.int64)
        lb = self.store.pad_count + int(base_rank[i])
        if self.fm is not None:
            rows = np.arange(lb + 1, lb + 1 + cb)     # SA$ rows of the run
            return self.fm.ranks_to_positions(rows).astype(np.int64)
        return self._sa()[lb:lb + cb].astype(np.int64)

    def scan_batch(self, patt, plen, top_k: int = 0) -> ScanOutcome:
        """Merged scan of an encoded batch with **text-order** semantics
        — the client frontend's batch entry point (no string cache).

        The batch is padded to a power-of-two bucket (row 0 repeated)
        before the fused merged dispatch, so coalesced batches of varying
        size reuse O(log B) compilations instead of one per size; pad
        slots are discarded here and attributed to
        ``planner.stats.pad_slots`` (slot accounting under
        ``bucketed_batches``), never to ``queries``.
        """
        B = len(plen)
        if B == 0:
            return ScanOutcome(
                found=np.zeros(0, bool), count=np.zeros(0, np.int64),
                first_pos=np.full(0, -1, np.int64),
                positions=(np.full((0, top_k), -1, np.int64)
                           if top_k else None))
        tr = self.tracer
        t_all = time.monotonic_ns()
        # "dispatch" is the device read: "upload" (bucket padding of the
        # host arrays; device inputs are copied back first), the
        # planner's "plen_check" (a host maximum) and "dispatch_<mode>"
        # (the launch, which carries the batch's one host-to-device
        # crossing: on the chip, host arrays handed to the jitted
        # executor cost less than a device_put before it) and "wait" (the
        # device's work and the copy back, forced by _scan_tiers' first
        # host conversions); "merge" below is pure host-side reduction
        with tr.span("dispatch"):
            with tr.span("upload"):
                if isinstance(patt, jax.Array) or isinstance(plen, jax.Array):
                    self.planner.stats.input_copybacks += 1
                patt_np, plen_np = np.asarray(patt), np.asarray(plen)
                bucket = 1 << (B - 1).bit_length() if B > 1 else 1
                if bucket != B:
                    reps = bucket - B
                    patt_np = np.concatenate(
                        [patt_np, np.repeat(patt_np[:1], reps, axis=0)])
                    plen_np = np.concatenate(
                        [plen_np, np.repeat(plen_np[:1], reps)])
            count, base_rank, base_count, tiers, text_min = \
                self._scan_tiers(patt_np, plen_np, n_real=B)
        with tr.span("merge"):
            delta = self._delta(tiers, plen_np, B)
            first_pos = self._base_min_positions(base_count, base_rank,
                                                 text_min)
            positions = (np.full((B, top_k), -1, np.int64)
                         if top_k else None)
            for i in range(B):
                g = (delta[i] if delta is not None
                     else np.zeros((0,), np.int64))
                if g.size and (first_pos[i] < 0 or g[0] < first_pos[i]):
                    first_pos[i] = int(g[0])
                if top_k:
                    run = self._base_smallest(base_count, base_rank, i,
                                              top_k)
                    cand = np.concatenate([run, g])
                    if cand.size > top_k:
                        cand = np.partition(cand, top_k - 1)[:top_k]
                    cand.sort()
                    positions[i, :cand.size] = cand
        tr.record("total", (time.monotonic_ns() - t_all) / 1e6)
        return ScanOutcome(found=count > 0, count=count,
                           first_pos=first_pos, positions=positions)

    def scan(self, patterns: list[str], top_k: int = 0) -> ScanOutcome:
        """String-level merged scan with **text-order** semantics: exact
        ``count``; ``first_pos`` is the smallest occurrence position;
        ``positions`` (when ``top_k > 0``) are the ``top_k`` smallest
        occurrence start positions, ascending, −1-padded — the complete
        set whenever ``count <= top_k``.  (The planner's own string API
        instead reports suffix-rank order over the base only.)  Results
        are LRU-cached; every write (:meth:`append` /
        :meth:`minor_compact` / :meth:`compact`) generation-bumps the
        cache so pre-write results are never served."""
        B = len(patterns)
        count = np.zeros(B, np.int64)
        first_pos = np.full(B, -1, np.int64)
        positions = (np.full((B, top_k), -1, np.int64) if top_k else None)
        miss_idx: list[int] = []
        tr = self.tracer
        with tr.span("cache_lookup"):
            for i, pat in enumerate(patterns):
                hit = self._cache.get(pat, top_k)
                if hit is not None:
                    count[i], first_pos[i] = hit[0], hit[1]
                    if top_k:
                        positions[i] = hit[2]
                else:
                    miss_idx.append(i)
        if miss_idx:
            with tr.span("encode"):
                patt, plen = self.planner.encode(
                    [patterns[i] for i in miss_idx])
            sub = self.scan_batch(patt, plen, top_k=top_k)
            with tr.span("cache_fill"):
                for j, i in enumerate(miss_idx):
                    count[i] = sub.count[j]
                    first_pos[i] = sub.first_pos[j]
                    row = sub.positions[j] if top_k else None
                    if top_k:
                        positions[i] = row
                    self._cache.put(patterns[i], int(count[i]),
                                    int(first_pos[i]), top_k, row)
        return ScanOutcome(found=count > 0, count=count,
                           first_pos=first_pos, positions=positions)

    def locate_range(self, pattern: str, *, after: int = -1,
                     limit: Optional[int] = 256) -> np.ndarray:
        """Up to ``limit`` occurrence start positions of ``pattern``
        STRICTLY greater than ``after``, ascending int64 — the paged-read
        primitive under :class:`repro.api.client.ReadSession`
        (``limit=None`` returns the complete enumeration, which the
        session caches per :attr:`write_generation` so a stream of pages
        costs ONE scan, not one per page).

        Positions are global text offsets, which are stable identifiers
        across minor and major compactions: a cursor (= the last position
        of the previous page) taken before a compaction resumes exactly
        after it.  The host-side gather is O(count) for the base tier;
        the returned chunk is what stays bounded."""
        if limit is not None and limit <= 0:
            raise ValueError(f"limit must be positive, got {limit}")
        patt, plen = self.planner.encode([pattern])
        _count, base_rank, base_count, tiers, _ = self._scan_tiers(
            patt, plen, n_real=1)
        run = self._base_slice(base_count, base_rank, 0)
        delta = self._delta(tiers, plen, 1)
        g = delta[0] if delta is not None else np.zeros((0,), np.int64)
        cand = np.concatenate([run, g]) if g.size else run
        cand = cand[cand > after]
        if limit is not None and cand.size > limit:
            cand = np.partition(cand, limit - 1)[:limit]
        cand.sort()
        return cand.astype(np.int64)

    def count(self, patterns: list[str]) -> np.ndarray:
        """Exact occurrence counts, (B,) int64."""
        return self.scan(patterns).count

    def contains(self, patterns: list[str]) -> np.ndarray:
        """Per-pattern membership, (B,) bool."""
        return self.scan(patterns).found

    def locate(self, patterns: list[str], top_k: int = 8) -> np.ndarray:
        """Up to ``top_k`` smallest occurrence positions per pattern,
        ascending, (B, top_k) int64, −1-padded."""
        return self.scan(patterns, top_k=top_k).positions

    # -- write path ----------------------------------------------------------
    def _open_wal(self, *, fresh: bool) -> None:
        """Attach the table's commit log.  ``fresh=True`` (create) starts
        an empty segment; ``fresh=False`` (open) recovers the live one:
        torn tails are discarded by CRC, records the latest snapshot
        already covers are skipped by sequence number, and the rest —
        exactly the appends acked after that snapshot — replay through
        the normal memtable path.  The summary lands in
        ``stats()["wal"]["recovery"]``."""
        from repro.api.catalog import table_wal_dir
        if self._manager is None:
            return
        path = os.path.join(table_wal_dir(self.root, self.name), "wal.log")
        if not self._wal_on:
            # opting out with a live log on disk: move it aside.  The
            # table's state will diverge from the log (appends now take
            # sequence numbers the log never sees), so a LATER wal=True
            # open must not find this segment and splice its stale
            # records into the diverged text — the orphan is preserved
            # for manual inspection, never replayed.
            if os.path.exists(path):
                os.replace(path, path + ".orphaned")
            return
        if fresh or not os.path.exists(path):
            self._wal = WriteAheadLog.create(
                path, start_seq=self._wal_seq + 1,
                group_commit_ms=self.group_commit_ms)
            return
        wal = WriteAheadLog(path, group_commit_ms=self.group_commit_ms)
        records, summary = wal.recover()
        self._wal = wal
        self._replaying = True      # no auto-seal mid-replay: a seal here
        try:                        # would truncate records not yet applied
            for seq, codes in records:
                if seq <= self._wal_seq:
                    summary.records_skipped += 1
                    continue
                if seq != self._wal_seq + 1:
                    # the log starts past the snapshot: records between
                    # them are gone, so nothing later can be applied
                    summary.reason = "snapshot_gap"
                    break
                self._apply_append(codes)
                self._wal_seq = seq
                summary.records_replayed += 1
        finally:
            self._replaying = False
        self._recovery = summary.as_dict()
        if wal._last_written_seq != self._wal_seq:
            # only stale (< snapshot) or unreachable (snapshot_gap)
            # records remain in the segment — re-seal so the next append
            # gets a contiguous sequence
            wal.seal(self._wal_seq + 1)
        if (self.memtable_limit is not None
                and self.memtable.size >= self.memtable_limit):
            self.minor_compact()    # deferred from replay; persists + seals

    def append(self, codes) -> int:
        """Append text to the table (memtable write path); visible to all
        subsequent reads with exact merged counts.  On a persistent table
        the batch is committed to the write-ahead log and **fsync'd
        before this method returns** — the returned ack means durable.
        Returns the memtable size; triggers :meth:`minor_compact` at
        ``memtable_limit`` (and, through it, :meth:`compact` at
        ``max_runs``)."""
        size, token = self.append_nowait(codes)
        self.wait_durable(token)
        return size

    def append_nowait(self, codes) -> tuple[int, Optional[int]]:
        """The two-phase append underneath :meth:`append`: validate, log
        the commit record (buffered, not yet fsync'd), apply to the
        memtable, and return ``(memtable_size, durability_token)``.  The
        caller must pass the token to :meth:`wait_durable` before acking
        — ``Database.append`` does exactly that, waiting OUTSIDE the
        table's write lock so concurrent clients share one group-commit
        fsync.  Readers may observe the appended text before it is
        durable (standard commit-wait semantics); the ack is what
        promises crash survival."""
        if isinstance(codes, (str, bytes, bytearray)):
            if not self.is_dna:
                raise TypeError("string appends are DNA-only; pass a code "
                                "array for token tables")
            codes = codec.encode_dna(codes)
        # validate BEFORE logging: a bad batch must fail the caller, not
        # poison the log with a record that re-raises on every recovery
        codes = Memtable.validate_codes(codes, is_dna=self.is_dna)
        if codes.size == 0:
            return self.memtable.size, None
        token = None
        if self._wal is not None:
            # log first, bump after: a failed write (disk full) leaves
            # the counter aligned with the log so a retry isn't wedged
            # on a phantom sequence number
            token = self._wal.append(codes, self._wal_seq + 1)
        self._wal_seq += 1          # counted even unlogged: snapshots
        self._apply_append(codes)   # persist it, keeping replay aligned
        return self.memtable.size, token

    def wait_durable(self, token: Optional[int]) -> None:
        """Block until the append that returned ``token`` is on disk
        (fsync'd, or covered by a sealed snapshot).  No-op for ``None``
        (empty appends, tables without a log)."""
        if token is not None and self._wal is not None:
            self._wal.wait(token)

    def _apply_append(self, codes: np.ndarray) -> None:
        """Memtable apply + cache invalidation — shared by live appends
        and log replay (replay defers the ``memtable_limit`` check: an
        auto-seal mid-replay would truncate not-yet-applied records).
        Callers guarantee ``codes`` passed ``validate_codes`` (live
        appends check before logging; replayed records were checked
        before they were ever logged)."""
        self.memtable.append(codes, _prevalidated=True)
        self._invalidate_caches()
        if (not self._replaying and self.memtable_limit is not None
                and self.memtable.size >= self.memtable_limit):
            self.minor_compact()

    def minor_compact(self) -> int:
        """Seal the active memtable into an immutable
        :class:`~repro.api.runs.Run` and start a fresh one, so appends
        stay fast (the rebuilt-per-read memtable index never grows past
        ``memtable_limit``) without losing read visibility.  Persistent
        tables re-publish the snapshot (same version) so the sealed run
        is durable.  No-op on an empty memtable.  Returns the number of
        live runs; when ``max_runs`` is reached the runs are folded into
        the base via :meth:`compact` first."""
        if self.memtable.size == 0:
            return len(self.runs)
        self.runs.append(Run.from_memtable(self.memtable))
        self._reset_memtable()
        self._invalidate_caches()
        if self.max_runs is not None and len(self.runs) >= self.max_runs:
            self.compact()
        elif self._manager is not None:
            self._persist()
        return len(self.runs)

    # -- frozen tier ---------------------------------------------------------
    def _fm_dir(self) -> str:
        from repro.api.catalog import table_fm_dir
        return table_fm_dir(self.root, self.name)

    def freeze(self, *, sample_rate: int = 32) -> "SuffixTable":
        """Convert the base tier to a frozen FM-index (docs/
        storage_tiers.md): the BWT is derived from the current base SA,
        2-bit-packed with blocked Occ checkpoints and a sampled SA, and
        the raw suffix array is dropped — ~10x less resident index per
        symbol.  Reads route through the backward-search kernel;
        ``count()`` becomes O(pattern_len), independent of text size.
        Post-freeze appends keep working: they land in the memtable /
        runs as usual and merge with FM base results through the same
        fused tier path.  Persistent tables save the artifact under the
        table's ``fm/`` dir and re-publish the snapshot.  Idempotent."""
        if self.fm is not None:
            return self
        from repro.api.fm import FMIndex
        sa_real = np.asarray(self.store.sa)[self.store.pad_count:]
        # merge-built SAs are exact only to the compare depth; build()
        # verifies full order and re-sorts if the check fails, so the
        # BWT is always derived from a true full suffix array
        fm = FMIndex.build(self._codes, sa_real, is_dna=self.is_dna,
                           sample_rate=sample_rate)
        self._attach_frozen(fm)
        if self._manager is not None:
            fm.save(self._fm_dir(), self.version)
            self._persist()
        return self

    def _maybe_freeze(self) -> None:
        """Apply the ``fm_threshold`` policy: freeze once the base tier
        reaches the threshold (checked after create/open/compact — the
        points where the base grows)."""
        if (self.fm is None and self.fm_threshold is not None
                and self.n_base >= int(self.fm_threshold)):
            from repro.api.fm import MAX_VOCAB
            if (not self.is_dna and self._codes.size
                    and int(self._codes.max()) >= MAX_VOCAB):
                return      # policy no-op: vocab beyond the frozen cap
            self.freeze()

    def _delta_codes(self) -> np.ndarray:
        """All un-compacted symbols (sealed runs + memtable), in order."""
        parts = [r.codes for r in self.runs]
        if self.memtable.size:
            parts.append(self.memtable.appended)
        if not parts:
            return np.zeros((0,), self._codes.dtype)
        return np.concatenate(
            [p.astype(self._codes.dtype, copy=False) for p in parts])

    def compact(self) -> int:
        """Major compaction: fold every sealed run plus the memtable into
        the base suffix array, clear the delta tiers, bump and persist
        the version.  Single-device tables MERGE (prefix doubling over
        only the dirty suffix range + batched window-compare insertion —
        see :mod:`repro.api.compaction`) so a small delta compacts far
        faster than a from-scratch build; tables with a live mesh keep
        the distributed full rebuild (the merge is a host-side path).
        No-op when there is nothing to fold.  Returns the version."""
        delta = self._delta_codes()
        if delta.size == 0:
            return self.version
        combined = np.concatenate([self._codes, delta])
        was_frozen = self.fm is not None
        fm_rate = self.fm.sample_rate if was_frozen else None
        if was_frozen:
            # the raw SA was dropped at freeze time; reconstruct it from
            # the index (vectorized LF walks) as the merge input, then
            # compact live and re-freeze over the merged text below
            base_sa = self.fm.suffix_array().astype(np.int32)
            sa_real = merge_delta_sa(
                combined, self.n_base, base_sa,
                is_dna=self.is_dna, max_query_len=self.max_query_len)
        elif self.mesh is not None and self._distributed_build:
            sa_real, _ = self.__class__._build_sa_for(combined)
        else:
            pad = self.store.pad_count
            sa_real = merge_delta_sa(
                combined, self.n_base, np.asarray(self.store.sa)[pad:],
                is_dna=self.is_dna, max_query_len=self.max_query_len)
        self._codes = combined
        self._attach(combined, sa_real)      # rebind bumps the planner
        self.runs = []                       # cache AND drops any FM binding
        self._reset_memtable()
        self._invalidate_caches()
        self.version += 1
        self._persist()
        if was_frozen:
            self.freeze(sample_rate=fm_rate)  # frozen is a sticky tier state
        else:
            self._maybe_freeze()
        return self.version

    def flush(self) -> None:
        """Persist the current state — base arrays, sealed runs, AND
        un-compacted memtable codes — without compacting (same version,
        re-published atomically).  :meth:`open` restores all of it.
        Raises on an in-memory table: durability is this method's entire
        contract."""
        if self._manager is None:
            raise RuntimeError(
                "flush() on a non-persistent table — build it with "
                "SuffixTable.create(...) to get durable storage")
        self._persist()

    def start_metrics(self, path: str, interval_s: float = 1.0,
                      name: Optional[str] = None) -> None:
        """Stream this table's full :meth:`stats` tree into a
        ``metrics.jsonl`` feed — the SAME feed schema the serving
        plane's workers and routers append to, so ``serve.py
        --dump-stats`` (and ``check_regression.py --from-feed``)
        aggregate one schema whether serving is in-process or
        multi-process (docs/observability.md).  Each row is
        ``metrics.table_record(name, stats())``; ``name`` overrides the
        row identity for anonymous in-memory tables (``self.name`` is
        the default).  Idempotent — a second call restarts the emitter
        on the new path/interval."""
        self.stop_metrics()
        row_name = name if name is not None else self.name
        self._metrics = MetricsEmitter(
            path, lambda: table_record(row_name, self.stats()),
            interval_s=interval_s)

    def stop_metrics(self) -> None:
        """Stop the feed emitter (writes one final row)."""
        if self._metrics is not None:
            self._metrics.stop()
            self._metrics = None

    def close(self) -> None:
        """Release the commit-log file handle and stop the metrics
        emitter.  Reads keep working; a later :meth:`append` raises
        instead of silently losing durability (reopen the table to
        resume writing)."""
        self.stop_metrics()
        if self._wal is not None:
            self._wal.close()

    def _persist(self) -> None:
        if self._manager is None:
            return
        if self.fm is not None:
            # frozen: the SA was dropped — the FM artifact (saved under
            # fm/ by freeze()) is the base index on disk; open() rebuilds
            # from codes if the artifact is ever missing
            sa_real = np.zeros((0,), np.int32)
        else:
            sa_real = self._sa()[self.store.pad_count:]
        state = {"codes": self._codes,
                 "sa_real": sa_real,
                 "mem_codes": self.memtable.appended}
        runs_meta = []
        for i, r in enumerate(self.runs):
            state[f"run{i}_tail"] = r.tail
            state[f"run{i}_codes"] = r.codes
            state[f"run{i}_sa"] = r.sa_padded   # frozen index, no re-sort
            runs_meta.append({"start": r.start, "length": r.length,
                              "overlap": r.overlap})
        extra = {"kind": "suffix_table", "name": self.name,
                 "version": self.version, "is_dna": self.is_dna,
                 "max_query_len": self.max_query_len,
                 "n_base": self.n_base, "runs": runs_meta,
                 "mem_len": self.memtable.size,
                 "wal_seq": self._wal_seq,
                 "frozen": self.fm is not None,
                 "fm_sample_rate": (self.fm.sample_rate
                                    if self.fm is not None else None),
                 "build": (self._build.to_dict()
                           if self._build is not None else None)}
        # always publish under a FRESH step: CheckpointManager.save on an
        # existing step rmtree's it before the rename, so re-publishing
        # the same version in place (flush / every automatic seal) would
        # open a crash window with zero live snapshots.  The step is a
        # plain publish sequence; the table version rides in ``extra``.
        step = (self._manager.latest_step() or 0) + 1
        self._manager.save(step, state, extra=extra)
        if self._wal is not None:
            # ONLY after the snapshot is published may the log be
            # truncated — there is never a moment with zero durable
            # copies of an acked append.  A crash landing between save
            # and seal is caught by the seq skip on replay.
            self._wal.seal(self._wal_seq + 1)


# Back-compat: the pre-table spelling, one call deep.
def open_table(name: str, *, root: Optional[str] = None,
               **kw) -> SuffixTable:
    return SuffixTable.open(name, root=root, **kw)


TableLike = Union[SuffixTable, TabletStore]
