"""FM-index artifact: build / persist / serve a frozen table's BWT tier.

``FMIndex`` is the host-side owner of one table's compressed index (the
artifact ``SuffixTable.freeze()`` emits): it derives the BWT from the
base suffix array, packs it (2-bit for DNA via the ``pack2bit`` layout),
builds the blocked Occ checkpoints and the sampled-SA structures, and
persists everything through the same ``CheckpointManager`` the table
snapshot uses (atomic publish, versioned, GC'd) — under the table's
``fm/`` directory so ``Catalog`` reconcile and ``drop_table`` manage it
with the rest of the table state.

Bytes per symbol (DNA, defaults SB=64, sample_rate=32):

====================  ================  =======
structure             size              B/sym
====================  ================  =======
packed BWT            n/4 bytes         0.25
Occ checkpoints       4*4*n/64          0.25
sampled SA            4*n/32            0.125
marked bitvector      n/8 + rank words  ~0.16
====================  ================  =======

~0.78 B/sym total vs ~8 B/sym for the live base tier (device SA + host
mirror) — the ~10x footprint win ROADMAP item 2 targets.

Conventions (must match ``kernels.fm_scan`` and the binary-search path):
the index is over ``T$``; ``SA$ = [n] + SA`` because the base builder
orders equal-prefix suffixes shorter-first, which IS the sentinel
order.  The sentinel row (``SA$ == 0``) stores dummy symbol 0 in the
BWT; Occ counts the raw stream and ``rank()`` subtracts the dummy.
"""
from __future__ import annotations

import re
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import codec
from repro.core.rangemin import block_minima
from repro.core.suffix_array import build_suffix_array
from repro.kernels import fm_scan
from repro.kernels.fm_scan import SB, WPB, FMArrays

FM_FORMAT = 2
DEFAULT_SAMPLE_RATE = 32
MAX_VOCAB = 64          # token tables above this stay on the live tier


def _named(arrays: dict) -> dict:
    """Strip checkpoint path decoration: ``"['bwt']"`` -> ``"bwt"``."""
    return {re.sub(r"[^0-9A-Za-z_]", "", k): v for k, v in arrays.items()}


if hasattr(np, "bitwise_count"):            # numpy >= 2.0
    def _popcount32(x: np.ndarray) -> np.ndarray:
        return np.bitwise_count(x).astype(np.int64)
else:                                       # byte-LUT fallback
    _POP8 = np.array([bin(i).count("1") for i in range(256)], dtype=np.int64)

    def _popcount32(x: np.ndarray) -> np.ndarray:
        b = np.ascontiguousarray(x, dtype=np.uint32).view(np.uint8)
        return _POP8[b].reshape(*x.shape, 4).sum(axis=-1)


def sa_is_fully_sorted(codes: np.ndarray, sa: np.ndarray) -> bool:
    """True iff ``sa`` is the FULL lexicographic suffix order of ``codes``
    (shorter-suffix-first on ties).  ``merge_delta_sa`` only guarantees
    depth-L order, which is enough for depth-capped scans but NOT for a
    BWT — freeze() checks and falls back to a fresh sort."""
    n = len(codes)
    if len(sa) != n:
        return False
    if n <= 1:
        return n == 0 or sa[0] == 0
    rank = np.empty(n + 1, dtype=np.int64)
    rank[sa] = np.arange(n)
    rank[n] = -1                      # empty suffix sorts first
    a, b = sa[:-1].astype(np.int64), sa[1:].astype(np.int64)
    ca, cb = codes[a].astype(np.int64), codes[b].astype(np.int64)
    ok = (ca < cb) | ((ca == cb) & (rank[a + 1] < rank[b + 1]))
    return bool(np.all(ok)) and bool(np.all(np.sort(sa) == np.arange(n)))


class FMIndex:
    """One table's frozen-tier index.  Host arrays are authoritative;
    the device view (``.arrays``) is materialized lazily."""

    def __init__(self, *, bwt, occ, cc, marked, marked_rank, samples,
                 row_min, sent_row: int, n: int, is_dna: bool,
                 sample_rate: int, vocab: int):
        self.bwt = bwt                    # DNA: (Wb,) u32 | tokens: (L,) u8
        self.occ = occ                    # (nblk + 1, vocab) int32
        self.cc = cc                      # (vocab,) int32
        self.marked = marked              # (Wm,) uint32
        self.marked_rank = marked_rank    # (Wm,) int32
        self.samples = samples            # (S,) int32
        self.row_min = row_min            # (rows / BLOCK,) SA$ block minima
        self.sent_row = int(sent_row)
        self.n = int(n)
        self.is_dna = bool(is_dna)
        self.sample_rate = int(sample_rate)
        self.vocab = int(vocab)
        self._arrays: Optional[FMArrays] = None

    # ------------------------------------------------------------- build
    @classmethod
    def build(cls, codes: np.ndarray, sa_real: Optional[np.ndarray] = None,
              *, is_dna: bool, sample_rate: int = DEFAULT_SAMPLE_RATE,
              validate: bool = True) -> "FMIndex":
        """Derive the index from text ``codes`` and (optionally) its base
        suffix array.  A non-fully-sorted or missing SA triggers a fresh
        ``build_suffix_array`` — correctness never depends on the LSM
        merge depth."""
        codes = np.asarray(codes, dtype=np.uint8)
        n = len(codes)
        if n == 0:
            raise ValueError("cannot freeze an empty table")
        if sample_rate < 2:
            raise ValueError("sample_rate must be >= 2")
        vocab = 4 if is_dna else int(codes.max()) + 1
        if vocab > MAX_VOCAB:
            raise ValueError(
                f"vocab {vocab} exceeds the frozen tier's cap {MAX_VOCAB}")
        if sa_real is not None:
            sa_real = np.asarray(sa_real, dtype=np.int64)
        if sa_real is None or (validate
                               and not sa_is_fully_sorted(codes, sa_real)):
            sa_real = np.asarray(build_suffix_array(codes), dtype=np.int64)

        rows = n + 1
        sa_dollar = np.empty(rows, dtype=np.int64)
        sa_dollar[0] = n                    # the $-only suffix
        sa_dollar[1:] = sa_real
        prev = sa_dollar - 1
        sent_row = int(np.nonzero(sa_dollar == 0)[0][0])
        bwt_codes = codes[np.where(prev >= 0, prev, 0)].copy()
        bwt_codes[sent_row] = 0             # dummy symbol for $

        # C$[c] = 1 + #{symbols in T < c}  (the +1 is the sentinel)
        counts = np.bincount(codes, minlength=vocab).astype(np.int64)
        cc = (1 + np.concatenate(([0], np.cumsum(counts)[:-1]))).astype(
            np.int32)

        nblk = -(-rows // SB)
        if is_dna:
            packed = codec.pack_2bit_batch(bwt_codes[None, :])[0]
            pad_w = nblk * WPB - len(packed)
            if pad_w:
                packed = np.pad(packed, (0, pad_w))
            # Occ from the PACKED words (what rank() reads), not the raw
            # codes — guarantees checkpoint/popcount agreement by design.
            blocks = codec.unpack_2bit_batch(packed.reshape(nblk, WPB), SB)
            blocks = blocks.astype(np.int16)
            tail = np.arange(nblk * SB).reshape(nblk, SB) >= rows
            blocks[tail] = -1               # pad slots count as nothing
            bwt_store = packed
        else:
            padded = np.full(nblk * SB, -1, dtype=np.int16)
            padded[:rows] = bwt_codes
            blocks = padded.reshape(nblk, SB)
            bwt_store = bwt_codes
        per_blk = np.stack(
            [(blocks == c).sum(axis=1) for c in range(vocab)], axis=1)
        occ = np.zeros((nblk + 1, vocab), dtype=np.int32)
        occ[1:] = np.cumsum(per_blk, axis=0)

        # sampled SA: mark rows whose TEXT position is ≡ 0 (mod k); the
        # p == 0 row is always marked, so every LF walk terminates.
        mark = (sa_dollar % sample_rate) == 0
        wm = -(-rows // 32)
        bits = np.zeros(wm * 32, dtype=np.uint32)
        bits[:rows] = mark
        words = bits.reshape(wm, 32)
        marked = (words << np.arange(32, dtype=np.uint32)).sum(
            axis=1, dtype=np.uint32)
        per_word = words.sum(axis=1, dtype=np.int64)
        marked_rank = np.concatenate(
            ([0], np.cumsum(per_word)[:-1])).astype(np.int32)
        samples = sa_dollar[mark].astype(np.int32)
        row_min = block_minima(sa_dollar).astype(np.int32)

        return cls(bwt=bwt_store, occ=occ, cc=cc, marked=marked,
                   marked_rank=marked_rank, samples=samples, row_min=row_min,
                   sent_row=sent_row, n=n, is_dna=is_dna,
                   sample_rate=sample_rate, vocab=vocab)

    # ------------------------------------------------------- device view
    @property
    def arrays(self) -> FMArrays:
        if self._arrays is None:
            bwt = (jnp.asarray(self.bwt, jnp.uint32) if self.is_dna
                   else jnp.asarray(self.bwt, jnp.int32))
            self._arrays = FMArrays(
                bwt=bwt,
                occ=jnp.asarray(self.occ, jnp.int32),
                cc=jnp.asarray(self.cc, jnp.int32),
                marked=jnp.asarray(self.marked, jnp.uint32),
                marked_rank=jnp.asarray(self.marked_rank, jnp.int32),
                samples=jnp.asarray(self.samples, jnp.int32),
                sent_row=jnp.int32(self.sent_row),
                n=jnp.int32(self.n),
                is_dna=self.is_dna,
                sample_rate=self.sample_rate,
                vocab=self.vocab)
        return self._arrays

    # --------------------------------------------------------- host rank
    def _rank_host(self, c: np.ndarray, i: np.ndarray) -> np.ndarray:
        """Occ(c, i) vectorized on the host — locate walks and the
        frozen-compaction SA reconstruction run here, off-device."""
        c = np.asarray(c, dtype=np.int64)
        i = np.asarray(i, dtype=np.int64)
        blk = i // SB
        base = self.occ[blk, c].astype(np.int64)
        rem = i - blk * SB
        if self.is_dna:
            idx = blk[:, None] * WPB + np.arange(WPB)
            w = self.bwt[np.clip(idx, 0, len(self.bwt) - 1)]
            v = np.clip(rem[:, None] - 16 * np.arange(WPB), 0, 16)
            x = w ^ (c[:, None].astype(np.uint32) * np.uint32(0x55555555))
            y = (~x) & ((~x) >> np.uint32(1)) & np.uint32(0x55555555)
            sh = (2 * (16 - np.clip(v, 1, 16))).astype(np.uint32)
            keep = np.where(v > 0,
                            np.uint32(0x55555555) << sh, np.uint32(0))
            cnt = _popcount32(y & keep).sum(axis=1)
        else:
            offs = np.arange(SB)
            idx = blk[:, None] * SB + offs
            vals = self.bwt[np.clip(idx, 0, len(self.bwt) - 1)]
            cnt = ((vals == c[:, None]) & (offs < rem[:, None])).sum(axis=1)
        return base + cnt - ((c == 0) & (self.sent_row < i)).astype(np.int64)

    def _bwt_sym_host(self, r: np.ndarray) -> np.ndarray:
        r = np.asarray(r, dtype=np.int64)
        if self.is_dna:
            w = self.bwt[r // 16]
            return ((w >> (30 - 2 * (r % 16)).astype(np.uint32)) & 3).astype(
                np.int64)
        return self.bwt[r].astype(np.int64)

    def ranks_to_positions(self, rows: np.ndarray) -> np.ndarray:
        """SA$[row] for a batch of rows, via LF walks to the nearest
        sampled position (≤ ``sample_rate`` steps each, all host numpy)."""
        r = np.asarray(rows, dtype=np.int64).copy()
        pos = np.full(r.shape, -1, dtype=np.int64)
        steps = np.zeros(r.shape, dtype=np.int64)
        done = np.zeros(r.shape, dtype=bool)
        cc = self.cc.astype(np.int64)
        for _ in range(self.sample_rate + 1):
            w = self.marked[r // 32]
            hit = (((w >> (r % 32).astype(np.uint32)) & 1) != 0) & ~done
            if hit.any():
                rh = r[hit]
                wlow = self.marked[rh // 32] & (
                    (np.uint32(1) << (rh % 32).astype(np.uint32))
                    - np.uint32(1))
                si = (self.marked_rank[rh // 32].astype(np.int64)
                      + _popcount32(wlow))
                pos[hit] = self.samples[si].astype(np.int64) + steps[hit]
                done |= hit
            act = ~done
            if not act.any():
                break
            s = self._bwt_sym_host(r[act])
            r[act] = cc[s] + self._rank_host(s, r[act])
            steps[act] += 1
        return pos

    def suffix_array(self) -> np.ndarray:
        """Reconstruct the full real SA (rows 1..n of SA$) — frozen-table
        compaction rebuilds its merge input from this instead of keeping
        an 8 B/sym live copy around."""
        return self.ranks_to_positions(np.arange(1, self.n + 1))

    def count(self, patt, plen):
        """(lo, hi) -> host (count, first_rank) for an encoded batch —
        convenience used by tests and benches.  ``first_rank`` follows
        the planner contract: the real-SA lower bound when found, -1
        otherwise."""
        lo, hi = fm_scan.backward_search(self.arrays, jnp.asarray(patt),
                                         jnp.asarray(plen, jnp.int32))
        lo = np.asarray(lo, dtype=np.int64)
        hi = np.asarray(hi, dtype=np.int64)
        return hi - lo, np.where(hi > lo, lo - 1, -1)

    # ------------------------------------------------------- persistence
    def state_dict(self) -> dict:
        return {"bwt": np.asarray(self.bwt), "occ": self.occ,
                "cc": self.cc, "marked": self.marked,
                "marked_rank": self.marked_rank, "samples": self.samples,
                "row_min": self.row_min}

    def extra_dict(self) -> dict:
        return {"kind": "fm_index", "format": FM_FORMAT, "n": self.n,
                "sample_rate": self.sample_rate, "sb": SB,
                "is_dna": self.is_dna, "vocab": self.vocab,
                "sent_row": self.sent_row}

    def save(self, directory: str, version: int) -> str:
        mgr = CheckpointManager(directory, keep_n=2)
        return mgr.save(version, self.state_dict(), extra=self.extra_dict())

    @classmethod
    def load(cls, directory: str) -> Optional["FMIndex"]:
        """Latest persisted artifact in ``directory``, or None when the
        dir is absent/empty or from an incompatible format — callers
        rebuild from codes in that case."""
        mgr = CheckpointManager(directory, keep_n=2)
        step = mgr.latest_step()
        if step is None:
            return None
        arrays, extra = mgr.restore_arrays(step)
        if extra.get("kind") != "fm_index" or extra.get("sb") != SB \
                or extra.get("format") != FM_FORMAT:
            return None
        a = _named(arrays)
        is_dna = bool(extra["is_dna"])
        return cls(bwt=a["bwt"].astype(np.uint32 if is_dna else np.uint8),
                   occ=a["occ"].astype(np.int32),
                   cc=a["cc"].astype(np.int32),
                   marked=a["marked"].astype(np.uint32),
                   marked_rank=a["marked_rank"].astype(np.int32),
                   samples=a["samples"].astype(np.int32),
                   row_min=a["row_min"].astype(np.int32),
                   sent_row=int(extra["sent_row"]), n=int(extra["n"]),
                   is_dna=is_dna, sample_rate=int(extra["sample_rate"]),
                   vocab=int(extra["vocab"]))

    # ------------------------------------------------------------- stats
    def resident_bytes(self) -> int:
        """Index bytes (host copy == device copy sizes)."""
        return int(np.asarray(self.bwt).nbytes + self.occ.nbytes
                   + self.cc.nbytes + self.marked.nbytes
                   + self.marked_rank.nbytes + self.samples.nbytes
                   + self.row_min.nbytes)
