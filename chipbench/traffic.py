"""The one traffic generator: a mix file's parameters in, requests out.

A mix (``mixes/<name>.json``) states

* ``clients``: closed-loop callers, each a thread that sends its next
  request only when the previous one has come back;
* ``entry``: ``submit`` (``Database.submit``, through the scheduler's
  coalescing window, one future per request) or ``query``
  (``Database.query``, inline, no window);
* ``kind`` and ``top_k``: the ``Query`` each request carries;
* ``patterns_per_request``;
* ``length``: pattern lengths, uniform over ``[lo, hi]``;
* ``from_text_share``: the share of patterns cut from the text at a
  uniform start (they occur); the rest are uniform random bases;
* ``source`` and ``assumed``: where the parameters come from, and why
  each one the source does not define was chosen (read by no code).

Every request, pattern and length is drawn from ``--seed`` before the
window, so a seed fixes the work offered.  The pools hold
``POOL_CACHE_MULTIPLE`` times the table's result cache in patterns; a
client that runs out starts its pool again, by then far out of the
cache.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import threading
import time

import numpy as np

ALPHABET = np.frombuffer(b"ACGT", np.uint8)
WAIT_AFTER_CLOSE_S = 60.0       # how long a request may run past the close
POOL_CACHE_MULTIPLE = 4         # pool patterns per result-cache entry


@dataclasses.dataclass
class Record:
    client: int
    t_send: float
    t_done: float
    query: object                 # repro.api.Query
    result: object                # repro.api.QueryResult; None: unanswered


def draw_patterns(text: np.ndarray, mix: dict, count: int, rng
                  ) -> list[np.ndarray]:
    """``count`` patterns as the mix describes them."""
    lo, hi = (int(v) for v in mix["length"])
    lens = rng.integers(lo, hi + 1, size=count)
    from_text = rng.random(count) < float(mix["from_text_share"])
    starts = rng.integers(0, np.maximum(1, text.size - lens + 1))
    out = []
    for m, ft, s in zip(lens, from_text, starts):
        if ft and text.size >= m:
            out.append(np.array(text[s:s + m], np.uint8))
        else:
            out.append(rng.integers(0, 4, size=int(m), dtype=np.uint8))
    return out


def to_str(p: np.ndarray) -> str:
    return ALPHABET[p].tobytes().decode()


_CODE = np.zeros(256, np.uint8)
_CODE[ALPHABET] = np.arange(4, dtype=np.uint8)


def to_codes(s: str) -> np.ndarray:
    return _CODE[np.frombuffer(s.encode(), np.uint8)]


def make_query(table: str, mix: dict, codes: list[np.ndarray]):
    from repro.api import Query
    strs = [to_str(p) for p in codes]
    if mix["kind"] == "count":
        return Query.count(table, strs)
    return Query.scan(table, strs, top_k=int(mix["top_k"]))


def pool_size(mix: dict, cache_size: int) -> int:
    """Requests drawn per client, so that all pools together hold
    ``POOL_CACHE_MULTIPLE`` times ``cache_size`` patterns: a pattern
    comes round again only after that many others, and a least recently
    used cache of ``cache_size`` has let it go."""
    per_client = (POOL_CACHE_MULTIPLE * int(cache_size)
                  / int(mix["clients"]) / int(mix["patterns_per_request"]))
    return max(1, math.ceil(per_client))


def make_pools(text: np.ndarray, mix: dict, table: str, cache_size: int,
               rng) -> list[list]:
    """One list of queries per client."""
    ppr = int(mix["patterns_per_request"])
    size = pool_size(mix, cache_size)
    pools = []
    for _ in range(int(mix["clients"])):
        pats = draw_patterns(text, mix, size * ppr, rng)
        pools.append([make_query(table, mix, pats[i:i + ppr])
                      for i in range(0, len(pats), ppr)])
    return pools


def warm_batches(mix: dict) -> list[int]:
    """Every batch size, as the table buckets it, that the window can
    dispatch: a coalescing window merges up to ``clients`` requests, an
    inline query dispatches its own patterns (fewer when some hit the
    result cache, which leaves the bucket unchanged below half)."""
    ppr = int(mix["patterns_per_request"])
    top = ppr * (int(mix["clients"]) if mix["entry"] == "submit" else 1)
    bucket = 1 << max(0, (top - 1).bit_length())
    if mix["entry"] == "submit":
        return [1 << i for i in range(bucket.bit_length())]
    return [bucket]


def warm(db, table: str, text: np.ndarray, mix: dict, rng) -> None:
    """Compile (or load) the program of every batch size the window can
    dispatch, through the entries the window uses, with patterns drawn
    apart from the pools; then drop their results from the caches."""
    for b in warm_batches(mix):
        pats = draw_patterns(text, mix, b, rng)
        db.table(table).clear_cache()   # a hit would shrink the batch
        res = db.query(make_query(table, mix, pats))
        if not res.ok:
            raise RuntimeError(f"warm-up batch of {b} failed: {res.error}")
    if mix["entry"] == "submit":
        pats = draw_patterns(text, mix, int(mix["patterns_per_request"]), rng)
        res = db.submit(make_query(table, mix, pats)).result(
            WAIT_AFTER_CLOSE_S)
        if not res.ok:
            raise RuntimeError(f"warm-up submit failed: {res.error}")
    db.table(table).clear_cache()


def run_closed_loop(db, mix: dict, pools: list[list],
                    seconds: float, annotate=None):
    """Drive every client for ``seconds``; returns (records, t_open,
    t_close, unanswered).  A client sends no request after the close and
    waits up to ``WAIT_AFTER_CLOSE_S`` for the one in flight; a request
    still out then is unanswered."""
    annotate = annotate or (lambda name: contextlib.nullcontext())
    submit = mix["entry"] == "submit"
    n = len(pools)
    records: list[list[Record]] = [[] for _ in range(n)]
    start = threading.Barrier(n + 1)
    clock: dict[str, float] = {}

    def client(i: int) -> None:
        pool = pools[i]
        out = records[i]
        start.wait()
        close = clock["close"]
        j = 0
        while True:
            t_send = time.perf_counter()
            if t_send >= close:
                return
            query = pool[j % len(pool)]
            j += 1
            with annotate("chipbench.request"):
                if submit:
                    try:
                        res = db.submit(query).result(
                            close - t_send + WAIT_AFTER_CLOSE_S)
                    except TimeoutError:
                        res = None          # never came: unanswered
                else:
                    res = db.query(query)
            out.append(Record(i, t_send, time.perf_counter(), query, res))
            if res is None:
                return

    threads = [threading.Thread(target=client, args=(i,), daemon=True,
                                name=f"chipbench-client-{i}")
               for i in range(n)]
    for t in threads:
        t.start()
    clock["open"] = time.perf_counter()
    clock["close"] = clock["open"] + float(seconds)
    start.wait()
    for t in threads:
        t.join(max(0.0, clock["close"] - time.perf_counter())
               + WAIT_AFTER_CLOSE_S)
    flat = [r for rs in records for r in rs]
    unanswered = (sum(t.is_alive() for t in threads)
                  + sum(r.result is None for r in flat))
    return ([r for r in flat if r.result is not None], clock["open"],
            clock["close"], unanswered)
