"""Share of the HBM roofline reached by the base binary search
(``core/query.py`` ``query``, run as the program ``jit_query`` by
``planner._query_single``), in percent.

The least time the chip could take is the bytes the search needs over
the peak HBM bandwidth (it does next to no arithmetic); the share is
that over the program's device time in the trace.  Per batch slot
(padding slots run too) the search needs, for each of its two bounds
and each of its ``ceil(log2(n_pad + 1))`` steps, one suffix-array entry
and the ``W + 1`` packed text words a ``W``-word window at an unaligned
position spans, where ``W = ceil(max_query_len / 16)``; the final
``first_pos`` gather is one more entry.  That counts needed bytes, not
the larger transfers a gather makes, so the share is a lower bound."""
import math

MODULE = "jit_query"
WORD = 4                # bytes in a suffix-array entry or a packed word


def bytes_needed(slots: int, n_pad: int, max_query_len: int) -> int:
    steps = max(1, math.ceil(math.log2(n_pad + 1)))
    words = -(-max_query_len // 16)
    return slots * (2 * steps * (WORD + WORD * (words + 1)) + WORD)


def read(ctx):
    tr, peaks, shapes = ctx["trace"], ctx["peaks"], ctx["shapes"]
    if tr is None or peaks is None or shapes["frozen"]:
        return None
    run = tr.module_s(MODULE)
    if run is None or run[0] <= 0:
        return None
    before, after = ctx["planner"]
    slots = (after["queries"] + after["pad_slots"]
             - before["queries"] - before["pad_slots"])
    need = bytes_needed(slots, shapes["n_pad"], shapes["max_query_len"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / run[0]
