"""Share of the four chips' HBM roofline reached by the broadcast
tablet search (``core/query.py`` ``query_sharded``, run as the program
``jit_broadcast`` by the planner's broadcast executor), in percent.

Every tablet searches every batch slot (padding slots run too) over its
own ``m = n_pad / p`` rows: for each of the two bounds and each of the
``ceil(log2(m + 1))`` steps, one suffix-array entry and the ``W + 1``
packed text words a ``W``-word window at an unaligned position spans,
where ``W = ceil(max_query_len / 16)``; then one entry for its share of
``first_pos``.  The least time is those bytes over ``p`` times the peak
HBM bandwidth; the share is that over the program's device time per
chip in the trace.  Needed bytes, not the larger transfers a gather
makes, so the share is a lower bound; the cross-chip reductions count
as time, not bytes."""
import math

MODULE = "jit_broadcast"
WORD = 4                # bytes in a suffix-array entry or a packed word


def bytes_needed(slots: int, tablets: int, n_pad: int,
                 max_query_len: int) -> int:
    m = n_pad // tablets
    steps = max(1, math.ceil(math.log2(m + 1)))
    words = -(-max_query_len // 16)
    return slots * tablets * (2 * steps * (WORD + WORD * (words + 1))
                              + WORD)


def read(ctx):
    tr, peaks, shapes = ctx["trace"], ctx["peaks"], ctx["shapes"]
    tablets = int(ctx["config"].get("tablets", 1))
    if tr is None or peaks is None or shapes["frozen"] or tablets < 2:
        return None
    run = tr.module_s(MODULE)
    if run is None or run[0] <= 0:
        return None
    before, after = ctx["planner"]
    slots = (after["queries"] + after["pad_slots"]
             - before["queries"] - before["pad_slots"])
    need = bytes_needed(slots, tablets, shapes["n_pad"],
                        shapes["max_query_len"])
    return 100.0 * need / (tablets * peaks["hbm_bytes_per_s"]) / run[0]
