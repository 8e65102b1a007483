"""Share of the HBM roofline reached by the FM backward search
(``kernels/fm_scan.py`` ``search_syms`` with its ``first_pos`` LF walk,
run as the program ``jit_fm_search`` by ``ops.fm_search``), in percent.

The least time is the bytes the search needs over the peak HBM
bandwidth; the share is that over the program's device time in the
trace.  Per pattern symbol the search needs, for each of the two range
ends, one Occ checkpoint and one BWT word; the LF walk and the steps
the loop runs past a pattern's end are not counted, so the share is a
lower bound.  Symbols per batch slot are the mean length of the
patterns the window sent."""

MODULE = "jit_fm_search"
WORD = 4                # bytes in an Occ checkpoint or a packed BWT word


def bytes_needed(slots: int, mean_len: float) -> float:
    return slots * mean_len * 2 * (WORD + WORD)


def read(ctx):
    tr, peaks, shapes = ctx["trace"], ctx["peaks"], ctx["shapes"]
    if tr is None or peaks is None or not shapes["frozen"]:
        return None
    run = tr.module_s(MODULE)
    if run is None or run[0] <= 0:
        return None
    before, after = ctx["planner"]
    slots = (after["queries"] + after["pad_slots"]
             - before["queries"] - before["pad_slots"])
    lens = [len(p) for r in ctx["records"] for p in r.query.patterns]
    if not lens:
        return None
    need = bytes_needed(slots, sum(lens) / len(lens))
    return 100.0 * need / peaks["hbm_bytes_per_s"] / run[0]
