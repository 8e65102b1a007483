"""Host time of the table's merge and enumeration per pattern it
scanned, in us: the table's ``merge`` span (``SuffixTable.scan_batch``:
``first_pos`` and top-k over block minima, LF walks on a frozen table),
its summed milliseconds over the patterns the planner ran in the
window."""


def read(ctx):
    span = ctx["spans"]["table"].get("merge")
    before, after = ctx["planner"]
    patterns = after["queries"] - before["queries"]
    if not span or patterns <= 0:
        return None
    return span["sum_ms"] * 1e3 / patterns
