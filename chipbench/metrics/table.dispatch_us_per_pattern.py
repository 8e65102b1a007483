"""Host-clock time of the table's device read per pattern it scanned,
in us: the table's ``dispatch`` span (``SuffixTable.scan_batch`` around
the planner's launch and the wait for its result), its summed
milliseconds over the patterns the planner ran in the window."""


def read(ctx):
    span = ctx["spans"]["table"].get("dispatch")
    before, after = ctx["planner"]
    patterns = after["queries"] - before["queries"]
    if not span or patterns <= 0:
        return None
    return span["sum_ms"] * 1e3 / patterns
