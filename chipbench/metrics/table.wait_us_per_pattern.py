"""Host-clock time of the table's wait for the device per pattern it
scanned, in us: the table's ``wait`` span (inside ``dispatch``: from the
planner's launch returning until the counts and ranks the merge reads
are host arrays, so the device's work and the copy back), its summed
milliseconds over the patterns the planner ran in the window."""


def read(ctx):
    span = ctx["spans"]["table"].get("wait")
    before, after = ctx["planner"]
    patterns = after["queries"] - before["queries"]
    if not span or patterns <= 0:
        return None
    return span["sum_ms"] * 1e3 / patterns
