"""Host-clock time of the table's upload per pattern it scanned, in us:
the table's ``upload`` span (inside ``dispatch``: padding the batch to
its bucket and copying patterns and lengths to the device), its summed
milliseconds over the patterns the planner ran in the window."""


def read(ctx):
    span = ctx["spans"]["table"].get("upload")
    before, after = ctx["planner"]
    patterns = after["queries"] - before["queries"]
    if not span or patterns <= 0:
        return None
    return span["sum_ms"] * 1e3 / patterns
