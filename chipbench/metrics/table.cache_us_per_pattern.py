"""Host-clock time of the table's result cache per pattern it scanned,
in us: the table's ``cache_lookup`` span (one probe per pattern asked)
plus its ``cache_fill`` span (copying the answers out and storing each
missed pattern), their summed milliseconds over the patterns the
planner ran in the window."""


def read(ctx):
    table = ctx["spans"]["table"]
    spans = [table[name] for name in ("cache_lookup", "cache_fill")
             if name in table]
    before, after = ctx["planner"]
    patterns = after["queries"] - before["queries"]
    if not spans or patterns <= 0:
        return None
    return sum(s["sum_ms"] for s in spans) * 1e3 / patterns
