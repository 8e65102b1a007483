"""Mean wait of a query in the scheduler's coalescing window before its
wave runs, in ms: the scheduler's ``coalesce_wait`` span, its summed
milliseconds over its count in the window (never its rolling
quantiles)."""


def read(ctx):
    span = ctx["spans"]["scheduler"].get("coalesce_wait")
    if not span or not span["total"]:
        return None
    return span["sum_ms"] / span["total"]
