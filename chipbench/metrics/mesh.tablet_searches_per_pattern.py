"""Tablet searches per pattern answered in the window: the planner's
``tablet_searches`` over its ``queries`` (``PlannerStats``).  A
broadcast batch is searched by every tablet, so it reads the tablet
count; routing, or pruning tablets by their split keys, lowers it.  A
program without the counter gives nothing to read."""


def read(ctx):
    before, after = ctx["planner"]
    if "tablet_searches" not in after:
        return None
    queries = after["queries"] - before["queries"]
    if queries <= 0:
        return None
    return (after["tablet_searches"] - before["tablet_searches"]) / queries
