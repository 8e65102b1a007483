"""Share of the patterns answered in the window whose text-order
``first_pos`` the frozen tier's search launch settled on the device,
in percent: the planner's ``device_min_settled`` over its ``queries``
(``PlannerStats``).  The rest took the host's LF walks.  A program
without the counter gives nothing to read."""


def read(ctx):
    before, after = ctx["planner"]
    if "device_min_settled" not in after:
        return None
    queries = after["queries"] - before["queries"]
    if queries <= 0:
        return None
    return 100.0 * (after["device_min_settled"]
                    - before["device_min_settled"]) / queries
