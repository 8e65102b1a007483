"""Patterns per wave the scheduler ran in the window: queries executed
times patterns per query, over batches executed (``SchedulerStats``)."""


def read(ctx):
    before, after = ctx["scheduler"]
    batches = after["batches"] - before["batches"]
    if batches <= 0:
        return None
    executed = after["executed"] - before["executed"]
    return executed * int(ctx["mix"]["patterns_per_request"]) / batches
