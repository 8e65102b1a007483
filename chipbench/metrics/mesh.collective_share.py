"""Share of the broadcast tablet search's device time (the program
``jit_broadcast``) spent in its cross-chip reductions, in percent: the
summed time of its all-reduce operations (the ``psum``s of the bounds
and of ``first_pos``; on a TPU also their ``-start`` / ``-done``
halves) inside the program's runs, over the program's time, both
averaged over the chips."""
import re

import numpy as np

import trace_reduce

MODULE = "jit_broadcast"
OPCODE = "all-reduce"
_RUN = re.compile(re.escape(MODULE) + r"(\(|$|\.)")     # as module_s


def _inside(iv: np.ndarray, runs: np.ndarray) -> float:
    """Nanoseconds of the intervals ``iv`` that fall inside ``runs``
    (disjoint, sorted)."""
    total = 0.0
    for lo, hi in runs:
        cut = np.clip(iv, lo, hi)
        total += float((cut[:, 1] - cut[:, 0]).clip(0).sum())
    return total


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not tr.devices:
        return None
    run = tr.module_s(MODULE)
    if run is None or run[0] <= 0:
        return None
    secs = []
    for dev in tr.devices:
        mods = [e for e in trace_reduce._line(dev, trace_reduce.MODULES_LINE)
                if _RUN.match(e[0])]
        runs = trace_reduce._union(trace_reduce._intervals(mods),
                                   tr.lo, tr.hi)
        ops = [e for e in trace_reduce._line(dev, trace_reduce.OPS_LINE)
               if trace_reduce.op_name(e[0]).split(" ")[-1]
               .startswith(OPCODE)]
        secs.append(_inside(trace_reduce._intervals(ops), runs) / 1e9
                    if ops and runs.size else 0.0)
    return 100.0 * float(np.mean(secs)) / run[0]
