"""The trace reduction: busy and idle time, kernel time and the
breakdown, on a hand-made trace with known answers, on a small trace
recorded on a TPU v5e, and through the profiler's own file format."""
import json
import os

import numpy as np
import pytest

from chipbench_testing import harness  # noqa: F401  (puts chipbench on the path)

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "trace_v5e_excerpt.json")


def _hand_made():
    # window 1000..2000 ns; ops overlap and stick out of the window
    ops = [["fusion.1", 900, 200],     # 1000..1100 inside
           ["fusion.2", 1050, 100],    # nested in the union: 1050..1150
           ["while.3", 1400, 300],     # 1400..1700
           ["fusion.1", 1950, 200]]    # 1950..2000 inside
    # the second run of jit_query idles 1700..1750 between its ops
    modules = [["jit_query(7)", 900, 250], ["jit_query(7)", 1400, 350],
               ["jit_query_other(2)", 1950, 200]]
    host = [["chipbench.window", 1000, 1000],
            ["chipbench.request", 1000, 1000],
            ["PjitFunction(query)", 1150, 200],      # covers gap 1150..1400
            ["encode", 1700, 250]]                   # covers gap 1750..1950
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": host[:2] + host[3:]},
            {"name": "worker", "events": host[2:3]}]}]}


def test_reduction_of_a_hand_made_trace():
    r = trace_reduce.Reduced(_hand_made())
    assert r.window_s == pytest.approx(1000e-9)
    # busy: 1000..1150, 1400..1700, 1950..2000 = 150 + 300 + 50
    assert r.busy_s == pytest.approx(500e-9)
    secs, runs = r.module_s("jit_query")
    assert runs == 2                       # jit_query_other is another program
    assert secs == pytest.approx((150 + 350) * 1e-9)
    assert r.module_s("jit_fm_search") is None
    ops = dict(r.device_ops())
    assert ops["fusion.1"] == pytest.approx(150e-9)
    gaps = dict(r.idle_gaps())
    assert gaps == pytest.approx({"PjitFunction(query)": 250e-9,
                                  "encode": 200e-9,
                                  "between ops of a program": 50e-9})


def test_reduction_without_a_chip_reports_nothing():
    tr = _hand_made()
    tr["planes"] = tr["planes"][1:]
    r = trace_reduce.Reduced(tr)
    assert r.busy_s is None and r.breakdown() is None


def _brute_busy(events, lo, hi):
    """Busy nanoseconds by marking every covered nanosecond."""
    lo_i = int(lo)
    mark = np.zeros(int(hi) - lo_i, bool)
    for _, start, dur in events:
        a = max(int(start), lo_i) - lo_i
        b = min(int(start + dur), int(hi)) - lo_i
        if b > a:
            mark[a:b] = True
    return mark.sum()


def test_reduction_of_a_trace_recorded_on_the_chip():
    with open(RECORDED) as f:
        tr = json.load(f)
    r = trace_reduce.Reduced(tr)
    dev = r.devices[0]
    ops = next(ln["events"] for ln in dev["lines"]
               if ln["name"] == trace_reduce.OPS_LINE)
    assert r.busy_s * 1e9 == pytest.approx(
        _brute_busy(ops, r.lo, r.hi), abs=2)
    assert 0 < r.busy_s < r.window_s
    mods = next(ln["events"] for ln in dev["lines"]
                if ln["name"] == trace_reduce.MODULES_LINE)
    want = sum(min(s + d, r.hi) - max(s, r.lo) for name, s, d in mods
               if name.startswith("jit_query(") and s + d > r.lo
               and s < r.hi)
    secs, runs = r.module_s("jit_query")
    assert secs * 1e9 == pytest.approx(want)
    assert runs >= 1
    bd = r.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and bd["idle_gaps"]
    idle = sum(v for _, v in r.idle_gaps())
    assert idle <= r.window_s - r.busy_s + 1e-9


def test_profiler_file_is_read_into_planes(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((256,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_reduce.from_xplane(trace_reduce.find_xplane(str(tmp_path)))
    r = trace_reduce.Reduced(tr)
    assert r.window_s > 0
    assert any(p["name"] == trace_reduce.HOST_PLANE for p in tr["planes"])
