"""The check fails when the timed path is broken underneath a run: the
control (text-order guarantee broken: the planner's suffix-rank order
served in place of the table's) and each fault a read cell can have.
Each fault returns the suffix of the checks it has to trip."""
import dataclasses

import numpy as np
import pytest

from chipbench_testing import run_tiny

import traffic

from repro.api.table import SuffixTable
from repro.core import planner
from repro.kernels import ops


def _control(monkeypatch):
    monkeypatch.setattr(
        SuffixTable, "scan",
        lambda self, patterns, top_k=0: self.planner.scan(patterns,
                                                          top_k=top_k))
    return "_wrong"


def _answer_altered(monkeypatch):
    """One count altered where the device search produces it."""
    def bump(fn):
        def wrapped(*a):
            res = fn(*a)
            return dataclasses.replace(res, count=res.count.at[0].add(1))
        return wrapped
    monkeypatch.setattr(planner, "_query_single",
                        bump(planner._query_single))
    monkeypatch.setattr(ops, "fm_search", bump(ops.fm_search))
    return "_wrong"


def _half_left_out(monkeypatch):
    """Only the first half of every batch is searched; the rest comes
    back as not found."""
    scan_batch = SuffixTable.scan_batch

    def half(self, patt, plen, top_k=0):
        B = int(np.asarray(plen).shape[0])
        keep = max(1, B // 2)
        out = scan_batch(self, np.asarray(patt)[:keep],
                         np.asarray(plen)[:keep], top_k=top_k)
        pad = B - keep
        return dataclasses.replace(
            out, found=np.concatenate([out.found, np.zeros(pad, bool)]),
            count=np.concatenate([out.count, np.zeros(pad, np.int64)]),
            first_pos=np.concatenate([out.first_pos,
                                      np.full(pad, -1, np.int64)]),
            positions=None if out.positions is None else np.concatenate(
                [out.positions, np.full((pad, top_k), -1, np.int64)]))
    monkeypatch.setattr(SuffixTable, "scan_batch", half)
    return "_wrong"


def _some_fail_fast(monkeypatch):
    """In the window, every third read comes back at once as an error,
    as an overloaded or refused request would."""
    state = {"on": False, "n": 0}
    scan = SuffixTable.scan
    run_closed_loop = traffic.run_closed_loop

    def flaky(self, patterns, top_k=0):
        if state["on"]:
            state["n"] += 1
            if state["n"] % 3 == 0:
                raise RuntimeError("overloaded")
        return scan(self, patterns, top_k=top_k)

    def armed(*a, **kw):
        state["on"] = True
        try:
            return run_closed_loop(*a, **kw)
        finally:
            state["on"] = False
    monkeypatch.setattr(SuffixTable, "scan", flaky)
    monkeypatch.setattr(traffic, "run_closed_loop", armed)
    return "failed"


@pytest.mark.parametrize("name", ["dna50.count20.live",
                                  "dna50.count20.frozen",
                                  "chr1.scan.users50"])
@pytest.mark.parametrize("fault", [_control, _answer_altered,
                                   _half_left_out, _some_fail_fast])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch,
                                          tmp_path):
    trips = fault(monkeypatch)
    res = run_tiny(name, tmp_path, seconds=0.5)
    assert not res["correct"], res["checks"]
    tripped = sum(res["checks"][k]["value"] for k in res["checks"]
                  if k.endswith(trips))
    assert tripped > 0
