"""Reduce a profiler trace to device busy time, kernel time and the
breakdown of where the device time and the idle time went.

A trace is kept as plain data, ``{"planes": [{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]}``: ``from_xplane``
reads the profiler's ``.xplane.pb`` into it, and the tests keep a small
recorded one as JSON.  On a TPU each chip is a plane ``/device:TPU:<i>``
whose line ``XLA Ops`` holds every operation the chip ran and whose
line ``XLA Modules`` holds every program (``jit_<name>(<id>)``) it ran;
the host's threads are the lines of ``/host:CPU``.  The harness marks
the measured window with a host span ``chipbench.window``.

* busy: the union of the chip's operation intervals inside the window,
  averaged over the chips;
* a kernel's time: the summed durations of its program's events inside
  the window, averaged over the chips;
* ``breakdown``: the operations that took the most device time, and the
  device's idle time: gaps inside a program run, and gaps between runs
  each put down to the most specific host span covering its midpoint.
"""
from __future__ import annotations

import gc
import glob
import os
import re

import numpy as np

WINDOW_SPAN = "chipbench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = []
    # millions of small lists: the collector's passes over them would
    # cost more than reading them
    was = gc.isenabled()
    gc.disable()
    try:
        for pl in ProfileData.from_file(path).planes:
            lines = [{"name": ln.name,
                      "events": [[ev.name, ev.start_ns, ev.duration_ns]
                                 for ev in ln.events]}
                     for ln in pl.lines]
            planes.append({"name": pl.name, "lines": lines})
    finally:
        if was:
            gc.enable()
    return {"planes": planes}


_OP = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def op_name(name: str) -> str:
    """``%fusion.60 fusion`` for an operation event named by its whole
    HLO instruction (``%fusion.60 = u32[2048]{...} fusion(...), ...``)."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def _intervals(events: list) -> np.ndarray:
    if not events:
        return np.zeros((0, 2), np.float64)
    start = np.fromiter((e[1] for e in events), np.float64, len(events))
    dur = np.fromiter((e[2] for e in events), np.float64, len(events))
    return np.stack([start, start + dur], axis=1)


def _union(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted intervals covering ``iv`` clipped to [lo, hi]."""
    iv = np.clip(iv, lo, hi)
    iv = iv[iv[:, 1] > iv[:, 0]]
    if not iv.size:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    new = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    starts = iv[new, 0]
    ends = reach[np.concatenate([np.flatnonzero(new)[1:] - 1,
                                 [iv.shape[0] - 1]])]
    return np.stack([starts, ends], axis=1)


def _total(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if iv.size else 0.0


class Reduced:
    """What one traced window says about the chips."""

    def __init__(self, trace: dict):
        self.trace = trace
        self.devices = [p for p in trace["planes"]
                        if DEVICE_PLANE.match(p["name"])]
        self.host = [p for p in trace["planes"] if p["name"] == HOST_PLANE]
        self.lo, self.hi = self._window()
        self.busy = [_union(_intervals(_line(d, OPS_LINE)), self.lo,
                            self.hi) for d in self.devices]

    def _window(self) -> tuple[float, float]:
        for p in self.host:
            for ln in p["lines"]:
                for name, start, dur in ln["events"]:
                    if name == WINDOW_SPAN:
                        return float(start), float(start + dur)
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host plane")

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float | None:
        """Seconds in which an operation ran, averaged over the chips;
        None when the trace holds no chip."""
        if not self.devices:
            return None
        return float(np.mean([_total(b) for b in self.busy])) / 1e9

    def module_s(self, module: str) -> tuple[float, int] | None:
        """(seconds, executions) of program ``module`` (``jit_<name>``)
        inside the window, averaged over the chips; None when it never
        ran."""
        pat = re.compile(re.escape(module) + r"(\(|$|\.)")
        secs, runs = [], 0
        for d in self.devices:
            ev = [e for e in _line(d, MODULES_LINE) if pat.match(e[0])]
            iv = np.clip(_intervals(ev), self.lo, self.hi)
            secs.append(float((iv[:, 1] - iv[:, 0]).clip(0).sum()) / 1e9)
            runs += len(ev)
        if not runs:
            return None
        return float(np.mean(secs)), runs // len(self.devices)

    def device_ops(self) -> list:
        """The ``TOP`` operations by device time, [[name, seconds]] (a
        loop's time holds the operations of its body, which are listed
        too)."""
        raw: dict[str, float] = {}
        for d in self.devices:
            for name, start, dur in _line(d, OPS_LINE):
                a, b = max(start, self.lo), min(start + dur, self.hi)
                if b > a:
                    raw[name] = raw.get(name, 0.0) + (b - a) / 1e9
        tot: dict[str, float] = {}
        for name, secs in raw.items():
            key = op_name(name)
            tot[key] = tot.get(key, 0.0) + secs
        n = max(1, len(self.devices))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n] for k, v in top]

    def idle_gaps(self) -> list:
        """Device idle time, [[name, seconds]], the ``TOP`` largest.  A
        gap between two operations of one program run is ``between ops
        of a program``; a gap between program runs is put down to the
        host span covering its midpoint (the shortest such span over
        all host threads)."""
        lines = []
        for p in self.host:
            for ln in p["lines"]:
                ev = [e for e in ln["events"] if e[0] != WINDOW_SPAN]
                if ev:
                    ev.sort(key=lambda e: e[1])
                    iv = _intervals(ev)
                    lines.append((iv[:, 0], iv[:, 1], [e[0] for e in ev]))
        tot: dict[str, float] = {}
        n = max(1, len(self.devices))
        for dev, busy in zip(self.devices, self.busy):
            # busy or inside a program run; what a run covers beyond its
            # operations is idle between them
            runs = _intervals(_line(dev, MODULES_LINE))
            cover = _union(np.concatenate([busy, runs]), self.lo, self.hi)
            inside = (_total(cover) - _total(busy)) / 1e9
            if inside > 0:
                tot["between ops of a program"] = \
                    tot.get("between ops of a program", 0.0) + inside
            edges = np.concatenate([[self.lo], cover.reshape(-1),
                                    [self.hi]])
            gaps = edges.reshape(-1, 2)
            gaps = gaps[gaps[:, 1] > gaps[:, 0]]
            mid = gaps.mean(axis=1)
            best = np.full(mid.size, np.inf)
            at_line = np.full(mid.size, -1)
            at_event = np.zeros(mid.size, np.int64)
            for li, (starts, ends, _) in enumerate(lines):
                idx = np.searchsorted(starts, mid, "right") - 1
                # spans of one thread nest: the covering ones are the
                # last few that start before the midpoint
                for back in range(32):
                    j = idx - back
                    ok = j >= 0
                    if not ok.any():
                        break
                    jj = np.where(ok, j, 0)
                    length = ends[jj] - starts[jj]
                    better = ok & (ends[jj] >= mid) & (length < best)
                    best = np.where(better, length, best)
                    at_line = np.where(better, li, at_line)
                    at_event = np.where(better, jj, at_event)
            key = np.where(at_line >= 0, at_line * (1 << 40) + at_event, -1)
            uniq, inv = np.unique(key, return_inverse=True)
            secs = np.bincount(inv, weights=gaps[:, 1] - gaps[:, 0],
                               minlength=uniq.size) / 1e9
            for u, sec in zip(uniq, secs):
                name = ("no host span" if u < 0 else
                        lines[int(u) >> 40][2][int(u) & ((1 << 40) - 1)])
                tot[name] = tot.get(name, 0.0) + float(sec)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v / n] for k, v in top]

    def breakdown(self) -> dict | None:
        if not self.devices:
            return None
        return {"device_ops": self.device_ops(),
                "idle_gaps": self.idle_gaps()}
