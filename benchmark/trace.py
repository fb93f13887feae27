"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

The window is the host span that the harness puts around the traced
discovery (`WINDOW_SPAN`).  Within it:

* busy time: the union of the intervals in which an operation ran on a
  device (the device plane's "XLA Ops" line), averaged over the devices;
* per-program and per-operation device time: summed durations on the
  "XLA Modules" and "XLA Ops" lines, by event name;
* idle gaps: the complement of the busy union, longest first, each named
  by the innermost span on the harness's own thread that covers the gap's
  midpoint (the program's stage spans and runtime calls, which the
  program's --trace option writes as TraceAnnotations).
"""

from __future__ import annotations

import dataclasses
import gzip
import re

import numpy as np

WINDOW_SPAN = "benchmark.discovery"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float  # mean over the device planes
    n_devices: int
    module_s: dict  # program name -> device seconds, summed over devices
    op_s: dict      # operation name -> device seconds, summed over devices
    gaps: list      # [(host span name, seconds)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def load(path: str):
    """A profile from an `.xplane.pb` file, or from a gzipped XSpace text
    proto (`.pbtxt.gz`, the trimmed form the tests keep)."""
    from jax.profiler import ProfileData

    if path.endswith(".pbtxt.gz"):
        with gzip.open(path, "rt") as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged, sorted intervals of possibly overlapping ones."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > run_end[:-1]]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def _events(line, lo: float, hi: float):
    """(names, starts, ends) of a line's events, clipped to [lo, hi]."""
    names, starts, ends = [], [], []
    for ev in line.events:
        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
        if e <= lo or s >= hi:
            continue
        names.append(ev.name)
        starts.append(max(s, lo))
        ends.append(min(e, hi))
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def _window(planes):
    """The harness's window span and the host line that holds it."""
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    return ev.start_ns, ev.start_ns + ev.duration_ns, line
    raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")


def reduce(profile, top: int = 10) -> Reduction:
    planes = list(profile.planes)
    lo, hi, host_line = _window(planes)
    devices = [p for p in planes if _DEVICE_PLANE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy = []
    module_s: dict = {}
    op_s: dict = {}
    first_union = None
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        for line_name, acc in ((OPS_LINE, op_s), (MODULES_LINE, module_s)):
            if line_name not in lines:
                continue
            names, s, e = _events(lines[line_name], lo, hi)
            for n, d in zip(names, e - s):
                acc[n] = acc.get(n, 0.0) + d * 1e-9
            if line_name == OPS_LINE:
                us, ue = _union(s, e)
                busy.append(float((ue - us).sum()) * 1e-9)
                if first_union is None:
                    first_union = (us, ue)
    if not busy:
        raise ValueError(f"no device plane has an {OPS_LINE!r} line")
    us, ue = first_union
    gap_s = np.r_[lo, ue]
    gap_e = np.r_[us, hi]
    keep = gap_e > gap_s
    gap_s, gap_e = gap_s[keep], gap_e[keep]
    longest = np.argsort(gap_s - gap_e, kind="stable")[:top]
    names, hs, he = _events(host_line, lo, hi)
    gaps = []
    for g in longest:
        mid = 0.5 * (gap_s[g] + gap_e[g])
        cover = np.flatnonzero((hs <= mid) & (he >= mid))
        label = "(no host span)"
        if len(cover):
            label = names[cover[np.argmin(he[cover] - hs[cover])]]
        gaps.append((label, float(gap_e[g] - gap_s[g]) * 1e-9))
    return Reduction(window_s=(hi - lo) * 1e-9,
                     busy_s=float(np.mean(busy)), n_devices=len(devices),
                     module_s=module_s, op_s=op_s, gaps=gaps)


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
