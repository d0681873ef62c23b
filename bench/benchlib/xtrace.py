"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The window is the span from the first to the last host annotation whose
name starts with ``bench.`` (the harness wraps each ``next_batch`` and
each step in one).  Within it:

* busy time: the union of the intervals of the device's XLA ops, per
  device, averaged over the devices;
* per-op and per-program device time: the ``XLA Ops`` and ``XLA
  Modules`` lines of each device plane;
* idle gaps: the parts of the window no device op covers, each split
  over the host annotations it overlaps (the rest is ``harness``).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control flow whose events enclose the ops of their bodies: they count
# towards busy time but not as ops of their own
CONTAINERS = ("while", "conditional", "call")


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: Dict[str, float] = field(default_factory=dict)
    modules: Dict[str, Tuple[int, float]] = field(default_factory=dict)
    idle_by_host: Dict[str, float] = field(default_factory=dict)
    spans: Dict[str, Tuple[int, float]] = field(default_factory=dict)

    def module_time(self, needle: str) -> Tuple[int, float]:
        """(calls, device seconds) of the programs whose name holds
        ``needle``."""
        n, s = 0, 0.0
        for name, (calls, secs) in self.modules.items():
            if needle in name:
                n, s = n + calls, s + secs
        return n, s


def op_name(event: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def read_planes(path: str):
    """[(plane name, [(line name, [(event name, start ns, dur ns)])])]"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [(e.name, float(e.start_ns),
                                       float(e.duration_ns))
                                      for e in line.events]))
        out.append((plane.name, lines))
    return out


def reduce(planes) -> TraceSummary:
    spans = [(n, s, s + d) for pname, lines in planes
             if not _is_device(pname) for _l, evs in lines
             for n, s, d in evs if n.startswith(SPAN_PREFIX)]
    if not spans:
        raise ValueError("the trace holds no bench.* host annotation")
    lo = min(s for _n, s, _e in spans)
    hi = max(e for _n, _s, e in spans)
    window = hi - lo
    busy_total, n_dev = 0.0, 0
    ops: Dict[str, float] = {}
    modules: Dict[str, Tuple[int, float]] = {}
    idle: Dict[str, float] = {}
    for pname, lines in planes:
        if not _is_device(pname):
            continue
        op_events = [ev for lname, evs in lines if lname == OPS_LINE
                     for ev in evs]
        if not op_events:
            continue
        n_dev += 1
        busy = _union(_clip([(s, s + d) for _n, s, d in op_events], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        for n, s, d in op_events:
            name = op_name(n)
            if lo <= s < hi and not name.startswith(CONTAINERS):
                ops[name] = ops.get(name, 0.0) + d * 1e-9
        for lname, evs in lines:
            if lname != MODULES_LINE:
                continue
            for n, s, d in evs:
                if lo <= s < hi:
                    c, t = modules.get(n, (0, 0.0))
                    modules[n] = (c + 1, t + d * 1e-9)
        gaps, prev = [], lo
        for a, b in busy:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        if prev < hi:
            gaps.append((prev, hi))
        for a, b in gaps:
            covered = 0.0
            for n, s, e in spans:
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    key = n[len(SPAN_PREFIX):]
                    idle[key] = idle.get(key, 0.0) + ov * 1e-9
                    covered += ov
            if b - a > covered:
                idle["harness"] = idle.get("harness", 0.0) \
                    + (b - a - covered) * 1e-9
    if n_dev == 0:
        raise ValueError("the trace holds no device op")
    span_tot: Dict[str, Tuple[int, float]] = {}
    for n, s, e in spans:
        c, t = span_tot.get(n, (0, 0.0))
        span_tot[n] = (c + 1, t + (e - s) * 1e-9)
    idle = {k: v / n_dev for k, v in idle.items()}
    return TraceSummary(window * 1e-9, busy_total / n_dev * 1e-9, n_dev,
                        ops, modules, idle, span_tot)


def breakdown(summary: TraceSummary, top: int = 10) -> Dict:
    ops = sorted(summary.ops.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(summary.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
