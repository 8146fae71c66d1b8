"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, device time per compiled
program, the operations that took most time, and the longest idle gaps
named by what the host was doing in them.

The layout says which planes are devices and which of their lines carry
operations and programs.  ``TPU`` is what a TPU host records; the tests
use ``CPU``, where XLA's operations run on the host's own threads.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

TPU = {
    "device_plane": r"^/device:TPU:\d+$",
    "op_lines": r"^XLA Ops$",
    "module_lines": r"^XLA Modules$",
    "skip_ops": r"^$",
    "host_plane": r"^/host:CPU$",
    "host_lines": r".",
}

CPU = {
    "device_plane": r"^/host:CPU$",
    "op_lines": r"^tf_XLA",
    "module_lines": r"^$^",
    "skip_ops": r"^(ThreadpoolListener|SlinkyThreadPool)::",
    "host_plane": r"^/host:CPU$",
    "host_lines": r"^python",
}

_ID_SUFFIX = re.compile(r"\(\d+\)$")
NAME = 120          # characters of an operation's name kept in a breakdown


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(plane, line_re, skip_re=None):
    for line in plane.lines:
        if not line_re.search(line.name):
            continue
        for ev in line.events:
            if ev.duration_ns <= 0:
                continue
            if skip_re is not None and skip_re.search(ev.name):
                continue
            yield ev


def _program(name: str) -> str:
    return _ID_SUFFIX.sub("", name)


def _name_gap(host, s: float, e: float) -> str:
    """The most specific host span that covers the middle of a gap."""
    mid = (s + e) / 2.0
    best = None
    for start, end, name in host:
        if start <= mid <= end and (best is None
                                    or end - start < best[1] - best[0]):
            best = (start, end, name)
    return best[2] if best else "no host span"


def reduce_trace(path: str, layout: Dict = TPU,
                 window_s: Optional[float] = None, top: int = 10) -> Dict:
    """Reduce one trace file.

    ``busy_s`` is the union of the intervals in which an operation ran,
    averaged over the device planes; ``window_s`` is the traced window: the
    caller's clock, or first to last device event where that is longer, so
    that ``busy_s <= window_s`` always holds.  ``programs`` maps
    each compiled program to its device seconds and calls, ``ops`` each
    operation name to its device seconds (both summed over devices).
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    dev_re = re.compile(layout["device_plane"])
    op_re, mod_re = (re.compile(layout["op_lines"]),
                     re.compile(layout["module_lines"]))
    skip_re = re.compile(layout["skip_ops"])
    host_plane_re = re.compile(layout["host_plane"])
    host_line_re = re.compile(layout["host_lines"])

    planes = [p for p in data.planes if dev_re.search(p.name)]
    ops: Dict[str, float] = {}
    programs: Dict[str, List[float]] = {}
    busy_ns, first, last = 0.0, float("inf"), float("-inf")
    merged_all: List[Tuple[float, float]] = []
    for plane in planes:
        spans = []
        for ev in _events(plane, op_re, skip_re):
            spans.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns * 1e-9
        for ev in _events(plane, mod_re):
            rec = programs.setdefault(_program(ev.name), [0.0, 0])
            rec[0] += ev.duration_ns * 1e-9
            rec[1] += 1
        merged = union(spans)
        busy_ns += sum(e - s for s, e in merged)
        if merged:
            first, last = min(first, merged[0][0]), max(last, merged[-1][1])
        merged_all += merged
    n = max(len(planes), 1)
    span_s = (last - first) * 1e-9 if merged_all else 0.0
    # the traced window holds every device event it recorded, also one that
    # ran past the caller's clock
    window_s = span_s if window_s is None else max(window_s, span_s)

    host = []
    for plane in data.planes:
        if not host_plane_re.search(plane.name):
            continue
        for line in plane.lines:
            if not host_line_re.search(line.name):
                continue
            for ev in line.events:
                if ev.duration_ns > 0:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    busy = union(merged_all)
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)[:top]
    return {
        "devices": len(planes),
        "busy_s": busy_ns * 1e-9 / n,
        "window_s": window_s,
        "programs": {k: {"seconds": v[0], "calls": v[1]}
                     for k, v in programs.items()},
        "ops": ops,
        "top_ops": sorted(([k[:NAME], v] for k, v in ops.items()),
                          key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[_name_gap(host, s, e)[:NAME], g * 1e-9]
                      for g, s, e in gaps],
    }


def program_seconds(reduced: Dict, pattern: str) -> Tuple[float, int]:
    """Device seconds and calls of every program whose name matches."""
    rx = re.compile(pattern)
    secs, calls = 0.0, 0
    for name, rec in reduced["programs"].items():
        if rx.search(name):
            secs += rec["seconds"]
            calls += rec["calls"]
    return secs, calls


def describe(path: str, per_line: int = 5) -> List[str]:
    """Planes, lines and their first events: read this once by hand before
    writing a reader against a new kind of trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for ev in evs:
                names[ev.name] = names.get(ev.name, 0) + ev.duration_ns
            heavy = sorted(names.items(), key=lambda kv: -kv[1])[:per_line]
            out.append(f"  line {line.name!r}: {len(evs)} events; "
                       f"heaviest {heavy}")
    return out
