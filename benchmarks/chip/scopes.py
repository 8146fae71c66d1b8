"""Reduce a profiler trace to the names the program gives its own work.

* ``scopes``: device **self** seconds per named-scope path.  An operation's
  self time is its duration less the union of the operations that start
  inside it on the same line (a TPU's ``XLA Ops`` line nests a loop's body
  inside the loop's own event), so the self times of a line add up to its
  busy time.  An operation's scope path comes from the ``op_name``
  metadata that ``jax.named_scope`` writes into the compiled program, read
  from the HLO the profiler records in its ``/host:metadata`` plane: the
  segments that are the program's scopes (``SCOPES``), in order, a scope
  under an autodiff ``transpose(...)`` written ``transpose(<scope>)``.  An
  operation that names no scope (XLA's copies in a loop's body carry no
  ``op_name``) counts under the scope of the innermost operation it runs
  inside; one inside none goes under ``none``.
* ``idle_by_span``: device idle seconds of the traced window, each gap
  between device operations split by overlap over the program's host spans
  (``jax.profiler.TraceAnnotation``: ``trainer.*``, and the ``train`` step
  around them), each piece under the innermost span covering it; a piece no
  span covers, and the window's edges before the first and after the last
  device operation, go under ``none``.

``ProfileData`` gives events and their own stats, but not the stats held on
event metadata, where the HLO sits; ``_fields`` reads those from the file's
protobuf wire format.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

import trace

SCOPES = ("model", "attention", "mlp", "lm_head", "inner_opt", "clip",
          "muon", "newton_schulz", "adamw", "outer_step")
SPAN = re.compile(r"^(trainer\.\w+|train)$")
NONE = "none"

# transforms that wrap a scope's name in an op's path: ``vmap(jvp(model))``
_WRAP = re.compile(r"^(vmap|jvp|transpose|pmap)\((.*)\)$")
_INSTR = re.compile(r"^%?([^\s=]+)")              # "%fusion.3 = f32[...] ..."


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as the trace's metadata needs it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _str(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane_metadata(plane) -> Tuple[str, Dict[int, str], List]:
    """A raw XPlane's name, stat names by id, and its event metadata
    (XEventMetadata messages); its lines are skipped unread."""
    name, stat_names, events = "", {}, []
    for num, value in _fields(plane):
        if num == 2:
            name = _str(value)
        elif num in (4, 5):    # map entries: event and stat metadata
            entry = dict(_fields(value))
            if num == 4:
                events.append(entry.get(2, b""))
            else:
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
    return name, stat_names, events


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """Instruction name -> ``op_name`` over every computation of an
    HloProto's module."""
    out = {}
    for num, module in _fields(hlo_proto):
        if num != 1:                                   # hlo_module
            continue
        for num, comp in _fields(module):
            if num != 3:                               # computations
                continue
            for num, instr in _fields(comp):
                if num != 2:                           # instructions
                    continue
                name, op_name = "", ""
                for fnum, value in _fields(instr):
                    if fnum == 1:
                        name = _str(value)
                    elif fnum == 7:                    # OpMetadata
                        op_name = _str(dict(_fields(value)).get(2, b""))
                if name and op_name:
                    out[name] = op_name
    return out


def program_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """Per compiled program (``<name>(<program id>)``, as the profiler
    names it), its instructions' ``op_name``s."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, plane in _fields(buf):
        if num != 1:
            continue
        name, stat_names, events = _plane_metadata(plane)
        if name != "/host:metadata":
            continue
        for meta in events:
            fields = list(_fields(meta))
            program = next((_str(v) for n, v in fields if n == 2), "")
            for n, stat in fields:
                if n != 5:
                    continue
                s = dict(_fields(stat))
                if stat_names.get(s.get(1)) == "Hlo Proto" and 6 in s:
                    out[program] = _hlo_op_names(s[6])
    return out


# ---------------------------------------------------------------------------
# scopes
# ---------------------------------------------------------------------------

def scope_path(op_name: str) -> str:
    """The program's scopes in an op's path, outermost first; a scope that
    autodiff transposed is written ``transpose(<scope>)``.  Where XLA merged
    ops (``a;b``), the first one's path."""
    parts: List[str] = []
    for seg in op_name.split(";")[0].split("/"):
        transposed = False
        m = _WRAP.match(seg)
        while m:
            transposed |= m.group(1) == "transpose"
            seg = m.group(2)
            m = _WRAP.match(seg)
        if seg in SCOPES:
            parts.append(f"transpose({seg})" if transposed else seg)
    return "/".join(parts) or NONE


def self_times(events: List[Tuple[float, float, str]]
               ) -> List[Tuple[float, str]]:
    """(self ns, scope path) of each (start, end, scope path) event: its
    duration less the union of the events that start inside it, so that
    each instant counts once, for the innermost event running then (the
    one that started last; a TPU loop's recorded end can reach past the
    next op's start).  An event that names no scope (a copy XLA put in a
    loop's body carries no ``op_name``) takes the scope of the innermost
    event it runs inside."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    inner: List[List[Tuple[float, float]]] = [[] for _ in events]
    keys: List[str] = []
    active: List[int] = []
    for i, (s, e, key) in enumerate(events):
        active = [j for j in active if events[j][1] > s]
        for j in active:
            inner[j].append((s, min(e, events[j][1])))
        if key == NONE and active:
            key = keys[active[-1]]
        keys.append(key)
        active.append(i)
    return [((e - s) - sum(b - a for a, b in trace.union(inner[i])), keys[i])
            for i, (s, e, _) in enumerate(events)]


def _event_program(ev, modules) -> Optional[str]:
    """The program an op event ran in: its own stats where it has them (the
    CPU's ``hlo_module`` and ``program_id``), else the ``XLA Modules`` event
    that covers it."""
    stats = dict(ev.stats)
    if "hlo_module" in stats and "program_id" in stats:
        return f"{stats['hlo_module']}({stats['program_id']})"
    k = bisect.bisect_right(modules, (ev.start_ns, float("inf"))) - 1
    if k >= 0 and modules[k][1] >= ev.start_ns + ev.duration_ns:
        return modules[k][2]
    return None


def _instruction(ev) -> str:
    stats = dict(ev.stats)
    if "hlo_op" in stats:
        return str(stats["hlo_op"])
    m = _INSTR.match(ev.name)
    return m.group(1) if m else ev.name


# ---------------------------------------------------------------------------
# idle time by host span
# ---------------------------------------------------------------------------

def idle_by_span(gaps: List[Tuple[float, float]],
                 spans: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of each (start, end) gap under the innermost (shortest)
    span covering each piece of it; ``none`` where no span does."""
    out: Dict[str, float] = {}
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    for a, b in gaps:
        cover = [sp for sp in spans[:bisect.bisect_left(starts, b)]
                 if sp[1] > a]
        cuts = sorted({a, b} | {t for s, e, _ in cover for t in (s, e)
                                if a < t < b})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            inside = [sp for sp in cover if sp[0] <= mid <= sp[1]]
            name = min(inside, key=lambda sp: sp[1] - sp[0])[2] \
                if inside else NONE
            out[name] = out.get(name, 0.0) + (hi - lo)
    return out


# ---------------------------------------------------------------------------

def reduce_scopes(path: str, layout: Dict = trace.TPU,
                  window_s: Optional[float] = None) -> Dict:
    """``scopes`` and ``idle_by_span`` (module docstring) of one trace file,
    in seconds summed over the device planes.  ``window_s`` is the traced
    window ``trace.reduce_trace`` reports; the idle it holds beyond the
    gaps between device operations is the window's edges."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    paths = {program: {instr: scope_path(op) for instr, op in ops.items()}
             for program, ops in program_op_names(path).items()}
    dev_re = re.compile(layout["device_plane"])
    op_re, mod_re = (re.compile(layout["op_lines"]),
                     re.compile(layout["module_lines"]))
    skip_re = re.compile(layout["skip_ops"])
    host_plane_re = re.compile(layout["host_plane"])
    host_line_re = re.compile(layout["host_lines"])

    scopes: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    busy_ns = span_ns = 0.0
    planes = [p for p in data.planes if dev_re.search(p.name)]
    for plane in planes:
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in trace._events(plane, mod_re))
        for line in plane.lines:
            if not op_re.search(line.name):
                continue
            evs = []
            for ev in line.events:
                if ev.duration_ns <= 0 or skip_re.search(ev.name):
                    continue
                key = paths.get(_event_program(ev, modules), {}).get(
                    _instruction(ev), NONE)
                evs.append((ev.start_ns, ev.start_ns + ev.duration_ns, key))
            for ns, key in self_times(evs):
                scopes[key] = scopes.get(key, 0.0) + ns * 1e-9
        busy = trace.union([(ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in trace._events(plane, op_re, skip_re)])
        if busy:
            busy_ns += sum(e - s for s, e in busy)
            span_ns += busy[-1][1] - busy[0][0]
            gaps += [(x[1], y[0]) for x, y in zip(busy, busy[1:])]

    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
             for plane in data.planes if host_plane_re.search(plane.name)
             for line in plane.lines if host_line_re.search(line.name)
             for ev in line.events if SPAN.match(ev.name)]
    idle = {k: v * 1e-9 for k, v in idle_by_span(gaps, spans).items()}
    n = max(len(planes), 1)
    if window_s is not None:
        edges = window_s - (span_ns / n) * 1e-9
        if edges > 0:
            idle[NONE] = idle.get(NONE, 0.0) + edges * n
    return {"busy_s": busy_ns * 1e-9 / n,
            "scopes": {k: v / n for k, v in scopes.items()},
            "idle_by_span": {k: v / n for k, v in idle.items()},
            "spans": len(spans)}


def newest_trace() -> Optional[str]:
    """The trace the run just wrote: the newest ``.xplane.pb`` under
    ``<checkout>/.bench_traces`` (``run.py`` writes each traced run's there
    and reduces it at once)."""
    import harness

    found = glob.glob(os.path.join(harness.checkout_root(), ".bench_traces",
                                   "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def of_run(run: Dict) -> Optional[Dict]:
    """``reduce_scopes`` of the run's trace, kept in ``run`` for all its
    readers."""
    if "scopes" not in run:
        path = newest_trace()
        run["scopes"] = path and reduce_scopes(
            path, window_s=run.get("trace", {}).get("window_s"))
    return run["scopes"]


def per_step_ms(run: Dict, first: str) -> Optional[float]:
    """Device milliseconds per inner step of every scope path whose
    outermost scope is ``first``; ``None`` where the program names no such
    scope."""
    red = of_run(run)
    if not red or not run.get("steps"):
        return None
    hits = [v for k, v in red["scopes"].items() if k.split("/")[0] == first]
    return 1000.0 * sum(hits) / run["steps"] if hits else None
