"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* ``busy_s``: the union of the intervals in which an operation ran on
  a device, inside the window, averaged over the devices traced.
* ``window_s``: the window, the ``bench.window`` span ``bench/drive.py`` opens
  at the start of the measured window and closes at its end.
* ``device_ops``: the ten device operations that took most time in
  the window, ``[name, seconds]``, named ``<program>/<op>``: the
  program (``jit__lambda(<fingerprint>)``) whose run the operation lies
  in, and the HLO instruction (with the parameter it converts or
  copies).  A loop's own event is left out: its body's operations are
  listed.
* ``idle_gaps``: idle device time in the window by what the host was
  doing, ``[label, seconds]``: ``bench/drive.py``'s spans (``bench.step``
  around a scheduler step, ``bench.pump`` around admission,
  ``bench.wait`` while it waits for an arrival), ``host`` for time in
  none of them.

Device operations are read from each device plane's ``XLA Ops`` line.
A trace in which no device plane holds an operation (the CPU backend
has none) reduces to ``None``: host work is never read as device time.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench.window"
LABELS = ("bench.step", "bench.pump", "bench.wait")
TOP = 10
# control flow: its time is its body's operations, listed on their own
NESTING = {"while", "conditional", "call"}
_HLO = re.compile(r"%?([\w.\-]+) = .*?\b([a-z][a-z\-]*)\(")
_PARAM = re.compile(r"%(p__[\w.]+)")


def op_name(text: str) -> Tuple[str, str]:
    """``(short name, opcode)`` of a device event named by its HLO text,
    e.g. ``convert.167(p__blocks____mlp____w1__.1)``, ``convert``."""
    m = _HLO.match(text)
    if not m:
        return text[:80], ""
    name, opcode = m.groups()
    p = _PARAM.search(text) if opcode in ("convert", "copy") else None
    return (f"{name}({p.group(1)})" if p else name), opcode


def _union(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[int, int]]:
    """Merged, sorted intervals of ``[starts, ends)``."""
    if not len(starts):
        return []
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    # a new interval starts where a start lies beyond every end before it
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(s) - 1)
    return list(zip(s[idx].tolist(), e[last].tolist()))


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _intersect(a, b) -> int:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        tot += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def reduce(path: str) -> Optional[dict]:
    """The trace's numbers, or ``None`` where it holds no device work."""
    data = load(path)
    host: Dict[str, List[Tuple[int, int]]] = collections.defaultdict(list)
    dev_events: Dict[str, List[Tuple[int, int, str, bool]]] = {}
    for plane in data.planes:
        name = plane.name
        if name.startswith("/device:") and "CPU" not in name:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                           ev.name) for ev in (lines["XLA Modules"].events
                                               if "XLA Modules" in lines
                                               else ()))
            evs, k = [], 0
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s = int(ev.start_ns)
                short, opcode = op_name(ev.name)
                while k < len(mods) and mods[k][1] < s:
                    k += 1
                prog = mods[k][2] if k < len(mods) and mods[k][0] <= s \
                    else None
                evs.append((s, s + int(ev.duration_ns),
                            f"{prog}/{short}" if prog else short,
                            opcode in NESTING))
            if evs:
                dev_events[name] = evs
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, d = int(ev.start_ns), int(ev.duration_ns)
                    if ev.name == WINDOW or ev.name in LABELS:
                        host[ev.name].append((s, s + d))
    if not dev_events:
        return None
    if host.get(WINDOW):
        w0, w1 = host[WINDOW][0]
    else:
        w0 = min(e[0] for evs in dev_events.values() for e in evs)
        w1 = max(e[1] for evs in dev_events.values() for e in evs)
    busy_total = 0
    ops: Dict[str, int] = collections.Counter()
    gaps: Dict[str, int] = collections.Counter()
    labelled = {k: _clip(_union(*map(np.asarray, zip(*v))), w0, w1)
                for k, v in host.items() if k in LABELS and v}
    for evs in dev_events.values():
        arr = np.asarray([e[:2] for e in evs], np.int64)
        busy = _clip(_union(arr[:, 0], arr[:, 1]), w0, w1)
        busy_total += sum(b - a for a, b in busy)
        for a, b, nm, nested in evs:
            if b > w0 and a < w1 and not nested:
                ops[nm] += min(b, w1) - max(a, w0)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        left = sum(b - a for a, b in idle)
        for lab, spans in labelled.items():
            ov = _intersect(idle, spans)
            gaps[lab] += ov
            left -= ov
        if left > 0:
            gaps["host"] += left
    n = len(dev_events)
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(TOP)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gaps.most_common(TOP)],
        "devices": sorted(dev_events),
    }
