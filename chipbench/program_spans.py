#!/usr/bin/env python3
"""The program's own host spans (``repro.obs``) as the run's trace holds
them, for the per-layer metrics; and the device's idle time split by host
span.

``spans()`` is what the metric readers call: the ``serve.`` and ``price.``
spans on the host planes of the run's trace (the traced last seconds of
the window), read once and shared by every reader, each with the name of
the innermost program span around it on the same thread.  Times are
seconds on the trace's clock.  A program that opens no such spans gives
none, and the readers then return ``None``.

  python chipbench/program_spans.py <file.xplane.pb>

prints the device's idle seconds in a trace by host span: each idle gap
is cut at the boundaries of the host spans (the harness's ``chipbench.``
spans, under their short names, and the program's, under their full
names), and each piece goes to the innermost span that covers it.  The
window, busy time and gaps are those of ``traces.reduce_trace``, whose
``idle_gaps`` still gives each whole gap to the span open at its middle;
this command goes once ``reduce_trace`` splits its gaps with
``split_gaps``.
"""
from __future__ import annotations

import heapq
import json
import sys
from collections import defaultdict
from typing import NamedTuple, Optional

HARNESS = "chipbench."
PROGRAM = ("serve.", "price.")
_kept = None


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[str]     # the innermost program span around it


def host_events(planes, prefixes):
    """``[(line, start_ns, end_ns, name)]`` of the host events whose name
    starts with one of ``prefixes``."""
    host = [p for p in planes if p.name.startswith("/host:")]
    return [((i, j), ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for i, plane in enumerate(host)
            for j, ln in enumerate(plane.lines) for ev in ln.events
            if ev.name.startswith(prefixes)]


def program_spans(planes):
    """The program's spans, parented by nesting on their thread's line."""
    out, stack, line = [], [], None
    for ln, s, e, name in sorted(host_events(planes, PROGRAM),
                                 key=lambda x: (x[0], x[1], -x[2])):
        if ln != line:
            stack, line = [], ln
        while stack and stack[-1][1] <= s:
            stack.pop()
        out.append(Span(name, s / 1e9, e / 1e9,
                        stack[-1][0] if stack else None))
        stack.append((name, e))
    return out


def spans():
    global _kept
    if _kept is None:
        from jax.profiler import ProfileData
        from run import STATE
        from traces import find
        path = find(str(STATE / "trace"))
        _kept = (program_spans(ProfileData.from_file(path).planes)
                 if path else [])
    return _kept


def named(name, parent=None):
    """The spans ``name`` (inside ``parent``, if given)."""
    return [s for s in spans()
            if s.name == name and (parent is None or s.parent == parent)]


def mean_ms(ss):
    return sum(s.t1 - s.t0 for s in ss) / len(ss) * 1e3 if ss else None


def split_gaps(gaps, host):
    """Seconds of ``gaps`` (sorted, disjoint ``(start, end)`` in ns) by the
    innermost of ``host`` (``(start, end, name)`` in ns, the shortest
    span) that covers each piece, or ``traces.NO_SPAN``."""
    from traces import NO_SPAN
    opens, closes = defaultdict(list), defaultdict(list)
    for i, (s, e, _) in enumerate(host):
        opens[s].append(i)
        closes[e].append(i)
    times = sorted(set(opens) | set(closes) | {t for g in gaps for t in g})
    heap, closed, out, g = [], set(), defaultdict(int), 0
    for a, b in zip(times, times[1:]):
        closed.update(closes.get(a, ()))
        for i in opens.get(a, ()):
            heapq.heappush(heap, (host[i][1] - host[i][0], i))
        while heap and heap[0][1] in closed:
            heapq.heappop(heap)
        while g < len(gaps) and gaps[g][1] <= a:
            g += 1
        if g < len(gaps) and gaps[g][0] <= a:       # [a, b) lies in gap g
            out[host[heap[0][1]][2] if heap else NO_SPAN] += b - a
    return {n: v / 1e9 for n, v in out.items()}


def idle_by_span(path):
    """The window, busy and idle seconds of a trace, and the idle seconds
    by host span (``split_gaps``), per device."""
    from jax.profiler import ProfileData
    from traces import MODULES_LINE, OPS_LINE, clip, gaps, union
    pd = ProfileData.from_file(path)
    devices = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and (OPS_LINE in lines
                                                 or MODULES_LINE in lines):
            devices.append(lines.get(OPS_LINE) or lines[MODULES_LINE])
    host = [(s, e, n[len(HARNESS):] if n.startswith(HARNESS) else n)
            for _, s, e, n in host_events(pd.planes, (HARNESS,) + PROGRAM)]
    own = [(s, e) for _, s, e, n in host_events(pd.planes, (HARNESS,))]
    if not devices or not own:
        return None
    lo, hi = min(s for s, _ in own), max(e for _, e in own)
    out = []
    for line in devices:
        busy = clip(union((ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events), lo, hi)
        split = split_gaps(gaps(busy, lo, hi), host)
        out.append({"window_s": (hi - lo) / 1e9,
                    "busy_s": sum(e - s for s, e in busy) / 1e9,
                    "idle_s": sum(split.values()),
                    "idle_by_span": sorted(split.items(),
                                           key=lambda kv: -kv[1])})
    return out


if __name__ == "__main__":
    print(json.dumps(idle_by_span(sys.argv[1]), indent=1))
