"""Reduction of a profiler trace to the benchmark's device numbers.

From one ``.xplane.pb`` file: the union of the intervals in which an
operation ran on each device (busy time), the device time of each jitted
program by name, the device operations that took most time, and the idle
gaps of the device, each attributed to the innermost host span (a
``jax.profiler.TraceAnnotation`` whose name starts with ``prefix``) that
was open in the middle of the gap.  The window is the stretch from the
first to the last host span with that prefix.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "(no host span)"


def find(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi):
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


CONTAINERS = ("while", "conditional", "call")


def op_name(text: str) -> str | None:
    """``%fusion.3 = bf16[16,2048]{...} fusion(...)`` -> ``fusion.3
    bf16[16,2048]``; ``None`` for a loop or call, whose time is that of
    the operations inside it."""
    if " = " not in text:
        return text[:100]
    name, rest = text.split(" = ", 1)
    name = name.lstrip("%")
    if name.split(".")[0] in CONTAINERS:
        return None
    shape = "" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    return f"{name} {shape}".strip()[:100]


def program_name(event_name: str) -> str:
    """``jit_decode(123)`` -> ``decode``."""
    base = event_name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def reduce_trace(path: str, prefix: str = "chipbench.") -> dict | None:
    """Busy and window seconds, per-program device time, top device ops and
    idle gaps by host span.  ``None`` when the trace holds no device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and (OPS_LINE in lines
                                                 or MODULES_LINE in lines):
            devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(prefix):
                        spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                      ev.name[len(prefix):]))
    if not devices or not spans:
        return None
    lo = min(s for s, _, _ in spans)
    hi = max(e for _, e, _ in spans)
    busy_ns, ops, progs = [], defaultdict(float), defaultdict(lambda: [0.0, 0])
    gap_by_span = defaultdict(float)
    by_name = defaultdict(list)
    for s, e, n in spans:
        by_name[n].append((s, e))
    by_name = {n: np.asarray(sorted(v), np.float64) for n, v in by_name.items()}
    for lines in devices:
        op_line = lines.get(OPS_LINE) or lines[MODULES_LINE]
        ivs = []
        for ev in op_line.events:
            ivs.append((ev.start_ns, ev.start_ns + ev.duration_ns))
            name = op_name(ev.name)
            if name:
                ops[name] += ev.duration_ns / 1e9
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                if lo <= ev.start_ns < hi:
                    p = progs[program_name(ev.name)]
                    p[0] += ev.duration_ns / 1e9
                    p[1] += 1
        busy = clip(union(ivs), lo, hi)
        busy_ns.append(sum(e - s for s, e in busy))
        g = np.asarray(gaps(busy, lo, hi), np.float64).reshape(-1, 2)
        mid = g.mean(axis=1)
        best = np.full(len(g), np.inf)
        who = np.full(len(g), NO_SPAN, dtype=object)
        for n, iv in by_name.items():        # innermost open span wins
            i = np.searchsorted(iv[:, 0], mid, side="right") - 1
            ok = (i >= 0) & (iv[np.maximum(i, 0), 1] > mid)
            length = np.where(ok, iv[np.maximum(i, 0), 1]
                              - iv[np.maximum(i, 0), 0], np.inf)
            take = length < best
            best[take], who[take] = length[take], n
        for n, dur in zip(who, (g[:, 1] - g[:, 0]) / 1e9):
            gap_by_span[n] += dur
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "programs": {k: tuple(v) for k, v in progs.items()},
            "device_ops": top(ops),
            "idle_gaps": top(gap_by_span),
            "n_devices": len(devices)}
