"""Milliseconds spent in ``Engine._pick_chunk`` (the pricing solve) per
engine step in the window (host spans around the call)."""


def read(run):
    steps = sum(run.t0 <= s < run.t1 for s, _ in run.steps)
    busy = sum(e - s for s, e in run.spans.get("pick_chunk", [])
               if run.t0 <= s < run.t1)
    return busy / steps * 1e3 if steps else None
