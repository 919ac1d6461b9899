"""Milliseconds per engine step that ``Engine.step`` spends in its own
Python, outside every span it opens (admission, pricing, the extend, the
decode's inputs, dispatch, wait and copy, sampling): the mean over the
program's ``serve.step`` spans in the run's trace of each one's length
less its children's."""
from bisect import bisect_left, bisect_right

import program_spans


def read(run):
    kids = sorted((s.t0, s.t1) for s in program_spans.spans()
                  if s.parent == "serve.step")
    starts = [s for s, _ in kids]
    own = []
    for st in program_spans.named("serve.step"):
        inner = kids[bisect_left(starts, st.t0):bisect_right(starts, st.t1)]
        own.append(st.t1 - st.t0 - sum(e - s for s, e in inner))
    return sum(own) / len(own) * 1e3 if own else None
