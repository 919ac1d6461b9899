"""Median seconds from a request's due time to its first token, over
every request due in the window (host clock; a token counts when the
engine step that made it returns)."""


def read(run):
    v = [r["first"] - (run.t0 + r["due"]) for r in run.window
         if r["first"] is not None]
    return float(run.np.median(v)) if v else None
