"""Mean number of the engine's ``prefill_chunk`` events per request due in
the window that got its first token (the engine's own event log)."""


def read(run):
    v = [r["chunks"] for r in run.window
         if r["first"] is not None and "chunks" in r]
    return float(sum(v) / len(v)) if v else None
