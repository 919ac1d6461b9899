"""Device milliseconds per call of the engine's ``extend`` program (one
prefill chunk), from the trace."""


def read(run):
    p = (run.trace or {}).get("programs", {}).get("extend")
    return p[0] / p[1] * 1e3 if p and p[1] else None
