"""Device milliseconds per call of the engine's ``decode`` program (one
token for every slot), from the trace."""


def read(run):
    p = (run.trace or {}).get("programs", {}).get("decode")
    return p[0] / p[1] * 1e3 if p and p[1] else None
