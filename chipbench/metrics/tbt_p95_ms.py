"""95th percentile, in milliseconds, of every gap between two consecutive
output tokens of a request whose later token came in the window (host
clock)."""


def read(run):
    np = run.np
    gaps = []
    for r in run.requests:
        s = np.asarray(r["stamps"])
        if len(s) > 1:
            d, end = np.diff(s), s[1:]
            gaps.append(d[(end >= run.t0) & (end < run.t1)])
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    return float(np.percentile(gaps, 95) * 1e3) if gaps.size else None
