"""Output tokens that came in the window, divided by its length (host
clock)."""


def read(run):
    n = sum(sum(run.t0 <= t < run.t1 for t in r["stamps"])
            for r in run.requests)
    return n / run.seconds
