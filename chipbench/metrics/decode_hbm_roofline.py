"""Share of the HBM roofline that the decode program reaches: the bytes
one decode step must move (``counts.decode``: weights once, the live
positions of the active slots, one position written per sequence),
averaged over the decode calls in the traced window, over the mean
device time of a decode call times the chip's peak bandwidth."""


def read(run):
    p = (run.trace or {}).get("programs", {}).get("decode")
    if not p or not p[1] or not run.trace_host or run.peak is None:
        return None
    lo, hi = run.trace_host
    nbytes = [run.counts.decode(run.dims, ctx)[1]
              for ctx in _contexts(run, lo, hi)]
    nbytes = [b for b in nbytes if b]
    if not nbytes:
        return None
    seconds = p[0] / p[1]
    return sum(nbytes) / len(nbytes) / (seconds
                                        * run.peak["hbm_bytes_per_s"]) * 100


def _contexts(run, lo, hi):
    for t, pos in run.decode_pos:
        if lo <= t < hi:
            yield [int(x) + 1 for x in pos if x < run.max_len]
