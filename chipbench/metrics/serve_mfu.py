"""Model FLOPs of the tokens prefilled and decoded in the traced window
(``counts.extend`` and ``counts.decode`` of every call dispatched in it),
over the traced window times the chip's peak bf16 FLOP/s."""


def flops(run):
    """Model FLOPs of the calls dispatched in the traced window."""
    lo, hi = run.trace_host
    c, dims = run.counts, run.dims
    total = sum(c.extend(dims, *note)[0] for name, t, note in run.calls
                if name == "extend" and lo <= t < hi)
    total += sum(c.decode(dims, [int(x) + 1 for x in pos
                                 if x < run.max_len])[0]
                 for t, pos in run.decode_pos if lo <= t < hi)
    return total


def read(run):
    if not run.trace or not run.trace_host or run.peak is None:
        return None
    return flops(run) / (run.trace["window_s"] * run.peak["bf16_flops"]) * 100
