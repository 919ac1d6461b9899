"""Milliseconds per decode step that the engine spends sampling on the host
(one argmax over the vocabulary per decoding slot, and the bookkeeping of
finished requests): the mean of the program's ``serve.sample`` spans in
the run's trace."""
import program_spans


def read(run):
    return program_spans.mean_ms(program_spans.named("serve.sample"))
