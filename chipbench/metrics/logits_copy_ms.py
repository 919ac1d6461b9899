"""Milliseconds per decode step that the engine spends copying the decode
logits (every slot's row over the vocabulary, float32) to the host, once
the device has them: the mean of the program's ``serve.decode.fetch``
spans in the run's trace."""
import program_spans


def read(run):
    return program_spans.mean_ms(program_spans.named("serve.decode.fetch"))
