"""Milliseconds per priced chunk decision in the pricing solve (pad, run
and copy back in ``core/estimator_jax.py``): the mean of the program's
``price.solve`` spans inside a ``serve.price`` span in the run's trace."""
import program_spans


def read(run):
    return program_spans.mean_ms(
        program_spans.named("price.solve", parent="serve.price"))
