"""Stage program: on the stage whose calls take longest at the median,
the median over the window's untraced requests of the program's
``stage<s>.wait`` span: the wait for the program's outputs
(``jax.block_until_ready``)."""
from harness import request_spans


def read(run):
    return request_spans.stage_step_ms(run, "wait")
