"""Stage program: on the stage whose calls take longest at the median,
the median over the window's untraced requests of the program's
``stage<s>.hop`` span: the move of the call's input onto the stage's
chip (``jax.device_put``; for stage 0 the image's copy from the host)."""
from harness import request_spans


def read(run):
    return request_spans.stage_step_ms(run, "hop")
