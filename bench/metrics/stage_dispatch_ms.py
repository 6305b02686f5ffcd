"""Stage program: on the stage whose calls take longest at the median,
the median over the window's untraced requests of the program's
``stage<s>.dispatch`` span: the jitted stage program's asynchronous
dispatch."""
from harness import request_spans


def read(run):
    return request_spans.stage_step_ms(run, "dispatch")
