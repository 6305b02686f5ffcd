"""Serving admission: median over the window's untraced requests of the
program's ``admit`` span, from the request's submit to the admission
loop's hand-off to the executor: the batcher's window
(``max_wait_s``) as each request sat in it."""
from harness import request_spans


def read(run):
    return request_spans.admit_ms(run)
