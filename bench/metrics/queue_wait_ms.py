"""Pipeline executor: median over the window's untraced requests of the
sum of the program's ``queue<s>`` spans, each from the hand-off to a
stage's queue to the start of that stage's call: the wait behind the
items ahead of the request."""
from harness import request_spans


def read(run):
    return request_spans.queue_ms(run)
