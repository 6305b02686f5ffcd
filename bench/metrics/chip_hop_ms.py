"""Stage program: the median over the window's untraced requests of the
sum of the program's ``stage<s>.hop`` spans of stages ``s >= 1``, each
the host's call that moves the boundary from stage ``s - 1``'s chip onto
stage ``s``'s (``jax.device_put``).  The call returns once the copy is
queued, and the copy ends inside the stage's ``.wait``: this is what a
hop costs the host, not the link's time.  Stage 0's hop, the image from
the host, is left out.  Nothing where the program has no such spans, or
one stage."""
from harness import hops, request_spans


def read(run):
    return request_spans.median_ms(
        [sum(secs for _, secs in request) for request in hops.chip_hops(run)
         if request])
