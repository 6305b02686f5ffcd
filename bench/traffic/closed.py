"""Closed loop: a fixed pool of clients, each with one request out.

Each of ``clients`` threads sends a request at the window's start and its
next one the moment the last is answered, until the window closes; none is
sent after it.  Each request number is handed out once: the first
``clients`` one to each client, in an order the seed permutes, and each
later one to the next client whose answer has come.  Request ``k``
carries pool image ``k mod pool`` (the harness's choice), so every seed
sends the same images and differs only in which client starts on which.
A request is due when it is sent.

Mix keys: ``clients``.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Tuple

import numpy as np


def drive(submit: Callable[[int], Any], mix: Dict[str, Any], seed: int,
          seconds: float, t0: float) -> List[Tuple[int, float, float, Any]]:
    """Keep ``mix["clients"]`` requests out from ``t0`` to ``t0 +
    seconds``; returns ``(k, due, sent, request)`` per request, due and
    sent both the time it was sent, in order of ``k``."""
    n = int(mix["clients"])
    t_end = t0 + seconds
    first = np.random.default_rng(seed).permutation(n).tolist()
    lock = threading.Lock()
    following = iter(range(n, 2**62))
    sent: List[Tuple[int, float, float, Any]] = []
    errors: List[BaseException] = []

    def client(k: int) -> None:
        wait = t0 - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        while True:
            t = time.perf_counter()
            if t >= t_end:
                return
            try:
                request = submit(k)
            except BaseException as e:     # re-raised by drive()
                errors.append(e)
                return
            with lock:
                sent.append((k, t, t, request))
            if not request.event.wait(max(0.0, t_end - time.perf_counter())):
                return
            with lock:
                k = next(following)

    threads = [threading.Thread(target=client, args=(k,), daemon=True,
                                name=f"closed-client{c}")
               for c, k in enumerate(first)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if errors:
        raise errors[0]
    return sorted(sent, key=lambda x: x[0])
