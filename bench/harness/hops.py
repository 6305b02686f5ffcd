"""The program's chip-to-chip hops of each request: its ``stage<s>.hop``
spans for ``s >= 1``, each the host's call that moves stage ``s - 1``'s
boundary onto stage ``s``'s device.  Stage 0's hop, the image from the
host, is not one.  A program without spans gives no hops here."""
from __future__ import annotations

import re
from typing import List, Tuple

from . import request_spans

_HOP = re.compile(r"^stage(\d+)\.hop$")


def chip_hops(run) -> List[List[Tuple[int, float]]]:
    """``(s, seconds)`` of each hop, one list per request of ``run.sent``
    that has spans."""
    out = []
    for r in request_spans.seconds(run):
        hops = []
        for name, secs in r.items():
            m = _HOP.match(name)
            if m and int(m.group(1)) >= 1:
                hops.append((int(m.group(1)), secs))
        out.append(hops)
    return out
