"""The comparison that decides ``correct``.

Every request answered in the window is compared with the plain reference
(:mod:`harness.reference`, float32 at ``highest`` precision) on the image
it carried.  The number compared, ``logit_err``, is the worst over those
requests of the relative L2 error of the request's logits,
``||served - reference|| / ||reference||``: over a thousand logits it
reads the rounding of the whole answer steadily from image to image,
where the largest single gap swings with the image.
A request never answered, or answered with an error, fails on its own
count, whose limit is 0.  The limits are the configuration's own
(``limits`` in its file); ``PERF.md`` gives the readings each was set from.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

# rows per reference call: bounds the reference's activation memory
REF_BLOCK = 32


def reference_logits(arch: Dict[str, Any], params, pool: np.ndarray,
                     rows: Sequence[int], control: bool = False
                     ) -> Dict[int, np.ndarray]:
    """Reference logits of ``pool[rows]`` on the default device, keyed by
    row: float32 at ``highest`` precision, or with ``control`` bfloat16
    throughout, the precision below the configuration's float32."""
    if not rows:
        return {}
    kw = {"dtype": jnp.bfloat16} if control else {}
    fn = jax.jit(lambda p, x: reference.forward(arch, p, x, **kw))
    out = reference.logits_in_blocks(
        lambda x: fn(params, x), jax.device_put(pool[list(rows)]), REF_BLOCK)
    logits = np.concatenate([np.asarray(o) for o in out])
    return dict(zip(rows, logits))


def row_errors(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """``||got - ref|| / ||ref||`` of each row."""
    got = np.asarray(got, np.float64).reshape(len(got), -1)
    ref = np.asarray(ref, np.float64).reshape(len(ref), -1)
    err = np.linalg.norm(got - ref, axis=1) / np.linalg.norm(ref, axis=1)
    return np.where(np.isfinite(err), err, np.inf)


def check(arch: Dict[str, Any], rows: List[int], got: np.ndarray,
          ref: Dict[int, np.ndarray], attempted: int,
          failed: int) -> Dict[str, Dict[str, float]]:
    """Each compared number with its limit."""
    if rows:
        worst = float(row_errors(got, np.stack([ref[r] for r in rows])).max())
    else:
        worst = float("inf")
    return {"unanswered": {"value": failed, "limit": 0},
            "no_requests": {"value": int(attempted == 0), "limit": 0},
            "logit_err": {"value": worst,
                          "limit": arch["limits"]["logit_err"]}}
