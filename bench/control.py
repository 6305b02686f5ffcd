"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the program's reading (a run of the cell
at its own load for ``--seconds``, as ``bench/run.py`` makes it) and the
control's (the plain reference computed in bfloat16, the precision below
the configuration's float32, put in the program's place on the same
images).  Each line of output is one seed's readings as JSON.  The
benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from run import ROOT, chips_or_exit, log  # noqa: E402


def control_reading(arch, seed, n_pool, chips):
    """The control's number on the pool of ``seed``: the worst row of the
    bfloat16 reference against the float32 one."""
    import jax
    import numpy as np
    from harness import compare
    from harness.cell import make_inputs
    params, pool = make_inputs(arch, seed, n_pool, chips[0])
    rows = list(range(n_pool))
    with jax.default_device(chips[0]):
        ref = compare.reference_logits(arch, params, pool, rows)
        ctl = compare.reference_logits(arch, params, pool, rows, control=True)
    return float(compare.row_errors(np.stack([ctl[r] for r in rows]),
                                    np.stack([ref[r] for r in rows])).max())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from harness.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    chips = chips_or_exit(cell.chips)
    from harness.cell import run_cell
    for seed in args.seeds:
        t = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, chips, t, log=log)
        ctl = control_reading(cell.config, seed, cell.traffic["pool"], chips)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "program": res["checks"]["logit_err"]["value"],
                          "control": ctl,
                          "limit": res["checks"]["logit_err"]["limit"],
                          "metrics": {k: v["value"] for k, v in
                                      res["metrics"].items()}}), flush=True)


if __name__ == "__main__":
    main()
