"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up (imports,
devices, weights and images from the seed, plan, stage build, warm-up)
ends where the measured window of ``--seconds`` begins.  Once the window
has closed and every request due in it has been answered, the served
logits are compared with the plain reference, and the last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number with its limit.  The same numbers are
the last lines of standard error.

Exits non-zero, printing no result, unless JAX's first device is a TPU and
there are as many as the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips_or_exit(n: int):
    """The first ``n`` devices, if they are TPUs; otherwise exit."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench/run.py: needs a TPU, but JAX's first device is on "
                 f"platform {devices[0].platform!r} ({devices[0]})")
    if len(devices) < n:
        sys.exit(f"bench/run.py: the cell needs {n} chips, JAX sees "
                 f"{len(devices)}")
    return devices[:n]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> None:
    args = parse(argv)
    from harness.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    chips = chips_or_exit(cell.chips)
    log(f"set-up: imports and devices {time.perf_counter() - T_START:.3f} s")
    from harness.cell import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), chips,
                      T_START, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
