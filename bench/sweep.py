"""Find the knee of an open-loop cell: the highest offered rate whose
answers keep pace with its arrivals through the whole window.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> --rates <r> [<r> ...]

One set-up, then one window per rate with the cell's own generator at
that rate, in the order given.  Prints a table row per rate: offered and
answered rates, requests still in flight at the close, latency from the
due time (median, 95th percentile, and the 95th of each half of the
window: a tail that grows from the first half to the second is a growing
backlog) and the generator's lateness.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from run import ROOT, chips_or_exit, log  # noqa: E402


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    from harness.spec import load_cell
    cell = load_cell(ROOT, args.workload)
    chips = chips_or_exit(cell.chips)
    from harness import cell as cm
    from harness.stats import nearest_rank
    cm.use_compile_cache()
    print("| offered /s | answered in window /s | in flight at close | "
          "p50 ms | p95 ms | p95 1st half ms | p95 2nd half ms | "
          "lateness max ms |")
    print("|---|---|---|---|---|---|---|---|")
    with cm.serving(cell, args.seed, chips, False, log) as sv:
        for rate in args.rates:
            mix = dict(cell.traffic, rate_per_s=rate)
            t0 = time.perf_counter() + 0.05
            pauses = cm.CollectorPauses()
            pauses.on = True
            sent = cm.window(sv, cell.generator, mix, args.seed,
                             args.seconds, t0)
            pauses.close()
            log(f"{rate}/s: collector passes inside the window: "
                f"{pauses.describe()}")
            close = t0 + args.seconds
            open_at_close = sum(1 for *_, r in sent
                                if r.t_done is None or r.t_done > close)
            lat = [(r.t_done - due) * 1e3 if r.error is None
                   else float("inf") for _, due, _, r in sent]
            half = len(lat) // 2
            done = sum(1 for *_, r in sent
                       if r.error is None and r.t_done <= close)
            print(f"| {len(sent) / args.seconds:.1f} | "
                  f"{done / args.seconds:.1f} | {open_at_close} | "
                  f"{nearest_rank(lat, 0.5):.3f} | "
                  f"{nearest_rank(lat, 0.95):.3f} | "
                  f"{nearest_rank(lat[:half], 0.95):.3f} | "
                  f"{nearest_rank(lat[half:], 0.95):.3f} | "
                  f"{max(t - d for _, d, t, _ in sent) * 1e3:.3f} |",
                  flush=True)
            time.sleep(0.5)


if __name__ == "__main__":
    main()
