"""Find a serving cell's knee: the highest offered rate that the window
completes as it arrives, with no queue growing. One process, one rate after
another, each a full run of the cell at that rate (chip only):

  python3 bench/sweep.py --workload sun100m.chat --rates 10,20,30 --seconds 20

Prints one JSON line per rate: arrivals and completions in the window, the
queue at its open and close, and the end-to-end tails. The knee is written
into the mix's file by hand, as a number; the benchmark never searches.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    from bench import cells
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    devs = bench_run.find_chips(cell.chips)
    bench_run.enable_cache()
    peak = bench_run.peaks_for(devs[0].device_kind)
    drv = cells.driver(cell.traffic["driver"])
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic["arrivals"]["rate_per_s"] = rate
        out = drv.run(cell, args.seed, args.seconds, False,
                      time.perf_counter(), peak)
        ctx = out["ctx"]
        print(json.dumps({
            "rate_per_s": rate, "arrived": ctx["arrived"],
            "completed": ctx["completed"],
            "queue_open_close": ctx["queue_open_close"],
            "occupancy": ctx["occupancy"], "e2e": out["e2e"],
            "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)          # as bench/run.py's script_paths()
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
