"""Readings that set a serving cell's limits (chip only; the benchmark's own
runs never run this). For each seed, in one process: a run of the cell at
its own load and sizes, then the served tokens compared with the reference
twice: as the program served them, and as the control would have chosen
them, the reference computed in float8_e4m3 (the precision below the
configuration's bfloat16):

  python3 bench/control.py --workload sun100m.chat --seeds 1,2,3 --seconds 10

Prints one JSON line per seed: the program's widest gap (its lower reading)
and the control's (its upper reading), with the tokens compared, and the
control's verdict: the run's `check` with the control's tokens in the
program's place (`control_correct`, false where the limit holds it).
bench/tests/test_bench_check.py keeps the same comparison at a size the CPU
holds.
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
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    devs = bench_run.find_chips(cell.chips)
    bench_run.enable_cache()
    peak = bench_run.peaks_for(devs[0].device_kind)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = bench_run.run_cell(cell, seed, args.seconds, False, devs, peak,
                                 t0, control=True)
        info = res["check_info"]
        print(json.dumps({
            "seed": seed,
            "program_widest_gap": info.get("program_widest_gap"),
            "control_widest_gap": info.get("control_widest_gap"),
            "tokens_compared": info.get("tokens_compared"),
            "nonzero_gaps": info.get("nonzero_gaps"),
            "control_correct": res["correct"],
            "checks": res["checks"],
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)          # as bench/run.py's script_paths()
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
