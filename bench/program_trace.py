"""What a traced run of a serving cell records that bench/run.py does not
report (chip only; the benchmark's own runs never run this):

  python3 bench/program_trace.py --workload sun100m.chat --seeds 1,2 --seconds 51

The benchmark reads the program's scopes, counters and stamps itself:
every `bench/run.py --trace 1` run reduces the trace by named scope
(bench/scopes.py) and keeps the engine's counters over the window, and
its per-layer metrics and `breakdown.scopes` come from them. Per seed, one
such run, driven through bench/drivers/serve_engine.py directly, and one
JSON line of what is left: the first-token wait split at the request
stamps, the host time of a `step()` inside the profiler's window and
outside it, the scopes' sum over the decode program's device time, the
programs compiled inside the measured window, and the run's `correct`.
A queue wait is counted from the request's scheduled arrival.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def first_deliveries(steps) -> dict:
    """{request uid: end of the `step()` that first returned a token of it}:
    when the benchmark's host saw its first token."""
    out = {}
    for st in steps:
        for uid, n in st.seen:
            if n and uid not in out:
                out[uid] = st.t1
    return out


def ttft_split(reqs, arrivals, first, t_open, t_end):
    """The first-token wait of the requests due in [t_open, t_end), split at
    the program's stamps: queue (arrival to admission), prefill (admission
    to the first token on the host, `first_token_at`) and hold (to the end
    of the `step()` that admitted it, which also runs a decode block), in
    ms. The p90 of each part, and the means over the requests whose whole
    wait is at or above the p90 of the whole. None without stamps."""
    rows = []
    for uid, (r, a) in enumerate(zip(reqs, arrivals)):
        if not t_open <= a < t_end or uid not in first or \
                getattr(r, "first_token_at", None) is None:
            continue
        rows.append((first[uid] - a, r.admitted_at - a,
                     r.first_token_at - r.admitted_at,
                     first[uid] - r.first_token_at))
    if not rows:
        return None
    a = 1e3 * np.asarray(rows)
    tail = a[a[:, 0] >= np.percentile(a[:, 0], 90)]
    parts = ("ttft", "queue", "prefill", "hold")
    return {"p90": dict(zip(parts, np.percentile(a, 90, axis=0).tolist())),
            "tail_mean": dict(zip(parts, tail.mean(axis=0).tolist()))}


def step_ms(steps, t_on, t_off):
    """Mean host time in ms of a `step()` that decoded, inside the
    profiler's window and outside it: what tracing costs the host."""
    if t_on is None or t_off is None:
        return None
    inside = [s.t1 - s.t0 for s in steps if s.n_active
              and s.t0 >= t_on and s.t1 <= t_off]
    outside = [s.t1 - s.t0 for s in steps if s.n_active
               and (s.t1 < t_on or s.t0 > t_off)]
    if not inside or not outside:
        return None
    return {"in_trace": 1e3 * float(np.mean(inside)),
            "outside": 1e3 * float(np.mean(outside))}


def one_run(cell, seed, seconds, peak) -> dict:
    from bench import cells, trace_reduce
    from bench.readers import DECODE

    out = cells.driver(cell.traffic["driver"]).run(
        cell, seed, seconds, True, time.perf_counter(), peak)
    rec, ctx = out["record"], out["ctx"]
    t_open, t_end = rec["t_open"], rec["t_end"]
    reqs, arrivals = rec["reqs"], rec["arrivals"]
    red = ctx["trace"] or {}
    hit = trace_reduce.module_time(red, DECODE) if red else None
    return {
        "seed": seed, "workload": cell.name,
        "ttft_split": ttft_split(reqs, arrivals, first_deliveries(
            rec["steps"]), t_open, t_end),
        "step_ms": step_ms(rec["steps"], *rec["traced"]),
        "scopes_over_program": hit and sum(
            sum(v.values()) for k, v in red["scopes"].items()
            if DECODE in k) / hit[1],
        "compiles_in_window": ctx["compiles_in_window"],
        "correct": all(c["value"] <= c["limit"]
                       for c in out["checks"].values()),
    }


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
        print(json.dumps(one_run(cell, seed, args.seconds, peak)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)          # as bench/run.py's script_paths()
    sys.path.insert(1, str(ROOT / "src"))
    sys.exit(main())
