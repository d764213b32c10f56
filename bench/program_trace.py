"""Traced runs of a serving cell that also read what the program records
about itself: its named scopes, request stamps and prefill counters (chip
only; the benchmark's own runs do not read them yet):

  python3 bench/program_trace.py --workload sun100m.chat --seeds 1,2 --seconds 51

Per seed, one run of the cell as `bench/run.py --trace 1` makes it, and one
JSON line: the per-layer metrics of bench/metrics/ that read these records
(`METRICS`, those of the cell's mix), the decode-block program's device time
by scope per sub-step, the scopes' sum over that program's device time, the
first-token wait split at the request stamps, the host time of a `step()`
inside the profiler's window and outside it, the programs compiled inside
the measured window, and the run's `correct`.

It drives bench/drivers/serve_engine.py with two hooks, in this process
only: the driver's Tracer also reduces the trace by scope
(bench/scopes.py) before deleting it, and its `drive` hands back the
requests and the engine's `stats` and `trace_count()` at the window's open
and close. A queue wait is counted from the request's scheduled arrival.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
METRICS = ("queue_wait_p90_ms.chat", "prefill_useful.chat",
           "decode_scan_self_ms.chat", "decode_attention_ms.chat",
           "decode_scan_self_ms.batch", "decode_attention_ms.batch")
COUNTERS = ("prefill_tokens", "prefill_slot_tokens")


def queue_waits(reqs, arrivals, t_open, t_end):
    """Seconds from scheduled arrival to admission of each request admitted
    in [t_open, t_end)."""
    return [r.admitted_at - a for r, a in zip(reqs, arrivals)
            if getattr(r, "admitted_at", None) is not None
            and t_open <= r.admitted_at < t_end]


def counts_in_window(at_open, at_close, keys=COUNTERS):
    """{key: growth over the window} of engine counters, or None where the
    engine keeps none of them."""
    if at_open is None or any(k not in at_open for k in keys):
        return None
    return {k: at_close[k] - at_open[k] for k in keys}


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


@contextlib.contextmanager
def hooked(drv, kept: dict):
    """bench/drivers/serve_engine.py with its Tracer and `drive` replaced
    for the duration: `kept` receives the scope reduction (in the trace
    reduction, as `scopes`), the recorded window (`run`), the tracer, and
    the engine's counters and trace count at the window's open and close."""
    from bench import scopes, trace_reduce

    base, orig_drive = drv.Tracer, drv.drive

    class Tracer(base):
        def tick(self, now):
            if "open" not in kept and now >= kept["t_open"]:
                eng = kept["eng"]
                kept["open"] = (dict(eng.stats), eng.trace_count())
            super().tick(now)

        def reduce(self):
            if self.t_off is None:
                return None
            try:
                tr = scopes.load(self.dir)
                red = trace_reduce.reduce(tr)
                return red and dict(red, scopes=scopes.reduce(tr))
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    def drive(eng, work, warm_s, seconds, tr):
        kept.update(eng=eng, t_open=time.perf_counter() + warm_s, tracer=tr)
        run_ = orig_drive(eng, work, warm_s, seconds, tr)
        kept.update(run=run_, close=(dict(eng.stats), eng.trace_count()))
        del kept["eng"]
        return run_

    drv.Tracer, drv.drive = Tracer, drive
    try:
        yield
    finally:
        drv.Tracer, drv.drive = base, orig_drive


def one_run(cell, seed, seconds, peak) -> dict:
    from bench import cells, scopes, trace_reduce
    from bench.readers import DECODE

    drv = cells.driver(cell.traffic["driver"])
    kept = {}
    with hooked(drv, kept):
        out = drv.run(cell, seed, seconds, True, time.perf_counter(), peak)
    run_, tr = kept["run"], kept["tracer"]
    t_open, t_end = run_["t_open"], run_["t_end"]
    reqs, arrivals = run_["reqs"], run_["arrivals"]
    at_open = kept.get("open", (None, -1))
    ctx = dict(out["ctx"],
               queue_waits=queue_waits(reqs, arrivals, t_open, t_end),
               prefill_window=counts_in_window(at_open[0], kept["close"][0]))
    mix = cell.name.rsplit(".", 1)[-1]
    metrics = {m: cells.metric_reader(m)(ctx) for m in METRICS
               if m.endswith("." + mix)}
    red = ctx["trace"] or {}
    hit = trace_reduce.module_time(red, DECODE) if red else None
    scoped = {s: scopes.ms_per_substep(ctx, s)
              for s in scopes.SCOPES + ("other",)}
    traces = (at_open[1], kept["close"][1])
    return {
        "seed": seed, "workload": cell.name, "metrics": metrics,
        "decode_scopes_ms": {k: v for k, v in scoped.items()
                             if v is not None},
        "decode_substep_ms": hit and hit[1] / hit[0] / ctx["decode_block"]
        * 1e3,
        "scopes_over_program": hit and sum(
            sum(v.values()) for k, v in red["scopes"].items()
            if DECODE in k) / hit[1],
        "ttft_split": ttft_split(reqs, arrivals, first_deliveries(
            run_["steps"]), t_open, t_end),
        "step_ms": step_ms(run_["steps"], tr.t_on, tr.t_off),
        "compiles_in_window": traces[1] - traces[0]
        if min(traces) >= 0 else None,
        "idle_gaps": red.get("idle_gaps"),
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
