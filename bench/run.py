"""Run one cell of BENCHMARK.json once, on the chip this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration, mix and driver by name (bench/cells.py),
makes the weights and inputs from --seed, warms every program the window
drives (set-up, timed as `setup_s`), measures for --seconds, checks the
served output against the plain reference, and prints one JSON line last
on stdout:

  {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
   "checks"}

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 a profiler trace of a few seconds inside the window gives its
per-layer metrics, and `breakdown` lists the top device operations, the
idle gaps by host span and the decode program's time per sub-step by
named scope (`scopes`). `checks` holds each number compared with its
limit; the same lines close stderr.

Without an accelerator, or with fewer chips than the cell asks for, it
exits nonzero and prints no result. The compile cache is kept in
bench/.jax_cache inside the checkout, so only a checkout's first run of a
cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def script_paths():
    """Run as a script, sys.path[0] is bench/: take the checkout root
    instead, so bench's modules import as a package and shadow nothing of
    the stdlib, and put the program's src/ beside it."""
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))


CACHE_DIR = ROOT / "bench" / ".jax_cache"


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache(path: Path = CACHE_DIR):
    """The program's own `enable_compile_cache()`, handed the benchmark's
    directory through JAX_COMPILATION_CACHE_DIR (a fixed path inside the
    checkout, so two checkouts share nothing), with every program cached
    however fast it compiled, so a second run compiles none."""
    import os

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    jax.config.update("jax_compilation_cache_dir", str(path))
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_chips(chips: int):
    """The accelerator's devices, or NoChip."""
    import jax

    devs = jax.devices()
    if devs[0].platform in ("cpu",):
        raise NoChip(f"no accelerator: JAX found only {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             peak: dict, t_start: float, control: bool = False) -> dict:
    """One run of `cell` on `devs`; returns the result object."""
    from bench import cells, scopes

    drv = cells.driver(cell.traffic["driver"])
    out = drv.run(cell, seed, seconds, trace, t_start, peak, control)
    checks = out["checks"]
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    metrics = {}
    if not trace:
        vals = dict(out["e2e"], setup_s=out["setup_s"])
        for m in cell.end_to_end:
            v = vals.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = cells.metric_reader(m["name"])(out["ctx"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes":
              out["memory_peak_bytes"]}
    res = {"correct": bool(correct), "attempted": int(out["attempted"]),
           "failed": int(out["failed"]), "metrics": metrics,
           "device": device}
    red = out["ctx"].get("trace")
    if trace and red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        res["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
        by_scope = scopes.decode_ms(out["ctx"])
        if by_scope:
            res["breakdown"]["scopes"] = by_scope
    res["check_info"] = out["check_info"]
    res["checks"] = checks
    return res


def main(argv=None) -> int:
    args = parse(argv)
    from bench import cells

    cell = cells.resolve(args.workload)
    try:
        devs = find_chips(cell.chips)
    except NoChip as e:
        print(f"bench/run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    enable_cache()
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                   peaks_for(devs[0].device_kind), T_START)
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    script_paths()
    sys.exit(main())
