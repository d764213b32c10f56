"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the same
files, driver, generator, reference and check, with the model and engine
shrunk (never used for a reported number). Each configuration's tiny sizes
and widest-gap limit are in bench/tests/tiny_sizes/<config>.json."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax

from bench import cells
from bench.run import peaks_for, run_cell

SIZES = Path(__file__).parent / "tiny_sizes"


def missing_sizes(configs, where=SIZES):
    """The configurations among `configs` that have no tiny-size file in
    `where`."""
    return [c for c in configs if not (where / f"{c}.json").is_file()]


def cell(name: str):
    c = cells.resolve(name)
    tiny = json.loads((SIZES / f"{c.config['name']}.json").read_text())
    c.config.update(tiny["config"])
    c.traffic["engine"].update(slots=4, max_len=128, decode_block=4)
    c.traffic["prompt_len"].update(min=4, max=60, median=20)
    c.traffic["output_len"].update(min=4, max=30, median=12)
    if "rate_per_s" in c.traffic["arrivals"]:
        c.traffic["arrivals"]["rate_per_s"] = 20.0   # dozens finish in 2 s
    c.traffic.update(warm_s=0.5, trace_s=1.0, check={"sample": 8})
    c.limits["widest_gap"]["limit"] = tiny["widest_gap"]
    return c


def run(c, seed=12345678901, seconds=2.0, trace=False, control=False):
    """One run with the chip check skipped (the CPU's devices)."""
    return run_cell(c, seed, seconds, trace, jax.devices(),
                    peaks_for("TPU v5 lite"), time.perf_counter(), control)
