"""A cell of BENCHMARK.json cut to a size the CPU runs in seconds: the same
files, driver, generator, reference and check, with the model and engine
shrunk (never used for a reported number)."""
from __future__ import annotations

import time

import jax

from bench import cells
from bench.run import peaks_for, run_cell

TINY_MODEL = dict(d_model=64, n_heads=4, head_dim=16, d_ff=128,
                  vocab_size=512, token_vocab=500)


# widest-gap limits at this size, set from CPU readings of seeds 1-4:
# sun100m program <= 0.045, fp8 control >= 0.31; minicpm2b (its muP scales
# make logits 100x smaller; six layers, since with two the input token
# decides most positions) program <= 2.6e-4, control >= 1.4e-3
LIMITS = {"sun100m": 0.15, "minicpm2b": 6e-4}
LAYERS = {"sun100m": 2, "minicpm2b": 6}


def cell(name: str):
    c = cells.resolve(name)
    c.config.update(TINY_MODEL, n_layers=LAYERS[c.config["name"]],
                    n_kv_heads=2 if c.config["n_kv_heads"] <
                    c.config["n_heads"] else 4)
    c.traffic["engine"].update(slots=4, max_len=128, decode_block=4)
    c.traffic["prompt_len"].update(min=4, max=60, median=20)
    c.traffic["output_len"].update(min=4, max=30, median=12)
    if "rate_per_s" in c.traffic["arrivals"]:
        c.traffic["arrivals"]["rate_per_s"] = 20.0   # dozens finish in 2 s
    c.traffic.update(warm_s=0.5, trace_s=1.0, check={"sample": 8})
    c.limits["widest_gap"]["limit"] = LIMITS[c.config["name"]]
    return c


def run(c, seed=12345678901, seconds=2.0, trace=False, control=False):
    """One run with the chip check skipped (the CPU's devices)."""
    return run_cell(c, seed, seconds, trace, jax.devices(),
                    peaks_for("TPU v5 lite"), time.perf_counter(), control)
