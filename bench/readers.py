"""Arithmetic shared by the per-layer metric readers in bench/metrics/.

Each reader takes the run's context (the trace reduction under "trace",
host counts beside it) and returns a number, or None where it finds
nothing to read; the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from bench import trace_reduce

PREFILL = "_prefill_impl"          # the engine's jitted prefill program
DECODE = "_engine_step_impl"       # its fused decode block


def module(ctx, name):
    red = ctx.get("trace")
    return None if red is None else trace_reduce.module_time(red, name)


def ms_per_call(ctx, name, per=1):
    hit = module(ctx, name)
    if not hit or not hit[0]:
        return None
    calls, secs = hit
    return secs / calls / per * 1e3


def idle_pct(ctx):
    red = ctx.get("trace")
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
