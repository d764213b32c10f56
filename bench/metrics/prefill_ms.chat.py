"""Device time of one prefill call (the engine's `_prefill_impl` program,
always at the full (slots, bucket) shape), mean over the traced calls."""
from bench.readers import PREFILL, ms_per_call


def read(ctx):
    return ms_per_call(ctx, PREFILL)
