"""Device time of one decode sub-step: the fused decode block's program
(`_engine_step_impl`) per call, divided by the block's sub-steps."""
from bench.readers import DECODE, ms_per_call


def read(ctx):
    return ms_per_call(ctx, DECODE, ctx.get("decode_block"))
