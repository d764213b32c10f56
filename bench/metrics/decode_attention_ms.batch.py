"""Device time per decode sub-step under the `attention` named scope of
the decode-block program in the batch cell (norm, q/k/v projections, rope,
cache write, page gather and its f32 convert, the attention core, the
output projection), per call and per sub-step (bench/scopes.py)."""
from bench.scopes import ms_per_substep


def read(ctx):
    return ms_per_substep(ctx, "attention")
