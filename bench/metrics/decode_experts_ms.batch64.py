"""Device time per decode sub-step under the `experts` named scope of the
decode-block program (routing, and the held experts' products and their
combination), per call and per sub-step (bench/scopes.py)."""
from bench.scopes import ms_per_substep


def read(ctx):
    return ms_per_substep(ctx, "experts")
