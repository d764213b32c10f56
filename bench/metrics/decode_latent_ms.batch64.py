"""Device time per decode sub-step under the `latent` named scope of the
decode-block program: the absorbed latent attention over the paged pool
(on the TPU the paged latent kernel), per call and per sub-step
(bench/scopes.py)."""
from bench.scopes import ms_per_substep


def read(ctx):
    return ms_per_substep(ctx, "latent")
