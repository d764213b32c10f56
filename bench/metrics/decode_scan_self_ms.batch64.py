"""Device time per decode sub-step of the layer scan's own ops in the
Moonlight batch cell: the decode-block program's ops whose innermost named
scope is `layers` (slicing each layer's weights and latent pool out of the
stacks, writing the pool back), per call and per sub-step
(bench/scopes.py)."""
from bench.scopes import ms_per_substep


def read(ctx):
    return ms_per_substep(ctx, "layers")
