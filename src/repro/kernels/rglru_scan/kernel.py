"""RG-LRU linear-recurrence kernel for TPU (Pallas).

The recurrence h_t = a_t * h_{t-1} + x_t is the sequential hot spot of the
recurrentgemma blocks. GPU implementations launch a parallel-scan tree; on
TPU the natural shape is a *channel-parallel sequential walk*: channels are
fully parallel (VPU lanes), so the grid tiles (B, D/bd) in parallel and walks
S sequentially in (bs, bd) VMEM blocks with the carry h in scratch —
one HBM read of a/x and one write of h per element, perfectly streamed.

Grid = (B, D/bd, S/bs), sequence axis innermost/"arbitrary"; carry scratch
(1, bd) f32 persists across sequence blocks. bd=128 matches the lane width;
bs=256 rows per block keeps 3 buffers * bs*bd*4B = 0.4 MB in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16      # rows per load/store: one packed bf16 sublane tile


def _rglru_kernel(a_ref, x_ref, o_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # Mosaic loads and stores whole (rows, bd) tiles at aligned row offsets
    # and lays out 2-D values only: walk the block in CHUNK-row tiles and
    # run the recurrence over the rows of each tile in registers
    def chunk(c, h):
        rows = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
        a = a_ref[0, rows, :].astype(jnp.float32)             # (CHUNK, bd)
        x = x_ref[0, rows, :].astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
        out = jnp.zeros_like(a)
        for r in range(CHUNK):
            h = a[r:r + 1] * h + x[r:r + 1]                    # (1, bd)
            out = jnp.where(row == r, h, out)
        o_ref[0, rows, :] = out.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s // CHUNK, chunk, h_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("block_s", "block_d", "interpret"))
def rglru_scan_fwd(a, x, *, block_s: int = 256, block_d: int = 128,
                   interpret: bool = False):
    """a, x: (B, S, D) -> h: (B, S, D). S % block_s == 0, D % block_d == 0
    (ops.py pads)."""
    b, s, d = x.shape
    assert s % block_s == 0 and d % block_d == 0 and block_s % CHUNK == 0
    grid = (b, d // block_d, s // block_s)
    return pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_d),
                               lambda bi, di, si: (bi, si, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, x)
