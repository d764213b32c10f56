"""Decode (single-token) attention kernel for TPU — the memory-bound server
hot spot: one query row streams the whole KV cache from HBM exactly once.

The kernel consumes the model's native cache layout (B, M, Hkv, dh), so the
serving path never transposes or re-pads the cache on the hot loop — the
cache is allocated block-aligned once at `init_cache` and handed straight to
`pallas_call`. kv_lens is a per-row (B,) SMEM vector: each batch row masks
only its own valid prefix, and `pl.when` skips whole cache blocks past a
row's length — a slot that just prefilled 40 tokens does not stream the
other rows' worst-case tail.

Grid = (B, M/bk) with the cache axis innermost/sequential. Each step takes
a (bk, Hkv, dh) slab of K and V — every KV head at once, so the block's two
minor dims are the array's own (Hkv, dh), which the TPU tiling rules accept
for any head count or head_dim — and all H query rows of the batch row. The
q-head -> kv-head GQA fold happens in the kernel: the scores of kv head g
are computed for every query row and kept for the rows of group g
(`_group_select`), so no operand is ever sliced at an unaligned sublane
offset. Online-softmax state (acc, m, l) lives in (H, .) VMEM scratch
across cache blocks.

Arithmetic intensity is O(1) FLOP/byte, so the roofline bound is
HBM bandwidth: bytes ~ 2 * kv_len * Hkv * dh * itemsize per batch row —
with ragged lengths the expected bytes follow the *mean* kv_len across
slots, not the max.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _group_select(hkv: int, group: int, per_head):
    """Combine per-kv-head results into one (H, n) array: row r takes
    `per_head(r // group)`. Every candidate is computed for all H rows and
    selected (not summed), so each row holds exactly its own group's
    value."""
    out = per_head(0)
    if hkv == 1:
        return out
    row_group = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0) // group
    for g in range(1, hkv):
        out = jnp.where(row_group == g, per_head(g), out)
    return out


def scratch_shapes(h: int, dh: int):
    return [pltpu.VMEM((h, dh), jnp.float32),     # acc
            pltpu.VMEM((h, 1), jnp.float32),      # running max
            pltpu.VMEM((h, 1), jnp.float32)]      # running denominator


def decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, block_k: int, hkv: int, group: int,
                  sm_scale: float):
    """Fold K/V block `program_id(1)` of batch row `program_id(0)` into
    the online-softmax state. Shared by the dense and the paged kernel
    (whose page table only steers the k/v DMA), so both do the same
    arithmetic on the same cache contents."""
    bi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = lens_ref[bi]                  # this row's valid cache prefix
    k_start = ki * block_k

    @pl.when(k_start < kv_len)             # ragged early-exit per row
    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale             # (H, dh)

        def scores(g):
            k = k_ref[0, :, g, :].astype(jnp.float32)           # (bk, dh)
            return jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        s = _group_select(hkv, group, scores)                   # (H, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]                                     # (H, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)

        def weighted(g):
            v = v_ref[0, :, g, :].astype(jnp.float32)           # (bk, dh)
            return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

        acc_ref[...] = (acc_ref[...] * alpha
                        + _group_select(hkv, group, weighted))
        m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        # kv_len == 0 rows never ran _compute: emit exact zeros, not 0/eps
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = jnp.where(kv_len > 0, out, 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def decode_attention_fwd(q, k_cache, v_cache, kv_lens, *, block_k: int = 512,
                         interpret: bool = False):
    """q: (B, H, dh); k/v_cache: (B, M, Hkv, dh) (model layout);
    kv_lens: (B,) int32 valid lengths (a scalar broadcasts to all rows)."""
    b, h, dh = q.shape
    m, hkv = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0 and m % block_k == 0
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(decode_kernel, block_k=block_k, hkv=hkv,
                               group=h // hkv, sm_scale=dh ** -0.5)
    kv_spec = pl.BlockSpec((1, block_k, hkv, dh),
                           lambda bi, ki: (bi, ki, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, m // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, h, dh), lambda bi, ki: (bi, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, h, dh), lambda bi, ki: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        scratch_shapes=scratch_shapes(h, dh),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens, q, k_cache, v_cache)
