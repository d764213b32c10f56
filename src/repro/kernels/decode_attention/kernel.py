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
for any head count or head_dim. `fold_block` folds the slab into the
online-softmax state in chunks of `chunk_tokens(bk)` positions, stopping
at the row's kv_len.

The query heads enter as (group, Hkv, dh): query row j of kv head g sits at
[j, g], so every contraction is batched over the KV heads and each query
row meets its own head's keys only — an elementwise product over (Hkv, dh)
and a sum over dh, in f32 on the vector unit. A one-query decode has one
row per head to contract, which the MXU would spend a whole pass on; for
MHA (group 1) this is one product per key, for GQA `group` of them, for
MQA (Hkv 1) `H`. Online-softmax state (acc, m, l) lives in (group, Hkv, .)
VMEM scratch across cache blocks.

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
FOLD = 16          # positions folded into the softmax state at a time


def chunk_tokens(block: int) -> int:
    """Positions folded at a time within a block of `block` positions."""
    return FOLD if block % FOLD == 0 else block


def group_heads(q, hkv: int):
    """(B, H, dh) -> (B, group, Hkv, dh): query head g * group + j (the
    j-th of kv head g's group) moves to [j, g]."""
    b, h, dh = q.shape
    return q.reshape(b, hkv, h // hkv, dh).transpose(0, 2, 1, 3)


def ungroup_heads(o):
    """Inverse of `group_heads`."""
    b, group, hkv, dh = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, group * hkv, dh)


def scratch_shapes(group: int, hkv: int, dh: int):
    return [pltpu.VMEM((group, hkv, dh), jnp.float32),   # acc
            pltpu.VMEM((group, hkv, 1), jnp.float32),    # running max
            pltpu.VMEM((group, hkv, 1), jnp.float32)]    # running denominator


def init_state(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def fold_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, *, k_start,
               kv_len, sm_scale: float):
    """Fold the block k_ref/v_ref (1, bk, Hkv, dh), holding positions
    k_start.., into the online-softmax state, one chunk at a time and only
    the chunks below kv_len. Shared by the dense and the paged kernel (which
    differ only in how a block reaches VMEM), so both do the same arithmetic
    on the same cache contents. Scores past kv_len go to -1e30 before the
    exp: whatever those positions hold contributes exact zeros."""
    bk = k_ref.shape[1]
    ch = chunk_tokens(bk)
    group = q_ref.shape[1]
    n = jnp.clip(pl.cdiv(kv_len - k_start, ch), 0, bk // ch)

    def chunk(c, carry):
        start = pl.multiple_of(c * ch, ch)
        # loads straight from the block: a sliced view of it (`.at`) is
        # refused where Hkv or dh is not a multiple of the tiling
        k = k_ref[0, pl.ds(start, ch)].astype(jnp.float32)     # (ch,Hkv,dh)
        v = v_ref[0, pl.ds(start, ch)].astype(jnp.float32)
        kpos = k_start + start + jax.lax.broadcasted_iota(
            jnp.int32, (ch, k.shape[1], 1), 0)
        for j in range(group):
            q = q_ref[0, j].astype(jnp.float32) * sm_scale     # (Hkv, dh)
            s = jnp.sum(k * q[None], axis=2, keepdims=True)    # (ch,Hkv,1)
            s = jnp.where(kpos < kv_len, s, NEG_INF)
            m_prev = m_ref[j]                                  # (Hkv, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=0)
            acc_ref[j] = acc_ref[j] * alpha + jnp.sum(p * v, axis=0)
            m_ref[j] = m_new
        return carry

    jax.lax.fori_loop(0, n, chunk, 0)


def finalize(o_ref, acc_ref, l_ref, kv_len):
    # kv_len == 0 rows never folded anything: emit exact zeros, not 0/eps
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = jnp.where(kv_len > 0, out, 0.0).astype(o_ref.dtype)


def decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, sm_scale: float):
    """Fold K/V block `program_id(1)` of batch row `program_id(0)`."""
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        init_state(acc_ref, m_ref, l_ref)

    kv_len = lens_ref[bi]                  # this row's valid cache prefix

    @pl.when(ki * bk < kv_len)             # ragged early-exit per row
    def _compute():
        fold_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref,
                   k_start=ki * bk, kv_len=kv_len, sm_scale=sm_scale)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref, kv_len)


@functools.partial(jax.jit,
                   static_argnames=("block_k", "interpret"))
def decode_attention_fwd(q, k_cache, v_cache, kv_lens, *, block_k: int = 512,
                         interpret: bool = False):
    """q: (B, H, dh); k/v_cache: (B, M, Hkv, dh) (model layout);
    kv_lens: (B,) int32 valid lengths (a scalar broadcasts to all rows)."""
    b, h, dh = q.shape
    m, hkv = k_cache.shape[1], k_cache.shape[2]
    assert h % hkv == 0 and m % block_k == 0
    group = h // hkv
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(decode_kernel, sm_scale=dh ** -0.5)
    kv_spec = pl.BlockSpec((1, block_k, hkv, dh),
                           lambda bi, ki: (bi, ki, 0, 0))
    row_spec = pl.BlockSpec((1, group, hkv, dh), lambda bi, ki: (bi, 0, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(b, m // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            row_spec,
            kv_spec,
            kv_spec,
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, group, hkv, dh), q.dtype),
        scratch_shapes=scratch_shapes(group, hkv, dh),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens, group_heads(q, hkv), k_cache, v_cache)
    return ungroup_heads(out)
