"""FlashAttention forward kernel for TPU (Pallas, online softmax).

Tiling: grid = (B, H, Sq/bq, Skv/bk) with the KV axis innermost and
"arbitrary" (sequential on core), so the f32 accumulator/max/denominator
scratch persists across KV steps. Block shapes are MXU-aligned
(bq, bk multiples of 128 by default; dh is the lane dimension).

VMEM working set per step: q (bq, dh) + k/v (bk, dh) + scores (bq, bk)
+ acc (bq, dh) in f32 — e.g. bq=bk=256, dh=128: ~0.8 MB, well under the
~16 MB/core VMEM budget, leaving room for double buffering.

GQA is zero-copy: the k/v BlockSpec index_map folds the q-head -> kv-head
mapping (h // group), so kv blocks are fetched once per kv head group.

Causal masking skips fully-masked KV blocks via pl.when (no FLOPs), and
applies the triangle mask only on diagonal blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                      causal: bool, sm_scale: float, block_q: int,
                      block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * sm_scale     # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, dh)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # skip blocks strictly above the diagonal (no query attends there)
        pl.when(k_start <= q_start + block_q - 1)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0, 0, :, :] = (acc_ref[...] /
                             jnp.maximum(l, 1e-30)[:, None]
                             ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        sm_scale: float | None = None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, dh); k, v: (B, Hkv, Skv, dh) with Hkv | H. -> (B,H,Sq,dh).

    Sq must be divisible by block_q and Skv by block_k (ops.py pads).
    """
    b, h, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert h % hkv == 0 and sq % block_q == 0 and skv % block_k == 0
    group = h // hkv
    if sm_scale is None:
        sm_scale = dh ** -0.5

    grid = (b, h, sq // block_q, skv // block_k)
    kernel = functools.partial(_flash_fwd_kernel, causal=causal,
                               sm_scale=sm_scale, block_q=block_q,
                               block_k=block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki: (bi, hi // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
