"""Shared neural-net layers: norms, RoPE/M-RoPE, attention variants, MLPs.

Pure-JAX, pytree-parameter style. Attention has three interchangeable
implementations selected by config:
  - "ref":     plain softmax(QK^T)V — materializes (S, S) scores
  - "chunked": online-softmax over KV blocks (FlashAttention recurrence in
               XLA; no S^2 materialization — the memory-roofline choice)
  - "pallas":  the Pallas TPU kernel from repro.kernels (training shapes)
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.distributed.hints import shard_hint


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def rms_norm(x, weight, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight).astype(dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * weight + bias
    return out.astype(dtype)


# --------------------------------------------------------------------------
# Rotary position embeddings (+ multimodal M-RoPE for Qwen2-VL)
# --------------------------------------------------------------------------
def rope_cos_sin(positions, head_dim: int, base: float = 10000.0,
                 dtype=jnp.float32):
    """positions: (..., S) -> cos/sin (..., S, head_dim/2)."""
    inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                     dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x: (B, S, H, Dh); cos/sin: (B, S, Dh/2) or (S, Dh/2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def mrope_cos_sin(positions_thw, head_dim: int, sections=(16, 24, 24),
                  base: float = 10000.0, dtype=jnp.float32):
    """Qwen2-VL multimodal RoPE: positions_thw (3, B, S) for (t, h, w);
    frequency slots split into `sections` (t/h/w) summing to head_dim/2."""
    assert sum(sections) == head_dim // 2
    cos_all, sin_all = [], []
    for i, sec in enumerate(sections):
        lo = sum(sections[:i])
        inv = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                         dtype=jnp.float32) / head_dim))
        ang = positions_thw[i][..., None].astype(jnp.float32) * inv[lo:lo + sec]
        cos_all.append(jnp.cos(ang))
        sin_all.append(jnp.sin(ang))
    return (jnp.concatenate(cos_all, -1).astype(dtype),
            jnp.concatenate(sin_all, -1).astype(dtype))


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def repeat_kv(k, n_rep: int):
    """(B, S, Hkv, Dh) -> (B, S, Hkv*n_rep, Dh)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :],
                            (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def _qpos(q_offset, sq):
    """Absolute query positions: (B, Sq) for a (B,) per-row offset vector,
    (Sq,) for a scalar offset."""
    if jnp.ndim(q_offset) == 1:
        return q_offset[:, None] + jnp.arange(sq)[None]
    return jnp.arange(sq) + q_offset


def _qk_mask(qpos, kpos, causal, window):
    """Causal + local-window visibility mask of shape qpos.shape + kpos.shape
    (shared by the ref and chunked attention paths)."""
    mask = jnp.ones(qpos.shape + kpos.shape, bool)
    if causal:
        mask &= kpos <= qpos[..., None]
    if window is not None:
        mask &= kpos > qpos[..., None] - window
    return mask


def attention_ref(q, k, v, *, causal: bool = True, window: int | None = None,
                  q_offset=0, kv_len: jnp.ndarray | None = None):
    """Reference attention. q: (B, Sq, H, Dh), k/v: (B, Skv, Hkv, Dh).

    `q_offset`: absolute position of q[0] — a scalar (decode/chunked
    prefill) or a (B,) vector of per-row offsets (ragged bucketed prefill).
    `window`: local attention span (attend to keys within `window`
    positions). `kv_len`: valid KV length for decode-time masking, scalar
    or (B,).
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    # GQA via head grouping in the einsum — never materialize repeated K/V.
    # (Materializing repeat_kv makes the SPMD partitioner reshard M-sharded
    # decode caches to head sharding every step; see EXPERIMENTS.md §Perf.)
    qg = (q * dh ** -0.5).reshape(b, sq, hkv, g, dh)
    # f32 ACCUMULATION without materializing an f32 copy of K (the MXU-
    # native mixed-precision contract; also stops XLA hoisting a full-cache
    # f32 convert out of the decode layer loop)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    kpos = jnp.arange(skv)
    mask = _qk_mask(_qpos(q_offset, sq), kpos, causal, window)
    # lift to (B|1, 1, 1, sq, skv) for the (b, hkv, g, sq, skv) scores
    mask = mask[:, None, None] if mask.ndim == 3 else mask[None, None, None]
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len)
        if kv_len.ndim == 1:                      # per-batch valid length
            mask = mask & (kpos[None, None, None, None, :]
                           < kv_len[:, None, None, None, None])
        else:
            mask = mask & (kpos[None, None, None, None, :] < kv_len)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, dh)


def attention_chunked(q, k, v, *, causal: bool = True,
                      window: int | None = None, q_offset=0,
                      kv_len: jnp.ndarray | None = None,
                      kv_block: int = 512):
    """Online-softmax attention: lax.scan over KV blocks (flash recurrence).

    Peak memory per block is (B, H, Sq, kv_block) instead of (B, H, Sq, Skv).
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    k, v = repeat_kv(k, h // hkv), repeat_kv(v, h // hkv)
    if skv % kv_block:
        pad = kv_block - skv % kv_block
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nblk = k.shape[1] // kv_block
    kb = k.reshape(b, nblk, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
    scale = dh ** -0.5
    qf = q.astype(jnp.float32) * scale
    qpos = _qpos(q_offset, sq)
    ragged = kv_len is not None and jnp.ndim(kv_len) == 1
    if ragged and qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos, (b, sq))  # per-row mask for (B,) kv_len

    @partial(jax.checkpoint,
             policy=jax.checkpoint_policies.nothing_saveable)
    def step(carry, blk):
        acc, m, l, i = carry
        kc, vc = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32))
        kpos = i * kv_block + jnp.arange(kv_block)
        mask = _qk_mask(qpos, kpos, causal, window)
        if kv_len is not None:
            kvl = jnp.asarray(kv_len)
            mask &= kpos < (kvl[:, None, None] if kvl.ndim == 1 else kvl)
        mask &= kpos < skv
        # lift (B|·, sq, bk) to broadcast over the (b, h, sq, bk) scores
        mask_b = mask[:, None] if mask.ndim == 3 else mask[None, None]
        s = jnp.where(mask_b, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32))
        acc_new = shard_hint(acc_new, ("batch", "model", None, None))
        return (acc_new, m_new, l_new, i + 1), None

    acc0 = jnp.zeros((b, h, sq, dh), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(step, (acc0, m0, l0, 0), (kb, vb))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(v.dtype)


def attention(q, k, v, *, impl: str = "ref", page_table=None, layer=None,
              **kw):
    if page_table is not None:
        # paged decode: k/v are the stacked (L, P+1, page_size, Hkv, dh)
        # pools, read at `layer`, and page_table is the (B, max_pages)
        # per-row physical map. On the TPU the kernel walks the table and
        # fetches only each row's live pages; elsewhere the reference
        # gathers the logical dense layout (and is the kernel's oracle).
        # Positions >= kv_len mask to exact-zero probability either way, so
        # paged == dense bitwise for identical cache contents on the same
        # path. REPRO_DECODE_ATTN=interpret runs the kernel in interpret
        # mode for a config with attn_impl="pallas", so CPU tests can reach
        # it.
        assert q.shape[1] == 1 and kw.get("window") is None \
            and kw.get("kv_len") is not None
        interpret = (impl == "pallas" and os.environ.get(
            "REPRO_DECODE_ATTN") == "interpret")
        if interpret or jax.default_backend() == "tpu":
            from repro.kernels.decode_attention.paged import \
                paged_decode_attention
            return paged_decode_attention(q, k, v, page_table, kw["kv_len"],
                                          layer, interpret=interpret)
        from repro.kernels.decode_attention.paged import gather_pages
        kw.pop("kv_block", None)
        return attention_ref(q, gather_pages(k, page_table, layer),
                             gather_pages(v, page_table, layer), **kw)
    if q.shape[1] == 1:
        # decode: one query row. impl == "pallas" on TPU streams the cache
        # through the ragged decode kernel (per-row kv_len, model layout —
        # no transpose/pad on the hot path). Otherwise the grouped-GQA ref
        # path (scores are (B,Hkv,G,1,M), tiny) and, crucially, no repeat_kv
        # materialization that would reshard an M-sharded cache to head
        # sharding per step.
        kw.pop("kv_block", None)
        if impl == "pallas" and kw.get("window") is None \
                and kw.get("kv_len") is not None:
            # REPRO_DECODE_ATTN=interpret forces the kernel path (interpret
            # mode) so CPU tests can cover the serving->kernel dispatch
            mode = os.environ.get("REPRO_DECODE_ATTN", "auto")
            if mode == "interpret" or (mode == "auto"
                                       and jax.default_backend() == "tpu"):
                from repro.kernels.decode_attention.ops import \
                    decode_attention
                return decode_attention(q, k, v, kw["kv_len"],
                                        interpret=mode == "interpret")
        return attention_ref(q, k, v, **kw)
    if impl == "chunked":
        return attention_chunked(q, k, v, **kw)
    if impl == "pallas":
        from repro.kernels.flash_attention.ops import flash_attention
        qo = kw.get("q_offset", 0)
        if kw.get("window") is None and kw.get("kv_len") is None \
                and jnp.ndim(qo) == 0 and not isinstance(qo, jax.Array) \
                and qo == 0 and q.shape[1] == k.shape[1]:
            return flash_attention(q, k, v, causal=kw.get("causal", True))
        kw.pop("impl", None)
        return attention_ref(q, k, v, **kw)  # fallback outside kernel domain
    return attention_ref(q, k, v, **kw)


# --------------------------------------------------------------------------
# MLPs
# --------------------------------------------------------------------------
def swiglu(x, wi_gate, wi_up, wo):
    """LLaMA-style gated MLP: (B,S,D) x (D,F)x2 x (F,D)."""
    g = jax.nn.silu(x @ wi_gate)
    return (g * (x @ wi_up)) @ wo


def geglu(x, wi_gate, wi_up, wo):
    g = jax.nn.gelu(x @ wi_gate, approximate=True)
    return (g * (x @ wi_up)) @ wo


def gelu_mlp(x, wi, bi, wo, bo):
    return jax.nn.gelu(x @ wi + bi, approximate=True) @ wo + bo
