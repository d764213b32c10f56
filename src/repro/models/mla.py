"""Multi-head latent attention with a leading dense block and a held share
of sigmoid-routed experts: the DeepSeek-V3 block (arXiv:2412.19437) as
Moonlight-16B-A3B configures it, for a `TransformerConfig` with
`kv_lora_rank > 0`.

Attention (MLA without a query low-rank): q = x W_q gives each head
`qk_nope_head_dim` + `qk_rope_head_dim` columns; kv_a = x W_kv_a gives the
`kv_lora_rank`-wide latent c_kv, which gets its own RMSNorm, and one rope
key k_pe shared by every head; W_kv_b expands c_kv into each head's k_nope
and v. The rope rotates DeepSeek's interleaved pairs (2i, 2i + 1). Scores
are scaled by (nope + rope) ** -0.5.

Two forms of the same arithmetic:
  naive     c_kv expanded into per-head K and V (training forward and
            prefill, where the tokens outnumber the latent's width)
  absorbed  q_nope W_UK moves each query into latent space, the scores and
            the weighted sum run over the cached [c_kv | k_pe] rows
            themselves (K = the whole row, V = its c_kv part), and the
            latent output comes back through W_UV (decode, which then
            reads 576 numbers a cached position instead of 16 x 256)

What a decode step keeps per position and layer is the row [c_kv | k_pe]:
dense rows (L, B, M, r + rope) under "ckv", or pages (L, P+1, ps, r +
rope) under "ckvp" read by `kernels/decode_attention/paged.py`'s latent
kernel on the TPU. The first `first_dense_layers` blocks, whose MLP is a
dense SwiGLU of `d_ff`, have a stack and a cache of their own ("ckv0",
"ckv0p"); the rest route each token to `top_k` of `num_experts` experts
of `moe_d_ff` (models/moe.py `held_experts_ffn`: this chip computes the
`experts_held` experts from `expert_offset` on) beside `n_shared_experts`
shared ones, one SwiGLU of n_shared x moe_d_ff.

Precision: matmul operands are in the compute dtype (bfloat16) with
float32 sums, as elsewhere; the residual stream between and through the
blocks stays float32. Rounded to bfloat16 at each add, the stream drifts
further from float32, the router's top-k flips more often near a tie, and
each flip swaps a whole expert's output (at the reduced size, the widest
served-token gap over 100 requests of one seed fell from 0.49 to 0.07
logits with the float32 stream).

Named scopes: `attention`, `latent` (the latent attention call), `mlp`
and `experts` (routing and the held experts' products). The block returns
each held expert's count of assigned rows, which the trunk
(models/transformer.py) sums into the paged state's counters.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .layers import apply_rope, rms_norm, rope_cos_sin, swiglu
from .moe import held_experts_ffn

KV_NORM_EPS = 1e-6      # the latent's RMSNorm keeps its class default
NEG_INF = -1e30
RESIDUAL = "float32"    # dtype of the residual stream (Precision, above)


def row_width(cfg) -> int:
    """Numbers cached per position and layer: [c_kv | k_pe]."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def rope_angles(cfg, positions):
    """cos, sin of the rope key's angles at positions (S,) or (B, S)."""
    return rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_base,
                        cfg.cdtype)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def layer_shapes(cfg, moe: bool) -> dict:
    """Shapes of one block's weights (without the stacked layer axis)."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {"attn_norm": (d,), "wq": (d, h * (dn + dr)), "wkv_a": (d, r + dr),
           "kv_norm": (r,), "wkv_b": (r, h * (dn + dv)), "wo": (h * dv, d),
           "mlp_norm": (d,)}
    if moe:
        e, f = cfg.num_experts, cfg.moe_d_ff
        held, fs = cfg.n_held, cfg.n_shared_experts * cfg.moe_d_ff
        out.update(router=(d, e), router_bias=(e,),
                   moe_wi_gate=(held, d, f), moe_wi_up=(held, d, f),
                   moe_wo=(held, f, d), shared_wi_gate=(d, fs),
                   shared_wi_up=(d, fs), shared_wo=(fs, d))
    else:
        out.update(wi_gate=(d, cfg.d_ff), wi_up=(d, cfg.d_ff),
                   wo_mlp=(cfg.d_ff, d))
    return out


def _fan_in(name, shape):
    if name.endswith("norm"):
        return None
    if name == "router_bias":
        return 0
    return shape[-2]


def init_stack(keys, cfg, n: int, moe: bool) -> dict:
    """One stack of n blocks, drawing from the key iterator `keys`:
    normal(0, fan_in^-1/2) projections, ones for norms, zeros for the
    selection bias."""
    dt = cfg.pdtype
    out = {}
    for name, shape in layer_shapes(cfg, moe).items():
        fan = _fan_in(name, shape)
        full = (n,) + shape
        out[name] = (jnp.ones(full, dt) if fan is None else
                     jnp.zeros(full, dt) if fan == 0 else
                     jax.random.normal(next(keys), full, dt) * fan ** -0.5)
    return out


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------
def deinterleave(x):
    """DeepSeek's rope pairs (2i, 2i + 1) -> halves (i, i + dr/2), where
    `apply_rope` rotates them. Queries and keys both take it, so their
    products are those of the interleaved rotation."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _project(cfg, lp, x, cos, sin):
    """x (B, S, D) -> q_nope (B, S, H, dn), q_pe (B, S, H, dr) rotated,
    row (B, S, r + dr): the normed latent c_kv and the rotated k_pe."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    hn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps).astype(cfg.cdtype)
    q = (hn @ lp["wq"]).reshape(b, s, h, dn + dr)
    kv_a = hn @ lp["wkv_a"]
    c = rms_norm(kv_a[..., :r], lp["kv_norm"], KV_NORM_EPS)
    q_pe = apply_rope(deinterleave(q[..., dn:]), cos, sin)
    k_pe = apply_rope(deinterleave(kv_a[..., None, r:]), cos, sin)[:, :, 0]
    return q[..., :dn], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _scale(cfg):
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def naive_attention(cfg, lp, q_nope, q_pe, rows, mask):
    """c_kv of `rows` (B, M, r + dr) expanded into per-head K and V; mask
    (B|1, Sq, M). Returns (B, Sq, H * dv)."""
    b, m, _ = rows.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = (rows[..., :r] @ lp["wkv_b"]).reshape(b, m, h, dn + dv)
    k_pe = jnp.broadcast_to(rows[:, :, None, r:], (b, m, h, rows.shape[-1] - r))
    k = jnp.concatenate([kv[..., :dn], k_pe], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * _scale(cfg)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(rows.dtype), kv[..., dn:])
    return o.reshape(b, q.shape[1], h * dv)


def _absorb(cfg, lp):
    r, h = cfg.kv_lora_rank, cfg.n_heads
    dn = cfg.qk_nope_head_dim
    w = lp["wkv_b"].reshape(r, h, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]                   # W_UK, W_UV


def latent_query(cfg, lp, q_nope, q_pe):
    """Queries in latent space: [q_nope W_UK^T | q_pe], (B, S, H, r + dr)."""
    w_uk, _ = _absorb(cfg, lp)
    return jnp.concatenate([jnp.einsum("bshd,rhd->bshr", q_nope, w_uk),
                            q_pe], axis=-1)


def latent_out(cfg, lp, o_lat):
    """Latent attention output (B, S, H, r) back through W_UV, (B, S, H *
    dv)."""
    _, w_uv = _absorb(cfg, lp)
    o = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv)
    return o.reshape(o.shape[0], o.shape[1], -1)


def latent_attention(cfg, q, rows_or_pool, kv_len, page_table=None,
                     layer=None):
    """Absorbed decode attention of q (B, H, r + dr) over dense rows (B, M,
    r + dr) or, with `page_table`, the stacked pools (L, P+1, ps, r + dr)
    read at `layer`. On the TPU a pool goes through the paged latent kernel
    (as paged MHA decode does, chosen by the platform;
    REPRO_DECODE_ATTN=interpret with attn_impl "pallas" runs it in
    interpret mode elsewhere); otherwise the pages are gathered and the
    reference runs. Returns (B, H, r)."""
    from repro.kernels.decode_attention.paged import (
        gather_pages, latent_attention_reference, paged_latent_attention)
    kw = dict(sm_scale=_scale(cfg), v_dim=cfg.kv_lora_rank)
    if page_table is None:
        return latent_attention_reference(q, rows_or_pool, kv_len, **kw)
    interpret = (cfg.attn_impl == "pallas" and os.environ.get(
        "REPRO_DECODE_ATTN") == "interpret")
    if interpret or jax.default_backend() == "tpu":
        return paged_latent_attention(q, rows_or_pool, page_table, kv_len,
                                      layer, interpret=interpret, **kw)
    return latent_attention_reference(
        q, gather_pages(rows_or_pool, page_table, layer), kv_len, **kw)


# --------------------------------------------------------------------------
# the block
# --------------------------------------------------------------------------
def _mlp(cfg, lp, x):
    """SwiGLU (a dense stack's block) or shared + held routed experts (a
    block with a router). Returns (out, per-held-expert counts or None)."""
    if "router" not in lp:
        return swiglu(x, lp["wi_gate"], lp["wi_up"], lp["wo_mlp"]), None
    b, s, d = x.shape
    with jax.named_scope("experts"):
        routed, counts = held_experts_ffn(
            x.reshape(b * s, d),
            {"router": lp["router"], "bias": lp["router_bias"],
             "wi_gate": lp["moe_wi_gate"], "wi_up": lp["moe_wi_up"],
             "wo": lp["moe_wo"]},
            top_k=cfg.top_k, scaling=cfg.routed_scaling,
            offset=cfg.expert_offset)
    shared = swiglu(x, lp["shared_wi_gate"], lp["shared_wi_up"],
                    lp["shared_wo"])
    return routed.reshape(b, s, d) + shared, counts


def block(cfg, x, lp, cos, sin, *, cache=None, pos=None, page_table=None,
          layer=None):
    """One block. cache None: causal attention over x itself (training
    forward). Else `cache` is (rows,): this layer's dense rows (B, M, r +
    dr) or, with `page_table`, its stack's pool (L, P+1, ps, r + dr),
    written and read at `layer` in place; the new rows are written at
    positions `pos` (a scalar, or per row (B,)) onward, and the queries
    attend to every cached position below their own + 1: naive for a
    prompt (S > 1), absorbed for a decode token. Returns (x, new cache,
    per-held-expert counts or None)."""
    b, s, _ = x.shape
    lp = jax.tree.map(lambda a: a.astype(cfg.cdtype), lp)
    with jax.named_scope("attention"):
        q_nope, q_pe, row = _project(cfg, lp, x, cos, sin)
        if cache is None:
            qp = jnp.arange(s)
            attn = naive_attention(cfg, lp, q_nope, q_pe, row,
                                   (qp[None, :] <= qp[:, None])[None])
        elif page_table is not None:
            (pool,) = cache
            ps = pool.shape[2]
            pids = page_table[jnp.arange(b), pos // ps]
            pool = pool.at[layer, pids, pos % ps].set(
                row[:, 0].astype(pool.dtype))
            cache = (pool,)
            q = latent_query(cfg, lp, q_nope, q_pe)[:, 0]
            with jax.named_scope("latent"):
                o = latent_attention(cfg, q, pool, pos + 1, page_table,
                                     layer)
            attn = latent_out(cfg, lp, o[:, None])
        else:
            (rows,) = cache
            pos = jnp.broadcast_to(pos, (b,))
            cols = pos[:, None] + jnp.arange(s)[None]
            rows = rows.at[jnp.arange(b)[:, None], cols].set(
                row.astype(rows.dtype))
            cache = (rows,)
            if s > 1:
                kpos = jnp.arange(rows.shape[1])
                attn = naive_attention(cfg, lp, q_nope, q_pe, rows,
                                       kpos[None, None] <= cols[..., None])
            else:
                q = latent_query(cfg, lp, q_nope, q_pe)[:, 0]
                with jax.named_scope("latent"):
                    o = latent_attention(cfg, q, rows, pos + 1)
                attn = latent_out(cfg, lp, o[:, None])
        x = x + (attn @ lp["wo"]).astype(x.dtype)
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(cfg.cdtype)
        out, counts = _mlp(cfg, lp, h)
        x = x + out.astype(x.dtype)
    return x, cache, counts
