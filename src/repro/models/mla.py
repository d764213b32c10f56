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

Named scopes: `attention`, `latent` (the latent attention call), `mlp`,
`experts` (routing and the held experts' products), and the trunk's
`embed`, `layers`, `head`. Paged decode adds to the state's counters:
`decode_steps` (one a sub-step), `expert_tokens` (assignments to held
experts) and `expert_tokens_peak` (per layer and sub-step, the busiest
held expert's count), summed over layers and sub-steps, and
`latent_positions` (per sub-step, the cached positions that the rows
holding pages attend to, one layer's worth).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from .layers import _qpos, apply_rope, rms_norm, rope_cos_sin, swiglu
from .moe import held_experts_ffn

KV_NORM_EPS = 1e-6      # the latent's RMSNorm keeps its class default
NEG_INF = -1e30
COUNTERS = ("decode_steps", "expert_tokens", "expert_tokens_peak",
            "latent_positions")


def row_width(cfg) -> int:
    """Numbers cached per position and layer: [c_kv | k_pe]."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def stacks(cfg):
    """(params key, dense cache key, layer count, is MoE) of each layer
    stack, in the order the trunk runs them."""
    f = cfg.first_dense_layers
    out = [("dense_layers", "ckv0", f, False)] if f else []
    return out + [("layers", "ckv", cfg.n_layers - f, cfg.moe_d_ff > 0)]


def cache_names(cfg):
    return tuple(c for _, c, _, _ in stacks(cfg))


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


def init_params(key, cfg):
    """normal(0, fan_in^-1/2) projections, ones for norms, zeros for the
    selection bias; embedding normal(0, 1); an untied head."""
    dt = cfg.pdtype
    d, v = cfg.d_model, cfg.vocab_size
    keys = iter(jax.random.split(key, 64))

    def stack(n, moe):
        out = {}
        for name, shape in layer_shapes(cfg, moe).items():
            fan = _fan_in(name, shape)
            full = (n,) + shape
            out[name] = (jnp.ones(full, dt) if fan is None else
                         jnp.zeros(full, dt) if fan == 0 else
                         jax.random.normal(next(keys), full, dt)
                         * fan ** -0.5)
        return out

    params = {"embed": jax.random.normal(next(keys), (v, d), dt),
              "final_norm": jnp.ones((d,), dt),
              "lm_head": jax.random.normal(next(keys), (d, v), dt)
              * d ** -0.5}
    for pname, _, n, moe in stacks(cfg):
        params[pname] = stack(n, moe)
    return params


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
def _mlp(cfg, lp, x, moe):
    """SwiGLU (dense stack) or shared + held routed experts. Returns (out,
    per-held-expert counts or None)."""
    if not moe:
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


def block(cfg, x, lp, cos, sin, moe, *, cache=None, pos=None,
          page_table=None, layer=None):
    """One block. cache None: causal attention over x itself (training
    forward). Else `cache` is this layer's dense rows (B, M, r + dr) or,
    with `page_table`, its stack's pools (L, P+1, ps, r + dr), written and
    read at `layer` in place; the new rows are written at per-row
    positions `pos` (B,) onward, and the queries attend to every cached
    position below their own + 1: naive for a prompt (S > 1), absorbed for
    a decode token. Returns (x, new cache, expert counts)."""
    b, s, _ = x.shape
    lp = jax.tree.map(lambda a: a.astype(cfg.cdtype), lp)
    with jax.named_scope("attention"):
        q_nope, q_pe, row = _project(cfg, lp, x, cos, sin)
        if cache is None:
            qp = jnp.arange(s)
            attn = naive_attention(cfg, lp, q_nope, q_pe, row,
                                   (qp[None, :] <= qp[:, None])[None])
        elif page_table is not None:
            ps = cache.shape[2]
            pids = page_table[jnp.arange(b), pos // ps]
            cache = cache.at[layer, pids, pos % ps].set(
                row[:, 0].astype(cache.dtype))
            q = latent_query(cfg, lp, q_nope, q_pe)[:, 0]
            with jax.named_scope("latent"):
                o = latent_attention(cfg, q, cache, pos + 1, page_table,
                                     layer)
            attn = latent_out(cfg, lp, o[:, None])
        else:
            cols = pos[:, None] + jnp.arange(s)[None]
            cache = cache.at[jnp.arange(b)[:, None], cols].set(
                row.astype(cache.dtype))
            if s > 1:
                kpos = jnp.arange(cache.shape[1])
                attn = naive_attention(cfg, lp, q_nope, q_pe, cache,
                                       kpos[None, None] <= cols[..., None])
            else:
                q = latent_query(cfg, lp, q_nope, q_pe)[:, 0]
                with jax.named_scope("latent"):
                    o = latent_attention(cfg, q, cache, pos + 1)
                attn = latent_out(cfg, lp, o[:, None])
        x = x + (attn @ lp["wo"]).astype(x.dtype)
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps).astype(cfg.cdtype)
        out, counts = _mlp(cfg, lp, h, moe)
        x = x + out.astype(x.dtype)
    return x, cache, counts


# --------------------------------------------------------------------------
# trunk
# --------------------------------------------------------------------------
def _cos_sin(cfg, positions):
    return rope_cos_sin(positions, cfg.qk_rope_head_dim, cfg.rope_base,
                        cfg.cdtype)


def _head(cfg, params, x):
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"].astype(jnp.float32),
                     cfg.rms_norm_eps).astype(cfg.cdtype)
        return x @ params["lm_head"].astype(cfg.cdtype)


def forward(params, tokens, cfg, positions=None):
    """tokens (B, S) -> logits (B, S, V); naive attention, causal."""
    x = params["embed"][tokens].astype(jnp.float32)
    cos, sin = _cos_sin(cfg, jnp.arange(tokens.shape[1])
                        if positions is None else positions)
    for pname, _, _, moe in stacks(cfg):
        def body(x, lp, moe=moe):
            return block(cfg, x, lp, cos, sin, moe)[0], None
        x, _ = jax.lax.scan(body, x, params[pname])
    return _head(cfg, params, x)


def init_cache(cfg, batch: int, max_len: int, dtype=None, pad_to: int = 128):
    """Dense latent rows per layer stack, (n, B, M, r + dr), M padded to
    `pad_to` as the MHA cache is."""
    dtype = dtype or cfg.cdtype
    m = -(-max_len // pad_to) * pad_to
    out = {c: jnp.zeros((n, batch, m, row_width(cfg)), dtype)
           for _, c, n, _ in stacks(cfg)}
    out["pos"] = jnp.zeros((), jnp.int32)
    return out


def init_paged_pools(cfg, pool_pages: int, page_size: int, dtype=None):
    """Latent pages per layer stack, (n, P+1, ps, r + dr); the last page
    is the trash page. Keys are the dense keys + "p"."""
    dtype = dtype or cfg.cdtype
    return {c + "p": jnp.zeros((n, pool_pages + 1, page_size,
                                row_width(cfg)), dtype)
            for _, c, n, _ in stacks(cfg)}


def _run_stacks(cfg, params, x, cache, pos, suffix, page_table=None):
    """Every stack's scan over its weights. Dense rows ride as the scan's
    xs and ys, a layer's rows each; with `page_table` the carry holds the
    stack's pools, written in place at each layer. Returns (x, new caches,
    summed held-expert counts, summed per-layer peaks)."""
    cos, sin = _cos_sin(cfg, _qpos(pos, x.shape[1]))
    new, total, peak = {}, jnp.int32(0), jnp.int32(0)
    with jax.named_scope("layers"):
        for pname, c, n, moe in stacks(cfg):
            if page_table is None:
                def body(x, xs, moe=moe):
                    lp, lc = xs
                    x, lc, counts = block(cfg, x, lp, cos, sin, moe,
                                          cache=lc, pos=pos)
                    return x, (lc, counts if moe
                               else jnp.zeros((1,), jnp.int32))
                x, (new[c + suffix], counts) = jax.lax.scan(
                    body, x, (params[pname], cache[c + suffix]))
            else:
                def body(carry, xs, moe=moe):
                    (x, pool), (lp, layer) = carry, xs
                    x, pool, counts = block(cfg, x, lp, cos, sin, moe,
                                            cache=pool, pos=pos,
                                            page_table=page_table,
                                            layer=layer)
                    return (x, pool), (counts if moe
                                       else jnp.zeros((1,), jnp.int32))
                (x, new[c + suffix]), counts = jax.lax.scan(
                    body, (x, cache[c + suffix]),
                    (params[pname], jnp.arange(n)))
            if moe:
                total += counts.sum(dtype=jnp.int32)
                peak += counts.max(axis=1).sum(dtype=jnp.int32)
    return x, new, total, peak


def _embed(cfg, params, tokens):
    with jax.named_scope("embed"):
        return params["embed"][tokens].astype(jnp.float32)


def decode_step(params, cache, tokens, cfg, positions=None, last_idx=None):
    """Dense-row decode or prefill of tokens (B, S) from per-row (or
    scalar) positions cache["pos"]. Returns (logits (B, V) at each row's
    `last_idx` or last position, new cache)."""
    pos0 = cache["pos"]
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)
    pos = jnp.broadcast_to(pos0, (b,))
    x, new, _, _ = _run_stacks(cfg, params, x, cache, pos, "")
    if last_idx is not None:
        x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
    logits = _head(cfg, params, x[:, -1:])[:, -1]
    return logits, {**cache, **new, "pos": pos0 + s}


def paged_decode_step(params, cache, tokens, cfg):
    """One decode token a row, tokens (B, 1), against the latent pools
    (keys of `init_paged_pools`), the (B, max_pages) table "ptab" and the
    (B,) positions "pos"; the counters, where the state has them, grow by
    this step's (a row whose pages were released attends to none)."""
    pos0 = cache["pos"]
    x = _embed(cfg, params, tokens)
    x, new, total, peak = _run_stacks(cfg, params, x, cache, pos0, "p",
                                      page_table=cache["ptab"])
    logits = _head(cfg, params, x)[:, -1]
    out = {**cache, **new, "pos": pos0 + 1}
    if COUNTERS[0] in cache:
        trash = cache[stacks(cfg)[-1][1] + "p"].shape[1] - 1
        live = jnp.where(cache["ptab"][:, 0] != trash, pos0 + 1, 0)
        for name, grow in zip(COUNTERS, (1, total, peak,
                                         live.sum(dtype=jnp.int32))):
            out[name] = cache[name] + grow
    return logits, out
