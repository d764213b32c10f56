"""Decoder-only transformer LM covering the dense, MoE, VLM and audio
architecture families via configuration.

Parameters are plain pytrees with per-layer weights STACKED on a leading L
axis and the forward pass runs `lax.scan` over layers — essential to keep
the HLO (and 512-device SPMD compile time) small for the 40-64 layer archs.

Supports:
  - GQA/MQA/MHA (+ optional QKV bias), RoPE / M-RoPE / sinusoidal positions
  - SwiGLU / GeGLU / GELU MLPs; parallel attention+FFN blocks (Command-R)
  - capacity-based top-k MoE FFN (granite / qwen3-moe)
  - multi-head latent attention, a leading dense block and a held share of
    sigmoid-routed dropless experts (DeepSeek-V3 / Moonlight; models/mla.py)
  - multi-codebook token streams (MusicGen EnCodec frontend stub)
  - local (windowed) attention
  - KV-cache prefill/decode for serving
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.hints import mesh_axis_size, shard_hint

from .layers import (_qpos, apply_rope, attention, gelu_mlp, geglu,
                     layer_norm, mrope_cos_sin, rms_norm, rope_cos_sin,
                     swiglu)
from . import mla
from .losses import chunked_lm_loss, softmax_xent
from .moe import init_moe_params, moe_ffn


@dataclass(frozen=True)
class TransformerConfig:
    name: str = "transformer"
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None
    rope_base: float = 10000.0
    qkv_bias: bool = False
    parallel_block: bool = False          # Command-R style
    norm: str = "rmsnorm"                 # or "layernorm"
    mlp_act: str = "swiglu"               # "geglu" | "gelu"
    # MoE
    num_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # latent attention (models/mla.py; kv_lora_rank 0 = none)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_dense_layers: int = 0              # leading blocks with a d_ff MLP
    # held-share dropless experts, routed by sigmoid scores (moe_d_ff 0 =
    # the capacity `moe_ffn`, routed by softmax)
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    experts_held: int = 0                    # 0 = all num_experts
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    # modality / position
    mrope_sections: Optional[tuple] = None   # qwen2-vl
    n_codebooks: int = 1                     # musicgen
    pos_embed: str = "rope"                  # "sinusoidal" for musicgen
    window: Optional[int] = None             # local attention
    # scaling / tying
    tie_embeddings: bool = True
    embed_scale: float = 1.0                 # minicpm: 12.0
    residual_scale: float = 1.0              # minicpm: 1.4/sqrt(L)
    logit_scale: float = 1.0                 # command-r: 0.0625
    # implementation
    attn_impl: str = "ref"                   # "chunked" | "pallas"
    loss_chunk: int = 0                      # seq-chunked xent (0 = off)
    fsdp_hints: bool = False                 # keep param slices sharded in-loop
    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    max_decode_len: int = 0                  # serving cache length

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_held(self) -> int:
        """Experts computed here (a share of num_experts under expert
        parallelism)."""
        return self.experts_held or self.num_experts

    def __post_init__(self):
        if self.moe_d_ff and not self.is_mla:
            raise ValueError("held-share experts (moe_d_ff) come with the "
                             "latent-attention block (kv_lora_rank > 0)")
        if self.expert_offset + self.n_held > self.num_experts:
            raise ValueError(f"experts {self.expert_offset}.."
                             f"{self.expert_offset + self.n_held - 1} are "
                             f"not among {self.num_experts}")

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def param_count(self) -> int:
        return sum(int(x.size) for x in jax.tree.leaves(
            jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), self))))

    def active_param_count(self) -> int:
        """Per-token active params (= total for dense; k/E of experts for MoE)."""
        total = self.param_count()
        if not self.is_moe:
            return total
        if self.moe_d_ff:
            expert = 3 * self.d_model * self.moe_d_ff * self.n_held * \
                (self.n_layers - self.first_dense_layers)
            return total - expert + expert * self.top_k // self.num_experts
        expert = 3 * self.d_model * self.d_ff * self.num_experts * \
            self.n_layers
        return total - expert + expert * self.top_k // self.num_experts


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def init_params(key, cfg: TransformerConfig):
    if cfg.is_mla:
        return mla.init_params(key, cfg)
    dt = cfg.pdtype
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    d, L = cfg.d_model, cfg.n_layers
    keys = jax.random.split(key, 16)
    s = d ** -0.5

    def nrm(k, shape, scale):
        return jax.random.normal(k, shape, dt) * scale

    layers = {
        "attn_norm": jnp.ones((L, d), dt),
        "wq": nrm(keys[0], (L, d, h * hd), s),
        "wk": nrm(keys[1], (L, d, hkv * hd), s),
        "wv": nrm(keys[2], (L, d, hkv * hd), s),
        "wo": nrm(keys[3], (L, h * hd, d), (h * hd) ** -0.5),
    }
    if cfg.norm == "layernorm":
        layers["attn_norm_bias"] = jnp.zeros((L, d), dt)
    if cfg.qkv_bias:
        layers["bq"] = jnp.zeros((L, h * hd), dt)
        layers["bk"] = jnp.zeros((L, hkv * hd), dt)
        layers["bv"] = jnp.zeros((L, hkv * hd), dt)
    if not cfg.parallel_block:
        layers["mlp_norm"] = jnp.ones((L, d), dt)
        if cfg.norm == "layernorm":
            layers["mlp_norm_bias"] = jnp.zeros((L, d), dt)
    if cfg.is_moe:
        moe = init_moe_params(keys[4], d, cfg.d_ff, cfg.num_experts, dt)
        layers["router"] = jnp.broadcast_to(moe["router"],
                                            (L, d, cfg.num_experts)).copy()
        for nm in ("wi_gate", "wi_up", "wo"):
            arr = moe[nm]
            layers["moe_" + nm] = jnp.broadcast_to(
                arr, (L,) + arr.shape).copy()
    else:
        f = cfg.d_ff
        if cfg.mlp_act == "gelu":
            layers["wi"] = nrm(keys[5], (L, d, f), s)
            layers["bi"] = jnp.zeros((L, f), dt)
            layers["wo_mlp"] = nrm(keys[6], (L, f, d), f ** -0.5)
            layers["bo"] = jnp.zeros((L, d), dt)
        else:
            layers["wi_gate"] = nrm(keys[5], (L, d, f), s)
            layers["wi_up"] = nrm(keys[7], (L, d, f), s)
            layers["wo_mlp"] = nrm(keys[6], (L, f, d), f ** -0.5)

    params = {
        "embed": nrm(keys[8], (cfg.n_codebooks, cfg.vocab_size, d), 1.0)
        if cfg.n_codebooks > 1 else nrm(keys[8], (cfg.vocab_size, d), 1.0),
        "layers": layers,
        "final_norm": jnp.ones((d,), dt),
    }
    if cfg.norm == "layernorm":
        params["final_norm_bias"] = jnp.zeros((d,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(keys[9],
                                (cfg.n_codebooks, d, cfg.vocab_size)
                                if cfg.n_codebooks > 1
                                else (d, cfg.vocab_size), s)
    return params


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _norm(cfg, x, w, b=None):
    if cfg.norm == "layernorm":
        return layer_norm(x, w, b)
    return rms_norm(x, w, cfg.rms_norm_eps)


def _mlp(cfg, lp, h):
    if cfg.is_moe:
        b, s, d = h.shape
        moe_params = {"router": lp["router"], "wi_gate": lp["moe_wi_gate"],
                      "wi_up": lp["moe_wi_up"], "wo": lp["moe_wo"]}
        out = moe_ffn(h.reshape(b * s, d), moe_params,
                      num_experts=cfg.num_experts, top_k=cfg.top_k,
                      capacity_factor=cfg.capacity_factor)
        return out.reshape(b, s, d)
    if cfg.mlp_act == "gelu":
        return gelu_mlp(h, lp["wi"], lp["bi"], lp["wo_mlp"], lp["bo"])
    fn = geglu if cfg.mlp_act == "geglu" else swiglu
    return fn(h, lp["wi_gate"], lp["wi_up"], lp["wo_mlp"])


# storage layout of each block weight (see distributed/sharding.py); used
# to pin the per-layer slices to their sharded layout INSIDE the layer loop,
# so the FSDP all-gather happens one layer at a time (in bf16) instead of
# being hoisted out of the scan as a full-model fp32 all-gather.
_BLOCK_WSPECS = {
    "wq": ("fsdp", "model"), "wk": ("fsdp", "model"), "wv": ("fsdp", "model"),
    "wo": ("model", "fsdp"), "wi_gate": ("fsdp", "model"),
    "wi_up": ("fsdp", "model"), "wo_mlp": ("model", "fsdp"),
    "wi": ("fsdp", "model"), "router": ("fsdp", None),
    "moe_wi_gate": ("model", "fsdp", None),
    "moe_wi_up": ("model", "fsdp", None), "moe_wo": ("model", None, "fsdp"),
}


def _block(cfg: TransformerConfig, x, lp, cos, sin, *, q_offset=0,
           cache=None, kv_len=None):
    """One transformer block. cache: (k, v) of (B, M, Hkv, hd) to update,
    or for paged decode (kp, vp, page_table, layer): the stacked pools
    (L, P+1, ps, Hkv, hd), updated at `layer` in place."""
    b, s, d = x.shape
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    if cfg.fsdp_hints:
        lp = {k: (shard_hint(v, _BLOCK_WSPECS[k]) if k in _BLOCK_WSPECS
                  else v) for k, v in lp.items()}
    # mixed precision: weights are stored in param_dtype, computed in cdtype
    lp = jax.tree.map(lambda a: a.astype(cfg.cdtype), lp)
    # Megatron-SP: the residual stream is sequence-sharded over "model";
    # gather S at block entry (all-gather fwd / reduce-scatter bwd), run the
    # projections tensor-parallel, reduce-scatter back at block exit.
    # (Gather placed after the norm: the XLA CPU partitioner then gathers the
    # norm's f32 internals — 2x wire bytes vs bf16 — but keeps the saved
    # checkpoints sequence-sharded. See EXPERIMENTS.md §Perf iteration 3.)
    with jax.named_scope("attention"):
        hnb = _norm(cfg, x, lp["attn_norm"], lp.get("attn_norm_bias"))
        hnb = shard_hint(hnb, ("batch", None, None))
        q = hnb @ lp["wq"]
        k = hnb @ lp["wk"]
        v = hnb @ lp["wv"]
        if cfg.qkv_bias:
            q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
        # attention zone: shard heads over "model" when they divide, else
        # fall back to sequence sharding of q (chunked attention handles
        # both)
        ms = mesh_axis_size("model")
        head_par = ms is not None and h % ms == 0 and cache is None
        seq_ax = None if (head_par or cache is not None) else "model"
        q = shard_hint(q.reshape(b, s, h, hd),
                       ("batch", seq_ax, "model" if head_par else None, None))
        kv_head_ax = "model" if (ms and hkv % ms == 0 and head_par) else None
        k = shard_hint(k.reshape(b, s, hkv, hd),
                       ("batch", None, kv_head_ax, None))
        v = shard_hint(v.reshape(b, s, hkv, hd),
                       ("batch", None, kv_head_ax, None))
        if cfg.pos_embed == "rope":
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)

        new_cache = None
        page_table = layer = None
        if cache is not None and len(cache) == 4:
            # paged decode (s == 1): the stacked k/v pools (L, P+1, ps, Hkv,
            # dh), the per-row page table and this block's layer. Each row
            # writes its token at (layer, table[pos // ps], pos % ps), in
            # place; rows with no mapped page there (inactive slots) land
            # on the trash page. Active rows always write distinct pages —
            # prefix-shared pages only cover positions < prompt_len, below
            # any decode write.
            kp, vp, page_table, layer = cache
            ps = kp.shape[2]
            pids = page_table[jnp.arange(b), q_offset // ps]
            at = (layer, pids, q_offset % ps)
            kp = kp.at[at].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[at].set(v[:, 0].astype(vp.dtype))
            k, v, new_cache = kp, vp, (kp, vp)
        elif cache is not None:
            ck, cv = cache
            if jnp.ndim(q_offset) == 1:   # per-slot positions
                rows = jnp.arange(b)[:, None]
                cols = q_offset[:, None] + jnp.arange(s)[None]
                ck = ck.at[rows, cols].set(k.astype(ck.dtype))
                cv = cv.at[rows, cols].set(v.astype(cv.dtype))
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    ck, k.astype(ck.dtype), q_offset, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cv, v.astype(cv.dtype), q_offset, axis=1)
            k, v, new_cache = ck, cv, (ck, cv)

        if jnp.ndim(q_offset) == 1:
            # ragged per-slot positions (continuous batching). s == 1
            # decode: kv_len mask IS the causal constraint, so drop the
            # triangle (and let impl="pallas" stream the cache through the
            # ragged decode kernel). s > 1 bucketed prefill: causal with
            # per-row offsets — pad queries past a row's prompt attend only
            # valid keys and their outputs/cache tail are masked downstream
            # by kv_len. q_offset stays the per-row position vector even at
            # s == 1: the causal triangle is vacuous there but the
            # local-attention window mask still needs each query's absolute
            # position
            attn = attention(q, k, v, impl=cfg.attn_impl, causal=s > 1,
                             window=cfg.window, kv_len=kv_len,
                             q_offset=q_offset, page_table=page_table,
                             layer=layer)
        else:
            attn = attention(q, k, v, impl=cfg.attn_impl, causal=True,
                             window=cfg.window, q_offset=q_offset,
                             kv_len=kv_len)
        attn_out = shard_hint(attn.reshape(b, s, h * hd) @ lp["wo"],
                              ("batch", "model" if cache is None else None,
                               None))   # reduce-scatter back to seq-sharded
        if not cfg.parallel_block:
            x = x + cfg.residual_scale * attn_out

    with jax.named_scope("mlp"):
        if cfg.parallel_block:
            x = x + cfg.residual_scale * (attn_out + _mlp(cfg, lp, hnb))
        else:
            h2 = _norm(cfg, x, lp["mlp_norm"], lp.get("mlp_norm_bias"))
            h2 = shard_hint(h2, ("batch", None, None))
            mlp_out = shard_hint(_mlp(cfg, lp, h2),
                                 ("batch", "model" if cache is None
                                  else None, None))
            x = x + cfg.residual_scale * mlp_out
    return x, new_cache


def _positions_to_cos_sin(cfg, positions, b, s, dtype):
    if cfg.pos_embed != "rope":
        return None, None
    if cfg.mrope_sections is not None:
        if positions is None:
            p = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            positions = jnp.stack([p, p, p])
        return mrope_cos_sin(positions, cfg.hd, cfg.mrope_sections,
                             cfg.rope_base, dtype)
    if positions is None:
        positions = jnp.arange(s)
    return rope_cos_sin(positions, cfg.hd, cfg.rope_base, dtype)


def _embed(cfg, params, tokens):
    if cfg.n_codebooks > 1:
        # tokens: (B, n_q, S); sum codebook embeddings (EnCodec stub)
        parts = [params["embed"][q][tokens[:, q]]
                 for q in range(cfg.n_codebooks)]
        x = sum(parts)
    else:
        x = params["embed"][tokens]
    return (x * cfg.embed_scale).astype(cfg.cdtype)


def _sinusoidal(cfg, s, offset=0):
    d = cfg.d_model
    pos = jnp.arange(offset, offset + s)[:, None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2)[None].astype(jnp.float32)
    ang = pos / (10000.0 ** (dim / d))
    pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
    return pe.astype(cfg.cdtype)


def _unembed(cfg, params, x):
    if cfg.n_codebooks > 1:
        head = (jnp.transpose(params["embed"], (0, 2, 1))
                if cfg.tie_embeddings else params["lm_head"])
        logits = jnp.einsum("bsd,qdv->bqsv", x, head.astype(cfg.cdtype))
        logits = shard_hint(logits, ("batch", None, None, "model"))
    else:
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(cfg.cdtype)
        logits = shard_hint(logits, ("batch", None, "model"))
    return logits * cfg.logit_scale


def _hidden(params, tokens, cfg: TransformerConfig, positions=None):
    """Common trunk: embeddings -> scan over blocks -> final norm."""
    x = _embed(cfg, params, tokens)
    # Megatron-style sequence parallelism: the residual stream (and thus the
    # per-layer activation checkpoints saved by the scan) shards its SEQUENCE
    # axis over "model". Per-token ops (norms, projections, MLP) need no
    # communication; chunked attention gathers only k/v (GQA: 8-64x smaller
    # than the stream). Dropped automatically when S % axis != 0 (decode).
    sp = ("batch", "model", None)
    x = shard_hint(x, sp)
    b, s = x.shape[0], x.shape[1]
    if cfg.pos_embed == "sinusoidal":
        x = x + _sinusoidal(cfg, s)[None]
    cos, sin = _positions_to_cos_sin(cfg, positions, b, s, cfg.cdtype)

    blk = _block
    if cfg.remat:
        blk = jax.checkpoint(
            _block, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(0,))

    def body(x, lp):
        x, _ = blk(cfg, x, lp, cos, sin)
        return shard_hint(x, sp), None  # residual stays sequence-sharded

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm(cfg, x, params["final_norm"].astype(cfg.cdtype),
                 params.get("final_norm_bias"))


def forward(params, tokens, cfg: TransformerConfig, positions=None):
    """tokens: (B, S) int32 — or (B, n_q, S) for multi-codebook.
    Returns logits (B, S, V) (or (B, n_q, S, V))."""
    if cfg.is_mla:
        return mla.forward(params, tokens, cfg, positions)
    x = _hidden(params, tokens, cfg, positions)
    return _unembed(cfg, params, x)


def loss_fn(params, batch, cfg: TransformerConfig):
    """Mean next-token cross-entropy. batch: {tokens, labels[, positions]}.

    With cfg.loss_chunk > 0 (and a single codebook) the (B, S, V) logits are
    never materialized: the xent scans the sequence in chunks."""
    labels = batch["labels"]
    if cfg.loss_chunk and cfg.n_codebooks == 1 and not cfg.is_mla \
            and labels.shape[-1] % cfg.loss_chunk == 0:
        x = _hidden(params, batch["tokens"], cfg,
                    positions=batch.get("positions"))
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(cfg.cdtype)
        return chunked_lm_loss(x, head, labels, chunk=cfg.loss_chunk,
                               logit_scale=cfg.logit_scale)
    logits = forward(params, batch["tokens"], cfg,
                     positions=batch.get("positions"))
    return jnp.mean(softmax_xent(logits, labels))


# --------------------------------------------------------------------------
# serving: prefill + decode with KV cache
# --------------------------------------------------------------------------
def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None, pad_to: int = 128):
    """KV cache in model layout (L, B, M, Hkv, dh). M is rounded up to a
    multiple of `pad_to` HERE, once, so the decode-attention kernel (block-
    strided over M) never pads or transposes the cache on the hot path;
    positions >= kv_len are masked everywhere downstream. Latent attention
    keeps latent rows instead (models/mla.py `init_cache`)."""
    if cfg.is_mla:
        return mla.init_cache(cfg, batch, max_len, dtype, pad_to)
    dtype = dtype or cfg.cdtype
    m = -(-max_len // pad_to) * pad_to
    shape = (cfg.n_layers, batch, m, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _decode_embed(cfg, params, tokens, pos0):
    """Token embeddings (plus sinusoidal positions) of a decode or prefill
    step whose first position is pos0: a scalar or a (B,) per-slot vector
    (continuous batching). The angles are computed directly at pos0 +
    arange(s) rather than sliced out of a (max) table."""
    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens)
        if cfg.pos_embed == "sinusoidal":
            d, s = cfg.d_model, x.shape[1]
            p = _qpos(pos0, s).astype(jnp.float32)
            if p.ndim == 1:
                p = p[None]                                 # (B|1, s)
            dim = jnp.arange(0, d, 2).astype(jnp.float32)
            ang = p[..., None] / (10000.0 ** (dim / d))     # (B|1, s, d/2)
            x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                                    -1).astype(x.dtype)
        return x


def decode_step(params, cache, tokens, cfg: TransformerConfig,
                positions=None, last_idx=None):
    """One decode step: tokens (B, S_new) (S_new=1 for pure decode, >1 for
    prefill). Returns (logits_last (B, [n_q,] V), new_cache).

    `last_idx`: optional (B,) per-row index of the position whose logits to
    return (ragged bucketed prefill: rows padded to a shared bucket length
    read their logits at prompt_len - 1, not at the pad tail)."""
    if cfg.is_mla:
        return mla.decode_step(params, cache, tokens, cfg, positions,
                               last_idx)
    pos0 = cache["pos"]
    x = _decode_embed(cfg, params, tokens, pos0)
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        pos_ids = _qpos(pos0, s)      # per-slot vector or scalar offset
        if cfg.mrope_sections is not None:
            p = jnp.broadcast_to(pos_ids, (b, s))
            positions = jnp.stack([p, p, p])
        else:
            positions = pos_ids
    cos, sin = _positions_to_cos_sin(cfg, positions, b, s, cfg.cdtype)
    kv_len = pos0 + s

    def body(x, xs):
        lp, ck, cv = xs
        x, new_cache = _block(cfg, x, lp, cos, sin, q_offset=pos0,
                              cache=(ck, cv), kv_len=kv_len)
        return x, new_cache

    with jax.named_scope("layers"):
        x, (nk, nv) = jax.lax.scan(
            body, x, (params["layers"], cache["k"], cache["v"]))
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["final_norm"].astype(cfg.cdtype),
                  params.get("final_norm_bias"))
        if last_idx is not None:
            assert cfg.n_codebooks == 1, "last_idx requires a single codebook"
            # gather each row's last real position BEFORE the unembed so
            # the (B, S, V) prefill logits are never materialized
            x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)
            logits = _unembed(cfg, params, x)[:, -1]
        elif cfg.n_codebooks > 1:
            logits = _unembed(cfg, params, x)[:, :, -1]      # (B, n_q, V)
        else:
            logits = _unembed(cfg, params, x[:, -1:])[:, -1]  # (B, V)
    return logits, {"k": nk, "v": nv, "pos": pos0 + s}


def init_paged_pool(cfg: TransformerConfig, pool_pages: int, page_size: int,
                    dtype=None):
    """Paged KV pool in layout (L, P+1, page_size, Hkv, dh). The last page
    id (pool_pages) is the trash page absorbing unmapped reads/writes —
    allocatable pages are 0..pool_pages-1."""
    dtype = dtype or cfg.cdtype
    shape = (cfg.n_layers, pool_pages + 1, page_size, cfg.n_kv_heads,
             cfg.hd)
    return jnp.zeros(shape, dtype)


def cache_names(cfg: TransformerConfig):
    """Keys of the cache leaves that grow with the sequence, in the dense
    layout (L, B, M, ...); their paged pools are keyed name + "p"."""
    return mla.cache_names(cfg) if cfg.is_mla else ("k", "v")


def init_paged_pools(cfg: TransformerConfig, pool_pages: int, page_size: int,
                     dtype=None):
    """{pool key: pool} of every paged cache leaf (see `cache_names`)."""
    if cfg.is_mla:
        return mla.init_paged_pools(cfg, pool_pages, page_size, dtype)
    kp = init_paged_pool(cfg, pool_pages, page_size, dtype)
    return {"kp": kp, "vp": jnp.zeros_like(kp)}


def paged_decode_step(params, cache, tokens, cfg: TransformerConfig):
    """One paged decode step: tokens (B, 1). cache carries "kp"/"vp" pools
    (L, P+1, ps, Hkv, dh), "ptab" (B, max_pages) int32 and "pos" (B,).
    Returns (logits (B, V), new cache). Positions/rope/sinusoidal handling
    mirrors decode_step exactly so paged == dense bitwise. The layer scan
    carries the stacked pools, so each layer writes its rows' new position
    in place and the kernel reads its layer from the same buffer."""
    if cfg.is_mla:
        return mla.paged_decode_step(params, cache, tokens, cfg)
    pos0 = cache["pos"]                      # (B,) per-slot positions
    x = _decode_embed(cfg, params, tokens, pos0)
    b, s = x.shape[0], x.shape[1]
    assert s == 1 and cfg.n_codebooks == 1
    pos_ids = _qpos(pos0, s)
    if cfg.mrope_sections is not None:
        p = jnp.broadcast_to(pos_ids, (b, s))
        positions = jnp.stack([p, p, p])
    else:
        positions = pos_ids
    cos, sin = _positions_to_cos_sin(cfg, positions, b, s, cfg.cdtype)
    kv_len = pos0 + s
    ptab = cache["ptab"]

    def body(carry, xs):
        x, kp, vp = carry
        lp, layer = xs
        x, (kp, vp) = _block(cfg, x, lp, cos, sin, q_offset=pos0,
                             cache=(kp, vp, ptab, layer), kv_len=kv_len)
        return (x, kp, vp), None

    with jax.named_scope("layers"):
        (x, nkp, nvp), _ = jax.lax.scan(
            body, (x, cache["kp"], cache["vp"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
    with jax.named_scope("head"):
        x = _norm(cfg, x, params["final_norm"].astype(cfg.cdtype),
                  params.get("final_norm_bias"))
        logits = _unembed(cfg, params, x[:, -1:])[:, -1]
    return logits, {**cache, "kp": nkp, "vp": nvp, "pos": pos0 + s}
