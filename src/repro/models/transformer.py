"""Decoder-only transformer LM covering the dense, MoE, VLM and audio
architecture families via configuration.

One trunk serves every config: embed -> a list of layer stacks -> final
norm and head, for the training forward, dense-row decode and prefill, and
paged decode alike (docs/ARCHITECTURE.md, "Model trunk"). `stacks(cfg)`
names each stack's params key, cache leaves, layer count and attention
kind; a kind (`Kind`) gives the block, its weights, its cached row shape,
its rope angles and the dtype of its residual stream. MHA/GQA is this
module's `_block`; latent attention is models/mla.py's.

Parameters are plain pytrees with per-layer weights STACKED on a leading L
axis and each stack runs `lax.scan` over layers — essential to keep the HLO
(and 512-device SPMD compile time) small for the 40-64 layer archs.

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

from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

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
        if self.moe_d_ff and not self.kv_lora_rank:
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
# the stack list
# --------------------------------------------------------------------------
class Kind(NamedTuple):
    """What the trunk takes from an attention kind. Its block is
    (cfg, x, lp, cos, sin, *, cache, pos, page_table, layer) -> (x, cache,
    per-expert counts or None)."""
    name: str            # DecodeState's state kind ("kv", "latent")
    block: Callable
    init: Callable       # (keys, cfg, n, moe) -> one stack's weights
    row: Callable        # cfg -> shape of a cache leaf's row per position
    angles: Callable     # (cfg, positions) -> rope (cos, sin)
    residual: Optional[str] = None   # residual dtype (None: compute dtype)


class Stack(NamedTuple):
    params: str          # params key of its stacked weights
    names: tuple         # its dense cache leaves; their pools add "p"
    n: int               # layers
    kind: Kind
    moe: bool            # whether its blocks route to experts


def stacks(cfg) -> list:
    """The layer stacks, in the order the trunk runs them."""
    if not cfg.is_mla:
        return [Stack("layers", ("k", "v"), cfg.n_layers, MHA, cfg.is_moe)]
    f = cfg.first_dense_layers
    out = [Stack("dense_layers", ("ckv0",), f, LATENT, False)] if f else []
    return out + [Stack("layers", ("ckv",), cfg.n_layers - f, LATENT,
                        cfg.moe_d_ff > 0)]


def _kind(cfg) -> Kind:
    """The kind whose residual dtype and rope angles the trunk keeps."""
    return stacks(cfg)[0].kind


def state_kind(cfg) -> str:
    """DecodeState's tag for this config's cache."""
    return _kind(cfg).name + ("+experts" if cfg.is_moe else "")


def cache_names(cfg: TransformerConfig):
    """Keys of the cache leaves that grow with the sequence, in the dense
    layout (L, B, M, ...); their paged pools are keyed name + "p"."""
    return tuple(n for st in stacks(cfg) for n in st.names)


# the paged state's counters (models/decode_state.py), grown by each paged
# decode step of a config with held experts: sub-steps, assignments to held
# experts, per layer and sub-step the busiest held expert's count (summed),
# and per sub-step the cached positions that rows holding pages attend to
COUNTERS = ("decode_steps", "expert_tokens", "expert_tokens_peak",
            "latent_positions")


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _init_layers(keys, cfg: TransformerConfig, L: int, moe: bool):
    """One MHA/GQA stack of L blocks, from the next 8 keys of `keys`."""
    dt = cfg.pdtype
    hd, h, hkv, d = cfg.hd, cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    k = [next(keys) for _ in range(8)]
    s = d ** -0.5

    def nrm(k, shape, scale):
        return jax.random.normal(k, shape, dt) * scale

    layers = {
        "attn_norm": jnp.ones((L, d), dt),
        "wq": nrm(k[0], (L, d, h * hd), s),
        "wk": nrm(k[1], (L, d, hkv * hd), s),
        "wv": nrm(k[2], (L, d, hkv * hd), s),
        "wo": nrm(k[3], (L, h * hd, d), (h * hd) ** -0.5),
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
    if moe:
        mp = init_moe_params(k[4], d, cfg.d_ff, cfg.num_experts, dt)
        layers["router"] = jnp.broadcast_to(mp["router"],
                                            (L, d, cfg.num_experts)).copy()
        for nm in ("wi_gate", "wi_up", "wo"):
            arr = mp[nm]
            layers["moe_" + nm] = jnp.broadcast_to(
                arr, (L,) + arr.shape).copy()
    else:
        f = cfg.d_ff
        if cfg.mlp_act == "gelu":
            layers["wi"] = nrm(k[5], (L, d, f), s)
            layers["bi"] = jnp.zeros((L, f), dt)
            layers["wo_mlp"] = nrm(k[6], (L, f, d), f ** -0.5)
            layers["bo"] = jnp.zeros((L, d), dt)
        else:
            layers["wi_gate"] = nrm(k[5], (L, d, f), s)
            layers["wi_up"] = nrm(k[7], (L, d, f), s)
            layers["wo_mlp"] = nrm(k[6], (L, f, d), f ** -0.5)
    return layers


def init_params(key, cfg: TransformerConfig):
    """Each stack's weights (its kind's init), then the embedding normal(0,
    1), ones for the final norm and, untied, a head normal(0, d^-1/2), all
    drawing in turn from the splits of `key`."""
    dt = cfg.pdtype
    d, v = cfg.d_model, cfg.vocab_size
    keys = iter(jax.random.split(key, 64))
    params = {st.params: st.kind.init(keys, cfg, st.n, st.moe)
              for st in stacks(cfg)}
    lead = (cfg.n_codebooks,) if cfg.n_codebooks > 1 else ()
    params["embed"] = jax.random.normal(next(keys), lead + (v, d), dt)
    params["final_norm"] = jnp.ones((d,), dt)
    if cfg.norm == "layernorm":
        params["final_norm_bias"] = jnp.zeros((d,), dt)
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            next(keys), lead + (d, v), dt) * d ** -0.5
    return params


# --------------------------------------------------------------------------
# the MHA/GQA block
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


def _block(cfg: TransformerConfig, x, lp, cos, sin, *, cache=None, pos=None,
           page_table=None, layer=None):
    """One MHA/GQA block. cache None: causal attention over x itself
    (training forward). Else `cache` is (k, v): this layer's dense rows,
    each (B, M, Hkv, dh), written at positions `pos` (a scalar, or per row
    (B,)) onward; or, with `page_table`, its stack's pools, each (L, P+1,
    ps, Hkv, dh), written and read at `layer` in place. Returns (x, new
    cache, None)."""
    b, s, d = x.shape
    hd, h, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
    q_offset = 0 if pos is None else pos
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
        kv_len = None if cache is None else pos + s
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

        if page_table is not None:
            # paged decode (s == 1). Each row writes its token at (layer,
            # table[pos // ps], pos % ps), in place; rows with no mapped
            # page there (inactive slots) land on the trash page. Active
            # rows always write distinct pages — prefix-shared pages only
            # cover positions < prompt_len, below any decode write.
            kp, vp = cache
            ps = kp.shape[2]
            pids = page_table[jnp.arange(b), q_offset // ps]
            at = (layer, pids, q_offset % ps)
            kp = kp.at[at].set(k[:, 0].astype(kp.dtype))
            vp = vp.at[at].set(v[:, 0].astype(vp.dtype))
            k, v, cache = kp, vp, (kp, vp)
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
            k, v, cache = ck, cv, (ck, cv)

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
    return x, cache, None


def _angles(cfg, positions):
    """Rope cos, sin at positions (S,) or (B, S), or the (3, B, S) t/h/w
    positions of M-RoPE; none for sinusoidal positions (added at the
    embedding)."""
    if cfg.pos_embed != "rope":
        return None, None
    if cfg.mrope_sections is not None:
        return mrope_cos_sin(positions, cfg.hd, cfg.mrope_sections,
                             cfg.rope_base, cfg.cdtype)
    return rope_cos_sin(positions, cfg.hd, cfg.rope_base, cfg.cdtype)


MHA = Kind("kv", _block, _init_layers,
           lambda cfg: (cfg.n_kv_heads, cfg.hd), _angles)
LATENT = Kind("latent", mla.block, mla.init_stack,
              lambda cfg: (mla.row_width(cfg),), mla.rope_angles,
              mla.RESIDUAL)


# --------------------------------------------------------------------------
# the trunk
# --------------------------------------------------------------------------
def _embed(cfg, params, tokens, pos=0):
    """Token embeddings (B, S, D) in the residual dtype, plus sinusoidal
    positions from pos (a scalar, or per row (B,)) onward."""
    with jax.named_scope("embed"):
        if cfg.n_codebooks > 1:
            # tokens: (B, n_q, S); sum codebook embeddings (EnCodec stub)
            x = sum(params["embed"][q][tokens[:, q]]
                    for q in range(cfg.n_codebooks))
        else:
            x = params["embed"][tokens]
        x = (x * cfg.embed_scale).astype(_kind(cfg).residual or cfg.cdtype)
        if cfg.pos_embed == "sinusoidal":
            d, s = cfg.d_model, x.shape[1]
            p = _qpos(pos, s).astype(jnp.float32)           # ([B,] s)
            dim = jnp.arange(0, d, 2).astype(jnp.float32)
            ang = p[..., None] / (10000.0 ** (dim / d))     # ([B,] s, d/2)
            x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                                    -1).astype(x.dtype)
        return x


def _run_stacks(cfg, params, x, cache, pos, positions=None,
                page_table=None):
    """Every stack's layer scan over x (B, S, D) at positions from `pos`
    (a scalar, or per row (B,)) onward; `positions` overrides them (the
    t/h/w positions of M-RoPE). With no cache it is the training forward:
    each block is rematerialised in the backward pass (cfg.remat) and the
    residual stream stays sequence-sharded between blocks. With a dense
    cache each layer's rows ride as the scan's xs and ys; with
    `page_table` the carry holds the stack's pools (keys name + "p"),
    written in place at each layer. Returns (x, new cache leaves, held
    expert assignments, sum of the per-layer busiest expert's)."""
    b, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = _qpos(pos, s)
        if cfg.mrope_sections is not None:               # text: t = h = w
            p = jnp.broadcast_to(positions, (b, s))
            positions = jnp.stack([p, p, p])
    cos, sin = _kind(cfg).angles(cfg, positions)
    # Megatron-style sequence parallelism: the residual stream (and thus the
    # per-layer activation checkpoints saved by the scan) shards its SEQUENCE
    # axis over "model". Per-token ops (norms, projections, MLP) need no
    # communication; chunked attention gathers only k/v (GQA: 8-64x smaller
    # than the stream).
    sp = ("batch", "model", None)
    if cache is None:
        x = shard_hint(x, sp)
    suffix = "" if page_table is None else "p"
    new, total, peak = {}, jnp.int32(0), jnp.int32(0)
    with jax.named_scope("layers"):
        for st in stacks(cfg):
            blk, names = st.kind.block, [n + suffix for n in st.names]
            if cache is None:
                if cfg.remat:
                    blk = jax.checkpoint(
                        blk, policy=jax.checkpoint_policies.nothing_saveable,
                        static_argnums=(0,))

                def body(x, lp, blk=blk):
                    return shard_hint(blk(cfg, x, lp, cos, sin)[0], sp), None
                x, counts = jax.lax.scan(body, x, params[st.params])
            elif page_table is None:
                def body(x, xs, blk=blk):
                    lp, rows = xs
                    x, rows, counts = blk(cfg, x, lp, cos, sin, cache=rows,
                                          pos=pos)
                    return x, (rows, counts)
                x, (rows, counts) = jax.lax.scan(
                    body, x, (params[st.params],
                              tuple(cache[n] for n in names)))
                new.update(zip(names, rows))
            else:
                def body(carry, xs, blk=blk):
                    (x, pools), (lp, layer) = carry, xs
                    x, pools, counts = blk(cfg, x, lp, cos, sin, cache=pools,
                                           pos=pos, page_table=page_table,
                                           layer=layer)
                    return (x, pools), counts
                (x, pools), counts = jax.lax.scan(
                    body, (x, tuple(cache[n] for n in names)),
                    (params[st.params], jnp.arange(st.n)))
                new.update(zip(names, pools))
            if counts is not None:
                total += counts.sum(dtype=jnp.int32)
                peak += counts.max(axis=1).sum(dtype=jnp.int32)
    return x, new, total, peak


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


def _final_norm(cfg, params, x):
    """Final norm, its weight in the residual dtype, to the compute dtype."""
    return _norm(cfg, x, params["final_norm"].astype(x.dtype),
                 params.get("final_norm_bias")).astype(cfg.cdtype)


def _hidden(params, tokens, cfg: TransformerConfig, positions=None):
    """embeddings -> every stack -> final norm."""
    x = _embed(cfg, params, tokens)
    x, _, _, _ = _run_stacks(cfg, params, x, None, 0, positions)
    return _final_norm(cfg, params, x)


def forward(params, tokens, cfg: TransformerConfig, positions=None):
    """tokens: (B, S) int32 — or (B, n_q, S) for multi-codebook.
    Returns logits (B, S, V) (or (B, n_q, S, V))."""
    return _unembed(cfg, params, _hidden(params, tokens, cfg, positions))


def loss_fn(params, batch, cfg: TransformerConfig):
    """Mean next-token cross-entropy. batch: {tokens, labels[, positions]}.

    With cfg.loss_chunk > 0 (and a single codebook) the (B, S, V) logits are
    never materialized: the xent scans the sequence in chunks."""
    labels = batch["labels"]
    if cfg.loss_chunk and cfg.n_codebooks == 1 \
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
# serving: prefill + decode with a dense or paged cache
# --------------------------------------------------------------------------
def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None, pad_to: int = 128):
    """Dense cache: each stack's leaves (n, B, M, *row). M is rounded up to
    a multiple of `pad_to` HERE, once, so the decode-attention kernel (block-
    strided over M) never pads or transposes the cache on the hot path;
    positions >= kv_len are masked everywhere downstream."""
    dtype = dtype or cfg.cdtype
    m = -(-max_len // pad_to) * pad_to
    out = {n: jnp.zeros((st.n, batch, m) + st.kind.row(cfg), dtype)
           for st in stacks(cfg) for n in st.names}
    out["pos"] = jnp.zeros((), jnp.int32)
    return out


def init_paged_pools(cfg: TransformerConfig, pool_pages: int, page_size: int,
                     dtype=None):
    """{name + "p": pool} of every cache leaf (see `cache_names`), each (n,
    P+1, page_size, *row). The last page id (pool_pages) is the trash page
    absorbing unmapped reads/writes — allocatable pages are
    0..pool_pages-1."""
    dtype = dtype or cfg.cdtype
    return {n + "p": jnp.zeros((st.n, pool_pages + 1, page_size)
                               + st.kind.row(cfg), dtype)
            for st in stacks(cfg) for n in st.names}


def decode_step(params, cache, tokens, cfg: TransformerConfig,
                positions=None, last_idx=None):
    """One decode step: tokens (B, S_new) (S_new=1 for pure decode, >1 for
    prefill) from positions cache["pos"] (a scalar, or per row (B,)).
    Returns (logits_last (B, [n_q,] V), new_cache).

    A cache holding "ptab" (B, max_pages) is paged: one token a row, the
    pools (keys of `init_paged_pools`) read through the table, each layer
    writing its rows' new position in place; the same arithmetic as the
    dense rows, so paged == dense bitwise. The `COUNTERS` a cache holds
    grow by this step's (a row whose pages were released attends to none).

    `last_idx`: optional (B,) per-row index of the position whose logits to
    return (ragged bucketed prefill: rows padded to a shared bucket length
    read their logits at prompt_len - 1, not at the pad tail)."""
    pos0 = cache["pos"]
    ptab = cache.get("ptab")
    x = _embed(cfg, params, tokens, pos0)
    s = x.shape[1]
    x, new, total, peak = _run_stacks(cfg, params, x, cache, pos0,
                                      positions, ptab)
    with jax.named_scope("head"):
        # take each row's last real position BEFORE the norm and unembed
        # so the (B, S, V) prefill logits are never materialized
        x = (x[:, -1:] if last_idx is None else
             jnp.take_along_axis(x, last_idx[:, None, None], axis=1))
        logits = _unembed(cfg, params, _final_norm(cfg, params, x))
        logits = logits[:, :, -1] if cfg.n_codebooks > 1 else logits[:, -1]
    out = {**cache, **new, "pos": pos0 + s}
    if COUNTERS[0] in cache:
        trash = cache[cache_names(cfg)[0] + "p"].shape[1] - 1
        live = jnp.where(ptab[:, 0] != trash, pos0 + s, 0)
        for name, grow in zip(COUNTERS, (1, total, peak,
                                         live.sum(dtype=jnp.int32))):
            out[name] = cache[name] + grow
    return logits, out
