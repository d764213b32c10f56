"""Plain float32 reference of the `mla_moe` family: the DeepSeek-V3 block as
Moonlight-16B-A3B configures it, with this chip's share of the experts.

Pre-norm blocks: RMSNorm -> multi-head latent attention -> residual ->
RMSNorm -> MLP -> residual; final RMSNorm; an untied head. Attention, in
its naive form only: q = x W_q (per head `qk_nope_head_dim` +
`qk_rope_head_dim`); [c_kv | k_pe] = x W_kv_a, c_kv RMS-normed (epsilon
1e-6, the norm's class default); K and V of every head from c_kv W_kv_b,
the rope part of K the one k_pe shared by all heads; rope on DeepSeek's
interleaved pairs (2i, 2i + 1) at `rope_base`; scores scaled by (nope +
rope) ** -0.5, causal softmax. The first `first_dense_layers` MLPs are
SwiGLU of `d_ff`; the others route: sigmoid scores over all `num_experts`,
the top `top_k` of scores + selection bias chosen, gates the chosen
unbiased scores renormalised and times `routed_scaling`; every held expert
(`experts_held` from `expert_offset`) is computed on every token and
weighted by its gate there, 0 where it was not chosen, so an assignment to
an expert held elsewhere adds nothing; plus the shared experts, one SwiGLU
of n_shared x `moe_d_ff`. Every matmul in float32 at `highest` precision,
one sequence at a time, the whole sequence at once, layer by layer.

This module imports nothing of the program. It makes the weights from the
seed alone, for the program (in its tree) and for itself, at the
configuration's `param_dtype`; the forward pass upcasts one layer's
weights at a time, so the 3.36B-parameter share fits beside its bfloat16
copy. `quant="fp8"` turns it into the control: every matmul operand
rounded to float8_e4m3 with a per-tensor scale.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0     # largest finite float8_e4m3fn
KV_NORM_EPS = 1e-6
BIAS_STD = 0.1      # selection bias: normal(0, 0.1), so it moves choices


def seed_key(seed: int):
    """A raw PRNG key from any whole number (beyond 32 bits too)."""
    a, b = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(
        2, np.uint32)
    return jnp.asarray([a, b], jnp.uint32)


def stacks(cfg: dict):
    """(params key, layer count, is MoE) of each layer stack."""
    f = cfg["first_dense_layers"]
    return ([("dense_layers", f, False)] if f else []) + \
        [("layers", cfg["n_layers"] - f, True)]


def layer_shapes(cfg: dict, moe: bool) -> dict:
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    out = {"attn_norm": (d,), "wq": (d, h * (dn + dr)), "wkv_a": (d, r + dr),
           "kv_norm": (r,), "wkv_b": (r, h * (dn + dv)), "wo": (h * dv, d),
           "mlp_norm": (d,)}
    if moe:
        e, f, held = cfg["num_experts"], cfg["moe_d_ff"], cfg["experts_held"]
        fs = cfg["n_shared_experts"] * f
        out.update(router=(d, e), router_bias=(e,),
                   moe_wi_gate=(held, d, f), moe_wi_up=(held, d, f),
                   moe_wo=(held, f, d), shared_wi_gate=(d, fs),
                   shared_wi_up=(d, fs), shared_wo=(fs, d))
    else:
        f = cfg["d_ff"]
        out.update(wi_gate=(d, f), wi_up=(d, f), wo_mlp=(f, d))
    return out


def weights_fn(cfg: dict, dtype):
    """A jitted function key -> weights in the program's layer-stacked tree:
    projections normal(0, fan_in^-1/2), norms ones, the selection bias
    normal(0, BIAS_STD), the embedding normal(0, 1), the head normal(0,
    d_model^-1/2)."""
    d, v = cfg["d_model"], cfg["vocab_size"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 64))

        def draw(shape, std):
            x = jax.random.normal(next(keys), shape, jnp.float32) * std
            return x.astype(dtype)

        def stack(n, moe):
            out = {}
            for name, shape in layer_shapes(cfg, moe).items():
                full = (n,) + shape
                if name.endswith("norm"):
                    out[name] = jnp.ones(full, dtype)
                elif name == "router_bias":
                    out[name] = draw(full, BIAS_STD)
                else:
                    out[name] = draw(full, shape[-2] ** -0.5)
            return out

        w = {"embed": draw((v, d), 1.0), "final_norm": jnp.ones((d,), dtype),
             "lm_head": draw((d, v), d ** -0.5)}
        for name, n, moe in stacks(cfg):
            w[name] = stack(n, moe)
        return w
    return make


def make_weights(cfg: dict, seed: int, dtype=None):
    return weights_fn(cfg, dtype or jnp.dtype(cfg["param_dtype"]))(
        seed_key(seed))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(quant):
    def mm(spec, a, b):
        if quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        elif quant == "bf16":
            a = a.astype(jnp.bfloat16).astype(jnp.float32)
            b = b.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope_pairs(x, pos, base):
    """x (S, ..., dr): rotate each pair (2i, 2i + 1) by pos * base^(-2i/dr),
    in place."""
    dr = x.shape[-1]
    inv = 1.0 / base ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
    ang = pos[:, None].astype(jnp.float32) * inv              # (S, dr/2)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos],
                     -1).reshape(x.shape)


def _swiglu(mm, x, g, u, o):
    return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, g))
              * mm("sd,df->sf", x, u), o)


def _attention(cfg, mm, x, lp, pos, causal):
    s = x.shape[0]
    h, r = cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    hn = _rms(x, lp["attn_norm"], cfg["rms_norm_eps"])
    q = mm("sd,de->se", hn, lp["wq"]).reshape(s, h, dn + dr)
    kv_a = mm("sd,de->se", hn, lp["wkv_a"])
    c = _rms(kv_a[:, :r], lp["kv_norm"], KV_NORM_EPS)
    k_pe = _rope_pairs(kv_a[:, r:], pos, cfg["rope_base"])
    q = jnp.concatenate([q[..., :dn],
                         _rope_pairs(q[..., dn:], pos, cfg["rope_base"])], -1)
    kv = mm("sr,re->se", c, lp["wkv_b"]).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe[:, None], (s, h, dr))], -1)
    sc = mm("qhd,khd->hqk", q, k) * (dn + dr) ** -0.5
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    o = mm("hqk,khd->qhd", p, kv[..., dn:]).reshape(s, h * dv)
    return mm("se,ed->sd", o, lp["wo"])


def _routed(cfg, mm, x, lp):
    """Every held expert on every token, weighted by its gate where the
    token chose it. Returns (output, chosen experts (S, top_k))."""
    held, off = cfg["experts_held"], cfg["expert_offset"]
    scores = jax.nn.sigmoid(mm("sd,de->se", x, lp["router"]))
    _, ix = jax.lax.top_k(scores + lp["router_bias"], cfg["top_k"])
    w = jnp.take_along_axis(scores, ix, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * cfg["routed_scaling"]
    mine = off + jnp.arange(held)                              # (H,)
    gate = jnp.sum(jnp.where(ix[:, None, :] == mine[None, :, None],
                             w[:, None, :], 0.0), -1)           # (S, H)
    g = mm("sd,hdf->hsf", x, lp["moe_wi_gate"])
    u = mm("sd,hdf->hsf", x, lp["moe_wi_up"])
    y = mm("hsf,hfd->hsd", jax.nn.silu(g) * u, lp["moe_wo"])
    return jnp.sum(y * gate.T[..., None], 0), ix


def logits(w, tokens, cfg: dict, quant=None):
    """tokens (S,) -> logits (S, V) in float32 (causal, positions 0..S-1)."""
    return forward(w, tokens, cfg, quant)[0]


def forward(w, tokens, cfg: dict, quant=None):
    """(logits (S, V), chosen experts (MoE layers, S, top_k)). `quant`:
    None, "fp8" (the control) or "bf16" (operands rounded to bfloat16, the
    configuration's precision; `routing_flips` compares its choices)."""
    mm = _mm(quant)
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(jnp.float32)

    def block(moe):
        def run(x, lp):
            lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
            x = x + _attention(cfg, mm, x, lp, pos, causal)
            hn = _rms(x, lp["mlp_norm"], eps)
            if not moe:
                return x + _swiglu(mm, hn, lp["wi_gate"], lp["wi_up"],
                                   lp["wo_mlp"]), None
            routed, ix = _routed(cfg, mm, hn, lp)
            return x + routed + _swiglu(mm, hn, lp["shared_wi_gate"],
                                        lp["shared_wi_up"],
                                        lp["shared_wo"]), ix
        return run

    routes = None
    for name, _, moe in stacks(cfg):
        x, got = jax.lax.scan(block(moe), x, w[name])
        routes = got if moe else routes
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    return mm("sd,dv->sv", x, w["lm_head"].astype(jnp.float32)), routes


@partial(jax.jit, static_argnames=("cfg_items",))
def _flips(w, tokens, cfg_items):
    cfg = dict(cfg_items)
    _, a = forward(w, tokens, cfg)
    _, b = forward(w, tokens, cfg, quant="bf16")
    held = cfg["expert_offset"] + jnp.arange(cfg["experts_held"])

    def mine(ix):                  # the held experts chosen, in order
        return jnp.sort(jnp.where(jnp.isin(ix, held), ix, -1), -1)

    moved = jnp.any(jnp.sort(a, -1) != jnp.sort(b, -1), -1)     # (L, S)
    touches = jnp.any(mine(a) != mine(b), -1)
    return {"choices": moved.size, "flipped": jnp.sum(moved),
            "flipped_held": jnp.sum(moved & touches),
            "tokens_with_a_flip": jnp.sum(jnp.any(moved, 0))}


def routing_flips(w, cfg: dict, tokens) -> dict:
    """How often rounding the reference's matmul operands to bfloat16
    changes an expert choice: over (MoE layer, position) pairs of `tokens`,
    how many choose another set of experts than in float32, how many of
    those change what this chip's held experts compute, and how many
    positions see a change in any layer."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return {k: int(v) for k, v in jax.device_get(
        _flips(w, jnp.asarray(tokens, jnp.int32), items)).items()}


@partial(jax.jit, static_argnames=("cfg_items", "control"))
def _gaps(w, tokens, served, cfg_items, control):
    cfg = dict(cfg_items)
    ref = logits(w, tokens, cfg)
    best = jnp.max(ref, -1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    gap = best - jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    out = {"gap": jnp.where(served, gap, 0.0)}
    if control:
        low = logits(w, tokens, cfg, quant="fp8")
        pick = jnp.argmax(low, -1)
        cgap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        out["control_gap"] = jnp.where(served, cgap, 0.0)
    return out


def served_gaps(w, cfg: dict, prompt, generated, pad_to: int,
                control: bool = False):
    """For each served token, how far its reference logit lies below the
    reference's best at that position (0 where the program chose the
    reference's own argmax), over prompt + served tokens padded to
    `pad_to` (causal attention keeps the padding out of the positions
    compared). With `control`, also the gap of the token the fp8 control
    puts first. Returns numpy arrays over served tokens."""
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated, np.int32)])
    n = len(seq)
    if n > pad_to:
        raise ValueError(f"sequence of {n} exceeds the padded length {pad_to}")
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:n] = seq
    served = np.zeros((pad_to,), bool)
    served[len(prompt) - 1:n - 1] = True
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    out = jax.device_get(_gaps(w, jnp.asarray(tokens), jnp.asarray(served),
                               items, control))
    return {k: np.asarray(v)[served] for k, v in out.items()}
