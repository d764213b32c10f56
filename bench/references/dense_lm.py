"""Plain float32 reference of the dense decoder-only LM family.

Pre-norm blocks: RMSNorm -> grouped-query attention with rotary positions
(half-split rotation) -> residual x `residual_scale` -> RMSNorm -> SwiGLU ->
residual x `residual_scale`; final RMSNorm; logits against the tied
embedding times `logit_scale`; token embeddings times `embed_scale` (the
muP scales of MiniCPM, all 1 for suncatcher). Every matmul runs in float32
at `highest` precision. No kernel, cache or batching: one sequence at a
time, the whole sequence at once, layer by layer.

This module imports nothing of the program. It also makes the weights,
from the seed alone, for the program (in the dtype it stores them in) and
for itself; the reference never takes an array the program has held.
`quant="fp8"` turns it into the control: every matmul operand rounded to
float8_e4m3 with a per-tensor scale, the precision below the bfloat16 the
configurations compute in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0     # largest finite float8_e4m3fn


def seed_key(seed: int):
    """A raw PRNG key from any whole number (beyond 32 bits too)."""
    a, b = np.random.SeedSequence(int(seed) % 2 ** 64).generate_state(
        2, np.uint32)
    return jnp.asarray([a, b], jnp.uint32)


def dims(cfg: dict):
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    return d, h, hkv, cfg["head_dim"], cfg["d_ff"], cfg["n_layers"], \
        cfg["vocab_size"]


def weights_fn(cfg: dict, dtype):
    """A jitted function key -> weights, in the layer-stacked tree the
    program serves from: normal(0, fan_in^-1/2) for projections, as the
    program's own initialiser draws them, ones for the norms, and the
    embedding at normal(0, d_model^-1/2 / embed_scale), so that the scaled
    input has rms d_model^-1/2. (The program draws its embedding at
    normal(0, 1); with a tied head, and more so under MiniCPM's embed scale
    of 12, the input token then outweighs every layer and each position
    predicts its own input by many logits: no rounding could ever change a
    greedy token, and the check would see nothing.)"""
    d, h, hkv, hd, f, nl, v = dims(cfg)

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 8)

        def nrm(k, shape, fan_in):
            x = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            return x.astype(dtype)

        one = lambda *s: jnp.ones(s, dtype)
        return {
            "embed": nrm(ks[0], (v, d), d * cfg["embed_scale"] ** 2),
            "layers": {
                "attn_norm": one(nl, d),
                "wq": nrm(ks[1], (nl, d, h * hd), d),
                "wk": nrm(ks[2], (nl, d, hkv * hd), d),
                "wv": nrm(ks[3], (nl, d, hkv * hd), d),
                "wo": nrm(ks[4], (nl, h * hd, d), h * hd),
                "mlp_norm": one(nl, d),
                "wi_gate": nrm(ks[5], (nl, d, f), d),
                "wi_up": nrm(ks[6], (nl, d, f), d),
                "wo_mlp": nrm(ks[7], (nl, f, d), f),
            },
            "final_norm": one(d),
        }
    return make


def make_weights(cfg: dict, seed: int, dtype=jnp.float32):
    return weights_fn(cfg, dtype)(seed_key(seed))


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
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    return mm


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, base):
    """x (S, H, hd): rotate the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(w, tokens, cfg: dict, quant=None):
    """tokens (S,) -> logits (S, V) in float32 (causal, positions 0..S-1)."""
    d, h, hkv, hd, f, nl, v = dims(cfg)
    mm = _mm(quant)
    eps, rs = cfg["rms_norm_eps"], cfg["residual_scale"]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    s = tokens.shape[0]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    x = w["embed"][tokens].astype(jnp.float32) * cfg["embed_scale"]

    def block(x, lp):
        lp = f32(lp)
        hn = _rms(x, lp["attn_norm"], eps)
        q = mm("sd,de->se", hn, lp["wq"]).reshape(s, h, hd)
        k = mm("sd,de->se", hn, lp["wk"]).reshape(s, hkv, hd)
        vv = mm("sd,de->se", hn, lp["wv"]).reshape(s, hkv, hd)
        q, k = _rope(q, pos, cfg["rope_base"]), _rope(k, pos, cfg["rope_base"])
        q = q.reshape(s, hkv, h // hkv, hd) * hd ** -0.5
        sc = mm("qhgd,khd->hgqk", q, k)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        o = mm("hgqk,khd->qhgd", p, vv).reshape(s, h * hd)
        x = x + rs * mm("se,ed->sd", o, lp["wo"])
        hn = _rms(x, lp["mlp_norm"], eps)
        g = mm("sd,df->sf", hn, lp["wi_gate"])
        u = mm("sd,df->sf", hn, lp["wi_up"])
        x = x + rs * mm("sf,fd->sd", jax.nn.silu(g) * u, lp["wo_mlp"])
        return x, None

    x, _ = jax.lax.scan(block, x, w["layers"])
    x = _rms(x, w["final_norm"].astype(jnp.float32), eps)
    return mm("sd,vd->sv", x, w["embed"].astype(jnp.float32)) \
        * cfg["logit_scale"]


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
    reference's own argmax). The sequence is prompt + served tokens, padded
    to `pad_to` so every request shares one compiled program; causal
    attention keeps the padding out of the positions compared.

    With `control`, also the gap of the token the fp8 control puts first
    at each of the same positions. Returns numpy arrays over served
    tokens."""
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
