"""Mixture-of-Experts FFN: top-k routing with capacity-based dispatch.

TPU-native adaptation notes (docs/ARCHITECTURE.md, "Model trunk"): instead
of the GPU-style scatter/gather with dynamic shapes — or the GShard one-hot
dispatch einsums, whose (tokens x experts x capacity) matmuls inflate HLO
FLOPs by orders of magnitude and wreck the compute roofline — we use a
sort-based static-shape dispatch:

  1. top-k expert choice per token (router in fp32),
  2. flat (token, expert) assignments sorted by expert id,
  3. rank-within-expert via a cumulative count; assignments whose rank
     exceeds the expert capacity C = ceil(k*T/E * capacity_factor) are
     dropped (GShard-style token dropping),
  4. one gather builds the (E, C, D) expert batch, two grouped einsums run
     the expert FFNs, one scatter-add combines weighted outputs.

All shapes are static; the only non-matmul costs are a sort and two
gathers, so cost_analysis FLOPs stay ~= 3 * 2 * T*k*D*F (the real MoE math).
Experts shard over the "model" mesh axis (expert parallelism): the gather is
local (activations are model-replicated), the combine scatter-add induces the
same single all-reduce as a dense tensor-parallel FFN.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.hints import shard_hint


def router_topk(x, w_router, k: int):
    """x: (T, D), w_router: (D, E) -> (weights (T,k), experts (T,k))."""
    logits = (x.astype(jnp.float32) @ w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, ix = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)  # renormalize over chosen k
    return w, ix


def aux_load_balance_loss(x, w_router, k: int, num_experts: int):
    """Switch-style load-balance auxiliary loss (mean fraction * mean prob)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    _, ix = jax.lax.top_k(probs, k)
    counts = jnp.zeros((num_experts,), jnp.float32).at[ix.reshape(-1)].add(1.0)
    frac = counts / counts.sum()
    return num_experts * jnp.sum(frac * probs.mean(0))


def moe_ffn(x, params, *, num_experts: int, top_k: int,
            capacity_factor: float = 1.25, activation: str = "swiglu"):
    """x: (T, D). params: router (D,E), wi_gate/wi_up (E,D,F), wo (E,F,D)."""
    t, d = x.shape
    e = num_experts
    capacity = int(max(1, (top_k * t * capacity_factor) // e))

    weights, experts = router_topk(x, params["router"], top_k)   # (T,k)
    flat_expert = experts.reshape(-1)                            # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), top_k)
    flat_weight = weights.reshape(-1)

    order = jnp.argsort(flat_expert)                             # stable
    se, st, sw = flat_expert[order], flat_token[order], flat_weight[order]
    # rank of each assignment within its expert segment
    counts = jnp.bincount(se, length=e)
    seg_start = jnp.cumsum(counts) - counts
    rank = jnp.arange(t * top_k) - seg_start[se]
    keep = rank < capacity

    # slot table: (E, C) token index per expert slot (T = sentinel "empty")
    slot_token = jnp.full((e, capacity), t, jnp.int32)
    slot_weight = jnp.zeros((e, capacity), x.dtype)
    se_c = jnp.where(keep, se, e - 1)
    rk_c = jnp.where(keep, rank, capacity - 1)
    slot_token = slot_token.at[se_c, rk_c].set(
        jnp.where(keep, st, t).astype(jnp.int32), mode="drop")
    slot_weight = slot_weight.at[se_c, rk_c].set(
        jnp.where(keep, sw, 0.0).astype(x.dtype), mode="drop")

    # gather -> expert FFN -> weighted scatter-add
    slot_token = shard_hint(slot_token, ("model", None))
    slot_weight = shard_hint(slot_weight, ("model", None))
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)
    xe = shard_hint(x_pad[slot_token], ("model", None, None))   # (E, C, D)
    if activation == "swiglu":
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"]))
        h = g * jnp.einsum("ecd,edf->ecf", xe, params["wi_up"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"]),
                        approximate=True)
    ye = shard_hint(jnp.einsum("ecf,efd->ecd", h, params["wo"]),
                    ("model", None, None))                       # (E, C, D)

    out = jnp.zeros((t + 1, d), x.dtype)
    out = out.at[slot_token].add(ye * slot_weight[..., None])
    return shard_hint(out[:t], ("batch", None))


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    return {
        "router": (jax.random.normal(k1, (d_model, num_experts), dtype)
                   * s_in),
        "wi_gate": (jax.random.normal(k2, (num_experts, d_model, d_ff), dtype)
                    * s_in),
        "wi_up": (jax.random.normal(k3, (num_experts, d_model, d_ff), dtype)
                  * s_in),
        "wo": (jax.random.normal(k4, (num_experts, d_ff, d_model), dtype)
               * s_out),
    }


# --------------------------------------------------------------------------
# a held share of sigmoid-routed experts, dropless (DeepSeek-V3 / Moonlight)
# --------------------------------------------------------------------------
def sigmoid_route(x, w_router, bias, *, top_k: int, scaling: float):
    """DeepSeek-V3 routing over every expert: scores sigmoid(x W_r); the
    top-k of scores + `bias` (the selection bias, which chooses but never
    weighs) are taken, and their unbiased scores, renormalised over the k
    and times `scaling`, are the gates. x (T, D) -> (gates, experts),
    each (T, k); gates in float32."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, ix = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, ix, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scaling, ix


DENSE_ROWS = 512        # at most this many tokens: every held expert on all
PREFILL_CHUNK = 4096    # more tokens are routed this many at a time


def held_experts_ffn(x, params, *, top_k: int, scaling: float, offset: int):
    """The routed part that this chip's experts give, with no assignment
    dropped. x: (T, D); params: router (D, E) and bias (E,) over all E
    experts, wi_gate / wi_up (H, D, F) and wo (H, F, D) for the H experts
    held here, experts offset .. offset + H - 1. Assignments to experts not
    held here contribute nothing (expert parallelism without its exchange).
    Returns (out (T, D), counts (H,) of assignments per held expert).

    Two forms of the same sum, chosen by T:
      T <= DENSE_ROWS (decode)  every held expert on all T rows, weighted
          by its gate (0 where not chosen): H x T rows of work, and reading
          the held weights once is the cost either way;
      larger T (prefill)  the held assignments sorted by expert through
          three grouped products (`lax.ragged_dot`, whose TPU kernel
          visits only the 512-row tiles the groups cover), so the work
          follows the ~T k H / E tokens routed here and not T x H; in
          chunks of PREFILL_CHUNK tokens, one after another, so the sorted
          copies stay small. (At decode each group would still fill a
          whole tile.)
    """
    t, d = x.shape
    if t > PREFILL_CHUNK and t % PREFILL_CHUNK == 0:
        out, counts = jax.lax.map(
            lambda xc: held_experts_ffn(xc, params, top_k=top_k,
                                        scaling=scaling, offset=offset),
            x.reshape(t // PREFILL_CHUNK, PREFILL_CHUNK, d))
        return out.reshape(t, d), counts.sum(0)
    held = params["wi_gate"].shape[0]
    gates, experts = sigmoid_route(x, params["router"], params["bias"],
                                   top_k=top_k, scaling=scaling)
    local = experts - offset
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held)           # not held: sorted last
    counts = jnp.bincount(group.reshape(-1), length=held + 1)[:held]
    counts = counts.astype(jnp.int32)
    if t <= DENSE_ROWS:
        gate = jnp.sum(jnp.where(group[:, None, :] == jnp.arange(held)[
            None, :, None], gates[:, None, :], 0.0), -1)      # (T, H)
        g = jnp.einsum("td,edf->etf", x, params["wi_gate"])
        u = jnp.einsum("td,edf->etf", x, params["wi_up"])
        y = jnp.einsum("etf,efd->etd", (jax.nn.silu(g) * u).astype(x.dtype),
                       params["wo"])
        out = jnp.sum(y.astype(jnp.float32) * gate.T[..., None], axis=0)
        return out.astype(x.dtype), counts
    order = jnp.argsort(group.reshape(-1))
    xs = x[order // top_k]                                 # (T*k, D)
    g = jax.lax.ragged_dot(xs, params["wi_gate"], counts)
    u = jax.lax.ragged_dot(xs, params["wi_up"], counts)
    y = jax.lax.ragged_dot((jax.nn.silu(g) * u).astype(x.dtype),
                           params["wo"], counts)
    # back to (token, choice) order; rows past the held assignments are
    # not computed, so they are selected away, not scaled
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = jnp.where(mine.reshape(-1, 1), y[back].astype(jnp.float32), 0.0)
    out = jnp.sum(y.reshape(t, top_k, d) * gates[..., None], axis=1)
    return out.astype(x.dtype), counts
