"""Counts of the `mla_moe` family: the DeepSeek-V3 block with this chip's
share of the experts (bench/references/mla_moe.py has the equations;
bench/counts/__init__.py says what a family file gives).

Operations are the model's own: the naive form of attention (each cached
position costs a head's nope + rope scores and its value sum), and per
token the expected work of the held experts, top_k x experts_held /
num_experts expert evaluations (a token's assignments to experts held
elsewhere are no work of this chip). Bytes are the weights a decode
sub-step must read once at bfloat16, every one but the embedding table
(a sub-step gathers only its rows' embeddings), plus the live latent rows
[c_kv | k_pe] of every layer. `experts_substep` and `latent_substep` count
the work of one part of a sub-step for its roofline share."""
from __future__ import annotations

BF16 = 2    # bytes


def _moe_layers(cfg: dict) -> int:
    return cfg["n_layers"] - cfg["first_dense_layers"]


def attn_params(cfg: dict) -> int:
    d, h, r = cfg["d_model"], cfg["n_heads"], cfg["kv_lora_rank"]
    dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def expert_params(cfg: dict) -> int:
    """One routed expert (its shared siblings are the same shape)."""
    return 3 * cfg["d_model"] * cfg["moe_d_ff"]


def param_count(cfg: dict) -> int:
    """Every parameter this chip holds: embedding and head, norms, the
    dense layers, and the MoE layers with their router, selection bias,
    held experts and shared experts."""
    d, v, r = cfg["d_model"], cfg["vocab_size"], cfg["kv_lora_rank"]
    norms = 2 * d + r
    dense = attn_params(cfg) + 3 * d * cfg["d_ff"] + norms
    moe = (attn_params(cfg) + d * cfg["num_experts"] + cfg["num_experts"]
           + (cfg["experts_held"] + cfg["n_shared_experts"])
           * expert_params(cfg) + norms)
    return (2 * v * d + d + cfg["first_dense_layers"] * dense
            + _moe_layers(cfg) * moe)


def held_share(cfg: dict) -> float:
    """Expected held-expert evaluations per token."""
    return cfg["top_k"] * cfg["experts_held"] / cfg["num_experts"]


def token_matmul_flops(cfg: dict) -> float:
    d = cfg["d_model"]
    moe = (d * cfg["num_experts"] + (held_share(cfg) + cfg["n_shared_experts"])
           * expert_params(cfg))
    return 2 * (cfg["n_layers"] * attn_params(cfg)
                + cfg["first_dense_layers"] * 3 * d * cfg["d_ff"]
                + _moe_layers(cfg) * moe + d * cfg["vocab_size"])


def attn_flops(cfg: dict, context: int) -> int:
    """Scores and weighted values of one query over `context` positions,
    all layers (naive form)."""
    per = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return 2 * cfg["n_layers"] * cfg["n_heads"] * per * context


def token_flops(cfg: dict, context: int) -> float:
    """One token through the model and the head, attending to `context`
    positions (its own included)."""
    return token_matmul_flops(cfg) + attn_flops(cfg, context)


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """A causal prompt of `prompt_len` tokens; logits at its last position
    only."""
    s = prompt_len
    head = 2 * cfg["d_model"] * cfg["vocab_size"]
    return (s * (token_matmul_flops(cfg) - head)
            + attn_flops(cfg, s * (s + 1) // 2) + head)


def latent_bytes_per_token(cfg: dict) -> int:
    """[c_kv | k_pe] of one position, all layers, at bfloat16."""
    return cfg["n_layers"] * (cfg["kv_lora_rank"]
                              + cfg["qk_rope_head_dim"]) * BF16


def weight_bytes(cfg: dict) -> int:
    """Every weight a decode sub-step reads once, at bfloat16: all but the
    embedding table."""
    return (param_count(cfg) - cfg["vocab_size"] * cfg["d_model"]) * BF16


def decode_substep(cfg: dict, contexts) -> tuple[float, int]:
    """(operations, bytes) of one decode sub-step whose active rows attend
    to `contexts` positions each: weights once, each row's live latent rows
    read, its new position written."""
    flops = sum(token_flops(cfg, c) for c in contexts)
    nbytes = weight_bytes(cfg) + sum(
        (c + 1) * latent_bytes_per_token(cfg) for c in contexts)
    return flops, nbytes


def experts_substep(cfg: dict, assignments: float) -> tuple[float, int]:
    """(operations, bytes) of the held experts in one decode sub-step that
    routes `assignments` token-expert pairs to them over all MoE layers:
    three products per assignment; every held expert's weights and the
    router read once a layer."""
    d, e = cfg["d_model"], cfg["num_experts"]
    flops = 2 * expert_params(cfg) * assignments
    nbytes = _moe_layers(cfg) * (cfg["experts_held"] * expert_params(cfg)
                                 + d * e + e) * BF16
    return flops, nbytes


def latent_substep(cfg: dict, positions: float) -> tuple[float, int]:
    """(operations, bytes) of the latent decode attention of one sub-step
    whose rows attend to `positions` cached positions in all, in each
    layer: absorbed scores over r + rope and values over r for every head,
    each cached row read once."""
    h, r = cfg["n_heads"], cfg["kv_lora_rank"]
    dr = cfg["qk_rope_head_dim"]
    per_pos = 2 * h * (2 * r + dr)
    return (cfg["n_layers"] * per_pos * positions,
            cfg["n_layers"] * (r + dr) * BF16 * positions)
