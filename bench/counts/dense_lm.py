"""Counts of the `dense_lm` family: a decoder of `n_layers` blocks, each
attention with `n_heads` query and `n_kv_heads` key/value heads of
`head_dim` and a three-matrix MLP of `d_ff`, and a tied embedding and head
(bench/counts/__init__.py says what a family file gives)."""
from __future__ import annotations

BF16 = 2    # bytes


def layer_matmul_params(cfg: dict) -> int:
    d, f = cfg["d_model"], cfg["d_ff"]
    q = cfg["n_heads"] * cfg["head_dim"]
    kv = cfg["n_kv_heads"] * cfg["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * f


def head_params(cfg: dict) -> int:
    return cfg["d_model"] * cfg["vocab_size"]


def param_count(cfg: dict) -> int:
    """All parameters: embedding (tied head), layers with their two norms,
    final norm."""
    d = cfg["d_model"]
    return (head_params(cfg) + cfg["n_layers"] * (layer_matmul_params(cfg)
                                                  + 2 * d) + d)


def attn_flops(cfg: dict, context: int) -> int:
    """Scores and weighted sum for one query over `context` keys, all
    layers."""
    return 4 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * context


def token_flops(cfg: dict, context: int) -> int:
    """One token through the model and the output head, attending to
    `context` positions (its own included)."""
    return (2 * cfg["n_layers"] * layer_matmul_params(cfg)
            + attn_flops(cfg, context) + 2 * head_params(cfg))


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """A causal prompt of `prompt_len` tokens; logits at its last position
    only, as serving needs."""
    s = prompt_len
    return (s * 2 * cfg["n_layers"] * layer_matmul_params(cfg)
            + attn_flops(cfg, s * (s + 1) // 2) + 2 * head_params(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """Keys and values of one position, all layers, at bfloat16."""
    return 2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"] * BF16


def weight_bytes(cfg: dict) -> int:
    """Every weight a decode step reads once, at bfloat16."""
    return param_count(cfg) * BF16


def decode_substep(cfg: dict, contexts) -> tuple[int, int]:
    """(operations, bytes) of one decode sub-step whose active rows attend
    to `contexts` positions each: weights once, each row's live keys and
    values read, its new position written."""
    flops = sum(token_flops(cfg, c) for c in contexts)
    kv = kv_bytes_per_token(cfg)
    nbytes = weight_bytes(cfg) + sum((c + 1) * kv for c in contexts)
    return flops, nbytes
