"""Operations and bytes that the work needs, computed from shapes, one file
per model family: bench/counts/<family>.py, named by the configuration's
`family` (bench/cells.py `counts`).

Counts are of the model, not of any implementation: no padding, no
recomputation, and the bytes a step must move whatever implements it
(weights once per step at bfloat16, the live cache of the rows that
decode). A multiply-add is 2 operations. A family file gives

  token_flops(cfg, context)       one token attending to `context` positions
  prefill_flops(cfg, prompt_len)  a causal prompt, logits at its last position
  decode_substep(cfg, contexts)   (operations, bytes) of one decode sub-step

and this module the roofline they are held to.
"""
from __future__ import annotations


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak rate and bytes over
    peak bandwidth."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])
