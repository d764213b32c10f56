"""How far the busiest held expert runs above the mean: growth of the
program's `expert_tokens_peak` (per layer and sub-step, the busiest held
expert's assignments, summed) over growth of `expert_tokens` / the experts
held (the mean held expert's, summed alike). 1 is an even load; the
slowest expert sets a grouped product's time."""


def read(ctx):
    got = ctx.get("counters") or {}
    if not got.get("expert_tokens") or "expert_tokens_peak" not in got:
        return None
    held = ctx["config"]["experts_held"]
    return got["expert_tokens_peak"] / (got["expert_tokens"] / held)
