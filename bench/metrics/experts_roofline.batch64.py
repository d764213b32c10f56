"""Roofline share of the held experts in a decode sub-step: the least time
of their work (bench/counts/mla_moe.py `experts_substep`: every held
expert's weights read once a layer, three products per assignment routed
here) over the device time under the `experts` scope, both per sub-step.
The assignments per sub-step are the window's growth of the program's
`expert_tokens` counter over its
`decode_steps`."""
from bench import cells
from bench.counts import least_time_s
from bench.scopes import ms_per_substep


def read(ctx):
    got = ctx.get("counters") or {}
    ms = ms_per_substep(ctx, "experts")
    if not ms or not got.get("decode_steps") or "expert_tokens" not in got:
        return None
    cfg = ctx["config"]
    per = got["expert_tokens"] / got["decode_steps"]
    least = least_time_s(*cells.counts(cfg["family"]).experts_substep(
        cfg, per), ctx["peak"])
    return 100.0 * least / (ms / 1e3)
