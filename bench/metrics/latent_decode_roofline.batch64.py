"""Roofline share of the latent decode attention: the least time of its
work in a sub-step (bench/counts/mla_moe.py `latent_substep`: each cached
latent row read once a layer, absorbed scores and values of every head)
over the device time under the `latent` scope, both per sub-step. The
positions attended per sub-step are the window's growth of the program's
`latent_positions` counter over its
`decode_steps`."""
from bench import cells
from bench.counts import least_time_s
from bench.scopes import ms_per_substep


def read(ctx):
    got = ctx.get("counters") or {}
    ms = ms_per_substep(ctx, "latent")
    if not ms or not got.get("decode_steps") or \
            "latent_positions" not in got:
        return None
    cfg = ctx["config"]
    per = got["latent_positions"] / got["decode_steps"]
    least = least_time_s(*cells.counts(cfg["family"]).latent_substep(
        cfg, per), ctx["peak"])
    return 100.0 * least / (ms / 1e3)
