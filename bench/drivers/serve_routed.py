"""Serving cells of a model that routes each token to a few of many experts:
the serving driver's run (bench/drivers/serve_engine.py), with `correct`
decided by one more number.

Near a tie of two experts' router scores, a bfloat16 rounding flips which
expert a token takes, and the flipped expert's output moves that token's
logits by as much as a lower precision does. So the widest gap of a sound
run reaches the fp8 control's, and `widest_gap` alone cannot tell them
apart. How often a served token lies clearly below the reference's best
can: a flip is rare, and the control moves most tokens.

Numbers compared (each against bench/limits/<cell>.json):
  off_best_share  share of the compared served tokens whose reference
                  logit lies more than `above` logits (the limits file's)
                  below the reference's best at that position
  widest_gap      the largest such gap, as serve_engine's: a bound on any
                  one token
  token_count     as serve_engine's

With `control`, the fp8 control's first choice at each compared position
stands in the program's, as in serve_engine. Traffic keys: serve_engine's.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import cells, generator
from bench.drivers import serve_engine


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        peak: dict, control: bool = False) -> dict:
    cfgd, traffic = cell.config, cell.traffic
    eng_cfg = traffic["engine"]
    warm_s = float(traffic["warm_s"])
    cfg, fns = serve_engine.program_config(cfgd)
    work = generator.make(traffic, seed, warm_s + seconds + 5.0,
                          cfgd.get("token_vocab", cfgd["vocab_size"]))
    trace_s = min(float(traffic.get("trace_s", 3.0)), seconds)
    tr = serve_engine.Tracer(trace, (seconds - trace_s) / 2,
                             (seconds + trace_s) / 2)
    out = serve_engine._serve(cfg, fns, cfgd, eng_cfg, work, seed, warm_s,
                              seconds, tr, peak)
    out["setup_s"] = out.pop("t_open") - t_start
    gc.collect()                       # the engine and its state are gone
    out["checks"], out["check_info"] = check(
        cell, seed, out.pop("finished"), eng_cfg["max_len"], control)
    return out


def check(cell, seed, finished, max_len, control=False):
    """The longest finished request and a sample drawn from the seed (the
    same requests serve_engine's check compares), token by token against
    the reference. `check_info` keeps the program's readings, and the
    control's beside them."""
    lim = cell.limits
    if not finished:
        return ({"no_request_finished": {"value": 1, "limit": 0}},
                {"reason": "no request finished"})
    wrong = sum(len(g) != min(b, max_len - len(p)) for p, g, b in finished)
    rng = generator.run_rng(seed, 3)
    longest = max(range(len(finished)), key=lambda k: len(finished[k][1]))
    k = min(int(cell.traffic["check"]["sample"]), len(finished) - 1)
    rest = [j for j in range(len(finished)) if j != longest]
    pick = [longest] + list(rng.choice(rest, k, replace=False))
    fam = cells.reference(cell.config["family"])
    w = fam.make_weights(cell.config, seed)
    gaps, cgaps = [], []
    for j in pick:
        p, g, _ = finished[j]
        out = fam.served_gaps(w, cell.config, p, g, max_len, control)
        gaps.append(out["gap"])
        if control:
            cgaps.append(out["control_gap"])
    del w
    above = float(lim["off_best_share"]["above"])
    gaps = np.concatenate(gaps)
    info = {"requests_compared": len(pick), "tokens_compared": int(gaps.size),
            "nonzero_gaps": int((gaps > 0).sum()),
            "program_widest_gap": float(gaps.max()),
            "program_off_best_share": float((gaps > above).mean())}
    judged = gaps
    if control:
        judged = np.concatenate(cgaps)
        info["control_widest_gap"] = float(judged.max())
        info["control_off_best_share"] = float((judged > above).mean())
    checks = {"off_best_share": {"value": float((judged > above).mean()),
                                 "limit": lim["off_best_share"]["limit"]},
              "widest_gap": {"value": float(judged.max()),
                             "limit": lim["widest_gap"]["limit"]},
              "token_count": {"value": int(wrong), "limit": 0}}
    return checks, info
