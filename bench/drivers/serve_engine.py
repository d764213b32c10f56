"""Serving cells: drive `ServingEngine.submit` / `ServingEngine.step` with a
mix from bench/generator.py, as `repro.launch.serve` builds the engine.

One run: make the weights from the seed on the device; build the engine;
run one request per prefill bucket the mix uses, so every program the
window drives is compiled or loaded; offer the mix for `warm_s` seconds so
slot ages are staggered; then measure for `--seconds`. After the window:
read the device's peak memory, free the engine, and compare a sample of the
finished requests with the plain reference (bench/references/).

Besides the times of its own loop, a run keeps what the program records
about itself: the engine's `stats` counters and `trace_count()` at the
window's open and close, the requests' `admitted_at` stamps, and, traced,
the device time of each program by named scope (bench/scopes.py). The run
context that bench/metrics/ reads holds their reductions.

Traffic keys read here (besides the generator's): `engine` (EngineConfig
fields: slots, max_len, decode_block, page_size, pool_pages, prefix_cache,
min_bucket), `warm_s`, `trace_s`, `check.sample`.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time
from dataclasses import dataclass

import jax
import numpy as np

from bench import cells, e2e, generator, scopes, trace_reduce
from bench.counts import least_time_s
from bench.e2e import Timeline

clock = time.perf_counter


# --------------------------------------------------------------------------
# building the program's objects
# --------------------------------------------------------------------------
def program_config(cfgd: dict):
    """The program's config for this configuration file: the registry's
    published config with the file's differing sizes applied, then checked
    key by key against the file."""
    from repro.models import registry

    cfg = registry.get_config(cfgd["registry_id"])
    fields = set(cfg.__dataclass_fields__)
    over = {k: v for k, v in cfgd.items()
            if k in fields and k != "head_dim" and getattr(cfg, k) != v}
    cfg = registry.get_config(cfgd["registry_id"], **over)
    bad = {k: (getattr(cfg, k), v) for k, v in cfgd.items()
           if k in fields and k != "head_dim" and getattr(cfg, k) != v}
    if cfg.hd != cfgd["head_dim"]:
        bad["head_dim"] = (cfg.hd, cfgd["head_dim"])
    if bad:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg, registry.model_fns(cfg)


def program_weights(cfgd: dict, cfg, fns, seed: int):
    """The weights, made on the device in one jitted call, in the tree and
    dtype the program's own initialiser gives."""
    fam = cells.reference(cfgd["family"])
    want = jax.eval_shape(lambda k: fns.init(k, cfg), jax.random.PRNGKey(0))
    got = jax.eval_shape(fam.weights_fn(cfgd, cfg.pdtype),
                         fam.seed_key(0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree no longer matches "
                         f"bench/references/{cfgd['family']}.py")
    return fam.make_weights(cfgd, seed, cfg.pdtype)


def engine_config(eng: dict):
    from repro.serving import EngineConfig

    return EngineConfig(max_batch=eng["slots"], max_len=eng["max_len"],
                        decode_block=eng["decode_block"],
                        min_bucket=eng.get("min_bucket", 16),
                        page_size=eng.get("page_size", 0),
                        pool_pages=eng.get("pool_pages"),
                        prefix_cache=eng.get("prefix_cache", 0))


# --------------------------------------------------------------------------
# tracing
# --------------------------------------------------------------------------
class Tracer:
    """Profiler on for [start_at, stop_at) of the window, with host spans
    around the steps of the benchmark's own loop (`bench.*`; the program
    opens its own, `engine.*`, inside them). Off: no spans at all."""

    def __init__(self, on: bool, start_at: float, stop_at: float):
        self.on, self.start_at, self.stop_at = on, start_at, stop_at
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self.t_on = self.t_off = None
        self._span = None

    def span(self, name):
        return jax.profiler.TraceAnnotation(name) if self.on \
            else contextlib.nullcontext()

    def next_event(self):
        """When the profiler next starts or stops (inf: never)."""
        if not self.on or self.t_off is not None:
            return float("inf")
        return self.start_at if self.t_on is None else self.stop_at

    def tick(self, now):
        if not self.on:
            return
        if self.t_on is None and now >= self.start_at:
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            self._span.__enter__()
            self.t_on = clock()
        elif self.t_on is not None and self.t_off is None \
                and now >= self.stop_at:
            self.stop()

    def stop(self):
        if self.t_on is None or self.t_off is not None:
            return
        self.t_off = clock()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self):
        """The trace reduction (bench/trace_reduce.py) with each program's
        device time by named scope (bench/scopes.py) as `scopes`; None
        untraced or where the trace holds no device operation."""
        if self.t_off is None:
            return None
        try:
            tr = scopes.load(self.dir)
            red = trace_reduce.reduce(tr)
            return red and dict(red, scopes=scopes.reduce(tr))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# the window
# --------------------------------------------------------------------------
@dataclass
class Step:
    t0: float
    t1: float
    n_active: int                # the engine's own count of decoded slots
    seen: list                   # [(uid, tokens so far)] after the step
    least_s: float = 0.0         # roofline time of its decode sub-steps
    model_flops: float = 0.0     # prompt + output tokens, no padding
    substeps: int = 0            # decode sub-steps that had work


def _warm(eng, work, seed):
    """One request per prefill bucket the mix uses, each long enough to
    reach a decode block: every program of the window runs once."""
    from repro.serving import Request

    buckets = eng.buckets()
    longest = {}
    for n in work.prompt_lens:
        b = next(b for b in buckets if n <= b)
        longest[b] = max(longest.get(b, 0), int(n))
    rng = generator.run_rng(seed, 2)
    for k, n in enumerate(sorted(longest.values())):
        eng.submit(Request(uid=-1 - k, max_new_tokens=2,
                           prompt=rng.integers(0, work.vocab, n,
                                               dtype=np.int32)))
    eng.run()
    jax.block_until_ready(eng.cache)
    eng.finished.clear()


def snapshot(eng):
    """The engine's counters, and how many programs it has compiled."""
    return dict(eng.stats), eng.trace_count()


def drive(eng, work, warm_s, seconds, tr):
    """Offer `work` from now; measure [now + warm_s, now + warm_s +
    seconds). The loop only records; `replay` does the arithmetic."""
    from repro.serving import Request

    reqs, arrivals, steps = [], [], []
    t0 = clock()
    t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
    tr.start_at += t_open
    tr.stop_at += t_open
    i, n_req, refused = 0, len(work), 0
    queue_open = opened = None

    def submit(k, arrival):
        nonlocal refused
        req = Request(uid=len(reqs), prompt=work.prompt(k),
                      max_new_tokens=int(work.output_lens[k]))
        reqs.append(req)
        arrivals.append(arrival)
        try:
            eng.submit(req)
        except ValueError:
            refused += 1

    while True:
        now = clock()
        if now >= t_close:
            break
        tr.tick(now)
        if queue_open is None and now >= t_open:
            queue_open, opened = len(eng.queue), snapshot(eng)
        with tr.span("bench.submit"):
            if work.backlog:
                while len(eng.queue) < work.backlog:
                    submit(i % n_req, now)
                    i += 1
            else:
                while i < n_req and t0 + work.arrival_s[i] <= now:
                    submit(i, t0 + work.arrival_s[i])
                    i += 1
        if not eng.queue and all(s is None for s in eng.slots):
            wake = min(t0 + work.arrival_s[i] if i < n_req else t_close,
                       t_close, tr.next_event())
            with tr.span("bench.wait"):
                time.sleep(max(0.0, wake - clock()))
            continue
        n_fin = len(eng.finished)
        ts = clock()
        with tr.span("bench.step"):
            n_active = eng.step()
        te = clock()
        with tr.span("bench.record"):
            seen = [(r.uid, len(r.generated)) for r in eng.slots
                    if r is not None]
            seen += [(r.uid, len(r.generated)) for r in eng.finished[n_fin:]]
        steps.append(Step(ts, te, n_active, seen))
    t_end = clock()
    tr.stop()
    return {"reqs": reqs, "arrivals": arrivals, "steps": steps,
            "t_open": t_open, "t_end": t_end, "refused": refused,
            "queue": (queue_open, len(eng.queue)),
            "opened": opened, "closed": snapshot(eng),
            "traced": (tr.t_on, tr.t_off)}


def queue_waits(reqs, arrivals, t_open, t_end):
    """Seconds from scheduled arrival to admission of each request admitted
    in [t_open, t_end)."""
    return [r.admitted_at - a for r, a in zip(reqs, arrivals)
            if getattr(r, "admitted_at", None) is not None
            and t_open <= r.admitted_at < t_end]


def counts_in_window(opened, closed):
    """{counter: growth over the window} of every engine counter, from the
    snapshots at the window's open and close; None where it never opened."""
    if opened is None:
        return None
    return {k: closed[0][k] - v for k, v in opened[0].items()}


def compiles_in_window(opened, closed):
    """Programs compiled between the window's open and close, or None where
    the window never opened or the engine cannot count them."""
    if opened is None or min(opened[1], closed[1]) < 0:
        return None
    return closed[1] - opened[1]


def replay(run_, cfgd, peak):
    """Timelines per request, and per step the roofline time and model
    operations of the work it did, from the recorded token counts, counted
    by the configuration's family (bench/counts/<family>.py)."""
    counts = cells.counts(cfgd["family"])
    reqs = run_["reqs"]
    tls = [Timeline(a) for a in run_["arrivals"]]
    have = [0] * len(reqs)
    for st in run_["steps"]:
        per_sub = {}
        for uid, n in st.seen:
            k = have[uid]
            if n <= k:
                continue
            tls[uid].deliveries.append((st.t1, n - k))
            p = len(reqs[uid].prompt)
            if k == 0:
                st.model_flops += counts.prefill_flops(cfgd, p)
            start = max(k, 1)
            for j in range(start, n):
                per_sub.setdefault(j - start, []).append(p + j)
                st.model_flops += counts.token_flops(cfgd, p + j)
            have[uid] = n
        st.substeps = len(per_sub)
        st.least_s = sum(
            least_time_s(*counts.decode_substep(cfgd, c), peak)
            for c in per_sub.values())
    return tls


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        peak: dict, control: bool = False) -> dict:
    cfgd, traffic = cell.config, cell.traffic
    eng_cfg = traffic["engine"]
    warm_s = float(traffic["warm_s"])
    cfg, fns = program_config(cfgd)
    work = generator.make(traffic, seed, warm_s + seconds + 5.0,
                          cfgd.get("token_vocab", cfgd["vocab_size"]))
    trace_s = min(float(traffic.get("trace_s", 3.0)), seconds)
    tr = Tracer(trace, (seconds - trace_s) / 2, (seconds + trace_s) / 2)
    out = _serve(cfg, fns, cfgd, eng_cfg, work, seed, warm_s, seconds, tr,
                 peak)
    out["setup_s"] = out.pop("t_open") - t_start
    gc.collect()                       # the engine and its state are gone
    out["checks"], out["check_info"] = check(
        cell, seed, out.pop("finished"), eng_cfg["max_len"], control)
    return out


def _serve(cfg, fns, cfgd, eng_cfg, work, seed, warm_s, seconds, tr, peak):
    from repro.serving import ServingEngine

    params = program_weights(cfgd, cfg, fns, seed)
    eng = ServingEngine(cfg, fns, params, engine_config(eng_cfg))
    del params
    _warm(eng, work, seed)
    run_ = drive(eng, work, warm_s, seconds, tr)
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    del eng
    tls = replay(run_, cfgd, peak)
    t_open, t_end = run_["t_open"], run_["t_end"]
    win = t_end - t_open
    steps = run_["steps"]
    in_win = [s for s in steps if t_open < s.t1 <= t_end]
    traced = [s for s in steps if tr.t_on is not None
              and s.t0 >= tr.t_on and s.t1 <= tr.t_off and s.substeps]
    vals = {"serve_tok_s": e2e.tokens_in(tls, t_open, t_end) / win}
    if not work.backlog:
        ttft = e2e.ttft(tls, t_open, t_end)
        tpot = e2e.tpot(tls, t_open, t_end)
        if ttft:
            vals["ttft_p90_ms"] = e2e.percentile(ttft, 90) * 1e3
        if tpot:
            vals["tpot_p90_ms"] = e2e.percentile(tpot, 90) * 1e3
        attempted = len(ttft)
    else:
        attempted = sum(1 for tl in tls if any(
            t_open <= t <= t_end for t, _ in tl.deliveries))
    decode_steps = [s for s in in_win if s.n_active]
    opened, closed = run_["opened"], run_["closed"]
    ctx = {
        "config": cfgd, "peak": peak, "trace": tr.reduce(),
        "decode_block": eng_cfg["decode_block"], "window_s": win,
        "occupancy": (float(np.mean([s.n_active for s in decode_steps]))
                      / eng_cfg["slots"]) if decode_steps else None,
        "model_flops": sum(s.model_flops for s in in_win),
        "traced_blocks": len(traced),
        "traced_least_s": sum(s.least_s for s in traced),
        "queue_open_close": run_["queue"],
        "queue_waits": queue_waits(run_["reqs"], run_["arrivals"], t_open,
                                   t_end),
        "counters": counts_in_window(opened, closed),
        "compiles_in_window": compiles_in_window(opened, closed),
        "arrived": int(sum(t_open <= a < t_end for a in run_["arrivals"])),
        "completed": sum(1 for r, tl in zip(run_["reqs"], tls) if r.done
                         and t_open <= tl.deliveries[-1][0] <= t_end),
    }
    finished = [(r.prompt, list(r.generated), r.max_new_tokens)
                for r in run_["reqs"] if r.done]
    return {"e2e": vals, "ctx": ctx, "attempted": attempted,
            "failed": run_["refused"], "memory_peak_bytes": int(mem),
            "finished": finished, "t_open": t_open, "record": run_}


# --------------------------------------------------------------------------
# correct: served tokens against the plain reference
# --------------------------------------------------------------------------
def check(cell, seed, finished, max_len, control=False):
    """Compare a sample of the finished requests, drawn from the seed, with
    the longest among them, token by token with the reference.

    Numbers compared (each against bench/limits/<cell>.json):
      widest_gap    largest gap, over every served token of the sample, by
                    which its reference logit lies below the reference's
                    best at that position
      token_count   finished requests whose token count is not
                    min(budget, max_len - prompt length); limit 0

    With `control`, the token the control puts first at each compared
    position stands in the program's: `widest_gap` reads the control's, so
    a sound limit makes that run not correct. `check_info` keeps both.
    """
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
    gaps = np.concatenate(gaps)
    info = {"requests_compared": len(pick), "tokens_compared": int(gaps.size),
            "nonzero_gaps": int((gaps > 0).sum()),
            "program_widest_gap": float(gaps.max())}
    judged = gaps
    if control:
        judged = np.concatenate(cgaps)
        info["control_widest_gap"] = float(judged.max())
    checks = {"widest_gap": {"value": float(judged.max()),
                             "limit": lim["widest_gap"]["limit"]},
              "token_count": {"value": int(wrong), "limit": 0}}
    return checks, info
