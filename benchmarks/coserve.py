"""Measured CPU micro-benchmark for serving/training co-residency.

One process, one serving engine: a serve-only phase (no training) is
measured first, then the SAME engine — same compiled traces — serves an
identical workload while DiLoCo rounds run under the supervisor and the
rollback-aware publisher hot-swaps the outer params into it. Reported:
serving tokens/s and p50 fused-block latency in both phases, the number
of live param swaps, and the engine trace counts before/after co-residency
(the swap invariant: flat — every swap is a jit cache hit).

Co-resident tokens/s is reported two ways:
  - wall-clock over the whole phase (training rounds included): on this
    single shared CPU it is the honest "what does a user see while the
    cluster trains" number, not an isolated serving figure;
  - per engine-active second (time actually spent inside eng.step()):
    this separates "the engine shares the device with training" (low
    engine_active_fraction, wall-clock ratio far below 1) from "the
    engine itself got slower" (active-second ratio below 1).
The smoke config is deliberately tiny so the quantity measured is the
orchestration overhead, not model FLOPs. Results land in
BENCH_coserve.json (repo root) next to the serve/train baselines.
"""
import json
import os
import tempfile
import time

import jax
import numpy as np

from repro.models import registry
from repro.serving import EngineConfig, Request, ServingEngine
from repro.train import (AdamWConfig, DataConfig, DiLoCoConfig,
                         DiLoCoSupervisor, FTConfig, ParamPublisher,
                         PublishConfig, SyntheticLM, TrainConfig,
                         diloco_init, make_diloco_round,
                         snapshot_global_params)

N_PODS = 2
H = 4
SEQ_LEN = 8
BATCH = 2                # training batch per pod
SLOTS = 2
MAX_LEN = 64
MAX_NEW = 12
N_REQUESTS = 8
ROUNDS = 8               # timed co-resident rounds


def _bench_setup():
    cfg = registry.get_reduced_config(
        "suncatcher-lm-100m", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=64, vocab_size=256)
    fns = registry.model_fns(cfg)
    tcfg = TrainConfig(adamw=AdamWConfig(), warmup_steps=2,
                       total_steps=1000)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=SEQ_LEN, global_batch=BATCH))
    dcfg = DiLoCoConfig(n_pods=N_PODS, inner_steps=H)
    return cfg, fns, tcfg, data, dcfg


def _requests(cfg, rng, n=N_REQUESTS):
    return [Request(uid=i,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(4, 24))).astype(np.int32),
                    max_new_tokens=MAX_NEW)
            for i in range(n)]


class _Timed:
    """Wraps engine.step() timing: p50 over fused blocks that decoded,
    plus total engine-active seconds (ALL time inside step())."""

    def __init__(self, eng):
        self.eng = eng
        self.block_s = []
        self.active_s = 0.0

    def step(self):
        t0 = time.perf_counter()
        n = self.eng.step()
        dt = time.perf_counter() - t0
        self.active_s += dt
        if n:
            self.block_s.append(dt)

    def drain(self, reqs):
        for r in reqs:
            self.eng.submit(r)
        while self.eng.queue or any(s is not None for s in self.eng.slots):
            self.step()

    def reset(self):
        self.block_s.clear()
        self.active_s = 0.0


def run():
    cfg, fns, tcfg, data, dcfg = _bench_setup()
    d_state = diloco_init(fns.init(jax.random.PRNGKey(0), cfg), dcfg,
                          screen_window=32)
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                            screen_window=32, supervise=True)
    eng = ServingEngine(cfg, fns, snapshot_global_params(d_state),
                        EngineConfig(max_batch=SLOTS, max_len=MAX_LEN,
                                     decode_block=8))
    rng = np.random.default_rng(0)

    # ---- serve-only baseline (same engine, same compiled traces) -------
    timer = _Timed(eng)
    timer.drain(_requests(cfg, rng))          # warm: compile buckets+decode
    timer.reset()
    tokens0 = eng.stats["tokens"]
    t0 = time.time()
    timer.drain(_requests(cfg, rng))
    dt_serve = time.time() - t0
    toks_serve = eng.stats["tokens"] - tokens0
    serve_tps = toks_serve / dt_serve
    serve_tps_active = toks_serve / timer.active_s
    p50_serve = float(np.percentile(timer.block_s, 50) * 1e3)

    # ---- co-resident: identical workload while DiLoCo rounds run -------
    with tempfile.TemporaryDirectory() as d:
        ft = FTConfig(checkpoint_dirs=(os.path.join(d, "a"),),
                      checkpoint_every=2 * H)
        publisher = ParamPublisher(eng.swap_params, PublishConfig())
        sup = DiLoCoSupervisor(rnd, d_state, dcfg, ft, publisher=publisher)
        sup.run(1)                            # warm the fused round jit
        traces0 = eng.trace_count()
        timer.reset()
        tokens0 = eng.stats["tokens"]
        swaps0 = eng.stats["swaps"]
        t0 = time.time()
        pending = _requests(cfg, rng)

        def pump(_sup):
            while pending and len(eng.queue) < SLOTS:
                eng.submit(pending.pop(0))
            for _ in range(2):
                if not (eng.queue
                        or any(s is not None for s in eng.slots)):
                    break
                timer.step()

        sup.run(1 + ROUNDS, on_round=pump)
        timer.drain(pending)                  # drain the tail, still timed
        dt_co = time.time() - t0
    toks_co = eng.stats["tokens"] - tokens0
    co_tps = toks_co / dt_co
    co_tps_active = toks_co / timer.active_s
    active_fraction = timer.active_s / dt_co
    p50_co = float(np.percentile(timer.block_s, 50) * 1e3)
    traces1 = eng.trace_count()
    swaps = eng.stats["swaps"] - swaps0

    extras = {
        "coserve_tokens_per_s": round(co_tps, 1),
        "serve_only_tokens_per_s": round(serve_tps, 1),
        # per engine-active second: tokens over time actually spent inside
        # eng.step(). The wall-clock ratio conflates "the engine shares
        # the device with training" with "the engine got slower"; this
        # pair separates them (active ratio ~1 => the engine itself is
        # unimpaired, the wall-clock gap is pure device sharing)
        "coserve_tokens_per_engine_active_s": round(co_tps_active, 1),
        "serve_only_tokens_per_engine_active_s": round(serve_tps_active,
                                                       1),
        "engine_active_fraction": round(active_fraction, 3),
        "coserve_p50_block_ms": round(p50_co, 2),
        "serve_only_p50_block_ms": round(p50_serve, 2),
        "throughput_ratio_vs_serve_only": round(co_tps / serve_tps, 3),
        "active_throughput_ratio_vs_serve_only": round(
            co_tps_active / serve_tps_active, 3),
        "rounds": ROUNDS,
        "param_swaps": swaps,
        "published_round": publisher.published_round,
        "traces_before_swaps": traces0,
        "traces_after_swaps": traces1,
        "n_pods": N_PODS,
        "inner_steps": H,
    }
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_coserve.json"), "w") as f:
        json.dump(extras, f, indent=2)
        f.write("\n")

    out = [
        ("coserve_tokens_per_s", dt_co * 1e6,
         f"{co_tps:.0f} tok/s wall-clock ({co_tps_active:.0f}/engine-"
         f"active-s, {active_fraction:.0%} active), p50 block "
         f"{p50_co:.1f} ms while {ROUNDS} DiLoCo rounds ({N_PODS} pods "
         f"x H={H}) ran, {swaps} live param swaps"),
        ("coserve_serve_only_baseline", dt_serve * 1e6,
         f"{serve_tps:.0f} tok/s, p50 block {p50_serve:.1f} ms "
         f"(same engine, no training)"),
        ("coserve_trace_flatness", 0.0,
         f"{traces0} traces before swaps == {traces1} after "
         f"(every swap a jit cache hit)"),
    ]
    return out, extras


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for row in run()[0]:
        print(row)
