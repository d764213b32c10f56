"""Measured CPU micro-benchmark for the serving fast path.

Mixed prompt lengths, more requests than slots (continuous batching), on the
demo model's smoke config. Reports the fused device-resident engine
(decode_block=8, bucketed prefill) against a seed-style baseline loop that
round-trips to the host every token and re-jits prefill per prompt length —
the ratio is the headline "host-sync elimination" win, and host-syncs/token
plus compiled-trace counts are reported alongside.

The paged scenario then runs 10x the slot count against a page pool sized
at HALF the dense max_len footprint: KV HBM tracks live tokens (pages
allocated on demand, recycled in-scan when a row finishes), admission
gates on free pages instead of free slots, and the outputs — greedy AND
sampled rows — are asserted bit-identical to the dense engine's, slot
placement and co-batching included.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.serving import EngineConfig, Request, ServingEngine

SLOTS = 4
MAX_LEN = 64
MAX_NEW = 16
N_REQUESTS = 12

PAGED_SLOTS = 40                    # 10x the dense scenario's slot count
PAGE_SIZE = 16
# pool sized at HALF the dense engines' max_len footprint: 40 slots would
# dense-allocate 40*64 token positions; the paged pool holds 80*16 = 1280.
PAGED_POOL = PAGED_SLOTS * MAX_LEN // (2 * PAGE_SIZE)
PAGED_N = 96


def _workload(cfg, rng, lengths):
    return [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
            for n in lengths]


def _naive_serve(cfg, fns, params, prompts, decode_jit, prefill_jit):
    """The seed engine's loop shape: b=1 prefill jit per prompt length, one
    batched decode per token, and per-slot host bookkeeping (int() syncs
    against device arrays) between every token."""
    cache = fns.init_cache(cfg, SLOTS, MAX_LEN)
    cache["pos"] = jnp.zeros((SLOTS,), jnp.int32)
    queue = [{"prompt": p, "generated": []} for p in prompts]
    slots = [None] * SLOTS
    done = []
    while queue or any(s is not None for s in slots):
        for i in range(SLOTS):
            if slots[i] is None and queue:
                req = queue.pop(0)
                one = fns.init_cache(cfg, 1, MAX_LEN)
                logits, new = prefill_jit(
                    params, one, jnp.asarray(req["prompt"])[None])
                cache["k"] = cache["k"].at[:, i].set(new["k"][:, 0])
                cache["v"] = cache["v"].at[:, i].set(new["v"][:, 0])
                cache["pos"] = cache["pos"].at[i].set(len(req["prompt"]))
                req["generated"].append(int(jnp.argmax(logits[0])))
                slots[i] = req
        last = np.zeros((SLOTS,), np.int32)
        for i, req in enumerate(slots):
            if req is not None:
                last[i] = req["generated"][-1]
        next_tok, cache = decode_jit(params, cache, jnp.asarray(last))
        next_np = np.asarray(next_tok)                 # host sync per token
        for i, req in enumerate(slots):
            if req is None:
                continue
            req["generated"].append(int(next_np[i]))
            if len(req["generated"]) >= MAX_NEW \
                    or int(cache["pos"][i]) + 1 >= MAX_LEN:  # per-slot sync
                done.append(req)
                slots[i] = None
    return done


def run():
    cfg = registry.get_reduced_config("suncatcher-lm-100m")
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)

    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=SLOTS, max_len=MAX_LEN,
                                     decode_block=8))

    def fused(prompts):
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=MAX_NEW))
        eng.run()
        return eng

    @jax.jit
    def decode_jit(params, cache, last):
        logits, new_cache = fns.decode_step(params, cache, last[:, None],
                                            cfg)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache

    @jax.jit
    def prefill_jit(params, one, toks):     # recompiles per prompt length,
        return fns.decode_step(params, one, toks, cfg)  # like the seed

    rng = np.random.default_rng(0)
    # warm both serving loops on one workload, then time a workload with
    # FRESH prompt lengths from the same distribution. The fused engine is
    # already fully compiled (its trace count is bounded by the bucket
    # list); the seed-style loop re-jits its b=1 prefill for every distinct
    # unseen length — the compile-on-the-hot-path pathology this PR removes
    # — on top of its per-token host round-trips.
    warm = _workload(cfg, rng, rng.integers(4, 48, size=N_REQUESTS))
    prompts = _workload(cfg, rng, rng.integers(4, 48, size=N_REQUESTS))

    fused(warm)                             # compile (buckets + decode)
    tokens0 = eng.stats["tokens"]
    t0 = time.time()
    fused(prompts)
    dt_fused = time.time() - t0
    toks = eng.stats["tokens"] - tokens0

    _naive_serve(cfg, fns, params, warm, decode_jit, prefill_jit)  # compile
    t0 = time.time()
    done = _naive_serve(cfg, fns, params, prompts, decode_jit, prefill_jit)
    dt_naive = time.time() - t0

    naive_toks = sum(len(r["generated"]) for r in done)
    fused_tps = toks / dt_fused
    naive_tps = naive_toks / dt_naive
    syncs = eng.stats["host_syncs"] / max(eng.stats["tokens"], 1)

    # ---- paged high-concurrency scenario: 10x slots, half the KV HBM ----
    # Same arch, 40 slots against an 80-page pool (40 dense rows would pin
    # 2x that), a serving-shaped length mix (80% short chat turns, 20%
    # long contexts — the mix where dense rows waste the most HBM), every
    # third request sampled at temperature 0.8.  A dense engine at the
    # SAME slot count serves the identical submission order: per-request
    # PRNG keys are seq-derived, so outputs must match bit-for-bit across
    # layouts.
    def _reqs(rng2):
        lens = np.where(rng2.random(PAGED_N) < 0.8,
                        rng2.integers(4, 17, size=PAGED_N),
                        rng2.integers(32, 48, size=PAGED_N))
        return [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW,
                        temperature=0.8 if i % 3 == 0 else 0.0)
                for i, p in enumerate(_workload(cfg, rng2, lens))]

    def _serve(engine, reqs):
        for r in reqs:
            engine.submit(r)
        return {r.uid: list(r.generated) for r in engine.run()}

    paged = ServingEngine(cfg, fns, params,
                          EngineConfig(max_batch=PAGED_SLOTS,
                                       max_len=MAX_LEN, decode_block=8,
                                       page_size=PAGE_SIZE,
                                       pool_pages=PAGED_POOL))
    dense40 = ServingEngine(cfg, fns, params,
                            EngineConfig(max_batch=PAGED_SLOTS,
                                         max_len=MAX_LEN, decode_block=8))
    def _warm_reqs():                       # fresh objects per engine
        return _reqs(np.random.default_rng(7))[:2 * SLOTS]

    _serve(paged, _warm_reqs())             # compile
    t0 = time.time()
    paged_out = _serve(paged, _reqs(np.random.default_rng(11)))
    dt_paged = time.time() - t0
    paged_toks = sum(len(g) for g in paged_out.values())
    paged_tps = paged_toks / dt_paged

    _serve(dense40, _warm_reqs())
    dense_out = _serve(dense40, _reqs(np.random.default_rng(11)))
    bit_identical = paged_out == dense_out

    # untimed pass sampling device-live pages per block: KV HBM residency
    # follows live tokens instead of slot-count * max_len.
    peak_live = 0
    for r in _reqs(np.random.default_rng(13)):
        paged.submit(r)
    while paged.queue or any(s is not None for s in paged.slots):
        paged.step()
        peak_live = max(peak_live, int(jax.device_get(
            paged.spec.live_pages(paged.cache))))
    kv_ratio = (PAGED_POOL * PAGE_SIZE) / (PAGED_SLOTS * MAX_LEN)
    peak_frac = peak_live * PAGE_SIZE / (PAGED_SLOTS * MAX_LEN)
    stalls = paged.stats["admission_stalls"]

    out = [
        ("serve_fused_tokens_per_s", dt_fused * 1e6,
         f"{fused_tps:.0f} tok/s, {syncs:.3f} host-syncs/token, "
         f"{eng.trace_count()} traces (buckets={eng.buckets()})"),
        ("serve_seed_loop_tokens_per_s", dt_naive * 1e6,
         f"{naive_tps:.0f} tok/s (per-token host loop, per-length "
         f"prefill re-jit)"),
        ("serve_speedup", 0.0,
         f"{fused_tps / naive_tps:.2f}x fused over seed-style loop"),
        ("serve_paged_tokens_per_s", dt_paged * 1e6,
         f"{paged_tps:.0f} tok/s at {PAGED_SLOTS} slots "
         f"({PAGED_SLOTS // SLOTS}x) on a {PAGED_POOL}-page pool "
         f"({kv_ratio:.2f}x dense max_len KV bytes), "
         f"{stalls} admission stalls, {paged.trace_count()} traces"),
        ("serve_paged_bit_identity", 0.0,
         f"paged == dense outputs (greedy + sampled rows): "
         f"{bit_identical}; peak live pages {peak_live}/{PAGED_POOL} "
         f"({peak_frac:.2f}x dense max_len footprint)"),
    ]
    extras = {"tokens_per_s": round(fused_tps, 1),
              "seed_loop_tokens_per_s": round(naive_tps, 1),
              "speedup_vs_seed_loop": round(fused_tps / naive_tps, 2),
              "host_syncs_per_token": round(syncs, 4),
              "traces": eng.trace_count(),
              "paged_slots": PAGED_SLOTS,
              "paged_tokens_per_s": round(paged_tps, 1),
              "paged_vs_fused_tokens_ratio": round(paged_tps / fused_tps, 2),
              "paged_kv_bytes_ratio": round(kv_ratio, 3),
              "paged_peak_live_tokens_frac": round(peak_frac, 3),
              "paged_bit_identical": bool(bit_identical),
              "paged_admission_stalls": int(stalls),
              "paged_traces": paged.trace_count()}
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_serve.json"), "w") as f:
        json.dump(extras, f, indent=2)
        f.write("\n")
    return out, extras


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for row in run()[0]:
        print(row)
