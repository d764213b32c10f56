"""Measured CPU micro-benchmark for the tuple-space serving grid.

Four phases on the same smoke model and workload distribution:

  1. single engine — the one-pod baseline (same per-pod slot count);
  2. grid, clean — N replicas behind the session grid, all pods alive,
     warm-standby replication running in the background;
  3. grid + chaos — the SAME repeated strike/repair schedule drives pod
     outages mid-run; failovers pointer-flip to the warm standbys and
     rejoins trigger background rebalancing;
  4. full-drain + chaos — the identical chaos schedule replayed against
     a plane with replication disabled (GridConfig(replicate=False), the
     PR 5 behavior): every failover pays the full export/import drain;
  5. mixed-arch + chaos — transformer pods and recurrent-carry (RG-LRU)
     pods behind ONE router (two arch groups), same strike grammar:
     failover and replication resolve within each group, carry standbys
     ship the whole O(1) state per sync and are always flip-ready.

The headline number is the FAILOVER STALL: wall time spent inside the
router's failover phase on ticks that moved >= 1 slot (device work
forced to completion on both edges, so a pointer flip's import-only
scatter and a drain's full-width export + import are compared on equal
terms — see ConstellationRouter.failover_stalls), p50/p99, grid vs
full-drain, on a bit-identical outage history
(`failover_p50_impact_vs_full_drain` < 1 means the pointer flip beats
the drain). The grid's invariants are CHECKED, not just recorded: both
chaos phases must complete every request (zero drops), the grid phase
must actually pointer-flip and rebalance, and the drain phase must
actually full-migrate. Replication incrementality is recorded as delta
rows shipped vs what full re-exports would have shipped every sync.
Absolute tok/s on the shared CPU is noise; the signal is the ratios and
the accounting. Results land in BENCH_fleet.json (repo root).
"""
import json
import os
import time

import jax
import numpy as np

from repro.models import registry
from repro.serving import (ConstellationRouter, EngineConfig, GridConfig,
                           Request, ServingEngine, parse_outage_spec)

REPLICAS = 3
SLOTS = 2                # per replica
MAX_LEN = 64
MAX_NEW = 24
N_REQUESTS = 24
CHAOS = "2:*:3,6:*:3,10:*:3"     # three strike/repair cycles, busiest pod


def _requests(cfg, rng, n=N_REQUESTS, arch=None, uid0=0):
    return [Request(uid=uid0 + i,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(4, 40))).astype(np.int32),
                    max_new_tokens=MAX_NEW,
                    temperature=0.0 if i % 2 == 0 else 0.8,
                    arch=arch)
            for i in range(n)]


def _drain(plane, reqs):
    """Submit + run to completion, timing each router step and tagging
    the steps in which >= 1 slot failed over. Returns (finished, dt_s,
    step_times_s, failover_times_s, tokens)."""
    is_plane = isinstance(plane, ConstellationRouter)
    tok0 = (sum(e.stats["tokens"] for e in plane.engines)
            if is_plane else plane.stats["tokens"])
    n0 = len(plane.finished)
    for r in reqs:
        plane.submit(r)
    steps_s, failover_s = [], []
    t0 = time.time()
    while plane.queue or any(s is not None for s in plane.slots) or (
            is_plane and any(e.queue for e in plane.engines)):
        m0 = plane.stats["migrated_slots"] if is_plane else 0
        t1 = time.perf_counter()
        n = plane.step()
        dt_step = time.perf_counter() - t1
        if is_plane and plane.stats["migrated_slots"] > m0:
            failover_s.append(dt_step)
        elif n:
            steps_s.append(dt_step)
    dt = time.time() - t0
    tok1 = (sum(e.stats["tokens"] for e in plane.engines)
            if is_plane else plane.stats["tokens"])
    return plane.finished[n0:], dt, steps_s, failover_s, tok1 - tok0


def _warm_engine(eng, cfg):
    """Compile every prefill bucket + the decode block on one engine, so
    the timed phases measure steady state, not first-touch compiles."""
    for j, n in enumerate((5, 20, 40)):               # buckets 16/32/64
        eng.submit(Request(uid=-1 - j,
                           prompt=np.arange(n, dtype=np.int32) % 7,
                           max_new_tokens=2, temperature=0.5))
    eng.run()
    eng.finished.clear()


def _wipe(engines):
    """Hygiene between routers sharing engines: deactivate every device
    row (a run that ends while a pod is still masked leaves its stale
    flipped-away rows pending a rejoin wipe that never came)."""
    for e in engines:
        e.clear_rows(list(range(e.ecfg.max_batch)))
        e.finished.clear()


def _p(v, q):
    return float(np.percentile(v, q) * 1e3) if v else 0.0


def run():
    cfg = registry.get_reduced_config("suncatcher-lm-100m")
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    ecfg = EngineConfig(max_batch=SLOTS, max_len=MAX_LEN, decode_block=8)
    # each phase is warmed by replaying its own request distribution
    # (identical seed => identical placement, strikes, and traces), so the
    # timed pass is pure steady state

    # ---- phase 1: single-engine (one-pod) baseline ---------------------
    single = ServingEngine(cfg, fns, params, ecfg)
    _warm_engine(single, cfg)
    _, dt_1, steps_1, _, tok_1 = _drain(
        single, _requests(cfg, np.random.default_rng(1)))

    # ---- phase 2: grid, all pods alive ---------------------------------
    engines = [ServingEngine(cfg, fns, params, ecfg)
               for _ in range(REPLICAS)]
    for e in engines:
        _warm_engine(e, cfg)
    _drain(ConstellationRouter(engines),       # warm the replication jits
           _requests(cfg, np.random.default_rng(2)))
    _wipe(engines)
    plane = ConstellationRouter(engines)
    _, dt_p, steps_p, _, tok_p = _drain(
        plane, _requests(cfg, np.random.default_rng(2)))

    # ---- phase 3: grid + chaos (warm the failover traces first) --------
    _wipe(engines)
    _drain(ConstellationRouter(engines,
                               forced_outage=parse_outage_spec(CHAOS)),
           _requests(cfg, np.random.default_rng(3)))
    _wipe(engines)
    grid = ConstellationRouter(engines,
                               forced_outage=parse_outage_spec(CHAOS))
    done_g, dt_g, steps_g, _, tok_g = _drain(
        grid, _requests(cfg, np.random.default_rng(3)))
    fail_g = grid.failover_stalls

    # ---- phase 4: full-drain + the SAME chaos schedule -----------------
    _wipe(engines)
    _drain(ConstellationRouter(engines,
                               forced_outage=parse_outage_spec(CHAOS),
                               grid=GridConfig(replicate=False)),
           _requests(cfg, np.random.default_rng(4)))
    _wipe(engines)
    drain = ConstellationRouter(engines,
                                forced_outage=parse_outage_spec(CHAOS),
                                grid=GridConfig(replicate=False))
    done_d, dt_d, steps_d, _, tok_d = _drain(
        drain, _requests(cfg, np.random.default_rng(4)))
    fail_d = drain.failover_stalls

    # ---- phase 5: mixed-arch plane (KV + carry groups) + chaos ---------
    rcfg = registry.get_reduced_config("recurrentgemma-2b")
    rfns = registry.model_fns(rcfg)
    rparams = rfns.init(jax.random.PRNGKey(0), rcfg)
    r_engines = [ServingEngine(rcfg, rfns, rparams, ecfg)
                 for _ in range(2)]
    for e in r_engines:
        _warm_engine(e, rcfg)
    _wipe(engines)
    mixed_engines = engines[:2] + r_engines

    def _mixed_reqs(seed):
        rng = np.random.default_rng(seed)
        kv = _requests(cfg, rng, n=N_REQUESTS // 2, arch=cfg.name)
        carry = _requests(rcfg, rng, n=N_REQUESTS // 2, arch=rcfg.name,
                          uid0=1000)
        return [r for pair in zip(kv, carry) for r in pair]

    _drain(ConstellationRouter(mixed_engines,
                               forced_outage=parse_outage_spec(CHAOS)),
           _mixed_reqs(5))                      # warm the mixed plane
    _wipe(mixed_engines)
    mixed = ConstellationRouter(mixed_engines,
                                forced_outage=parse_outage_spec(CHAOS))
    done_m, dt_m, steps_m, _, tok_m = _drain(mixed, _mixed_reqs(5))
    occ = mixed.plane_stats()["arch_occupancy"]

    # the contracts the grid exists for — checked, not just recorded
    if len(done_g) != N_REQUESTS or len(done_d) != N_REQUESTS:
        raise RuntimeError(
            f"chaos dropped requests: grid {len(done_g)}/{N_REQUESTS}, "
            f"full-drain {len(done_d)}/{N_REQUESTS}")
    if grid.stats["pointer_flips"] < 1:
        raise RuntimeError("grid chaos run produced no pointer flips")
    if grid.stats["rebalanced_slots"] < 1:
        raise RuntimeError("grid chaos run produced no rebalances")
    if drain.stats["migrated_slots"] < 1 or drain.stats["pointer_flips"]:
        raise RuntimeError("full-drain phase did not drain-migrate")
    if len(done_m) != N_REQUESTS or mixed.dropped:
        raise RuntimeError(
            f"mixed-arch chaos dropped requests: {len(done_m)}/"
            f"{N_REQUESTS}")
    if mixed.stats["pointer_flips"] < 1:
        raise RuntimeError("mixed-arch chaos run produced no pointer flips")
    if set(occ) != {cfg.name, rcfg.name}:
        raise RuntimeError(f"mixed plane lost an arch group: {set(occ)}")

    g50, g99 = _p(fail_g, 50), _p(fail_g, 99)
    d50, d99 = _p(fail_d, 50), _p(fail_d, 99)
    extras = {
        "replicas": REPLICAS,
        "slots_per_replica": SLOTS,
        "chaos_schedule": CHAOS,
        "single_tokens_per_s": round(tok_1 / dt_1, 1),
        "plane_tokens_per_s": round(tok_p / dt_p, 1),
        "grid_chaos_tokens_per_s": round(tok_g / dt_g, 1),
        "full_drain_chaos_tokens_per_s": round(tok_d / dt_d, 1),
        "single_p50_step_ms": round(_p(steps_1, 50), 2),
        "plane_p50_step_ms": round(_p(steps_p, 50), 2),
        # the replicas time-share ONE CPU here, so ~1.0 means the router
        # adds negligible orchestration overhead — horizontal scaling
        # needs real per-pod devices, which this container doesn't have
        "plane_throughput_ratio_vs_single": round(
            (tok_p / dt_p) / (tok_1 / dt_1), 2),
        # failover stall: duration of router ticks that moved >= 1 slot
        "grid_failover_p50_stall_ms": round(g50, 2),
        "grid_failover_p99_stall_ms": round(g99, 2),
        "full_drain_failover_p50_stall_ms": round(d50, 2),
        "full_drain_failover_p99_stall_ms": round(d99, 2),
        "failover_p50_impact_vs_full_drain": round(g50 / d50, 2)
        if d50 else 0.0,
        "grid_failover_events": len(fail_g),
        "full_drain_failover_events": len(fail_d),
        "grid_pointer_flips": grid.stats["pointer_flips"],
        "grid_full_migrations": grid.stats["full_migrations"],
        "grid_rebalanced_slots": grid.stats["rebalanced_slots"],
        "full_drain_migrated_slots": drain.stats["migrated_slots"],
        # replication incrementality: delta rows actually shipped vs what
        # full per-sync re-exports would have shipped
        "grid_replicated_rows": grid.stats["replicated_rows"],
        "grid_full_rows_equiv": grid.stats["full_rows_equiv"],
        "replication_savings_ratio": round(
            grid.stats["replicated_rows"]
            / max(grid.stats["full_rows_equiv"], 1), 3),
        "masked_pod_ticks": grid.stats["masked_pod_ticks"],
        "zero_drops_under_chaos": True,
        "traces": grid.trace_count(),
        # mixed-arch phase: two DecodeState families behind one router
        "mixed_archs": "+".join(sorted(occ)),
        "mixed_chaos_tokens_per_s": round(tok_m / dt_m, 1),
        "mixed_p50_step_ms": round(_p(steps_m, 50), 2),
        "mixed_pointer_flips": mixed.stats["pointer_flips"],
        "mixed_full_migrations": mixed.stats["full_migrations"],
        "mixed_replicated_rows": mixed.stats["replicated_rows"],
        "mixed_full_rows_equiv": mixed.stats["full_rows_equiv"],
        "mixed_arch_occupancy": occ,
        "mixed_zero_drops_under_chaos": True,
    }
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_fleet.json"), "w") as f:
        json.dump(extras, f, indent=2)
        f.write("\n")

    out = [
        ("fleet_grid_tokens_per_s", dt_p * 1e6,
         f"{tok_p / dt_p:.0f} tok/s on {REPLICAS}x{SLOTS} slots, p50 "
         f"step {_p(steps_p, 50):.1f} ms "
         f"({extras['plane_throughput_ratio_vs_single']}x one pod on a "
         f"time-shared CPU)"),
        ("fleet_single_pod_baseline", dt_1 * 1e6,
         f"{tok_1 / dt_1:.0f} tok/s on 1x{SLOTS} slots, p50 step "
         f"{_p(steps_1, 50):.1f} ms"),
        ("fleet_grid_chaos_failover", dt_g * 1e6,
         f"chaos '{CHAOS}': zero drops, "
         f"{grid.stats['pointer_flips']} pointer flips + "
         f"{grid.stats['full_migrations']} full drains, "
         f"{grid.stats['rebalanced_slots']} rebalanced, failover stall "
         f"p50 {g50:.1f} ms"),
        ("fleet_full_drain_chaos_baseline", dt_d * 1e6,
         f"same chaos, replication off: {drain.stats['migrated_slots']} "
         f"slots full-drained, failover stall p50 {d50:.1f} ms (grid = "
         f"{extras['failover_p50_impact_vs_full_drain']}x of this)"),
        ("fleet_mixed_arch_chaos", dt_m * 1e6,
         f"{extras['mixed_archs']} on one router, chaos '{CHAOS}': zero "
         f"drops, {mixed.stats['pointer_flips']} pointer flips + "
         f"{mixed.stats['full_migrations']} full drains, "
         f"{tok_m / dt_m:.0f} tok/s"),
    ]
    return out, extras


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for row in run()[0]:
        print(row)
