"""Serving launcher: --arch <id>, device-resident continuous batching.

  PYTHONPATH=src python -m repro.launch.serve --arch suncatcher-lm-100m \
      --requests 8 --slots 4 --max-len 128 --decode-block 8

Tuple-space serving grid: --replicas N fronts N engine replicas (one per
serving pod) with a liveness-routed session grid — requests partition by
key across pods, every in-flight slot keeps a warm standby replica on a
neighbor pod (incremental background replication), and a masked pod
fails over by pointer-flipping to the standbys (full drain only as a
fallback; --full-drain disables replication for the PR 5 drain-only
plane). --serving-constellation derives the pod mask + bandwidth weights
from the orbital/ISL/radiation stack, and --force-outage-at takes a
chaos schedule `AT[:POD[:TICKS]][,...]` (POD `*` = busiest pod at strike
time, TICKS omitted = rest of run) — repeated multi-pod strike/repair
cycles, bit-deterministically replayable; the launcher asserts the
zero-drop contract, plus --expect-pointer-flip / --expect-rebalance for
the grid-specific guarantees. --waves splits the workload into
sequential waves and asserts the jit trace count stays flat after the
first (failover, rejoin-wipe, rebalance and replication must all be
cache hits by wave 2):

  PYTHONPATH=src python -m repro.launch.serve --replicas 3 --requests 9 \
      --slots 2 --max-len 64 --force-outage-at 3

  PYTHONPATH=src python -m repro.launch.serve --replicas 2 --requests 6 \
      --slots 3 --max-len 64 --waves 2 --max-new-tokens 48 \
      --force-outage-at "2:1:3,10:1:3" --expect-pointer-flip \
      --expect-rebalance

  PYTHONPATH=src python -m repro.launch.serve --replicas 2 \
      --serving-constellation --requests 8

--arch also takes a comma-separated list for a HETEROGENEOUS plane:
`--replicas N` then builds N pods PER ARCH GROUP (N >= 2 keeps same-arch
standby flips available inside every group), requests round-robin over
the groups, and the same chaos/zero-drop/flat-trace contracts apply to
the mixed plane:

  PYTHONPATH=src python -m repro.launch.serve \
      --arch suncatcher-lm-100m,recurrentgemma-2b --replicas 2 \
      --requests 8 --max-len 64 --force-outage-at "2:*:3" \
      --expect-pointer-flip

For serving WHILE training (hot-swapped DiLoCo outer params), see
repro.launch.coserve.
"""
import argparse
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.serving import (ConstellationRouter, EngineConfig, GridConfig,
                           Request, ServingEngine,
                           check_forced_outage_contract, liveness_mask_fn,
                           parse_outage_spec)


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="suncatcher-lm-100m",
                    help="arch id, or a comma-separated list for a "
                         "heterogeneous plane (--replicas pods per arch)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per replica (EngineConfig.max_batch)")
    ap.add_argument("--max-len", type=int, default=128,
                    help="KV-cache length per slot")
    ap.add_argument("--decode-block", type=int, default=8,
                    help="tokens decoded per host round-trip")
    ap.add_argument("--page-size", type=int, default=0,
                    help="paged KV cache page size in tokens (0 = dense "
                         "per-slot rows); > 0 stores KV in a shared pool "
                         "of pages behind per-row page tables so HBM "
                         "tracks live tokens, not slots x max-len")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="physical page-pool size (paged only; default "
                         "sizes the pool dense-equivalent) — undersize "
                         "it to oversubscribe slots against live tokens")
    ap.add_argument("--prefix-cache", type=int, default=0,
                    help="prefix-cache entries (paged only; 0 = off): "
                         "identical whole-page prompt heads share "
                         "physical pages via refcounts")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving-pod replicas behind the liveness router "
                         "(1 = single engine, no router)")
    ap.add_argument("--serving-constellation", action="store_true",
                    help="derive the serving pod mask + admission weights "
                         "from the orbital/ISL/radiation stack")
    ap.add_argument("--force-outage-at", type=str, default=None,
                    help="chaos schedule 'AT[:POD[:TICKS]][,...]': strike "
                         "pod POD ('*' or omitted = busiest) at router "
                         "tick AT for TICKS ticks (omitted = rest of "
                         "run); repeatable, comma-separated (requires "
                         "--replicas >= 2)")
    ap.add_argument("--full-drain", action="store_true",
                    help="disable warm-standby replication: failover "
                         "falls back to full export/import drains (the "
                         "pre-grid serving plane)")
    ap.add_argument("--repl-chunk", type=int, default=None,
                    help="KV rows shipped per slot per replication tick "
                         "(default: whole row — standby catches up in "
                         "one sync)")
    ap.add_argument("--defer-deadline", type=int, default=100,
                    help="max ticks a failover may stay deferred (frozen "
                         "on a masked pod with no capacity anywhere) "
                         "before the router raises")
    ap.add_argument("--waves", type=int, default=1,
                    help="serve the workload in N sequential waves and "
                         "require a FLAT jit trace count after wave 1")
    ap.add_argument("--expect-pointer-flip", action="store_true",
                    help="outage contract: require >= 1 pointer-flip "
                         "failover (standby promotion, not a full drain)")
    ap.add_argument("--expect-rebalance", action="store_true",
                    help="outage contract: require >= 1 rebalanced slot "
                         "after a pod rejoined")
    return ap


def build_models(archs, full: bool):
    """(cfg, fns, params) per arch id: the published config with --full,
    else the reduced one; params are random from PRNGKey(0)."""
    builds = []
    for a in archs:
        cfg = (registry.get_config(a) if full
               else registry.get_reduced_config(a))
        fns = registry.model_fns(cfg)
        builds.append((cfg, fns, fns.init(jax.random.PRNGKey(0), cfg)))
    return builds


def engine_config(args) -> EngineConfig:
    return EngineConfig(max_batch=args.slots, max_len=args.max_len,
                        decode_block=args.decode_block,
                        page_size=args.page_size,
                        pool_pages=args.pool_pages,
                        prefix_cache=args.prefix_cache)


def build_plane(builds, args):
    """Engine replicas behind a ConstellationRouter: `args.replicas` pods
    per (cfg, fns, params) build — one arch group each. Replica i lives on
    device i mod the device count, so a four-chip host gives four
    one-chip replicas."""
    ecfg = engine_config(args)
    devices = jax.devices()
    pods = [b for b in builds for _ in range(args.replicas)]
    engines = [ServingEngine(cfg, fns, params, ecfg,
                             device=devices[i % len(devices)])
               for i, (cfg, fns, params) in enumerate(pods)]
    mask_fn = None
    if args.serving_constellation:
        from repro.core.isl import ConstellationLinkModel, LivenessConfig
        mask_fn = liveness_mask_fn(ConstellationLinkModel(
            cfg=LivenessConfig(n_pods=len(engines))))
    forced = (parse_outage_spec(args.force_outage_at)
              if args.force_outage_at is not None else None)
    grid = GridConfig(replicate=not args.full_drain,
                      repl_chunk=args.repl_chunk,
                      defer_deadline=args.defer_deadline)
    return ConstellationRouter(engines, mask_fn=mask_fn,
                               forced_outage=forced, grid=grid)


def admission_summary(stats: dict, done) -> str:
    """The single engine's admission line: prefill calls and rows per call,
    the share of the prefill program's token rows that hold prompt tokens
    (the rest is padding to the bucket and to max_batch rows), and p90s of
    the finished requests' queue wait (arrival to admission) and of their
    admission to first token on the host, from the request stamps."""
    def p90_ms(xs):
        return 1e3 * float(np.percentile(xs, 90)) if xs else 0.0

    calls = stats["prefill_calls"]
    useful = stats["prefill_tokens"] / max(stats["prefill_slot_tokens"], 1)
    return (f"  admission: {calls} prefill calls, "
            f"{stats['prefill_rows'] / max(calls, 1):.1f} rows/call, "
            f"{100 * useful:.1f}% of prefill token rows useful | p90 queue "
            f"wait {p90_ms([r.admitted_at - r.arrival for r in done]):.1f}"
            f" ms, admission to first token "
            f"{p90_ms([r.first_token_at - r.admitted_at for r in done]):.1f}"
            f" ms")


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.force_outage_at is not None and args.replicas < 2:
        raise SystemExit("--force-outage-at needs --replicas >= 2 (a "
                         "one-pod group has nowhere to migrate)")

    archs = [a.strip() for a in args.arch.split(",") if a.strip()]
    for a in archs:
        if a not in registry.ARCH_IDS:
            raise SystemExit(f"unknown --arch {a!r}; known: "
                             f"{registry.ARCH_IDS}")
        if registry.input_kind(a) != "tokens":
            raise SystemExit("serve CLI demo supports token-LM archs")
    mixed = len(archs) > 1
    if mixed and args.replicas < 2:
        raise SystemExit("a mixed --arch plane needs --replicas >= 2: "
                         "standbys and failover stay inside an arch "
                         "group, so every group needs a second pod")
    builds = build_models(archs, args.full)
    cfg, fns, params = builds[0]
    if mixed or args.replicas > 1 or args.serving_constellation:
        eng = build_plane(builds, args)
    else:
        eng = ServingEngine(cfg, fns, params, engine_config(args))
    rng = np.random.default_rng(0)
    reqs = []
    for uid in range(args.requests):
        rcfg = builds[uid % len(builds)][0]
        reqs.append(Request(
            uid=uid,
            prompt=rng.integers(
                0, rcfg.vocab_size,
                size=int(rng.integers(4, 16))).astype(np.int32),
            max_new_tokens=args.max_new_tokens,
            temperature=args.temperature,
            arch=rcfg.name if mixed else None))
    waves = max(1, args.waves)
    per_wave = -(-len(reqs) // waves)
    t0 = time.time()
    trace_marks = []
    done = []
    for w in range(waves):
        for r in reqs[w * per_wave:(w + 1) * per_wave]:
            eng.submit(r)
        done = eng.run()
        trace_marks.append(eng.trace_count())
    dt = time.time() - t0
    for r in sorted(done, key=lambda r: r.uid):
        print(f"req {r.uid}: {len(r.prompt)} prompt toks -> "
              f"{len(r.generated)} generated")
    if isinstance(eng, ConstellationRouter):
        s = eng.plane_stats()
        tok = s["engines"]["tokens"]
        label = "+".join(c.name for c, _, _ in builds)
        print(f"{label}: grid of {eng.n_pods} pods x "
              f"{args.slots} slots served {len(done)} requests | "
              f"{tok / dt:.0f} tok/s | {s['pointer_flips']} pointer "
              f"flips + {s['full_migrations']} full drains "
              f"({s['migrated_slots']} slots failed over) | "
              f"{s['rebalanced_slots']} rebalanced | "
              f"{s['replication_syncs']} standby syncs "
              f"({s['replicated_rows']} delta rows vs "
              f"{s['full_rows_equiv']} full-row equiv) | "
              f"{s['masked_pod_ticks']} masked pod-ticks | "
              f"admitted/pod {s['admitted_per_pod']} "
              f"(home {s['admitted_home']}/spill {s['admitted_spill']}) | "
              f"{eng.trace_count()} traces")
        if mixed:
            for name, occ in s["arch_occupancy"].items():
                print(f"  group {name} [{occ['state_kind']}]: "
                      f"{occ['pods']} pods / {occ['slots']} slots")
        if args.force_outage_at is not None:
            check_forced_outage_contract(
                eng, done, args.requests,
                expect_pointer_flip=args.expect_pointer_flip,
                expect_rebalance=args.expect_rebalance)
            print(f"  chaos schedule '{args.force_outage_at}': zero "
                  f"drops, {s['migrated_slots']} slots failed over "
                  f"({s['pointer_flips']} flips), "
                  f"{s['rebalanced_slots']} rebalanced OK")
    else:
        s = eng.stats
        print(f"{cfg.name}: served {len(done)} requests on {args.slots} "
              f"slots | {s['tokens'] / dt:.0f} tok/s | "
              f"{s['host_syncs'] / max(s['tokens'], 1):.3f} "
              f"host-syncs/token | {eng.trace_count()} traces "
              f"(buckets={eng.buckets()}, decode_block={args.decode_block})")
        print(admission_summary(s, done))
        if args.page_size:
            ps = eng.page_stats()
            print(f"  paged KV: {ps['pool_pages']} pool pages x "
                  f"{ps['page_size']} toks | "
                  f"{s['pages_reserved']} reserved, "
                  f"{s['pages_shared']} prefix-shared | "
                  f"{s['prefix_hits']} prefix hits / "
                  f"{s['prefix_stores']} stores | "
                  f"{s['admission_stalls']} admission stalls")
    if waves > 1 and trace_marks[0] >= 0 \
            and trace_marks[-1] != trace_marks[0]:
        raise SystemExit(
            f"trace count not flat across waves: {trace_marks} — wave 1 "
            f"must compile everything the steady state needs")
    if waves > 1:
        print(f"  {waves} waves, trace count flat at {trace_marks[-1]} "
              f"after wave 1")


if __name__ == "__main__":
    main()
