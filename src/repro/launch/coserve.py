"""Serving/training co-residency launcher: ONE process runs DiLoCo rounds
and serves live traffic from the freshest *verified* outer params.

The paper's deployment story is that the orbital cluster that trains also
serves — compute is too precious to idle a pod between outer syncs. Here
the DiLoCoSupervisor's round loop and a ServingEngine share the process:
after every drained round the engine pumps its queue (the device is idle
until the next round is dispatched), and a rollback-aware ParamPublisher
releases the outer params to `engine.swap_params` once the snapshot
watermark (+ --holdback-rounds) has passed them — a round that is later
rolled back is never served, and every swap is a jit cache hit (no
re-trace: same shapes/dtypes).

  PYTHONPATH=src python -m repro.launch.coserve --arch suncatcher-lm-100m \
      --steps 24 --diloco-pods 2 --inner-steps 4 --serve-slots 2 \
      --requests 8 --publish-every 1 --holdback-rounds 1

  # exercise the holdback path: the forced rollback drops the staged
  # unverified candidates instead of serving them
  PYTHONPATH=src python -m repro.launch.coserve --steps 16 \
      --inner-steps 4 --force-rollback-at 1

  # pod liveness from the orbital/ISL/radiation stack while serving
  PYTHONPATH=src python -m repro.launch.coserve --steps 24 --constellation

  # constellation serving plane: N engine replicas behind the liveness
  # router; the publisher fans verified outer params to ALL replicas in
  # lockstep, and serving traffic obeys the same mask as training
  PYTHONPATH=src python -m repro.launch.coserve --steps 24 --replicas 2 \
      --constellation --serving-constellation

  # forced serving-pod outage mid-run: in-flight generations fail over
  # bit-exactly to the surviving replica (zero drops); the schedule
  # grammar allows repeated strike/repair cycles ("2:1:3,9:1:3")
  PYTHONPATH=src python -m repro.launch.coserve --steps 16 --replicas 2 \
      --force-outage-at 2
"""
import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.serving import (ConstellationRouter, EngineConfig, Request,
                           ServingEngine, check_forced_outage_contract,
                           liveness_mask_fn, parse_outage_spec)
from repro.train import (AdamWConfig, DataConfig, DiLoCoConfig,
                         DiLoCoSupervisor, FTConfig, ParamPublisher,
                         PublishConfig, SyntheticLM, TrainConfig,
                         diloco_init, make_diloco_round, outer_wire_bytes,
                         snapshot_global_params)


def run_coserve(sup, eng, requests, n_rounds, *, forced_rollback_at=None,
                blocks_per_round=2, max_steps=10_000):
    """Interleave the supervisor's round loop with the serving engine.

    Per drained round (success OR rollback) the engine admits queued
    requests and decodes up to `blocks_per_round` fused blocks; once
    training reaches `n_rounds` the remaining traffic drains. Publication
    happens inside the supervisor (its ParamPublisher), not here — this
    loop only moves tokens. `eng` may be a single ServingEngine or a
    ConstellationRouter plane; while training runs, a router's liveness
    tick is pinned to the supervisor's round index (a pod masked for
    training round r is masked for serving while round r trains), and
    once training finishes the pin is released so the serving clock — and
    any pod's repair window — advances on the router's own ticks during
    the drain. Returns the finished list.
    """
    pending = list(requests)
    # a router plane admits across n_pods replicas; keep its queue sized
    # to the PLANE, not to one replica
    cap = getattr(eng, "n_pods", 1) * eng.ecfg.max_batch

    def pump(_sup):
        if hasattr(eng, "round_override"):
            eng.round_override = _sup.round
        while pending and len(eng.queue) < cap:
            eng.submit(pending.pop(0))
        for _ in range(blocks_per_round):
            if not (eng.queue or any(s is not None for s in eng.slots)):
                break
            eng.step()

    sup.run(n_rounds, forced_rollback_at=forced_rollback_at, on_round=pump)

    if hasattr(eng, "round_override"):
        eng.round_override = None     # drain on the router's own clock
    steps = 0
    while (pending or eng.queue
           or any(s is not None for s in eng.slots)) and steps < max_steps:
        while pending and len(eng.queue) < cap:
            eng.submit(pending.pop(0))
        eng.step()
        steps += 1
    return eng.finished


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="suncatcher-lm-100m",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=24,
                    help="total inner training steps (rounds = "
                         "ceil(steps / inner-steps))")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4,
                    help="training batch per pod")
    ap.add_argument("--diloco-pods", type=int, default=2)
    ap.add_argument("--inner-steps", type=int, default=4,
                    help="DiLoCo H: local steps between outer syncs")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    help="steps between supervisor snapshots — the "
                         "publication watermark advances on this cadence")
    ap.add_argument("--serve-slots", type=int, default=2,
                    help="serving engine decode slots (EngineConfig."
                         "max_batch), per replica")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving-pod engine replicas behind the liveness "
                         "router (1 = single engine, no router)")
    ap.add_argument("--serving-constellation", action="store_true",
                    help="route serving traffic by the constellation "
                         "liveness mask (the serving twin of "
                         "--constellation; reuses the training link model "
                         "when pod counts match)")
    ap.add_argument("--force-outage-at", type=str, default=None,
                    help="chaos schedule 'AT[:POD[:TICKS]][,...]': strike "
                         "pod POD ('*' or omitted = busiest) at router "
                         "tick AT for TICKS ticks (omitted = rest of "
                         "run); in-flight generations must fail over, "
                         "not drop (requires --replicas >= 2)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--decode-block", type=int, default=8,
                    help="tokens decoded per host round-trip")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--publish-every", type=int, default=1,
                    help="stage a publish candidate every N rounds")
    ap.add_argument("--holdback-rounds", type=int, default=1,
                    help="further completed rounds a publish candidate "
                         "must survive, on top of the snapshot-watermark "
                         "gate")
    ap.add_argument("--constellation", action="store_true",
                    help="derive pod liveness from the orbital/ISL/"
                         "radiation stack")
    ap.add_argument("--force-rollback-at", type=int, default=None,
                    help="force ONE whole-round rollback at this round "
                         "(the publisher must drop, not serve, it)")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg = registry.get_reduced_config(args.arch)
    if registry.input_kind(args.arch) != "tokens":
        raise SystemExit("coserve supports token-LM archs (the serving "
                         "half decodes token streams; any DecodeState "
                         "family — KV or recurrent carry — works)")
    fns = registry.model_fns(cfg)
    dcfg = DiLoCoConfig(n_pods=args.diloco_pods,
                        inner_steps=args.inner_steps)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3),
                       warmup_steps=max(2, args.steps // 10),
                       total_steps=args.steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.batch))
    ft_proto = FTConfig()
    params = fns.init(jax.random.PRNGKey(0), cfg)
    d_state = diloco_init(params, dcfg,
                          screen_window=ft_proto.gnorm_window)
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                            screen_window=ft_proto.gnorm_window,
                            min_screen=ft_proto.min_screen,
                            supervise=True)

    if args.force_outage_at is not None and args.replicas < 2:
        raise SystemExit("--force-outage-at needs --replicas >= 2 (a "
                         "one-pod plane has nowhere to migrate)")

    liveness = None
    if args.constellation:
        from repro.core.isl import ConstellationLinkModel, LivenessConfig
        liveness = ConstellationLinkModel(cfg=LivenessConfig(
            n_pods=dcfg.n_pods,
            outer_wire_bytes=outer_wire_bytes(params)))

    # the engine(s) serve the round-0 globals until the first publish; they
    # must hold their OWN buffers (the fused round donates d_state's)
    ecfg = EngineConfig(max_batch=args.serve_slots, max_len=args.max_len,
                        decode_block=args.decode_block)
    params0 = snapshot_global_params(d_state)
    if args.replicas > 1 or args.serving_constellation:
        mask_fn = None
        if args.serving_constellation:
            # the serving twin of the training mask: same link model when
            # the pod counts line up, so one masked pod silences both
            # planes at the same round
            if liveness is not None and dcfg.n_pods == args.replicas:
                serve_model = liveness
            else:
                from repro.core.isl import (ConstellationLinkModel,
                                            LivenessConfig)
                serve_model = ConstellationLinkModel(cfg=LivenessConfig(
                    n_pods=args.replicas,
                    outer_wire_bytes=outer_wire_bytes(params)))
            mask_fn = liveness_mask_fn(serve_model)
        forced = (parse_outage_spec(args.force_outage_at)
                  if args.force_outage_at is not None else None)
        eng = ConstellationRouter(
            [ServingEngine(cfg, fns, params0, ecfg)
             for _ in range(args.replicas)],
            mask_fn=mask_fn, forced_outage=forced)
    else:
        eng = ServingEngine(cfg, fns, params0, ecfg)
    publisher = ParamPublisher(
        eng.swap_params,
        PublishConfig(publish_every=args.publish_every,
                      holdback_rounds=args.holdback_rounds))

    rng = np.random.default_rng(0)
    reqs = [Request(uid=uid,
                    prompt=rng.integers(
                        0, cfg.vocab_size,
                        size=int(rng.integers(4, 16))).astype(np.int32),
                    max_new_tokens=args.max_new_tokens,
                    temperature=args.temperature)
            for uid in range(args.requests)]

    n_rounds = -(-args.steps // dcfg.inner_steps)
    forced = ([args.force_rollback_at]
              if args.force_rollback_at is not None else None)
    with tempfile.TemporaryDirectory() as d:
        ft = FTConfig(checkpoint_dirs=(os.path.join(d, "replica-a"),
                                       os.path.join(d, "replica-b")),
                      checkpoint_every=args.checkpoint_every)
        sup = DiLoCoSupervisor(rnd, d_state, dcfg, ft, liveness=liveness,
                               publisher=publisher)
        t0 = time.time()
        done = run_coserve(sup, eng, reqs, n_rounds,
                           forced_rollback_at=forced)
        dt = time.time() - t0

    if publisher.published_round > sup.verified_round:
        raise RuntimeError(
            f"published round {publisher.published_round} past the "
            f"verification watermark {sup.verified_round}")
    losses = sup.mean_losses
    print(f"{cfg.name}: co-resident {len(sup.history)} DiLoCo rounds x "
          f"H={dcfg.inner_steps} ({dcfg.n_pods} pods) + {len(done)} "
          f"requests served in {dt:.1f}s, mean pod loss "
          f"{losses[0]:.3f} -> {losses[-1]:.3f}")
    print(f"  publish: {publisher.stats['staged']} staged, "
          f"{publisher.stats['published']} published (newest round "
          f"{publisher.published_round}/{sup.round}), "
          f"{publisher.stats['dropped_rollback']} dropped by rollback, "
          f"{sup.stats['rollbacks']} whole-round rollbacks")
    if isinstance(eng, ConstellationRouter):
        s = eng.plane_stats()
        print(f"  serve: plane of {args.replicas} replicas, "
              f"{s['engines']['tokens'] / dt:.0f} tok/s co-resident, "
              f"{s['swaps']} plane-wide param swaps (v"
              f"{eng.params_version}), {s['migrated_slots']} slots "
              f"migrated, {s['masked_pod_ticks']} masked pod-ticks, "
              f"{eng.trace_count()} traces")
        if args.force_outage_at is not None:
            check_forced_outage_contract(eng, done, args.requests)
    else:
        s = eng.stats
        print(f"  serve: {s['tokens'] / dt:.0f} tok/s co-resident, "
              f"{s['swaps']} live param swaps (engine v"
              f"{eng.params_version}), {eng.trace_count()} traces — flat "
              f"across swaps (buckets={eng.buckets()})")


if __name__ == "__main__":
    main()
