"""Training launcher: --arch <id> with the full space-runtime stack.

On this CPU container it runs reduced configs by default; on a real TPU
cluster the same driver takes the full config (--full) + production mesh.

  # fault-tolerant single-replica training, fused K-step drains
  PYTHONPATH=src python -m repro.launch.train --arch suncatcher-lm-100m \
      --steps 50 --drain-every 8 --mesh test

  # DiLoCo: 2 pods, fused device-resident rounds, int8 EF-compressed
  # outer sync on the FSO wire hop
  PYTHONPATH=src python -m repro.launch.train --arch granite-moe-1b-a400m \
      --steps 50 --diloco-pods 2 --inner-steps 8 --compress int8

  # constellation-in-the-loop: pod liveness derived from the orbital/ISL/
  # radiation stack (cluster breathing -> straggler masking, SEFI/UECC
  # outages -> repair windows), per-pod in-graph rollback
  PYTHONPATH=src python -m repro.launch.train --arch suncatcher-lm-100m \
      --steps 50 --diloco-pods 2 --constellation
"""
import argparse
import os
import tempfile

import jax

from repro.core.radiation import RadiationEnvironment, SDCInjector
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import mesh_for
from repro.models import registry
from repro.train import (AdamWConfig, DataConfig, DiLoCoConfig,
                         DiLoCoSupervisor, FTConfig, FaultTolerantTrainer,
                         SyntheticLM, TrainConfig, diloco_init,
                         init_train_state, isl_bytes_per_step,
                         make_diloco_round, make_fused_steps,
                         make_sharded_fused_steps, make_sharded_train_step,
                         make_train_step, outer_wire_bytes)


def _run_diloco(args, cfg, fns, tcfg, data):
    """Device-resident DiLoCo rounds under the DiLoCoSupervisor: per-pod
    in-graph rollback, replicated async checkpoints, and (with
    --constellation) pod masks derived from the orbital/ISL/radiation
    stack instead of a hand-fed constant."""
    dcfg = DiLoCoConfig(n_pods=args.diloco_pods,
                        inner_steps=args.inner_steps)
    compress = None if args.compress == "none" else args.compress
    mesh = mesh_for(args.mesh)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    ft_proto = FTConfig()
    d_state = diloco_init(params, dcfg, compress=compress,
                          screen_window=ft_proto.gnorm_window)
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, compress=compress,
                            data=data, screen_window=ft_proto.gnorm_window,
                            min_screen=ft_proto.min_screen, mesh=mesh,
                            supervise=True)
    wire = outer_wire_bytes(params, compress)

    liveness = None
    if args.constellation:
        from repro.core.isl import ConstellationLinkModel, LivenessConfig
        liveness = ConstellationLinkModel(cfg=LivenessConfig(
            n_pods=dcfg.n_pods, outer_wire_bytes=wire,
            round_time_s=args.round_time_s,
            round_deadline_s=args.round_deadline_s,
            outage_rate_multiplier=args.outage_rate_multiplier))

    n_rounds = -(-args.steps // dcfg.inner_steps)
    forced = ([args.force_rollback_at]
              if args.force_rollback_at is not None else None)
    with tempfile.TemporaryDirectory() as d:
        ft = FTConfig(checkpoint_dirs=(os.path.join(d, "replica-a"),
                                       os.path.join(d, "replica-b")))
        sup = DiLoCoSupervisor(rnd, d_state, dcfg, ft, liveness=liveness)
        hist = sup.run(n_rounds, forced_rollback_at=forced)
    stats = {k: v for k, v in sup.stats.items() if v}

    acct = isl_bytes_per_step(cfg.param_count(), dcfg.inner_steps, compress)
    losses = sup.mean_losses
    print(f"{cfg.name}: DiLoCo {dcfg.n_pods} pods x H={dcfg.inner_steps}, "
          f"{len(hist)} rounds, mean pod loss "
          f"{losses[0]:.3f} -> {losses[-1]:.3f}, stats {stats}")
    print(f"  ISL wire: {wire/1e6:.2f} MB/pod/outer-sync "
          f"({args.compress}), {acct['reduction']:.0f}x less pod-axis "
          f"traffic than sync DP")
    if liveness is not None:
        masked = sup.stats["masked_pod_rounds"] / (n_rounds * dcfg.n_pods)
        print(f"  constellation: round_time {liveness.round_time_s:.0f}s, "
              f"deadline {liveness.round_deadline_s:.2e}s, "
              f"{sup.stats['mask_transitions']} mask transitions, "
              f"{masked:.0%} pod-rounds masked "
              f"({sup.stats['straggler_pod_rounds']} straggler, "
              f"{sup.stats['outage_pod_rounds']} outage)")
    return hist


def _run_supervised(args, cfg, fns, tcfg, data):
    """Single-replica fault-tolerant loop (per-step or fused drains)."""
    mesh = mesh_for(args.mesh)
    state = init_train_state(jax.random.PRNGKey(0), cfg, fns)
    if mesh is not None:
        step = make_sharded_train_step(cfg, fns, tcfg, mesh,
                                       data.batch_at(0), donate=False)
    else:
        step = jax.jit(make_train_step(cfg, fns, tcfg))

    injector = None
    if args.sdc_rate_multiplier:
        injector = SDCInjector(RadiationEnvironment(), n_chips=81 * 256,
                               step_time_s=1.0,
                               rate_multiplier=args.sdc_rate_multiplier)
    fused = None
    if args.drain_every > 1 and injector is None:
        if mesh is not None:
            fused = make_sharded_fused_steps(
                cfg, fns, tcfg, mesh, data.batch_at(0),
                drain_every=args.drain_every)
        else:
            fused = jax.jit(make_fused_steps(cfg, fns, tcfg),
                            donate_argnums=(0, 1))
    with tempfile.TemporaryDirectory() as d:
        trainer = FaultTolerantTrainer(
            step, state, data,
            FTConfig(checkpoint_dirs=(d,), checkpoint_every=20,
                     drain_every=args.drain_every),
            injector=injector, fused_steps=fused)
        if fused is not None:
            hist = trainer.run_fused(args.steps)
        else:
            hist = trainer.run(args.steps)
    mode = (f"fused drains (K={args.drain_every})" if fused is not None
            else "per-step host loop")
    print(f"{cfg.name}: {len(hist)} steps [{mode}], loss "
          f"{hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}, "
          f"ft stats {trainer.stats}")
    return hist


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="suncatcher-lm-100m",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (TPU-scale; default reduced)")
    ap.add_argument("--sdc-rate-multiplier", type=float, default=0.0)
    ap.add_argument("--schedule", default=None, help="cosine|wsd")
    ap.add_argument("--diloco-pods", type=int, default=0,
                    help="run DiLoCo with this many pods (0 = off)")
    ap.add_argument("--inner-steps", type=int, default=8,
                    help="DiLoCo H: local steps between outer syncs")
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"],
                    help="error-feedback compression on the outer wire hop")
    ap.add_argument("--mesh", default="test",
                    choices=["none", "test", "single", "multi"],
                    help="device mesh for explicit shardings "
                         "(single/multi need the production chip count)")
    ap.add_argument("--drain-every", type=int, default=8,
                    help="metrics-block drain cadence K (1 = seed-style "
                         "per-step host loop)")
    ap.add_argument("--constellation", action="store_true",
                    help="derive DiLoCo pod masks from the orbital/ISL/"
                         "radiation stack (cluster breathing + SEFI/UECC "
                         "outages) instead of a hand-fed constant")
    ap.add_argument("--round-deadline-s", type=float, default=None,
                    help="outer-sync deadline; a pod whose cross-pod ISL "
                         "transfer exceeds it is masked as a straggler "
                         "(default: auto percentile over the orbit)")
    ap.add_argument("--round-time-s", type=float, default=None,
                    help="wall time one DiLoCo round maps to on the orbit "
                         "(default: period/16, sweeping the full orbit in "
                         "a smoke run)")
    ap.add_argument("--outage-rate-multiplier", type=float, default=1.0,
                    help="scale on the measured SEFI+HBM-UECC restart "
                         "rates feeding the outage model")
    ap.add_argument("--force-rollback-at", type=int, default=None,
                    help="force ONE whole-round rollback at this round "
                         "(exercises the bit-deterministic replay path)")
    return ap


def setup(args):
    """(model cfg, model fns, TrainConfig, SyntheticLM) for these flags."""
    cfg = (registry.get_config(args.arch) if args.full
           else registry.get_reduced_config(args.arch))
    fns = registry.model_fns(cfg)
    sched = args.schedule or ("wsd" if args.arch == "minicpm-2b"
                              else "cosine")
    tcfg = TrainConfig(adamw=AdamWConfig(lr=args.lr), schedule=sched,
                       warmup_steps=max(2, args.steps // 10),
                       total_steps=args.steps)
    data = SyntheticLM(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch,
        n_codebooks=getattr(cfg, "n_codebooks", 1),
        kind=registry.input_kind(args.arch)))
    return cfg, fns, tcfg, data


def run(args):
    """Train as the command line says; returns the per-step (or, with
    --diloco-pods, per-round) history."""
    cfg, fns, tcfg, data = setup(args)
    if args.diloco_pods > 0:
        return _run_diloco(args, cfg, fns, tcfg, data)
    return _run_supervised(args, cfg, fns, tcfg, data)


def main():
    ap = build_parser()
    args = ap.parse_args()
    if args.diloco_pods > 0 and args.sdc_rate_multiplier:
        ap.error("--sdc-rate-multiplier needs the host-driven injector "
                 "and is not supported with --diloco-pods (the DiLoCo "
                 "round is fully device-resident); drop one of the two")
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
