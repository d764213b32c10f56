import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes and extract the roofline inputs from the compiled artifact.

The two lines above MUST run before any jax import (jax pins the device
count at first init) — and must NOT be set globally: smoke tests and
benchmarks see the real single CPU device.

For each cell this driver:
  1. builds the step function:  train_4k -> train_step (fwd+bwd+AdamW),
     prefill_32k -> logits forward, decode_* / long_* -> serve_step
     (one token against a seq_len KV cache / recurrent state),
  2. builds ShapeDtypeStruct stand-ins for params/opt/cache/batch (zero
     allocation) with NamedShardings from repro.distributed.sharding,
  3. jit(...).lower(...).compile() on the 16x16 single-pod mesh and the
     (2,16,16) multi-pod mesh,
  4. records memory_analysis / cost_analysis / per-collective HLO bytes to
     JSON for EXPERIMENTS.md and benchmarks/roofline.py.

Usage:
  python -m repro.launch.dryrun --arch granite-moe-1b-a400m --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]

DiLoCo outer-sync cells (--outer-sync): lower + compile ONLY the masked
Nesterov outer step — the FSO pod-axis hop — on the (2,16,16) multi-pod
mesh, with the int8 / top-k error-feedback compressor in the graph, and
record the per-collective / per-dtype byte accounting next to the
`outer_wire_bytes` static prediction:
  python -m repro.launch.dryrun --outer-sync --compress int8 [--check]

By default the compressed cell lowers the WIRE-format shard_map hop (the
path make_diloco_round takes on a mesh): the s8 payload + f32 scales (or
top-k f32 values + s32 indices) are what the pod-axis all-gather
carries. --simulated lowers the legacy pod-local compressor instead,
reproducing the PR 5 finding (full-f32 delta all-gather, ~100x the
payload). --check exits nonzero when measured bytes exceed
`budget_factor` x the prediction — the CI gate.
"""
import argparse
import json
import math
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.analytic import analytic_roofline
from repro.analysis.hlo import collective_bytes, collective_bytes_loop_aware
from repro.analysis.roofline import model_flops_for, roofline
from repro.distributed.sharding import (batch_specs, cache_specs,
                                        opt_state_specs, param_specs,
                                        sanitize_specs, shardings_for)
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train.loop import TrainConfig, make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "..", "..", "..", "benchmarks", "results",
                           "dryrun")

# Gradient-accumulation factor per arch for train_4k: keeps the per-device
# activation-checkpoint stacks (L x B_loc x S_loc x d) within the 16 GiB HBM
# (§Perf iteration log in EXPERIMENTS.md).
TRAIN_MICROBATCHES = {
    "command-r-35b": 2,
    "qwen2.5-32b": 2,
    "stablelm-12b": 2,
    "minicpm-2b": 2,
    "musicgen-medium": 2,
    "qwen3-moe-30b-a3b": 4,
    "granite-moe-1b-a400m": 2,
    "recurrentgemma-2b": 2,
    "xlstm-350m": 1,
    "qwen2-vl-2b": 1,
    "suncatcher-lm-100m": 1,
}


def _sds(tree, dtype_map=None):
    def conv(x):
        dt = x.dtype
        if dtype_map and jnp.issubdtype(dt, jnp.floating):
            dt = dtype_map
        return jax.ShapeDtypeStruct(x.shape, dt)
    return jax.tree.map(conv, tree)


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               attn_impl: str = "chunked", mesh_shape=None):
    """Returns (fn, args_sds, out_shardings, meta). Zero device allocation."""
    seq_len, global_batch, kind = registry.SHAPES[shape_name]
    overrides = {"loss_chunk": 1024}
    if arch not in ("xlstm-350m",):
        overrides["attn_impl"] = attn_impl
    seq_kind = registry.SHAPES[shape_name][2]
    # training: ZeRO-3/FSDP storage with in-loop per-layer gathering.
    # serving: weights stay resident, tensor-parallel only (no regather
    # per token) — the standard inference layout.
    train_cell = seq_kind == "train"
    overrides["fsdp_hints"] = train_cell
    cfg = registry.get_config(arch, **overrides)
    fns = registry.model_fns(cfg)
    ikind = registry.input_kind(arch)
    mesh = make_production_mesh(multi_pod=multi_pod, shape=mesh_shape)
    pspecs = param_specs(cfg, fsdp=train_cell, multi_pod=multi_pod)
    params_sds = jax.eval_shape(
        lambda: fns.init(jax.random.PRNGKey(0), cfg))

    def tok_sds(b, s):
        if ikind == "codebooks":
            return jax.ShapeDtypeStruct((b, cfg.n_codebooks, s), jnp.int32)
        return jax.ShapeDtypeStruct((b, s), jnp.int32)

    bspec = P(("pod", "data") if multi_pod else ("data",))
    tokens_n = global_batch * (seq_len if kind != "decode" else 1)
    meta = {"arch": arch, "shape": shape_name, "kind": kind,
            "seq_len": seq_len, "global_batch": global_batch,
            "multi_pod": multi_pod, "tokens_per_step": tokens_n,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count()}

    if kind == "train":
        from repro.train.loop import init_train_state
        tcfg = TrainConfig(microbatches=TRAIN_MICROBATCHES.get(arch, 1))
        meta["microbatches"] = tcfg.microbatches
        step = make_train_step(cfg, fns, tcfg)
        state_sds = {
            "params": params_sds,
            "opt": {"m": _sds(params_sds, jnp.float32),
                    "v": _sds(params_sds, jnp.float32),
                    "step": jax.ShapeDtypeStruct((), jnp.int32)},
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }
        batch_sds = {"tokens": tok_sds(global_batch, seq_len),
                     "labels": tok_sds(global_batch, seq_len)}
        bspecs = {"tokens": bspec, "labels": bspec}
        if ikind == "vlm":
            batch_sds["positions"] = jax.ShapeDtypeStruct(
                (3, global_batch, seq_len), jnp.int32)
            bspecs["positions"] = P(None, *bspec)
        state_spec = {"params": pspecs, "opt": opt_state_specs(pspecs),
                      "step": P()}
        state_sh = shardings_for(state_spec, state_sds, mesh)
        batch_sh = shardings_for(bspecs, batch_sds, mesh)
        fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None))
        return fn, (state_sds, batch_sds), mesh, meta

    if kind == "prefill":
        def prefill(params, tokens):
            return fns.forward(params, tokens, cfg)
        params_bf16 = _sds(params_sds, jnp.bfloat16)
        params_sh = shardings_for(pspecs, params_bf16, mesh)
        tokens_sds = tok_sds(global_batch, seq_len)
        tok_sh = shardings_for(bspec, tokens_sds, mesh)
        fn = jax.jit(prefill, in_shardings=(params_sh, tok_sh),
                     out_shardings=None)
        return fn, (params_bf16, tokens_sds), mesh, meta

    # decode / long-context decode: serve_step = one token vs seq_len cache
    def serve_step(params, cache, tokens):
        return fns.decode_step(params, cache, tokens, cfg)

    params_bf16 = _sds(params_sds, jnp.bfloat16)
    params_sh = shardings_for(pspecs, params_bf16, mesh)
    cache_sds = jax.eval_shape(
        lambda: fns.init_cache(cfg, global_batch, seq_len))
    cspecs = cache_specs(cfg, multi_pod=multi_pod)
    # transformer KV cache: shard cache length over "model" (sequence-
    # parallel decode attention); recurrent states shard channels instead.
    if "k" in cache_sds:
        cspecs = {"k": P(None, bspec[0], "model"),
                  "v": P(None, bspec[0], "model"), "pos": P()}
    cache_sh = shardings_for(cspecs, cache_sds, mesh)
    tokens_sds = tok_sds(global_batch, 1)
    tok_sh = shardings_for(bspec, tokens_sds, mesh)
    fn = jax.jit(serve_step, in_shardings=(params_sh, cache_sh, tok_sh),
                 out_shardings=(None, cache_sh))
    return fn, (params_bf16, cache_sds, tokens_sds), mesh, meta


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, attn_impl: str = "chunked",
             verbose: bool = True, mesh_shape=None, tag_suffix: str = ""):
    t0 = time.time()
    fn, args, mesh, meta = build_cell(arch, shape_name, multi_pod, attn_impl,
                                      mesh_shape)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo_txt = compiled.as_text()
        coll = collective_bytes(hlo_txt)
        coll_la = collective_bytes_loop_aware(hlo_txt)

    chips = math.prod(mesh.devices.shape)
    mf = model_flops_for(registry.get_config(arch), meta["kind"],
                         meta["tokens_per_step"])
    terms = roofline(cost, coll["wire_bytes"], chips=chips, model_flops=mf)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    analytic = analytic_roofline(
        registry.get_config(arch), meta["kind"], meta["global_batch"],
        meta["seq_len"], chips=chips,
        data_shards=sizes.get("data", 1) * sizes.get("pod", 1),
        model_shards=sizes.get("model", 1),
        wire_bytes_per_device=coll_la["wire_bytes"],
        microbatches=meta.get("microbatches", 1))
    result = {
        **meta,
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float))},
        "collectives": coll,
        "collectives_loop_aware": coll_la,
        "analytic": analytic,
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "step_time_s": terms.step_time_s,
            "model_flops": mf,
            "utility_ratio": terms.utility_ratio,
            "mfu": terms.mfu,
        },
    }
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}" \
        + tag_suffix
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if verbose:
        hbm = (result["memory"]["argument_size_in_bytes"]
               + result["memory"]["temp_size_in_bytes"]) / 2**30
        print(f"[OK] {tag}: compile {t_compile:.0f}s, "
              f"args+temp {hbm:.2f} GiB/device, "
              f"dominant={analytic['dominant']}, "
              f"terms(c/m/n)=({analytic['compute_s']:.4f}/"
              f"{analytic['memory_s']:.4f}/"
              f"{analytic['collective_s']:.4f})s, "
              f"MFU~{analytic['mfu']:.1%}", flush=True)
    return result


def run_outer_sync_cell(arch: str = "suncatcher-lm-100m",
                        compress: str | None = "int8",
                        topk_frac: float = 0.01, n_pods: int = 2,
                        out_dir: str = RESULTS_DIR, verbose: bool = True,
                        simulated: bool = False):
    """Dry-run the DiLoCo outer sync (the pod-axis FSO hop) on the
    (2,16,16) production mesh and account its collective bytes.

    The inner H steps are pod-local by construction, so the outer step is
    lowered ALONE: its pod-axis collectives are exactly the wire traffic
    `train/diloco.py:outer_wire_bytes` predicts from static shapes. With
    compress="int8"/"topk" the WIRE-format shard_map hop runs in-graph
    (each device quantizes its own shard; blocks padded inside the
    shard), and `collective_bytes`'s per-dtype split shows the s8 payload
    (+ f32 scales) / top-k f32+s32 pairs crossing the mesh instead of the
    f32 baseline. simulated=True lowers the legacy pod-local compressor
    instead — the PR 5 regression, preserved as a measurable artifact.
    Zero device allocation (eval_shape + AOT lower/compile)."""
    from repro.distributed.compression import wire_format_for
    from repro.distributed.sharding import diloco_specs
    from repro.train.diloco import (LINT_BUDGET, DiLoCoConfig, diloco_init,
                                    outer_step, outer_wire_bytes)

    comp = None if compress in (None, "none") else compress
    cfg = registry.get_config(arch)
    fns = registry.model_fns(cfg)
    dcfg = DiLoCoConfig(n_pods=n_pods)
    mesh = make_production_mesh(multi_pod=True)          # (2, 16, 16)
    params_sds = jax.eval_shape(
        lambda: fns.init(jax.random.PRNGKey(0), cfg))
    d_sds = jax.eval_shape(
        partial(diloco_init, dcfg=dcfg, compress=comp), params_sds)
    pspecs = param_specs(cfg, fsdp=True, multi_pod=True)
    state_sh = shardings_for(
        diloco_specs(pspecs, compress=comp is not None, screen=False),
        d_sds, mesh)
    wire = None
    if comp is not None and not simulated:
        wire = wire_format_for(params_sds, pspecs, mesh, n_pods,
                               method=comp, topk_frac=topk_frac)
    fn = jax.jit(
        lambda d: outer_step(d, dcfg, compress=comp, topk_frac=topk_frac,
                             wire=wire),
        in_shardings=(state_sh,), out_shardings=state_sh)

    t0 = time.time()
    with jax.set_mesh(mesh):
        compiled = fn.lower(d_sds).compile()
        hlo_txt = compiled.as_text()
    dt = time.time() - t0
    coll = collective_bytes(hlo_txt)
    coll_la = collective_bytes_loop_aware(hlo_txt)
    predicted = outer_wire_bytes(params_sds, compress=comp,
                                 topk_frac=topk_frac, wire=wire)
    factor = LINT_BUDGET["outer_wire_budget_factor"]
    measured = coll["wire_bytes"]
    ratio = measured / predicted if predicted else float("inf")
    result = {
        "arch": arch, "compress": compress or "none", "n_pods": n_pods,
        "wire_format": wire is not None or comp is None,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "lower_compile_s": round(dt, 2),
        "params": cfg.param_count(),
        "predicted_outer_wire_bytes_per_pod": predicted,
        "measured_over_predicted": round(ratio, 4),
        "budget_factor": factor,
        "within_budget": bool(measured <= factor * predicted),
        "collectives": coll,
        "collectives_loop_aware": coll_la,
    }
    if comp is not None and simulated:
        # the PR 5 finding, preserved: the legacy ef_roundtrip quantizes
        # AND dequantizes pod-locally in-graph (a numerics simulation, not
        # a wire format) and its row-padding reshapes defeat the
        # partitioner, so the lowered graph ALL-GATHERS the full f32
        # delta per device before compressing — more collective bytes
        # than the uncompressed masked mean.
        result["note"] = (
            "legacy simulated compressor: measured collectives are f32 "
            "(full-delta all-gather per device); the wire-format hop "
            "(default) ships predicted_outer_wire_bytes_per_pod instead")
    elif comp is not None:
        result["note"] = (
            "wire format: each device quantizes its own shard and the "
            "compressed payload (s8 q + f32 scales for int8; f32 values "
            "+ s32 lane-local indices for topk) is what the pod-axis "
            "all-gather carries")
    os.makedirs(out_dir, exist_ok=True)
    tag = f"diloco_outer_{arch}_{compress or 'none'}_multi"
    if simulated and comp is not None:
        tag += "_simulated"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    if verbose:
        dts = coll["bytes_by_dtype"]
        print(f"[OK] {tag}: compile {dt:.0f}s, "
              f"collective wire ~{measured / 2**20:.2f} MiB "
              f"(predicted payload/pod {predicted / 2**20:.2f} MiB, "
              f"{ratio:.2f}x, budget {factor}x), by dtype "
              + "; ".join(f"{k}: " + ", ".join(
                  f"{d}={b / 2**20:.2f}MiB" for d, b in sorted(v.items()))
                  for k, v in sorted(dts.items())),
              flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attn", default="chunked", choices=["chunked", "ref"])
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--outer-sync", action="store_true",
                    help="dry-run the DiLoCo outer sync alone on the "
                         "(2,16,16) mesh and account its collective bytes")
    ap.add_argument("--compress", default="int8",
                    choices=["none", "int8", "topk"],
                    help="outer-sync wire compression (--outer-sync only)")
    ap.add_argument("--simulated", action="store_true",
                    help="lower the legacy pod-local simulated compressor "
                         "instead of the wire-format hop (reproduces the "
                         "PR 5 full-f32 regression; --outer-sync only)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero if measured collective bytes exceed "
                         "the declared budget factor x the outer_wire_bytes "
                         "prediction (--outer-sync only; the CI gate)")
    args = ap.parse_args()

    if args.outer_sync:
        result = run_outer_sync_cell(arch=args.arch or "suncatcher-lm-100m",
                                     compress=args.compress,
                                     out_dir=args.out,
                                     simulated=args.simulated)
        if args.check and not result["within_budget"]:
            raise SystemExit(
                f"outer-sync wire budget EXCEEDED: measured "
                f"{result['measured_over_predicted']}x the predicted "
                f"payload (budget {result['budget_factor']}x)")
        return

    if args.all:
        cells = registry.cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}_{shape}_{'multi' if mp else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if args.skip_existing and os.path.exists(path):
                print(f"[SKIP] {tag}", flush=True)
                continue
            try:
                run_cell(arch, shape, mp, args.out, args.attn)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"[FAIL] {tag}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + ", ".join(t for t, _ in failures))
    print("all cells passed", flush=True)


if __name__ == "__main__":
    main()
