"""Measured CPU micro-benchmark: the fused device-resident DiLoCo round
against the seed-style per-step host loop.

The seed training path ran ONE jit call per step with a host sync for
loss/grad-norm after every step (the fault-tolerance screens lived on the
host), generated each batch host-side, and ran DiLoCo's outer sync as a
separate eager host call. The fused round (train/diloco.py:
make_diloco_round) moves all of it device-side: H inner steps x n_pods,
in-graph data generation, in-graph SDC screens over a metrics ring buffer,
and the masked Nesterov outer sync run in ONE donated jit, and the host
drains a single (n_pods, H) metrics block per round — host syncs per
global step are 1/H instead of ~2.

The smoke config is deliberately tiny (d_model=32, seq 8): the quantity
being measured is the eliminated per-step host overhead (dispatch + sync +
eager outer), which a large model's compute would mask. Results land in
BENCH_train.json (repo root) next to the serving baseline.

The outer_wire_* keys measure the WIRE-format outer sync: a subprocess
(8 forced CPU devices, (2,2,2) pod/data/model mesh — this process pinned
the single real device at jax import) lowers the shard_map int8 hop and
reads the pod-axis collective bytes out of the compiled HLO next to the
`outer_wire_bytes` prediction — the headline artifact records that the
compressed payload, not the f32 delta, is what crosses the pod axis.
"""
import collections
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.train import (AdamWConfig, DataConfig, DiLoCoConfig, SyntheticLM,
                         TrainConfig, diloco_init, make_diloco_round,
                         make_train_step, outer_step, pod_step_grid)

N_PODS = 2
H = 8                    # inner steps per round
SEQ_LEN = 8
BATCH = 2                # per pod
WARM_ROUNDS = 1
FUSED_ROUNDS = 10
SEED_ROUNDS = 4


# Lowered in a fresh subprocess because the forced device count must be
# set before the first jax import (same pattern as the lint budget
# worker). Prints one JSON line on the last stdout line.
_WIRE_WORKER = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import json
from functools import partial
import jax
from repro.analysis.hlo import collective_bytes
from repro.distributed.compression import wire_format_for
from repro.distributed.sharding import diloco_specs, param_specs, \\
    shardings_for
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train.diloco import (LINT_BUDGET, DiLoCoConfig, diloco_init,
                                outer_step, outer_wire_bytes)
compress = "int8"
cfg = registry.get_reduced_config("suncatcher-lm-100m")
fns = registry.model_fns(cfg)
dcfg = DiLoCoConfig(n_pods=2)
mesh = make_production_mesh(multi_pod=True, shape=(2, 2, 2))
params_sds = jax.eval_shape(lambda: fns.init(jax.random.PRNGKey(0), cfg))
d_sds = jax.eval_shape(
    partial(diloco_init, dcfg=dcfg, compress=compress), params_sds)
pspecs = param_specs(cfg, fsdp=True, multi_pod=True)
state_sh = shardings_for(
    diloco_specs(pspecs, compress=True, screen=False), d_sds, mesh)
wire = wire_format_for(params_sds, pspecs, mesh, dcfg.n_pods,
                       method=compress)
fn = jax.jit(lambda d: outer_step(d, dcfg, wire=wire),
             in_shardings=(state_sh,), out_shardings=state_sh)
with jax.set_mesh(mesh):
    hlo = fn.lower(d_sds).compile().as_text()
measured = collective_bytes(hlo)["wire_bytes"]
predicted = outer_wire_bytes(params_sds, compress=compress, wire=wire)
factor = LINT_BUDGET["outer_wire_budget_factor"]
print(json.dumps({
    "compress": compress, "predicted": predicted, "measured": measured,
    "ratio": round(measured / predicted, 4),
    "within_budget": bool(measured <= factor * predicted)}))
"""


def _measure_outer_wire():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src")
               + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", _WIRE_WORKER], env=env,
                         capture_output=True, text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"wire worker failed: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


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


def _seed_round(d_state, r, step, data, dcfg, screens):
    """The seed loop shape: per-pod per-step jit calls, a loss + gnorm host
    sync per step (host-side screens), host-side batch generation, eager
    host outer step."""
    losses, gnorms = screens
    grid = pod_step_grid(r, dcfg.n_pods, dcfg.inner_steps)
    pod_p, pod_o = [], []
    syncs = 0
    for p in range(dcfg.n_pods):
        st = {"params": jax.tree.map(lambda x: x[p], d_state["pod_params"]),
              "opt": jax.tree.map(lambda x: x[p], d_state["pod_opt"]),
              "step": d_state["step"]}
        for i in range(dcfg.inner_steps):
            b = data.batch_at(int(grid[p, i]))
            st, m = step(st, b)
            loss = float(m["loss"])                      # host sync
            gnorm = float(m["grad_norm"])                # host sync
            syncs += 2
            if np.isfinite(loss) and len(gnorms) >= 8:   # host screens
                np.median(gnorms), np.median(losses)
            losses.append(loss)
            gnorms.append(gnorm)
        pod_p.append(st["params"])
        pod_o.append(st["opt"])
    d_state = {**d_state,
               "pod_params": jax.tree.map(lambda *xs: jnp.stack(xs), *pod_p),
               "pod_opt": jax.tree.map(lambda *xs: jnp.stack(xs), *pod_o),
               "step": d_state["step"] + dcfg.inner_steps}
    return outer_step(d_state, dcfg), syncs


def run():
    cfg, fns, tcfg, data, dcfg = _bench_setup()
    params = fns.init(jax.random.PRNGKey(0), cfg)
    mask = jnp.ones((N_PODS,), jnp.float32)
    thresholds = jnp.asarray([1e9, 1e9], jnp.float32)   # screens armed, quiet

    # ---- fused device-resident round (screens + in-graph data) ----------
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                            screen_window=32)
    d_state = diloco_init(params, dcfg, screen_window=32)
    for r in range(WARM_ROUNDS):
        d_state, m = rnd(d_state, jnp.asarray(pod_step_grid(r, N_PODS, H)), mask,
                         thresholds)
    jax.block_until_ready(m["loss"])
    fused_syncs = 0
    t0 = time.time()
    for r in range(WARM_ROUNDS, WARM_ROUNDS + FUSED_ROUNDS):
        d_state, m = rnd(d_state, jnp.asarray(pod_step_grid(r, N_PODS, H)), mask,
                         thresholds)
        jax.device_get(m)                  # the one drain per round
        fused_syncs += 1
    dt_fused = (time.time() - t0) / FUSED_ROUNDS

    # ---- seed-style per-step host loop ----------------------------------
    step = jax.jit(make_train_step(cfg, fns, tcfg))
    screens = (collections.deque(maxlen=32), collections.deque(maxlen=32))
    d_seed = diloco_init(fns.init(jax.random.PRNGKey(0), cfg), dcfg)
    d_seed, _ = _seed_round(d_seed, 0, step, data, dcfg, screens)   # warm
    seed_syncs = 0
    t0 = time.time()
    for r in range(1, 1 + SEED_ROUNDS):
        d_seed, syncs = _seed_round(d_seed, r, step, data, dcfg, screens)
        seed_syncs += syncs
    dt_seed = (time.time() - t0) / SEED_ROUNDS

    tokens = N_PODS * H * BATCH * SEQ_LEN          # per round
    fused_tps = tokens / dt_fused
    seed_tps = tokens / dt_seed
    speedup = dt_seed / dt_fused
    syncs_per_step_fused = fused_syncs / (FUSED_ROUNDS * H)
    syncs_per_step_seed = seed_syncs / (SEED_ROUNDS * H)

    wire = _measure_outer_wire()

    extras = {
        "fused_round_ms": round(dt_fused * 1e3, 2),
        "seed_loop_round_ms": round(dt_seed * 1e3, 2),
        "speedup_vs_seed_loop": round(speedup, 2),
        "fused_tokens_per_s": round(fused_tps, 1),
        "seed_loop_tokens_per_s": round(seed_tps, 1),
        "host_syncs_per_step": round(syncs_per_step_fused, 4),
        "seed_host_syncs_per_step": round(syncs_per_step_seed, 2),
        "n_pods": N_PODS,
        "inner_steps": H,
        "outer_sync_compress": wire["compress"],
        "outer_wire_predicted_bytes": wire["predicted"],
        "outer_wire_measured_bytes": wire["measured"],
        "outer_wire_measured_over_predicted": wire["ratio"],
        "outer_wire_within_budget": wire["within_budget"],
    }
    with open(os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_train.json"), "w") as f:
        json.dump(extras, f, indent=2)
        f.write("\n")

    out = [
        ("train_fused_diloco_round", dt_fused * 1e6,
         f"{fused_tps:.0f} tok/s, {syncs_per_step_fused:.3f} host-syncs/"
         f"step ({N_PODS} pods x H={H}, screens in-graph)"),
        ("train_seed_step_loop", dt_seed * 1e6,
         f"{seed_tps:.0f} tok/s, {syncs_per_step_seed:.1f} host-syncs/step "
         f"(per-step jit + host screens + eager outer)"),
        ("train_diloco_speedup", 0.0,
         f"{speedup:.2f}x fused round over seed-style per-step loop"),
        ("train_outer_wire_bytes", 0.0,
         f"wire-format {wire['compress']} outer sync moves "
         f"{wire['measured']:.0f} collective bytes/device vs "
         f"{wire['predicted']} predicted payload/pod "
         f"({wire['ratio']:.2f}x, within_budget={wire['within_budget']})"),
    ]
    return out, extras


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for row in run()[0]:
        print(row)
