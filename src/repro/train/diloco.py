"""DiLoCo: distributed low-communication training across satellites.

The paper (§3) points to DiLoCo [ref 41] as the research direction for
fault/communication-tolerant training in orbit. Mapping: the inner optimizer
runs H steps entirely inside one satellite-pod (ICI-only traffic); only the
outer step — a parameter *delta* all-reduce over the "pod" axis — crosses
the FSO inter-satellite links, cutting ISL bandwidth needs by ~H (and ~4x
more with int8 delta compression from repro.distributed.compression).

Implementation: per-pod replicas are an explicit leading axis of the param
pytree. Inner steps vmap over that axis (on the production mesh the axis is
sharded over "pod", so vmap = pod-local compute, zero cross-pod collectives);
the outer step is a masked mean over per-pod deltas + Nesterov momentum.

The pod mask makes satellite loss / straggler drop-out a *first-class*
operation: a pod that died or fell behind is excluded from the outer
average (bounded-staleness semantics) and simply re-broadcasts the new
global params when it rejoins — elastic scaling without restart. A round
in which EVERY pod is masked is a no-op (global params and outer momentum
unchanged): there is no delta to average, so nothing may move.

`make_diloco_round` is the device-resident hot path: ONE donated, jitted
call runs the H inner AdamW steps (lax.scan), the in-graph SDC screens
(fault_tolerance.screen_update over a per-pod metrics ring buffer), the
optional int8/top-k error-feedback compression on the wire hop, and the
masked Nesterov outer sync — the host drains one (n_pods, H) metrics block
per round instead of syncing loss/gnorm every step.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .fault_tolerance import screen_init, screen_update
from .loop import TrainConfig, make_train_step
from .optimizer import init_opt_state

# Enforced by `python -m repro.analysis.lint --budgets` (entries
# "diloco-round" and "diloco-outer-sync{,-int8,-topk}"): the fused round
# compiles with zero host callbacks, and the outer sync's measured
# collective wire bytes stay within outer_wire_budget_factor x the
# `outer_wire_bytes` prediction FOR ITS DECLARED COMPRESS MODE — an
# entry claiming int8 must ship the small payload. The wire-format
# shard_map hop (`_wire_shard_hop`) satisfies this; the legacy
# simulated compressor does not (full-f32 all-gather, the PR 5 dryrun
# finding) and is pinned as the hidden known-bad
# `diloco-outer-sync-regression` entry.
LINT_BUDGET = {"host_callbacks": 0, "outer_wire_budget_factor": 2.0}


@dataclass(frozen=True)
class DiLoCoConfig:
    """DiLoCo outer-loop knobs.

    Fields:
      n_pods: satellite-pod replicas — the leading axis of the replicated
        param pytree; on the production mesh it is sharded over "pod".
      inner_steps: H, local AdamW steps between outer syncs; ISL
        pod-axis traffic drops by ~H vs sync data-parallel.
      outer_lr: Nesterov SGD learning rate on the pod-averaged delta
        (DiLoCo paper default).
      outer_momentum: Nesterov momentum on the outer "gradient".
    """
    n_pods: int = 2
    inner_steps: int = 10           # H
    outer_lr: float = 0.7           # Nesterov SGD on deltas (DiLoCo defaults)
    outer_momentum: float = 0.9


def diloco_init(params, dcfg: DiLoCoConfig, compress: str | None = None,
                screen_window: int = 0):
    """Global state: master params + outer momentum + per-pod replicas.

    compress: "int8"/"topk" adds per-pod error-feedback residuals for the
    compressed wire hop; screen_window > 0 adds per-pod metrics ring
    buffers for the in-graph SDC screens.
    """
    rep = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (dcfg.n_pods,) + x.shape), params)
    state = {
        "global_params": params,
        "outer_m": jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                params),
        "pod_params": rep,
        "pod_opt": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (dcfg.n_pods,) + x.shape).copy(),
            init_opt_state(params)),
        "step": jnp.zeros((), jnp.int32),
    }
    if compress is not None:
        state["pod_ef"] = jax.tree.map(
            lambda x: jnp.zeros((dcfg.n_pods,) + x.shape, jnp.float32),
            params)
    if screen_window:
        state["screen"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (dcfg.n_pods,) + x.shape).copy(),
            screen_init(screen_window))
    return state


def _make_pod_inner(model_cfg, fns, tcfg: TrainConfig, collect):
    """H local AdamW steps on one pod's replica, vmapped over the pod axis.
    `collect(metrics)` picks what the scan stacks per step — the training
    math is IDENTICAL regardless of what is collected, which is what makes
    the fused round bit-identical to make_inner_steps + outer_step."""
    step_fn = make_train_step(model_cfg, fns, tcfg)

    def pod_inner(params, opt, step0, batches):
        state = {"params": params, "opt": opt, "step": step0}

        def body(state, batch):
            state, metrics = step_fn(state, batch)
            return state, collect(metrics)

        state, out = jax.lax.scan(body, state, batches)
        return state["params"], state["opt"], out

    return jax.vmap(pod_inner, in_axes=(0, 0, None, 0))


def make_inner_steps(model_cfg, fns, tcfg: TrainConfig,
                     dcfg: DiLoCoConfig):
    """H local AdamW steps per pod, vmapped over the pod axis.

    batches: pytree with leading axes (n_pods, H, ...). Pod-local: contains
    no cross-pod collectives by construction.
    """
    vmapped = _make_pod_inner(model_cfg, fns, tcfg,
                              collect=lambda m: m["loss"])

    def inner(d_state, batches):
        new_p, new_o, losses = vmapped(d_state["pod_params"],
                                       d_state["pod_opt"], d_state["step"],
                                       batches)
        return {**d_state, "pod_params": new_p, "pod_opt": new_o,
                "step": d_state["step"] + dcfg.inner_steps}, \
            jnp.mean(losses, axis=-1)

    return inner


def _compress_pod_deltas(deltas, ef, pod_mask, method: str,
                         topk_frac: float):
    """LEGACY simulated hop: error-feedback compress/decompress each pod's
    outer delta pod-locally, single-lane layout. Dead pods transmit
    nothing: their EF residual is preserved, not overwritten with a bogus
    round-trip of itself.

    Kept verbatim as the known-bad wire citizen: its whole-leaf padding
    reshapes defeat the SPMD partitioner, so on a sharded mesh the full
    f32 delta is all-gathered before quantization (the PR 5 finding, now
    pinned by the hidden `diloco-outer-sync-regression` lint budget
    entry). The wire-format path below replaces it whenever a mesh is
    available."""
    from repro.distributed.compression import ef_roundtrip
    kw = {"frac": topk_frac} if method == "topk" else {}

    def per_leaf(d, e):
        def one(d1, e1):
            # the compressed payload stays inside the vmap (its static
            # shape/n fields can't cross the batching boundary)
            _, sent, resid = ef_roundtrip(d1, e1, method, **kw)
            return sent, resid
        return jax.vmap(one)(d, e)

    pairs = jax.tree.map(per_leaf, deltas, ef)
    is_pair = lambda x: isinstance(x, tuple)
    sent = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
    resid = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)

    def keep_ef(r, e):
        w = pod_mask.reshape((-1,) + (1,) * (e.ndim - 1))
        return jnp.where(w > 0, r, e)

    return sent, jax.tree.map(keep_ef, resid, ef)


def _wire_sim_hop(deltas, ef, pod_mask, denom, fmt):
    """Simulated wire hop in the SHARD-ALIGNED lane layout (vmap over
    pods, no collectives): the single-process twin of `_wire_shard_hop`.
    Returns (outer grad tree, new EF tree) — bit-identical to the
    shard_map hop on any mesh whose tile grid matches fmt.layout."""
    from repro.distributed.compression import ef_wire_roundtrip, is_wire_leaf

    def per_leaf(d, e, lay):
        def one(d1, e1):
            _, sent, resid = ef_wire_roundtrip(
                d1, e1, lay.counts, fmt.method, fmt.block, fmt.topk_frac)
            return sent, resid
        sent, resid = jax.vmap(one)(d, e)
        w = pod_mask.reshape((-1,) + (1,) * (e.ndim - 1))
        grad = jnp.sum(sent * w, axis=0) / denom
        return grad, jnp.where(w > 0, resid, e)

    pairs = jax.tree.map(per_leaf, deltas, ef, fmt.layout,
                         is_leaf=lambda x: is_wire_leaf(x))
    is_pair = lambda x: isinstance(x, tuple)
    grad = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
    new_ef = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)
    return grad, new_ef


def _wire_shard_hop(deltas, ef, pod_mask, denom, fmt):
    """THE wire hop: each device quantizes its own shard of each pod
    delta (blocks padded inside the shard, so they never straddle shard
    boundaries) and the COMPRESSED payload — s8 q + f32 scales, or top-k
    f32 values + s32 lane-local indices — is what the pod-axis all-gather
    carries; decode and the masked mean happen after the hop. The only
    collectives in the lowered graph are those payload all-gathers: the
    BG002 budget and tests/test_wire_format.py hold it to ~n_pods/S of
    the f32 baseline instead of the ~100x regression the simulated
    compressor lowers to."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.compression import (int8_wire_compress,
                                               int8_wire_decompress,
                                               is_wire_leaf,
                                               topk_wire_compress,
                                               topk_wire_decompress)

    mesh = fmt.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    p_loc = fmt.n_pods // sizes.get("pod", 1)

    def leaf_hop(d, e, lay):
        spec = tuple(lay.spec)

        def local(d_loc, e_loc, mask, den):
            t = (d_loc.reshape(p_loc, -1) + e_loc.reshape(p_loc, -1))
            m = t.shape[1]
            if fmt.method == "int8":
                q, scale = int8_wire_compress(t, fmt.block)
                qg = jax.lax.all_gather(q, "pod", axis=0, tiled=True)
                sg = jax.lax.all_gather(scale, "pod", axis=0, tiled=True)
                sent_all = int8_wire_decompress(qg, sg, m)
            else:
                vals, idx = topk_wire_compress(t, fmt.topk_frac)
                vg = jax.lax.all_gather(vals, "pod", axis=0, tiled=True)
                ig = jax.lax.all_gather(idx, "pod", axis=0, tiled=True)
                sent_all = topk_wire_decompress(vg, ig, m)
            w = mask.reshape(-1, 1)
            grad = jnp.sum(sent_all * w, axis=0) / den
            row0 = jax.lax.axis_index("pod") * p_loc
            sent_own = jax.lax.dynamic_slice_in_dim(sent_all, row0, p_loc, 0)
            w_own = jax.lax.dynamic_slice_in_dim(w, row0, p_loc, 0)
            resid = jnp.where(w_own > 0, t - sent_own,
                              e_loc.reshape(p_loc, -1))
            return (grad.reshape(d_loc.shape[1:]),
                    resid.reshape(d_loc.shape))

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(P("pod", *spec), P("pod", *spec), P(), P()),
            out_specs=(P(*spec), P("pod", *spec)),
            check_vma=False)(d, e, pod_mask, denom)

    pairs = jax.tree.map(leaf_hop, deltas, ef, fmt.layout,
                         is_leaf=lambda x: is_wire_leaf(x))
    is_pair = lambda x: isinstance(x, tuple)
    grad = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
    new_ef = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)
    return grad, new_ef


def outer_step(d_state, dcfg: DiLoCoConfig, pod_mask=None,
               compress: str | None = None, topk_frac: float = 0.01,
               wire=None):
    """Nesterov outer update on the pod-averaged delta; re-broadcast.

    pod_mask: (n_pods,) 0/1 — dead/straggling pods excluded from the average
    (they are overwritten with the new global params regardless: rejoin).
    An all-dead round is a NO-OP on global params and outer momentum —
    without the guard the clamped denominator would turn "no surviving
    deltas" into a huge bogus `global - 0` Nesterov update.

    compress: "int8"/"topk" runs each surviving pod's delta through the
    error-feedback compressor (d_state must carry "pod_ef", see
    diloco_init) — this is the quantized FSO wire hop. Without `wire` it
    is the LEGACY pod-local simulation (single-lane layout, known to
    defeat the partitioner on a mesh).

    wire: a `repro.distributed.compression.WireFormat` (overrides
    `compress` with wire.method). With wire.mesh set, the hop is the real
    shard_map wire transfer — the compressed payload is what crosses the
    pod axis; with wire.mesh=None the same shard-aligned layout runs
    pod-locally (bit-identical result, simulation bytes).
    """
    if wire is not None:
        compress = wire.method
        topk_frac = wire.topk_frac
    if pod_mask is None:
        pod_mask = jnp.ones((dcfg.n_pods,), jnp.float32)
    pod_mask = pod_mask.astype(jnp.float32)
    n_alive = jnp.sum(pod_mask)
    alive = n_alive > 0
    denom = jnp.maximum(n_alive, 1.0)

    def per_pod_delta(gp, pp):
        w = pod_mask.reshape((-1,) + (1,) * gp.ndim)
        # zero out dead pods BEFORE any arithmetic: a NaN-poisoned replica
        # must not leak through the average OR the error-feedback state
        return jnp.where(
            w > 0, gp.astype(jnp.float32)[None] - pp.astype(jnp.float32),
            0.0)

    deltas = jax.tree.map(per_pod_delta, d_state["global_params"],
                          d_state["pod_params"])

    def masked_mean(d):
        w = pod_mask.reshape((-1,) + (1,) * (d.ndim - 1))
        return jnp.sum(d * w, axis=0) / denom

    new_ef = None
    if wire is not None:
        hop = _wire_shard_hop if wire.mesh is not None else _wire_sim_hop
        grad, new_ef = hop(deltas, d_state["pod_ef"], pod_mask, denom, wire)
    else:
        if compress is not None:
            deltas, new_ef = _compress_pod_deltas(
                deltas, d_state["pod_ef"], pod_mask, compress, topk_frac)
        grad = jax.tree.map(masked_mean, deltas)   # "outer gradient"
    m = jax.tree.map(
        lambda m_, g: dcfg.outer_momentum * m_ + g,
        d_state["outer_m"], grad)
    new_global = jax.tree.map(
        lambda gp, m_, g: jnp.where(
            alive,
            (gp.astype(jnp.float32)
             - dcfg.outer_lr * (dcfg.outer_momentum * m_ + g)
             ).astype(gp.dtype),
            gp),
        d_state["global_params"], m, grad)
    new_m = jax.tree.map(lambda m_new, m_old: jnp.where(alive, m_new, m_old),
                         m, d_state["outer_m"])
    new_pods = jax.tree.map(
        lambda gp: jnp.broadcast_to(gp, (dcfg.n_pods,) + gp.shape),
        new_global)
    out = {**d_state, "global_params": new_global, "outer_m": new_m,
           "pod_params": new_pods}
    if new_ef is not None:
        out["pod_ef"] = new_ef
    return out


def make_diloco_round(model_cfg, fns, tcfg: TrainConfig, dcfg: DiLoCoConfig,
                      *, compress: str | None = None, topk_frac: float = 0.01,
                      data=None, screen_window: int = 0, min_screen: int = 8,
                      mesh=None, fsdp: bool = True, donate: bool = True,
                      supervise: bool = False):
    """ONE jitted, donated DiLoCo round — the device-resident training twin
    of the serving engine's fused decode block.

    Returns round(d_state, batches, pod_mask, thresholds) -> (d_state,
    metrics):
      - batches: pytree with leading (n_pods, H) axes — or, when `data` (a
        SyntheticLM) is given, an (n_pods, H) int32 array of step ids whose
        batches are generated in-graph (zero host data movement).
      - pod_mask: (n_pods,) 0/1 liveness; masked pods' inner work is
        discarded by the outer average and they rejoin on re-broadcast.
      - thresholds: traced (loss_thr, gnorm_thr) for the in-graph screens
        (ignored when screen_window=0; widenable without recompile; the
        d_state must come from diloco_init with the same screen_window).
      - metrics: (n_pods, H) loss/grad_norm + screen flags — the single
        per-round host drain.

    The inner H steps, screens, EF compression, and masked Nesterov outer
    sync all run inside the one jit: zero host round-trips inside the
    round. With `mesh`, in/out NamedShardings come from
    repro.distributed.sharding (pod replicas on "pod", FSDP on "data",
    tensor-parallel on "model"), sanitized so the same builder runs on the
    1-device CPU container and the (2, 16, 16) production mesh.

    supervise=True is the DiLoCoSupervisor contract — PER-POD rollback,
    entirely in-graph:
      - a pod any of whose inner steps tripped a screen is excluded from
        the outer average (its corrupted delta never touches the outer
        state) and rejoins on the re-broadcast global params, exactly as
        if the host had rolled the round back and replayed it with that
        pod masked — but with zero extra host syncs or snapshots;
      - the flagged pod's error-feedback residual, inner optimizer
        moments, and screen ring buffer are reset (its own state is
        suspect and would otherwise carry the corruption — NaN Adam
        moments especially — into the next round; a merely-unreachable
        pod keeps all three);
      - metrics gain "pod_bad" (n_pods,), "pod_alive" (the effective mask
        the outer step used) and "outer_ok" (global params + outer
        momentum all-finite) — the supervisor escalates to a whole-round
        rollback only when outer_ok is False.
    """
    inner = _make_pod_inner(model_cfg, fns, tcfg,
                            collect=lambda m: (m["loss"], m["grad_norm"]))

    # With a mesh AND compression, the outer hop runs in the WIRE format:
    # shard-aligned lanes derived from the same (sanitized) partition
    # specs the state shardings use, so each device quantizes exactly its
    # own tile and the s8 payload is what the pod-axis all-gather carries.
    wire_fmt = None
    if mesh is not None and compress is not None:
        from repro.distributed.compression import wire_format_for
        from repro.distributed.sharding import param_specs as _param_specs
        psds = jax.eval_shape(
            lambda: fns.init(jax.random.PRNGKey(0), model_cfg))
        wire_fmt = wire_format_for(
            psds, _param_specs(model_cfg, fsdp=fsdp), mesh, dcfg.n_pods,
            method=compress, topk_frac=topk_frac)

    def round_fn(d_state, batches, pod_mask, thresholds):
        if data is not None:
            batches = jax.vmap(jax.vmap(data.batch_at))(batches)
        new_p, new_o, (losses, gnorms) = inner(
            d_state["pod_params"], d_state["pod_opt"], d_state["step"],
            batches)
        d_state = {**d_state, "pod_params": new_p, "pod_opt": new_o,
                   "step": d_state["step"] + dcfg.inner_steps}

        if screen_window:
            def pod_screen(s, l, g):
                def body(s, lg):
                    return screen_update(s, lg[0], lg[1], thresholds[0],
                                         thresholds[1], min_screen)
                return jax.lax.scan(body, s, (l, g))

            scr, flags = jax.vmap(pod_screen)(
                d_state["screen"], losses, gnorms)
            d_state = {**d_state, "screen": scr}
        else:
            nonfinite = ~(jnp.isfinite(losses) & jnp.isfinite(gnorms))
            no = jnp.zeros_like(nonfinite)
            flags = {"nonfinite": nonfinite, "loss_spike": no,
                     "gnorm_spike": no, "suspect": nonfinite}

        metrics = {"loss": losses, "grad_norm": gnorms, **flags}
        eff_mask = pod_mask
        if supervise:
            pod_bad = jnp.any(flags["suspect"], axis=1)
            eff_mask = pod_mask * (1.0 - pod_bad.astype(jnp.float32))
        d_state = outer_step(d_state, dcfg, eff_mask, compress=compress,
                             topk_frac=topk_frac, wire=wire_fmt)
        if supervise:
            def reset_rows(tree, init_row=None):
                def per_leaf(x, i=None):
                    w = pod_bad.reshape((-1,) + (1,) * (x.ndim - 1))
                    zero = jnp.zeros_like(x) if i is None else \
                        jnp.broadcast_to(i.astype(x.dtype), x.shape)
                    return jnp.where(w, zero, x)
                if init_row is None:
                    return jax.tree.map(per_leaf, tree)
                return jax.tree.map(per_leaf, tree, init_row)

            # pod_opt zeros == a fresh init_opt_state row: the rejoining
            # pod restarts from the re-broadcast globals with clean moments
            d_state = {**d_state, "pod_opt": reset_rows(d_state["pod_opt"])}
            if "pod_ef" in d_state:
                d_state = {**d_state, "pod_ef": reset_rows(d_state["pod_ef"])}
            if screen_window:
                init = jax.tree.map(lambda x: x[None],
                                    screen_init(screen_window))
                d_state = {**d_state,
                           "screen": reset_rows(d_state["screen"], init)}
            outer_ok = jnp.stack(
                [jnp.all(jnp.isfinite(x.astype(jnp.float32)))
                 for x in (jax.tree.leaves(d_state["global_params"])
                           + jax.tree.leaves(d_state["outer_m"]))]).all()
            metrics.update(pod_bad=pod_bad, pod_alive=eff_mask,
                           outer_ok=outer_ok)
        return d_state, metrics

    donate_args = (0,) if donate else ()
    if mesh is None:
        return jax.jit(round_fn, donate_argnums=donate_args)

    from repro.distributed.sharding import (diloco_specs, param_specs,
                                            shardings_for)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    params_sds = jax.eval_shape(
        lambda: fns.init(jax.random.PRNGKey(0), model_cfg))
    d_sds = jax.eval_shape(
        partial(diloco_init, dcfg=dcfg, compress=compress,
                screen_window=screen_window),
        params_sds)
    pspecs = param_specs(model_cfg, fsdp=fsdp)
    state_sh = shardings_for(
        diloco_specs(pspecs, compress=compress is not None,
                     screen=screen_window > 0),
        d_sds, mesh)
    steps_sh = None
    if data is not None:
        steps_sh = shardings_for(
            P("pod", None),
            jax.ShapeDtypeStruct((dcfg.n_pods, dcfg.inner_steps),
                                 jnp.int32), mesh)
    mask_sh = NamedSharding(mesh, P())
    return jax.jit(round_fn,
                   in_shardings=(state_sh, steps_sh, mask_sh, None),
                   out_shardings=(state_sh, None),
                   donate_argnums=donate_args)


_snapshot_jit = jax.jit(lambda p: jax.tree.map(jnp.copy, p))


def snapshot_global_params(d_state):
    """Fresh device buffers holding the outer (global) params at the drain
    boundary — the co-residency publish hook.

    The fused round donates its input state, so any reference held into
    `d_state` (including the initial `params` passed to `diloco_init`,
    which ARE `d_state["global_params"]`'s buffers) is deleted by the next
    round call. This returns a jitted device->device tree copy: no
    device->host transfer, no host sync, and — jit without donation never
    aliases outputs to inputs — buffers that stay valid for as long as a
    `ParamPublisher` / `ServingEngine` holds them. Shapes and dtypes are
    identical across snapshots, so an engine serving from successive
    snapshots re-traces nothing.
    """
    return _snapshot_jit(d_state["global_params"])


def outer_wire_bytes(params, compress: str | None = None,
                     topk_frac: float = 0.01, wire=None) -> int:
    """Per-pod FSO bytes for ONE outer sync, from static shapes.

    With `wire` (a WireFormat) the accounting follows the shard-aligned
    lane layout — per-lane padding and per-lane top-k are charged exactly
    as the shard_map hop ships them; without it, the legacy single-lane
    formulas."""
    if wire is not None:
        from repro.distributed.compression import wire_tree_bytes
        return wire_tree_bytes(params, wire)
    total = 0
    for x in jax.tree.leaves(params):
        n = math.prod(x.shape) if x.shape else 1
        if compress == "int8":
            rows = -(-n // 256)
            total += rows * 256 + rows * 4       # int8 payload + f32 scales
        elif compress == "topk":
            k = max(1, int(n * topk_frac))
            total += 8 * k                       # f32 values + i32 indices
        else:
            total += 4 * n
    return total


def isl_bytes_per_step(n_params: int, inner_steps: int,
                       compress: str | None = None,
                       topk_frac: float = 0.01) -> dict:
    """ISL (pod-axis) traffic accounting: sync DP vs DiLoCo (§3/ref 41)."""
    sync = 4 * n_params                       # f32 grad all-reduce every step
    outer = 4 * n_params / inner_steps        # amortized delta sync
    if compress == "int8":
        outer /= 4                            # int8 payload vs f32
    elif compress == "topk":
        outer *= 8 * topk_frac / 4            # f32 value + i32 index per kept
    return {"sync_bytes_per_step": sync,
            "diloco_bytes_per_step": outer,
            "reduction": sync / outer}
