"""Bring-up smoke test on a TPU: drive serving and training once at the full
width of suncatcher-lm-100m (12 x 768, 12 query / 4 KV heads, vocab 32768)
and check what comes out.

  python chip_smoke.py             # one chip: device, serving, training,
                                   # kernels
  python chip_smoke.py --chips 4   # four chips: DiLoCo pod axis + router
                                   # replicas, each against its reference

Each phase prints one line; any failed check raises, so the script exits
nonzero. Without a TPU it exits nonzero before any phase runs. On success
the last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Throughput figures printed here are smoke figures, not benchmarks.
`tests/test_chip_smoke.py` rehearses the phases on the CPU at tiny sizes
through their size arguments.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch import serve, train  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import Request, ServingEngine  # noqa: E402

ARCH = "suncatcher-lm-100m"

# One-chip workload (the sizes the phases below run at).
SERVE = dict(slots=32, max_len=1024, decode_block=8, requests=64,
             min_prompt=16, max_prompt=512, new_tokens=32, page_size=16)
TRAIN = dict(batch=8, seq_len=1024, steps=4, drain_every=2, pods=2,
             inner_steps=2, rounds=2)
# The paged decode of the MiniCPM-2B batch cell: 36 MHA heads of 64.
PAGED_MHA = dict(arch="minicpm-2b", slots=32, pages=128, page_size=16,
                 pool_pages=1024)
# Four-chip workload: prompts fit one prefill bucket, so each replica
# compiles few programs.
PLANE = dict(slots=8, max_len=128, decode_block=8, requests=16,
             min_prompt=4, max_prompt=16, new_tokens=32)


def phase(name, msg):
    print(f"[{name}] {msg}", flush=True)


# --------------------------------------------------------------------------
# (a) device
# --------------------------------------------------------------------------
def check_device(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is "
                 f"{devs[0].platform}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"found {len(devs)}")
    phase("device", f"{devs[0].device_kind} x{len(devs)} | jax "
          f"{jax.__version__} jaxlib {metadata.version('jaxlib')} libtpu "
          f"{metadata.version('libtpu')} | compile cache "
          f"{enable_compile_cache()}")
    return devs


# --------------------------------------------------------------------------
# (b) serving
# --------------------------------------------------------------------------
def serve_args(sz, *extra):
    """Parse the serving launcher's own flags (the --full build)."""
    return serve.build_parser().parse_args(
        ["--full", "--arch", ARCH, "--slots", str(sz["slots"]),
         "--max-len", str(sz["max_len"]),
         "--decode-block", str(sz["decode_block"]), *extra])


def prompts(sz, vocab, n, seed=0):
    """`n` prompts whose lengths cycle through the power-of-two prefill
    buckets between min_prompt and max_prompt (each wave then compiles
    every bucket), drawn uniformly inside each bucket."""
    rng = np.random.default_rng(seed)
    edges, bucket = [sz["min_prompt"] - 1], 16
    while bucket < sz["max_prompt"]:
        if bucket > edges[-1]:
            edges.append(bucket)
        bucket *= 2
    spans = list(zip(edges, edges[1:] + [sz["max_prompt"]]))
    out = []
    for i in range(n):
        lo, hi = spans[i % len(spans)]
        out.append(rng.integers(0, vocab, int(rng.integers(lo + 1, hi + 1)),
                                dtype=np.int32))
    return out


def serve_waves(eng, prompt_list, new_tokens, waves=2):
    """Serve `prompt_list` in `waves` sequential waves; returns ({uid:
    tokens}, trace count after each wave, wall seconds of the last wave)."""
    per = -(-len(prompt_list) // waves)
    marks, dt = [], 0.0
    for w in range(waves):
        for uid in range(w * per, min((w + 1) * per, len(prompt_list))):
            eng.submit(Request(uid=uid, prompt=prompt_list[uid],
                               max_new_tokens=new_tokens))
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        marks.append(eng.trace_count())
    done = eng.finished
    assert len(done) == len(prompt_list), (len(done), len(prompt_list))
    got = {r.uid: list(r.generated) for r in done}
    short = [u for u, t in got.items() if len(t) != new_tokens]
    assert not short, f"requests without {new_tokens} tokens: {short}"
    return got, marks, dt


def run_serving(sz=SERVE, builds=None):
    args = serve_args(sz)
    builds = builds or serve.build_models([ARCH], args.full)
    cfg, fns, params = builds[0]
    plist = prompts(sz, cfg.vocab_size, sz["requests"])
    # paged decode runs its kernel on the TPU; the dense engine runs the
    # dense kernel, which folds the same chunks of the same cache contents
    pcfg = replace(cfg, attn_impl="pallas")
    out = {}
    for layout, extra in (("dense", ()),
                          ("paged", ("--page-size", str(sz["page_size"])))):
        eng = ServingEngine(pcfg, fns, params,
                            serve.engine_config(serve_args(sz, *extra)))
        got, marks, dt = serve_waves(eng, plist, sz["new_tokens"])
        assert marks[0] >= 0 and marks[-1] == marks[0], \
            f"{layout}: trace count not flat after wave 1: {marks}"
        wave = len(plist) - len(plist) // 2
        phase("serve", f"{cfg.name} {layout}: {len(got)} requests x "
              f"{sz['new_tokens']} tokens on {sz['slots']} slots, traces "
              f"flat at {marks[-1]}, wave 2 {wave * sz['new_tokens'] / dt:.1f}"
              f" tok/s (smoke figure, not a benchmark)")
        out[layout] = got
    assert out["paged"] == out["dense"], "paged greedy tokens != dense"
    phase("serve", "paged greedy tokens bitwise equal to dense")
    return builds


# --------------------------------------------------------------------------
# (c) training
# --------------------------------------------------------------------------
def run_training(sz=TRAIN, full=True):
    from repro.launch.mesh import mesh_for
    from repro.train import (DiLoCoConfig, diloco_init, make_diloco_round,
                             pod_step_grid)

    argv = ["--arch", ARCH, "--steps", str(sz["steps"]),
            "--drain-every", str(sz["drain_every"]), "--batch",
            str(sz["batch"]), "--seq-len", str(sz["seq_len"])]
    args = train.build_parser().parse_args(argv + ["--full"] * full)
    hist = train.run(args)       # make_sharded_fused_steps on --mesh test
    losses = [h["loss"] for h in hist]
    assert len(losses) == sz["steps"] and np.all(np.isfinite(losses)), losses
    phase("train", f"fused steps (K={sz['drain_every']}, batch "
          f"{sz['batch']} x seq {sz['seq_len']}): losses {losses}")

    cfg, fns, tcfg, data = train.setup(args)
    dcfg = DiLoCoConfig(n_pods=sz["pods"], inner_steps=sz["inner_steps"])
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, compress="int8", data=data,
                            mesh=mesh_for("test"))
    d_state = diloco_init(fns.init(jax.random.PRNGKey(0), cfg), dcfg,
                          compress="int8")
    mask, thr = jnp.ones((dcfg.n_pods,)), jnp.zeros((2,))
    round_losses = []
    for r in range(sz["rounds"]):
        steps = jnp.asarray(pod_step_grid(r, dcfg.n_pods, dcfg.inner_steps))
        d_state, m = rnd(d_state, steps, mask, thr)
        round_losses.append(np.asarray(m["loss"]).tolist())
    assert np.all(np.isfinite(round_losses)), round_losses
    assert all(np.all(np.isfinite(np.asarray(x)))
               for x in jax.tree.leaves(d_state["global_params"]))
    phase("train", f"DiLoCo {dcfg.n_pods} pods x H={dcfg.inner_steps}, int8 "
          f"sync, {sz['rounds']} rounds: pod losses {round_losses}")


# --------------------------------------------------------------------------
# (d) kernels, compiled (never interpret mode)
# --------------------------------------------------------------------------
def _close(name, got, want, tol, why):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = float(np.max(np.abs(got - want) / (tol + tol * np.abs(want))))
    assert np.all(np.isfinite(got)) and err <= 1.0, \
        f"{name}: error {err:.3g} x the tolerance ({why})"
    phase("kernel", f"{name}: {got.shape} within {tol:g} of the reference "
          f"(worst {err:.3g} of the bound; {why})")


def run_kernels(builds, sz=SERVE, tsz=TRAIN, msz=PAGED_MHA,
                interpret=False):
    from repro.kernels.decode_attention.kernel import decode_attention_fwd
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.paged import (
        pages_per_block, paged_decode_attention_fwd,
        paged_decode_attention_reference)
    from repro.kernels.decode_attention.ref import decode_attention_reference
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_reference
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rglru_scan.ref import rglru_scan_reference
    from repro.models import registry

    cfg, fns, params = builds[0]
    b, m, ps = sz["slots"], sz["max_len"], sz["page_size"]
    h, hkv, dh, dt = cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.cdtype
    bf16 = "bf16 output: 2^-8 relative rounding, f32 sums in another order"
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    q = jax.random.normal(ks[0], (b, h, dh), dt)
    kc = jax.random.normal(ks[1], (b, m, hkv, dh), dt)
    vc = jax.random.normal(ks[2], (b, m, hkv, dh), dt)
    lens = jax.random.randint(ks[3], (b,), 0, m + 1).at[:2].set(
        jnp.asarray([0, m]))
    dense = decode_attention(q, kc, vc, lens, interpret=interpret)
    _close("decode", dense, decode_attention_reference(q, kc, vc, lens),
           2e-2, bf16)
    assert not np.asarray(dense[0], np.float32).any(), "kv_len 0 row != 0"

    # the same cache contents as a shuffled page pool + trash page
    mp = m // ps
    perm = jax.random.permutation(ks[4], b * mp)
    pool = lambda c: jnp.zeros((b * mp + 1, ps, hkv, dh), dt).at[perm].set(
        c.reshape(b * mp, ps, hkv, dh))
    ptab = perm.reshape(b, mp).astype(jnp.int32)
    ptab = jnp.where(jnp.arange(mp)[None] < -(-lens[:, None] // ps), ptab,
                     b * mp)
    kp, vp = pool(kc), pool(vc)
    paged = paged_decode_attention_fwd(q, kp, vp, ptab, lens,
                                       interpret=interpret)
    _close("paged decode", paged,
           paged_decode_attention_reference(q, kp, vp, ptab, lens), 2e-2,
           bf16)
    same = decode_attention_fwd(q, kc, vc, lens, block_k=ps,
                                interpret=interpret)
    assert np.array_equal(np.asarray(paged), np.asarray(same)), \
        "paged kernel != dense kernel at block_k = page_size"
    phase("kernel", "paged decode bitwise equal to dense decode at block_k "
          f"= page size {ps}")

    # MiniCPM widths: many MHA heads, blocks of several pages, a row at
    # kv_len 0, one ending mid-page, one on a block's end, the full table
    mcfg = registry.get_config(msz["arch"])
    mb, mmp, mps, mpool = (msz[k] for k in ("slots", "pages", "page_size",
                                             "pool_pages"))
    mh, mhkv, mdh = mcfg.n_heads, mcfg.n_kv_heads, mcfg.hd
    mk = jax.random.split(jax.random.PRNGKey(2), 5)
    mq = jax.random.normal(mk[0], (mb, mh, mdh), mcfg.cdtype)
    mkp, mvp = (jax.random.normal(k, (mpool + 1, mps, mhkv, mdh),
                                  mcfg.cdtype) for k in mk[1:3])
    block = pages_per_block(mps, mmp) * mps
    mlens = jax.random.randint(mk[3], (mb,), 0, mmp * mps + 1).at[:4].set(
        jnp.asarray([0, 5 * mps + 7, block, mmp * mps]))
    mtab = jax.random.randint(mk[4], (mb, mmp), 0, mpool)
    mtab = jnp.where(jnp.arange(mmp)[None] < -(-mlens[:, None] // mps), mtab,
                     mpool).astype(jnp.int32)
    mpaged = paged_decode_attention_fwd(mq, mkp, mvp, mtab, mlens,
                                        interpret=interpret)
    _close(f"paged decode {mcfg.name} widths ({mh} MHA heads)", mpaged,
           paged_decode_attention_reference(mq, mkp, mvp, mtab, mlens), 2e-2,
           bf16)
    assert not np.asarray(mpaged[0], np.float32).any(), "kv_len 0 row != 0"

    tb, ts = tsz["batch"], tsz["seq_len"]
    fq = jax.random.normal(ks[5], (tb, ts, h, dh), dt)
    fk = jax.random.normal(ks[6], (tb, ts, hkv, dh), dt)
    fv = jax.random.normal(ks[7], (tb, ts, hkv, dh), dt)
    _close("flash", flash_attention(fq, fk, fv, causal=True,
                                    interpret=interpret),
           attention_reference(*(x.transpose(0, 2, 1, 3)
                                 for x in (fq, fk, fv))).transpose(0, 2, 1, 3),
           2e-2, bf16)

    d = registry.get_config("recurrentgemma-2b").d_model
    a = jax.random.uniform(ks[0], (4, ts, d), jnp.float32, 0.2, 0.999)
    x = jax.random.normal(ks[1], (4, ts, d), jnp.float32)
    _close("rglru scan", rglru_scan(a, x, interpret=interpret),
           rglru_scan_reference(a, x), 1e-4,
           "same f32 recurrence; a rounding difference per step decays "
           "by a <= 0.999, so it compounds to at most ~1e3 ulp")

    # the dense engine with the decode kernel on its hot path (the paged
    # engine runs its kernel whatever attn_impl says: run_serving)
    pcfg = replace(cfg, attn_impl="pallas")
    plist = prompts(sz, cfg.vocab_size, sz["slots"], seed=1)
    args = serve.engine_config(serve_args(sz))
    got, _, _ = serve_waves(ServingEngine(pcfg, fns, params, args), plist,
                            sz["new_tokens"], waves=1)
    want, _, _ = serve_waves(ServingEngine(cfg, fns, params, args), plist,
                             sz["new_tokens"], waves=1)
    same = sum(got[u] == want[u] for u in got)
    phase("kernel", f"engine attn_impl=pallas dense: {len(got)} requests "
          f"completed; greedy tokens equal to the ref path for "
          f"{same}/{len(got)}")


# --------------------------------------------------------------------------
# four chips: DiLoCo pod axis
# --------------------------------------------------------------------------
def _tree_diff(a, b):
    """(number of differing elements, max |a - b|) over two trees."""
    pairs = [(np.asarray(x), np.asarray(y))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    return (sum(int(np.sum(x != y)) for x, y in pairs),
            max(float(np.max(np.abs(x - y))) for x, y in pairs))


def run_diloco_mesh(sz=TRAIN, full=True):
    from functools import partial

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.analysis.hlo import collective_bytes
    from repro.distributed.compression import wire_format_for
    from repro.distributed.sharding import (diloco_specs, param_specs,
                                            shardings_for)
    from repro.launch.mesh import make_production_mesh
    from repro.train import (DiLoCoConfig, diloco_init, make_diloco_round,
                             make_inner_steps, outer_step, pod_step_grid)

    args = train.build_parser().parse_args(
        ["--arch", ARCH, "--batch", str(sz["batch"]), "--seq-len",
         str(sz["seq_len"]), "--steps", str(sz["inner_steps"])]
        + ["--full"] * full)
    cfg, fns, tcfg, data = train.setup(args)
    mesh = make_production_mesh(multi_pod=True, shape=(2, 2, 1))
    dcfg = DiLoCoConfig(n_pods=2, inner_steps=sz["inner_steps"])
    params = fns.init(jax.random.PRNGKey(0), cfg)
    pspecs = param_specs(cfg, fsdp=True)
    fmt = wire_format_for(params, pspecs, mesh, dcfg.n_pods, method="int8")
    assert fmt.mesh is not None, "the pod axis must host the wire hop"
    d_sds = jax.eval_shape(partial(diloco_init, dcfg=dcfg, compress="int8"),
                           params)
    state_sh = shardings_for(diloco_specs(pspecs, compress=True), d_sds, mesh)
    rep = NamedSharding(mesh, P())
    steps = jnp.asarray(pod_step_grid(0, dcfg.n_pods, dcfg.inner_steps))
    steps_sh = shardings_for(P("pod", None), steps, mesh)
    mask, thr = jnp.ones((dcfg.n_pods,)), jnp.zeros((2,))
    d0 = jax.device_put(diloco_init(params, dcfg, compress="int8"), state_sh)

    # the fused round, wire hop: real s8 all-gathers across the pod axis
    rnd = make_diloco_round(cfg, fns, tcfg, dcfg, compress="int8", data=data,
                            mesh=mesh, donate=False)
    compiled = rnd.lower(d0, steps, mask, thr).compile()
    d_wire, metrics = compiled(d0, steps, mask, thr)
    placed = {len(x.devices()) for x in jax.tree.leaves(d_wire)}
    assert placed == {4}, f"round state not spread over 4 chips: {placed}"

    # the round's inner steps as their own program; the wire hop and the
    # vmapped simulated hop (_wire_sim_hop) then take the SAME pre-sync
    # state, so any difference between them is the hop's alone
    inner = make_inner_steps(cfg, fns, tcfg, dcfg)
    d_in, inner_losses = jax.jit(
        lambda d, s: inner(d, jax.vmap(jax.vmap(data.batch_at))(s)),
        in_shardings=(state_sh, steps_sh), out_shardings=(state_sh, None),
    )(d0, steps)
    wire_losses = jnp.mean(metrics["loss"], axis=-1)
    assert np.array_equal(np.asarray(wire_losses),
                          np.asarray(inner_losses)), (wire_losses,
                                                      inner_losses)
    hop = lambda w: jax.jit(lambda d, m: outer_step(d, dcfg, m, wire=w),
                            in_shardings=(state_sh, rep),
                            out_shardings=state_sh)
    wire_hop = hop(fmt).lower(d_in, mask).compile()
    d_hop, d_sim = wire_hop(d_in, mask), hop(fmt.simulated())(d_in, mask)

    keys = ("global_params", "outer_m", "pod_ef")
    hop_diff = {k: _tree_diff(d_hop[k], d_sim[k]) for k in keys}
    fused_diff = {k: _tree_diff(d_wire[k], d_hop[k]) for k in keys}
    phase("diloco4", f"wire hop vs simulated hop on the same state, "
          f"(differing elements, max |diff|): {hop_diff}; fused round vs "
          f"the same hop after separately compiled inner steps: "
          f"{fused_diff}")
    assert not any(n for n, _ in hop_diff.values()), hop_diff
    gathered = collective_bytes(wire_hop.as_text())["bytes_by_dtype"].get(
        "all-gather", {})
    round_gathered = collective_bytes(compiled.as_text())[
        "bytes_by_dtype"].get("all-gather", {})
    assert gathered.get("s8", 0) > gathered.get("f32", 0), gathered
    phase("diloco4", f"(pod=2, data=2, model=1) round, int8 wire: losses "
          f"{np.asarray(wire_losses).tolist()} equal the inner steps', and "
          f"the wire hop's outer params/momentum/EF are bitwise equal to "
          f"the simulated hop's; state on 4 devices")
    phase("diloco4", f"all-gathered bytes/device: outer sync {gathered}, "
          f"whole round (incl. FSDP gathers) {round_gathered}")


# --------------------------------------------------------------------------
# four chips: router replicas
# --------------------------------------------------------------------------
def run_plane(sz=PLANE, builds=None, outage="3:*:3"):
    from repro.serving import ConstellationRouter, check_forced_outage_contract

    n = 4
    args = serve_args(sz, "--replicas", str(n), "--force-outage-at", outage,
                      "--expect-pointer-flip")
    builds = builds or serve.build_models([ARCH], args.full)
    cfg, fns, params = builds[0]
    plane = serve.build_plane(builds, args)
    assert isinstance(plane, ConstellationRouter)
    devs = jax.devices()[:n]
    for i, e in enumerate(plane.engines):
        held = {d for x in jax.tree.leaves((e.params, e.cache, e.state))
                for d in x.devices()}
        assert held == {devs[i]}, f"replica {i} holds arrays on {held}"
    plist = prompts(sz, cfg.vocab_size, sz["requests"], seed=2)
    for uid, p in enumerate(plist):
        plane.submit(Request(uid=uid, prompt=p,
                             max_new_tokens=sz["new_tokens"]))
    done = plane.run()
    check_forced_outage_contract(plane, done, len(plist),
                                 expect_pointer_flip=True)
    got = {r.uid: list(r.generated) for r in done}
    single = ServingEngine(cfg, fns, params, serve.engine_config(args))
    want, _, _ = serve_waves(single, plist, sz["new_tokens"], waves=1)
    assert got == want, "router tokens != single-engine tokens"
    s = plane.plane_stats()
    phase("plane4", f"{n} replicas on devices {[str(d) for d in devs]}: "
          f"outage '{outage}', {s['pointer_flips']} pointer flips + "
          f"{s['full_migrations']} full drains, {len(done)} requests, "
          f"greedy tokens equal to a single engine's")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the paths that exist across chips")
    chips = ap.parse_args().chips
    devs = check_device(chips)
    if chips == 4:
        run_diloco_mesh()
        run_plane()
    else:
        builds = run_serving()
        run_training()
        run_kernels(builds)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
