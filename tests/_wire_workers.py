"""Subprocess worker for tests/test_wire_format.py: multi-device checks
that need XLA_FLAGS set before the first jax import (the parent test
process already pinned the single real CPU device).

Runs on 8 forced CPU devices, (2, 2, 2) pod/data/model mesh — real
multi-lane shards (S > 1), real pod-axis all-gathers — and EXECUTES:

  1. wire shard_map hop vs the pod-local simulated hop in the same lane
     layout: bit-identical output trees (masked pod included);
  2. error feedback across consecutive rounds: the residual carried out
     of round 1 feeds round 2 identically on both paths;
  3. the lowered wire hop's collective bytes stay within the declared
     budget factor of the wire prediction, and the payload dtypes are
     the compressed ones (s8 for int8, s32 indices for topk).

Prints "WIRE-WORKER-OK" as the last line on success.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import collective_bytes
from repro.distributed.compression import wire_format_for
from repro.distributed.sharding import (diloco_specs, param_specs,
                                        shardings_for)
from repro.launch.mesh import make_production_mesh
from repro.models import registry
from repro.train.diloco import (LINT_BUDGET, DiLoCoConfig, diloco_init,
                                outer_step, outer_wire_bytes)


def _assert_trees_equal(a, b, what):
    flat_a, _ = jax.tree_util.tree_flatten_with_path(a)
    flat_b = jax.tree.leaves(b)
    assert len(flat_a) == len(flat_b)
    bad = [jax.tree_util.keystr(kp) for (kp, x), y in zip(flat_a, flat_b)
           if not np.array_equal(np.asarray(x), np.asarray(y))]
    assert not bad, f"{what}: trees differ at {bad[:5]}"


def main():
    cfg = registry.get_reduced_config(
        "suncatcher-lm-100m", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=64, vocab_size=256)
    fns = registry.model_fns(cfg)
    dcfg = DiLoCoConfig(n_pods=2)
    mesh = make_production_mesh(multi_pod=True, shape=(2, 2, 2))
    pspecs = param_specs(cfg, fsdp=True, multi_pod=True)
    params = fns.init(jax.random.PRNGKey(0), cfg)

    for method in ("int8", "topk"):
        fmt = wire_format_for(params, pspecs, mesh, dcfg.n_pods,
                              method=method)
        assert fmt.mesh is not None, "pod axis must host the wire hop"
        # multi-lane leaves exist (S > 1), or this worker proves nothing
        lanes = [int(np.prod(l.counts)) for l in jax.tree.leaves(
            fmt.layout, is_leaf=lambda x: hasattr(x, "counts"))]
        assert max(lanes) > 1, f"no sharded leaves on (2,2,2): {lanes}"

        d0 = diloco_init(params, dcfg, compress=method)
        key = jax.random.PRNGKey(7)
        d0 = {**d0, "pod_params": jax.tree.map(
            lambda x: x + 0.01 * jax.random.normal(
                jax.random.fold_in(key, x.size), x.shape,
                jnp.float32).astype(x.dtype), d0["pod_params"])}
        mask = jnp.asarray([1.0, 0.0])          # pod 1 masked: EF preserved
        d_sds = jax.eval_shape(lambda: d0)
        state_sh = shardings_for(
            diloco_specs(pspecs, compress=True, screen=False), d_sds, mesh)
        wire_fn = jax.jit(
            lambda d, m: outer_step(d, dcfg, pod_mask=m, wire=fmt),
            in_shardings=(state_sh, None), out_shardings=state_sh)
        sim_fn = jax.jit(
            lambda d, m: outer_step(d, dcfg, pod_mask=m,
                                    wire=fmt.simulated()),
            in_shardings=(state_sh, None), out_shardings=state_sh)

        with jax.set_mesh(mesh):
            d0_dev = jax.device_put(d0, state_sh)
            # round 1 (pod 1 dead) -> round 2 (all alive): EF residuals
            # carried across rounds on both paths
            w1 = wire_fn(d0_dev, mask)
            s1 = sim_fn(d0_dev, mask)
            _assert_trees_equal(w1, s1, f"{method} round 1")
            all_alive = jnp.ones((2,))
            w2 = wire_fn(w1, all_alive)
            s2 = sim_fn(s1, all_alive)
            _assert_trees_equal(w2, s2, f"{method} round 2 (EF carried)")
            # masked pod's EF must be preserved verbatim from its input
            ef_in = jax.tree.leaves(d0["pod_ef"])
            ef_out = jax.tree.leaves(w1["pod_ef"])
            for a, b in zip(ef_in, ef_out):
                np.testing.assert_array_equal(np.asarray(a)[1],
                                              np.asarray(b)[1])

            # bytes: the lowered hop must ship the compressed payload
            hlo = wire_fn.lower(d_sds, jax.ShapeDtypeStruct((2,),
                                jnp.float32)).compile().as_text()
        coll = collective_bytes(hlo)
        predicted = outer_wire_bytes(params, compress=method, wire=fmt)
        factor = LINT_BUDGET["outer_wire_budget_factor"]
        assert coll["wire_bytes"] <= factor * predicted, (
            method, coll["wire_bytes"], predicted)
        gathered = coll["bytes_by_dtype"].get("all-gather", {})
        if method == "int8":
            assert gathered.get("s8", 0) > 0, gathered
            assert gathered.get("s8", 0) > gathered.get("f32", 0), gathered
        else:
            assert gathered.get("s32", 0) > 0, gathered
        assert "f64" not in gathered
        print(f"[{method}] OK: wire==sim over 2 rounds, "
              f"{coll['wire_bytes']:.0f}B <= {factor}x{predicted}B, "
              f"payload dtypes {sorted(gathered)}")

    print("WIRE-WORKER-OK")


if __name__ == "__main__":
    main()
