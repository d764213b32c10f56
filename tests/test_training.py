"""Training-substrate integration tests: loop, schedule, data determinism,
checkpoint integrity, SDC detection/rollback, DiLoCo (incl. the fused
device-resident round), compression."""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.models import registry
from repro.train import (AdamWConfig, DataConfig, DiLoCoConfig,
                         DiLoCoSupervisor, FTConfig, FaultTolerantTrainer,
                         SyntheticLM, TrainConfig, diloco_init,
                         init_train_state, make_diloco_round,
                         make_fused_steps, make_inner_steps,
                         make_sharded_train_step, make_train_step,
                         outer_step, screen_init, screen_update)
from repro.train import checkpoint as ckpt
from repro.train.diloco import isl_bytes_per_step
from repro.train.schedule import warmup_cosine, wsd


def _tiny_setup(seed=0, lr=3e-3):
    cfg = registry.get_reduced_config("suncatcher-lm-100m")
    fns = registry.model_fns(cfg)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=lr), warmup_steps=5,
                       total_steps=200)
    state = init_train_state(jax.random.PRNGKey(seed), cfg, fns)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                  global_batch=8, seed=seed))
    step = jax.jit(make_train_step(cfg, fns, tcfg))
    return cfg, fns, state, data, step


def _micro_diloco_setup(n_pods=2, inner_steps=4):
    """Deliberately tiny (d_model=32) so the many fused-round jit variants
    compile fast."""
    cfg = registry.get_reduced_config(
        "suncatcher-lm-100m", n_layers=2, d_model=32, n_heads=2,
        n_kv_heads=1, d_ff=64, vocab_size=256)
    fns = registry.model_fns(cfg)
    tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=2,
                       total_steps=100)
    dcfg = DiLoCoConfig(n_pods=n_pods, inner_steps=inner_steps)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=8,
                                  global_batch=2))
    params = fns.init(jax.random.PRNGKey(0), cfg)
    return cfg, fns, tcfg, dcfg, data, params


def _assert_trees_equal(a, b, keys=None):
    if keys is not None:
        a = {k: a[k] for k in keys}
        b = {k: b[k] for k in keys}
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


class TestTrainLoop:
    def test_loss_decreases(self):
        _, _, state, data, step = _tiny_setup()
        losses = []
        for s in range(30):
            state, m = step(state, data.batch_at(s))
            losses.append(float(m["loss"]))
        assert np.mean(losses[-5:]) < 0.7 * np.mean(losses[:5])

    def test_microbatching_matches_full_batch_loss(self):
        cfg = registry.get_reduced_config("suncatcher-lm-100m")
        fns = registry.model_fns(cfg)
        state = init_train_state(jax.random.PRNGKey(0), cfg, fns)
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                      global_batch=8))
        batch = data.batch_at(0)
        t1 = TrainConfig(microbatches=1)
        t4 = TrainConfig(microbatches=4)
        _, m1 = make_train_step(cfg, fns, t1)(state, batch)
        _, m4 = make_train_step(cfg, fns, t4)(state, batch)
        assert float(m1["loss"]) == pytest.approx(float(m4["loss"]),
                                                  rel=1e-4)

    def test_schedules(self):
        assert float(warmup_cosine(0, warmup=10, total=100)) == 0.0
        assert float(warmup_cosine(10, warmup=10, total=100)) == \
            pytest.approx(1.0, abs=0.01)
        assert float(warmup_cosine(100, warmup=10, total=100)) == \
            pytest.approx(0.1, abs=0.01)
        assert float(wsd(50, warmup=10, total=100)) == 1.0
        assert float(wsd(100, warmup=10, total=100)) == \
            pytest.approx(0.01, abs=0.005)


class TestData:
    def test_deterministic_replay(self):
        data = SyntheticLM(DataConfig(seed=7))
        b1, b2 = data.batch_at(123), data.batch_at(123)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))

    def test_different_steps_differ(self):
        data = SyntheticLM(DataConfig(seed=7))
        assert not np.array_equal(np.asarray(data.batch_at(0)["tokens"]),
                                  np.asarray(data.batch_at(1)["tokens"]))

    def test_labels_are_shifted_tokens(self):
        data = SyntheticLM(DataConfig())
        b = data.batch_at(0)
        np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                      np.asarray(b["labels"][:, :-1]))


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        _, _, state, _, _ = _tiny_setup()
        ckpt.save(state, str(tmp_path), 7)
        step, restored = ckpt.restore_into(state, str(tmp_path))
        assert step == 7
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_corruption_detected_and_replica_used(self, tmp_path):
        _, _, state, _, _ = _tiny_setup()
        d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
        ckpt.save_replicated(state, [d1, d2], 3)
        # corrupt the newest replica's arrays in d1
        path = os.path.join(d1, "step-00000003", "arrays.npz")
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(path, "wb").write(bytes(raw))
        step, restored = ckpt.restore_latest(state, [d1, d2])
        assert step == 3   # served from the intact replica
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_retention(self, tmp_path):
        _, _, state, _, _ = _tiny_setup()
        for s in range(5):
            ckpt.save(state, str(tmp_path), s, keep=2)
        names = sorted(os.listdir(tmp_path))
        assert names == ["step-00000003", "step-00000004"]

    def test_prune_tolerates_vanished_entries(self, tmp_path, monkeypatch):
        """save_async threads race in _prune: entries listed by one thread
        may already be gone when it gets to rmtree them."""
        _, _, state, _, _ = _tiny_setup()
        d = str(tmp_path)
        ckpt.save(state, d, 7, keep=5)
        real_listdir = os.listdir
        monkeypatch.setattr(
            os, "listdir",
            lambda p: (["step-00000001", "step-00000002"] + real_listdir(p)
                       if str(p) == d else real_listdir(p)))
        ckpt._prune(d, 1)          # ghost entries: must not raise
        monkeypatch.undo()
        assert sorted(os.listdir(d)) == ["step-00000007"]
        ckpt._prune(str(tmp_path / "never-existed"), 1)   # also quiet

    def test_concurrent_saves_do_not_race(self, tmp_path):
        from concurrent.futures import ThreadPoolExecutor
        _, _, state, _, _ = _tiny_setup()
        state = jax.tree.map(np.asarray, state)
        d = str(tmp_path)
        with ThreadPoolExecutor(max_workers=4) as ex:
            futs = [ex.submit(ckpt.save, state, d, s, 1) for s in range(8)]
            for f in futs:
                f.result()   # propagates any prune/rename race exception
        # the newest surviving checkpoint restores cleanly
        step, restored = ckpt.restore_latest(state, [d])
        assert step in range(8)


class TestFaultTolerance:
    def test_sdc_detected_and_rolled_back(self, tmp_path):
        from repro.core.radiation import RadiationEnvironment, SDCInjector
        _, _, state, data, step = _tiny_setup()
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=10,
                      gnorm_threshold=8.0, loss_threshold=2.5)
        inj = SDCInjector(RadiationEnvironment(), n_chips=1, step_time_s=1.0,
                          rate_multiplier=0.0)
        tr = FaultTolerantTrainer(step, state, data, ft, injector=inj)
        # big burst of flips at step 25 -> must be caught, training continues
        hist = tr.run(40, forced_sdc_at={25: 2048})
        assert tr.stats["sdc_injected"] >= 2048
        assert tr.stats["rollbacks"] >= 1
        assert int(tr.state["step"]) == 40
        losses = [h["loss"] for h in hist]
        assert np.isfinite(losses).all()

    def test_clean_run_no_rollbacks(self, tmp_path):
        _, _, state, data, step = _tiny_setup()
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=20)
        tr = FaultTolerantTrainer(step, state, data, ft)
        tr.run(25)
        assert tr.stats["rollbacks"] == 0
        assert tr.stats["checkpoints"] >= 2

    def test_checkpoints_are_async_joined_and_restorable(self, tmp_path):
        """Snapshots now ride background serializer threads off the drain
        boundary (like DiLoCoSupervisor's): run() must join them before
        returning, both replica dirs must hold the final verified
        snapshot, and the async-written replicas must restore
        bit-identically to the live state they captured."""
        _, _, state, data, step = _tiny_setup()
        ft = FTConfig(checkpoint_dirs=(str(tmp_path / "a"),
                                       str(tmp_path / "b")),
                      checkpoint_every=10)
        tr = FaultTolerantTrainer(step, state, data, ft)
        tr.run(20)
        assert tr._ckpt_threads == []           # run() joined the writers
        for d in ft.checkpoint_dirs:
            names = sorted(p for p in os.listdir(d)
                           if p.startswith("step-"))
            assert names and names[-1] == "step-00000020"
        got_step, restored = ckpt.restore_latest(
            jax.tree.map(np.asarray, tr.state), ft.checkpoint_dirs)
        assert got_step == 20
        _assert_trees_equal(restored, jax.tree.map(np.asarray, tr.state))

    def test_persistent_spike_widens_thresholds_and_completes(self,
                                                              tmp_path):
        """A GENUINE loss spike (not transient SDC) re-triggers the same
        screen after every bit-deterministic replay — the seed supervisor
        livelocked forever. The cap + threshold widening must let the run
        finish."""
        cfg, fns, state, data, _ = _tiny_setup()
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        raw = make_train_step(cfg, fns, tcfg)

        def spiky(state, batch):   # deterministic, persists across replays
            st, m = raw(state, batch)
            f = jnp.where(state["step"] == 19, 50.0, 1.0)
            return st, {**m, "loss": m["loss"] * f}

        # spike lands >= min_screen steps after the checkpoint, so the
        # screen re-arms during every replay
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=10)
        tr = FaultTolerantTrainer(jax.jit(spiky), state, data, ft)
        hist = tr.run(25)
        assert int(tr.state["step"]) == 25
        assert tr.stats["threshold_widenings"] >= 1
        assert tr.stats["rollbacks"] > ft.max_rollbacks_per_step
        assert hist[-1]["step"] == 24   # reached the end despite the spike

    def test_persistent_nonfinite_raises_instead_of_livelock(self,
                                                             tmp_path):
        cfg, fns, state, data, _ = _tiny_setup()
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        raw = make_train_step(cfg, fns, tcfg)

        def nan_step(state, batch):
            st, m = raw(state, batch)
            f = jnp.where(state["step"] == 19, jnp.nan, 1.0)
            return st, {**m, "loss": m["loss"] * f}

        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=10)
        tr = FaultTolerantTrainer(jax.jit(nan_step), state, data, ft)
        with pytest.raises(RuntimeError, match="non-finite"):
            tr.run(25)

    def test_run_fused_matches_per_step_run(self, tmp_path):
        """Device-screened block mode must train bit-identically to the
        per-step host loop on a clean run, with ~1/K the host syncs."""
        cfg, fns, state, data, step = _tiny_setup()
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        ft1 = FTConfig(checkpoint_dirs=(str(tmp_path / "a"),),
                       checkpoint_every=16)
        tr1 = FaultTolerantTrainer(step, state, data, ft1)
        h1 = tr1.run(24)

        fused = jax.jit(make_fused_steps(cfg, fns, tcfg),
                        donate_argnums=(0, 1))
        state2 = init_train_state(jax.random.PRNGKey(0), cfg, fns)
        ft2 = FTConfig(checkpoint_dirs=(str(tmp_path / "b"),),
                       checkpoint_every=16, drain_every=8)
        tr2 = FaultTolerantTrainer(step, state2, data, ft2,
                                   fused_steps=fused)
        h2 = tr2.run_fused(24)
        _assert_trees_equal(tr1.state, tr2.state)
        assert tr2.stats["drains"] == 3
        assert [h["loss"] for h in h1] == [h["loss"] for h in h2]

    def test_run_fused_tail_screens_stay_armed(self, tmp_path):
        """The ragged tail falls back to run(); the host deques must be
        pre-seeded from the drained blocks or a finite spike in the last
        n_steps % K steps would pass with the median screens disarmed."""
        cfg, fns, state, data, _ = _tiny_setup()
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        raw = make_train_step(cfg, fns, tcfg)

        def spiky(state, batch):   # spike inside the tail (steps 16..19)
            st, m = raw(state, batch)
            f = jnp.where(state["step"] == 17, 50.0, 1.0)
            return st, {**m, "loss": m["loss"] * f}

        fused = jax.jit(make_fused_steps(cfg, fns, tcfg, step_fn=spiky),
                        donate_argnums=(0, 1))
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=10,
                      drain_every=8)
        tr = FaultTolerantTrainer(jax.jit(spiky), state, data, ft,
                                  fused_steps=fused)
        tr.run_fused(20)
        assert int(tr.state["step"]) == 20
        assert tr.stats["rollbacks"] >= 1   # tail spike was caught

    def test_run_fused_rejects_host_driven_mechanisms(self, tmp_path):
        """The injector and duplicate-step verify are per-step host
        mechanisms; run_fused must refuse rather than silently skip them."""
        from repro.core.radiation import RadiationEnvironment, SDCInjector
        _, _, state, data, step = _tiny_setup()
        inj = SDCInjector(RadiationEnvironment(), n_chips=1, step_time_s=1.0)
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), drain_every=8)
        tr = FaultTolerantTrainer(step, state, data, ft, injector=inj,
                                  fused_steps=lambda *a: None)
        with pytest.raises(ValueError, match="SDCInjector"):
            tr.run_fused(16)

    def test_run_fused_detects_and_recovers_from_spike(self, tmp_path):
        cfg, fns, state, data, _ = _tiny_setup()
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        raw = make_train_step(cfg, fns, tcfg)

        def spiky(state, batch):
            st, m = raw(state, batch)
            f = jnp.where(state["step"] == 19, 50.0, 1.0)
            return st, {**m, "loss": m["loss"] * f}

        fused = jax.jit(make_fused_steps(cfg, fns, tcfg, step_fn=spiky),
                        donate_argnums=(0, 1))
        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=10,
                      drain_every=5)
        tr = FaultTolerantTrainer(jax.jit(spiky), state, data, ft,
                                  fused_steps=fused)
        hist = tr.run_fused(25)
        assert int(tr.state["step"]) == 25
        assert tr.stats["rollbacks"] >= 1
        assert tr.stats["threshold_widenings"] >= 1
        assert np.isfinite([h["loss"] for h in hist]).all()


class TestDiLoCo:
    def test_diloco_trains_and_matches_sync_ballpark(self):
        cfg = registry.get_reduced_config("suncatcher-lm-100m")
        fns = registry.model_fns(cfg)
        tcfg = TrainConfig(adamw=AdamWConfig(lr=3e-3), warmup_steps=5,
                           total_steps=200)
        dcfg = DiLoCoConfig(n_pods=2, inner_steps=5)
        data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                      global_batch=4))
        params = fns.init(jax.random.PRNGKey(0), cfg)
        d_state = diloco_init(params, dcfg)
        inner = jax.jit(make_inner_steps(cfg, fns, tcfg, dcfg))

        losses = []
        s = 0
        for outer in range(6):
            batches = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[jax.tree.map(lambda *h: jnp.stack(h),
                               *[data.batch_at(s + p * 1000 + i)
                                 for i in range(dcfg.inner_steps)])
                  for p in range(dcfg.n_pods)])
            d_state, loss = inner(d_state, batches)   # loss: (n_pods,)
            d_state = outer_step(d_state, dcfg)
            losses.append(float(jnp.mean(loss)))
            s += dcfg.inner_steps
        assert losses[-1] < 0.7 * losses[0]

    def test_pod_dropout_masked_outer_step(self):
        cfg = registry.get_reduced_config("suncatcher-lm-100m")
        fns = registry.model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        dcfg = DiLoCoConfig(n_pods=3, inner_steps=1)
        d_state = diloco_init(params, dcfg)
        # poison pod 2's params: with the mask, outer step must ignore them
        poison = jax.tree.map(
            lambda x: x.at[2].set(jnp.nan), d_state["pod_params"])
        d_state = {**d_state, "pod_params": poison}
        out = outer_step(d_state, dcfg, pod_mask=jnp.array([1.0, 1.0, 0.0]))
        for leaf in jax.tree.leaves(out["global_params"]):
            assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))

    def test_isl_traffic_accounting(self):
        acct = isl_bytes_per_step(int(1e9), inner_steps=50, compress="int8")
        assert acct["reduction"] == pytest.approx(200.0)

    def test_all_dead_outer_step_is_noop(self):
        """Regression: with an all-zero pod mask the clamped denominator
        used to turn 'no surviving deltas' into a full global - 0 Nesterov
        update; a fully-dead round must leave params + momentum unchanged."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup(n_pods=3)
        d_state = diloco_init(params, dcfg)
        # give the momentum + replicas non-trivial values first
        inner = jax.jit(make_inner_steps(cfg, fns, tcfg, dcfg))
        d_state, _ = inner(d_state, data.batch_block(
            np.arange(3 * dcfg.inner_steps).reshape(3, -1)))
        d_state = outer_step(d_state, dcfg)
        d_live, _ = inner(d_state, data.batch_block(
            np.arange(100, 100 + 3 * dcfg.inner_steps).reshape(3, -1)))
        out = outer_step(d_live, dcfg, pod_mask=jnp.zeros((3,)))
        _assert_trees_equal(out, d_live, keys=("global_params", "outer_m"))
        # dead pods rejoin on the (unchanged) global params
        for gp, pp in zip(jax.tree.leaves(out["global_params"]),
                          jax.tree.leaves(out["pod_params"])):
            for p in range(3):
                np.testing.assert_array_equal(np.asarray(pp[p]),
                                              np.asarray(gp))


class TestDiLoCoFused:
    """The fused device-resident round must be bit-identical to the
    (jitted) make_inner_steps + outer_step sequence it replaces."""

    @pytest.mark.parametrize("mask", [(1.0, 1.0), (1.0, 0.0)])
    def test_fused_round_bit_identical(self, mask):
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        batches = data.batch_block(
            np.arange(dcfg.n_pods * dcfg.inner_steps).reshape(dcfg.n_pods,
                                                              -1))
        pod_mask = jnp.asarray(mask, jnp.float32)
        thr = jnp.asarray([3.0, 10.0], jnp.float32)

        inner = jax.jit(make_inner_steps(cfg, fns, tcfg, dcfg))
        outer = jax.jit(partial(outer_step, dcfg=dcfg))
        ref, _ = inner(diloco_init(params, dcfg), batches)
        ref = outer(ref, pod_mask=pod_mask)

        rnd = make_diloco_round(cfg, fns, tcfg, dcfg, donate=False)
        got, metrics = rnd(diloco_init(params, dcfg), batches, pod_mask,
                           thr)
        _assert_trees_equal(got, ref)
        assert metrics["loss"].shape == (dcfg.n_pods, dcfg.inner_steps)
        assert not bool(np.asarray(metrics["suspect"]).any())

    @pytest.mark.parametrize("method", ["int8", "topk"])
    def test_fused_round_compressed_bit_identical(self, method):
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        batches = data.batch_block(
            np.arange(dcfg.n_pods * dcfg.inner_steps).reshape(dcfg.n_pods,
                                                              -1))
        pod_mask = jnp.asarray([1.0, 1.0], jnp.float32)
        thr = jnp.asarray([3.0, 10.0], jnp.float32)

        inner = jax.jit(make_inner_steps(cfg, fns, tcfg, dcfg))
        outer = jax.jit(partial(outer_step, dcfg=dcfg, compress=method))
        ref, _ = inner(diloco_init(params, dcfg, compress=method), batches)
        ref = outer(ref, pod_mask=pod_mask)

        rnd = make_diloco_round(cfg, fns, tcfg, dcfg, compress=method,
                                donate=False)
        got, _ = rnd(diloco_init(params, dcfg, compress=method), batches,
                     pod_mask, thr)
        _assert_trees_equal(got, ref)
        # error feedback engaged: residuals are non-zero after a round
        assert any(float(jnp.abs(x).max()) > 0
                   for x in jax.tree.leaves(got["pod_ef"]))

    def test_fused_round_mesh_and_in_graph_data(self):
        """The sharded round (CPU test mesh) and the in-graph data variant
        both produce the same training math as the plain round."""
        from repro.launch.mesh import make_test_mesh
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        steps = np.arange(dcfg.n_pods * dcfg.inner_steps).reshape(
            dcfg.n_pods, -1)
        batches = data.batch_block(steps)
        pod_mask = jnp.ones((dcfg.n_pods,), jnp.float32)
        thr = jnp.asarray([3.0, 10.0], jnp.float32)

        plain = make_diloco_round(cfg, fns, tcfg, dcfg, donate=False)
        ref, _ = plain(diloco_init(params, dcfg), batches, pod_mask, thr)

        meshed = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                                   screen_window=16,
                                   mesh=make_test_mesh(), donate=False)
        got, metrics = meshed(diloco_init(params, dcfg, screen_window=16),
                              jnp.asarray(steps, jnp.int32), pod_mask, thr)
        _assert_trees_equal(got, ref, keys=("global_params", "pod_params",
                                            "outer_m", "pod_opt"))
        # the in-graph screens saw every clean inner step
        np.testing.assert_array_equal(np.asarray(got["screen"]["count"]),
                                      dcfg.inner_steps)
        assert not bool(np.asarray(metrics["suspect"]).any())

    def test_fused_round_donation(self):
        """donate_argnums is on by default: the round consumes its input
        state (in-place buffer reuse on the hot path)."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        batches = data.batch_block(
            np.arange(dcfg.n_pods * dcfg.inner_steps).reshape(dcfg.n_pods,
                                                              -1))
        rnd = make_diloco_round(cfg, fns, tcfg, dcfg)
        d0 = diloco_init(params, dcfg)
        d1, _ = rnd(d0, batches, jnp.ones((dcfg.n_pods,)),
                    jnp.asarray([3.0, 10.0], jnp.float32))
        leaf = jax.tree.leaves(d0["pod_params"])[0]
        assert leaf.is_deleted()
        assert int(d1["step"]) == dcfg.inner_steps


class TestDiLoCoSupervisor:
    """Constellation-in-the-loop supervisor: in-graph per-pod rollback,
    whole-round rollback only for suspect outer state, bit-deterministic
    replay."""

    def test_forced_rollback_replay_bit_identical(self, tmp_path):
        """A whole-round rollback replays bit-deterministically: final
        state and loss history identical to an uninterrupted run, and the
        history is truncated at the snapshot round (regression: the old
        launcher loop re-appended replayed rounds to mean_losses, skewing
        the printed first->last loss)."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        rnd = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                                screen_window=16, supervise=True)

        def mk(sub):
            ft = FTConfig(checkpoint_dirs=(str(tmp_path / sub / "a"),
                                           str(tmp_path / sub / "b")),
                          checkpoint_every=8)
            return DiLoCoSupervisor(
                rnd, diloco_init(params, dcfg, screen_window=16), dcfg, ft)

        s1 = mk("clean")
        h1 = s1.run(6)
        s2 = mk("forced")
        h2 = s2.run(6, forced_rollback_at=[3])
        _assert_trees_equal(s1.d_state, s2.d_state)
        assert [h["loss"] for h in h1] == [h["loss"] for h in h2]
        assert len(s2.mean_losses) == 6    # no duplicated replay rounds
        assert s2.stats["rollbacks"] == 1
        # forced at round 3, snapshot cadence 2 -> replays rounds 2 and 3
        assert s2.stats["drains"] == 8
        assert s2.stats["replay_verified_rounds"] >= 1
        assert s2.stats["replay_mismatches"] == 0
        # replicated checkpoints landed in both replica directories
        assert any((tmp_path / "forced" / "a").iterdir())
        assert any((tmp_path / "forced" / "b").iterdir())

    def test_restore_from_checkpoint_resumes_bit_identically(self,
                                                             tmp_path):
        """Restart-class (SEFI/UECC) recovery: a NEW supervisor process
        restores the newest checksum-verified replica and finishes the run
        bit-identically to an uninterrupted one."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        rnd = make_diloco_round(cfg, fns, tcfg, dcfg, data=data,
                                screen_window=16, supervise=True)

        def mk(sub):
            ft = FTConfig(checkpoint_dirs=(str(tmp_path / sub / "a"),
                                           str(tmp_path / sub / "b")),
                          checkpoint_every=8)
            return DiLoCoSupervisor(
                rnd, diloco_init(params, dcfg, screen_window=16), dcfg, ft)

        s1 = mk("clean")
        s1.run(6)

        s2 = mk("crashed")
        s2.run(4)          # snapshots land at rounds 2 and 4, then "SEFI"
        s3 = mk("crashed")   # fresh process over the same replica dirs
        assert s3.restore_from_checkpoint() == 4
        s3.run(6)
        _assert_trees_equal(s1.d_state, s3.d_state)

    def test_persistent_outer_corruption_raises_not_livelock(self,
                                                             tmp_path):
        """Bit-deterministic replay re-produces a genuine outer corruption
        forever; the supervisor must raise past the rollback cap even when
        interleaved per-pod detections keep resetting DetectionPolicy's
        consecutive-label counter."""
        dcfg = DiLoCoConfig(n_pods=2, inner_steps=4)

        def bad_round(d, grid, mask, thr):
            # replay-deterministic fake: pod 0 trips a screen at round 0,
            # the OUTER state is corrupt at round 1 -> every rollback
            # replays 'pod 0' between two 'round 1' detections, so
            # DetectionPolicy's same-label consecutive counter never
            # exceeds 1 and only the supervisor-side cap can fire
            r = int(np.asarray(grid)[0, 0]) // dcfg.inner_steps
            z = jnp.zeros((2, 4), bool)
            return d, {"loss": jnp.ones((2, 4)),
                       "grad_norm": jnp.ones((2, 4)),
                       "nonfinite": z, "loss_spike": z, "gnorm_spike": z,
                       "suspect": z,
                       "pod_bad": jnp.asarray([r == 0, False]),
                       "pod_alive": mask,
                       "outer_ok": jnp.asarray(r != 1)}

        ft = FTConfig(checkpoint_dirs=(str(tmp_path),), checkpoint_every=8)
        sup = DiLoCoSupervisor(bad_round,
                               {"step": jnp.zeros((), jnp.int32)}, dcfg, ft)
        with pytest.raises(RuntimeError, match="outer"):
            sup.run(4)
        # raised on the detection past the cap, before a 4th rollback
        assert sup.stats["rollbacks"] == ft.max_rollbacks_per_step

    def test_supervise_round_per_pod_rollback(self):
        """A NaN-poisoned pod is rolled back per-pod, in-graph: its delta
        never reaches the outer state (bit-identical to replaying the
        round with that pod masked), it rejoins on the re-broadcast
        globals, and its opt moments + screen are reset."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        batches = data.batch_block(
            np.arange(dcfg.n_pods * dcfg.inner_steps).reshape(dcfg.n_pods,
                                                              -1))
        thr = jnp.asarray([3.0, 10.0], jnp.float32)
        ones = jnp.ones((dcfg.n_pods,), jnp.float32)

        def poisoned():
            d = diloco_init(params, dcfg, screen_window=16)
            pp = jax.tree.map(lambda x: x.at[1].set(jnp.nan),
                              d["pod_params"])
            return {**d, "pod_params": pp}

        sup = make_diloco_round(cfg, fns, tcfg, dcfg, screen_window=16,
                                supervise=True, donate=False)
        got, m = sup(poisoned(), batches, ones, thr)
        np.testing.assert_array_equal(np.asarray(m["pod_bad"]),
                                      [False, True])
        assert bool(np.asarray(m["outer_ok"]))
        np.testing.assert_array_equal(np.asarray(m["pod_alive"]),
                                      [1.0, 0.0])
        # reference: the same round replayed with pod 1 hand-masked
        plain = make_diloco_round(cfg, fns, tcfg, dcfg, screen_window=16,
                                  donate=False)
        ref, _ = plain(poisoned(), batches,
                       jnp.asarray([1.0, 0.0], jnp.float32), thr)
        _assert_trees_equal(got, ref, keys=("global_params", "outer_m",
                                            "pod_params"))
        for leaf in jax.tree.leaves(got["global_params"]):
            assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
        # pod 1 rejoined with fresh optimizer moments; pod 0 kept its own
        for leaf in jax.tree.leaves(got["pod_opt"]):
            np.testing.assert_array_equal(np.asarray(leaf[1]),
                                          np.zeros_like(leaf[1]))
        assert float(max(jnp.max(jnp.abs(leaf[0].astype(jnp.float32)))
                         for leaf in jax.tree.leaves(got["pod_opt"]))) > 0
        np.testing.assert_array_equal(np.asarray(got["screen"]["count"]),
                                      [dcfg.inner_steps, 0])

    def test_supervise_one_pod_equals_whole_round_rollback(self):
        """1-pod config: flagging the only pod makes the round an outer
        no-op — global params and outer momentum stay bit-identical to the
        pre-round snapshot a whole-round rollback would restore, and the
        pod rejoins on the (unchanged) re-broadcast globals."""
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup(n_pods=1)
        thr = jnp.asarray([3.0, 10.0], jnp.float32)
        ones = jnp.ones((1,), jnp.float32)
        rnd = make_diloco_round(cfg, fns, tcfg, dcfg, screen_window=16,
                                supervise=True, donate=False)
        # one clean round first so outer momentum is non-trivial
        d1, m1 = rnd(diloco_init(params, dcfg, screen_window=16),
                     data.batch_block(np.arange(dcfg.inner_steps)[None]),
                     ones, thr)
        assert not bool(np.asarray(m1["pod_bad"]).any())
        pre = jax.tree.map(np.asarray, d1)
        poisoned = {**d1, "pod_params": jax.tree.map(
            lambda x: x * jnp.nan, d1["pod_params"])}
        d2, m2 = rnd(poisoned,
                     data.batch_block(
                         (dcfg.inner_steps
                          + np.arange(dcfg.inner_steps))[None]),
                     ones, thr)
        assert bool(np.asarray(m2["pod_bad"]).all())
        assert bool(np.asarray(m2["outer_ok"]))
        _assert_trees_equal(d2, pre, keys=("global_params", "outer_m"))
        for gp, pp in zip(jax.tree.leaves(d2["global_params"]),
                          jax.tree.leaves(d2["pod_params"])):
            np.testing.assert_array_equal(np.asarray(pp[0]),
                                          np.asarray(gp))


class TestDeviceScreens:
    def test_spike_flagged_after_window_arms(self):
        s = screen_init(16)
        thr_l, thr_g = jnp.float32(3.0), jnp.float32(10.0)
        for _ in range(10):
            s, flags = screen_update(s, jnp.float32(1.0), jnp.float32(0.5),
                                     thr_l, thr_g)
            assert not bool(flags["suspect"])
        s, flags = screen_update(s, jnp.float32(50.0), jnp.float32(0.5),
                                 thr_l, thr_g)
        assert bool(flags["loss_spike"]) and bool(flags["suspect"])
        # the flagged sample must NOT enter the ring (median stays clean)
        assert int(s["count"]) == 10
        s, flags = screen_update(s, jnp.float32(1.0), jnp.float32(20.0),
                                 thr_l, thr_g)
        assert bool(flags["gnorm_spike"])

    def test_nonfinite_always_flags(self):
        s = screen_init(16)
        s, flags = screen_update(s, jnp.float32(jnp.nan), jnp.float32(1.0),
                                 jnp.float32(3.0), jnp.float32(10.0))
        assert bool(flags["nonfinite"]) and bool(flags["suspect"])
        assert int(s["count"]) == 0

    def test_screens_quiet_before_window_arms(self):
        s = screen_init(16)
        for loss in [1.0, 100.0, 1.0]:   # spikes before min_count: no flag
            s, flags = screen_update(s, jnp.float32(loss), jnp.float32(1.0),
                                     jnp.float32(3.0), jnp.float32(10.0))
            assert not bool(flags["suspect"])


class TestSharding:
    def test_test_mesh_axes_are_auto(self):
        """jax.make_mesh builds Explicit axes by default, under which the
        models' unannotated embedding gather raises ShardingTypeError."""
        from jax.sharding import AxisType

        from repro.launch.mesh import make_test_mesh
        assert set(make_test_mesh().axis_types) == {AxisType.Auto}

    def test_launcher_default_sharded_fused_path(self):
        """`python -m repro.launch.train` defaults to --mesh test with
        fused drains: the sharded fused path must train on that mesh."""
        from repro.launch import train
        args = train.build_parser().parse_args(
            ["--steps", "2", "--drain-every", "2", "--seq-len", "16",
             "--batch", "2"])
        assert args.mesh == "test" and not args.full
        hist = train.run(args)
        assert len(hist) == 2
        assert np.all(np.isfinite([h["loss"] for h in hist]))

    def test_sharded_train_step_bit_identical(self):
        from repro.launch.mesh import make_test_mesh
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        batch = data.batch_at(0)
        state = init_train_state(jax.random.PRNGKey(0), cfg, fns)
        s1, m1 = jax.jit(make_train_step(cfg, fns, tcfg))(state, batch)
        sharded = make_sharded_train_step(cfg, fns, tcfg, make_test_mesh(),
                                          batch, donate=False)
        s2, m2 = sharded(state, batch)
        _assert_trees_equal(s1, s2)
        assert np.asarray(m1["loss"]).tobytes() == \
            np.asarray(m2["loss"]).tobytes()

    def test_sharded_fused_steps_bit_identical(self):
        from repro.launch.mesh import make_test_mesh
        from repro.train import make_sharded_fused_steps
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        K = 4
        batches = data.batch_block(np.arange(K))
        thr = jnp.asarray([3.0, 10.0], jnp.float32)
        state = init_train_state(jax.random.PRNGKey(0), cfg, fns)

        plain = jax.jit(make_fused_steps(cfg, fns, tcfg))
        s1, scr1, blk1 = plain(state, screen_init(8), batches, thr)

        sharded = make_sharded_fused_steps(cfg, fns, tcfg, make_test_mesh(),
                                           data.batch_at(0), drain_every=K,
                                           window=8)
        s2, scr2, blk2 = sharded(state, screen_init(8), batches, thr)
        _assert_trees_equal(s1, s2)
        _assert_trees_equal(scr1, scr2)
        np.testing.assert_array_equal(np.asarray(blk1["loss"]),
                                      np.asarray(blk2["loss"]))

    def test_diloco_specs_cover_state_tree(self):
        from repro.distributed.sharding import (diloco_specs, param_specs,
                                                shardings_for)
        from repro.launch.mesh import make_test_mesh
        cfg, fns, tcfg, dcfg, data, params = _micro_diloco_setup()
        d = diloco_init(params, dcfg, compress="int8", screen_window=8)
        specs = diloco_specs(param_specs(cfg), compress=True, screen=True)
        sh = shardings_for(specs, jax.eval_shape(lambda: d),
                           make_test_mesh())
        # structure mismatch (a state key without a spec) would raise here
        jax.tree.map(lambda x, s: None, d, sh)


class TestCompression:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 1000))
    def test_int8_roundtrip_error_bounded(self, seed):
        from repro.distributed import int8_compress, int8_decompress
        x = jax.random.normal(jax.random.PRNGKey(seed), (777,)) * 3.0
        y = int8_decompress(int8_compress(x))
        err = jnp.max(jnp.abs(x - y))
        bound = jnp.max(jnp.abs(x)) / 127.0
        assert float(err) <= float(bound) * 1.01

    def test_topk_keeps_largest(self):
        from repro.distributed import topk_compress, topk_decompress
        x = jnp.array([0.1, -5.0, 0.2, 3.0, -0.05])
        y = topk_decompress(topk_compress(x, frac=0.4))
        np.testing.assert_allclose(np.asarray(y),
                                   [0.0, -5.0, 0.0, 3.0, 0.0])

    def test_error_feedback_is_unbiased_over_time(self):
        from repro.distributed import ef_compress_tree, ef_init, decompress_tree
        tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (512,))}
        ef = ef_init(tree)
        sent_total = jnp.zeros((512,))
        for i in range(30):
            c, ef, nbytes = ef_compress_tree(tree, ef, method="topk",
                                             frac=0.05)
            sent_total = sent_total + decompress_tree(c, "topk")["w"]
        # cumulative transmitted signal approaches 30 * x
        ratio = float(jnp.linalg.norm(sent_total) /
                      (30 * jnp.linalg.norm(tree["w"])))
        assert ratio > 0.8

    @pytest.mark.parametrize("method", ["int8", "topk"])
    def test_ef_compress_tree_roundtrips_under_jit(self, method):
        """ef_roundtrip (shared by ef_compress_tree and the fused DiLoCo
        round's per-pod delta hop) must trace under jit, and
        (sent + residual) must reconstruct the error-feedback target
        exactly."""
        from repro.distributed import (decompress_tree, ef_compress_tree,
                                       ef_init)
        tree = {"w": jax.random.normal(jax.random.PRNGKey(1), (300,)) * 2.0,
                "b": jax.random.normal(jax.random.PRNGKey(2), (7,))}
        ef = jax.tree.map(
            lambda x: 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                              x.shape), tree)

        @jax.jit
        def roundtrip(tree, ef):
            c, new_ef, _ = ef_compress_tree(tree, ef, method=method)
            return decompress_tree(c, method), new_ef

        sent_j, ef_j = roundtrip(tree, ef)
        c_e, ef_e, nbytes = ef_compress_tree(tree, ef, method=method)
        sent_e = decompress_tree(c_e, method)
        for a, b in zip(jax.tree.leaves(sent_j), jax.tree.leaves(sent_e)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for x, e, s, r in zip(jax.tree.leaves(tree), jax.tree.leaves(ef),
                              jax.tree.leaves(sent_j),
                              jax.tree.leaves(ef_j)):
            np.testing.assert_allclose(np.asarray(s) + np.asarray(r),
                                       np.asarray(x) + np.asarray(e),
                                       rtol=0, atol=1e-6)
        assert nbytes > 0
