"""Serving engine tests: continuous batching, determinism, decode parity."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry
from repro.serving import EngineConfig, Request, ServingEngine


@pytest.fixture(scope="module")
def setup():
    cfg = registry.get_reduced_config("suncatcher-lm-100m")
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    return cfg, fns, params


def test_continuous_batching_completes_more_requests_than_slots(setup):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64))
    rng = np.random.default_rng(0)
    for uid in range(5):
        eng.submit(Request(uid=uid,
                           prompt=rng.integers(0, cfg.vocab_size,
                                               size=4).astype(np.int32),
                           max_new_tokens=5))
    done = eng.run()
    assert len(done) == 5
    assert all(len(r.generated) == 5 for r in done)


def test_greedy_engine_matches_manual_decode(setup):
    cfg, fns, params = setup
    prompt = np.arange(5, dtype=np.int32)
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=3, max_len=64))
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=6))
    done = eng.run()

    cache = fns.init_cache(cfg, 1, 64)
    lg, cache = fns.decode_step(params, cache, jnp.asarray(prompt)[None],
                                cfg)
    seq = [int(jnp.argmax(lg[0]))]
    for _ in range(5):
        lg, cache = fns.decode_step(params, cache,
                                    jnp.asarray([[seq[-1]]]), cfg)
        seq.append(int(jnp.argmax(lg[0])))
    assert done[0].generated == seq


def test_mixed_prompt_lengths_isolated_between_slots(setup):
    """Ragged per-slot positions: slot A's tokens must not leak into B."""
    cfg, fns, params = setup
    pa = np.arange(3, dtype=np.int32)
    pb = np.arange(9, dtype=np.int32)
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64))
    eng.submit(Request(uid=0, prompt=pa, max_new_tokens=4))
    eng.submit(Request(uid=1, prompt=pb, max_new_tokens=4))
    batched = {r.uid: r.generated for r in eng.run()}

    solo = {}
    for uid, p in ((0, pa), (1, pb)):
        e = ServingEngine(cfg, fns, params,
                          EngineConfig(max_batch=1, max_len=64))
        e.submit(Request(uid=uid, prompt=p, max_new_tokens=4))
        solo[uid] = e.run()[0].generated
    assert batched == solo


def test_temperature_zero_deterministic(setup):
    cfg, fns, params = setup
    outs = []
    for seed in (0, 1):
        eng = ServingEngine(cfg, fns, params,
                            EngineConfig(max_batch=1, max_len=64, seed=seed))
        eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=5, temperature=0.0))
        outs.append(eng.run()[0].generated)
    assert outs[0] == outs[1]


def _mixed_workload(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(sz)).astype(np.int32)
            for sz in rng.integers(3, 40, size=n)]


def test_multi_token_decode_bit_identical_n1_vs_n8(setup):
    """The fused N-token decode block must not change outputs: greedy AND
    temperature sampling are bit-identical for decode_block 1 vs 8."""
    cfg, fns, params = setup
    prompts = _mixed_workload(cfg)

    def serve(n_block):
        eng = ServingEngine(cfg, fns, params,
                            EngineConfig(max_batch=3, max_len=64, seed=7,
                                         decode_block=n_block))
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=9,
                               temperature=0.0 if uid % 2 == 0 else 0.8))
        return {r.uid: r.generated for r in eng.run()}

    assert serve(1) == serve(8)


def test_mixed_lengths_compile_bounded_traces(setup):
    """A mixed-length workload compiles at most len(buckets) + 2 distinct
    traces (bucketed prefill + one fused decode block)."""
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64,
                                     decode_block=4))
    for uid, p in enumerate(_mixed_workload(cfg, n=9, seed=3)):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=6))
    done = eng.run()
    assert len(done) == 9
    traces = eng.trace_count()
    if traces < 0:
        pytest.skip("jit cache introspection unavailable in this jax")
    assert traces <= len(eng.buckets()) + 2


def test_host_syncs_amortized_over_decode_block(setup):
    """Device-resident state: host round-trips are O(tokens / N), not
    O(tokens * slots) as in the per-token loop."""
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64,
                                     decode_block=8))
    for uid in range(4):
        eng.submit(Request(uid=uid,
                           prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=16))
    eng.run()
    assert eng.stats["tokens"] == 4 * 16
    # 2 admission waves + ceil(15/8) blocks per wave = far below 1/token
    assert eng.stats["host_syncs"] / eng.stats["tokens"] <= 0.25


def test_engine_through_pallas_decode_kernel(setup, monkeypatch):
    """REPRO_DECODE_ATTN=interpret forces the serving stack through the
    ragged decode-attention kernel (interpret mode on CPU): the full
    engine->decode_step->kernel dispatch must produce the same greedy
    tokens as the ref attention path."""
    from dataclasses import replace

    cfg, fns, _ = setup
    pcfg = replace(cfg, attn_impl="pallas")
    params = fns.init(jax.random.PRNGKey(2), pcfg)
    prompts = _mixed_workload(cfg, n=3, seed=9)

    def serve():
        eng = ServingEngine(pcfg, fns, params,
                            EngineConfig(max_batch=2, max_len=64))
        for uid, p in enumerate(prompts):
            eng.submit(Request(uid=uid, prompt=p, max_new_tokens=5))
        return {r.uid: r.generated for r in eng.run()}

    ref = serve()
    monkeypatch.setenv("REPRO_DECODE_ATTN", "interpret")
    assert serve() == ref


def test_windowed_attention_decode_matches_manual(setup):
    """Local-attention window masking must survive the ragged (vector-pos)
    decode path: engine output == scalar-pos manual decode."""
    from dataclasses import replace

    cfg, fns, _ = setup
    wcfg = replace(cfg, window=8)
    params = fns.init(jax.random.PRNGKey(1), wcfg)
    prompt = np.arange(6, dtype=np.int32)
    eng = ServingEngine(wcfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64))
    eng.submit(Request(uid=0, prompt=prompt, max_new_tokens=12))
    got = eng.run()[0].generated

    cache = fns.init_cache(wcfg, 1, 64)
    lg, cache = fns.decode_step(params, cache, jnp.asarray(prompt)[None],
                                wcfg)
    seq = [int(jnp.argmax(lg[0]))]
    for _ in range(11):
        lg, cache = fns.decode_step(params, cache,
                                    jnp.asarray([[seq[-1]]]), wcfg)
        seq.append(int(jnp.argmax(lg[0])))
    assert got == seq


def test_max_new_tokens_one_finishes_at_prefill(setup):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64))
    eng.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                       max_new_tokens=1))
    done = eng.run()
    assert len(done) == 1 and len(done[0].generated) == 1


# ------------------------------------------------------------- paged KV --

def _paged_ecfg(**kw):
    base = dict(max_batch=4, max_len=64, page_size=16, decode_block=8,
                seed=7)
    base.update(kw)
    return EngineConfig(**base)


def _serve_all(eng, prompts, max_new=9, temps=True):
    for uid, p in enumerate(prompts):
        eng.submit(Request(uid=uid, prompt=p, max_new_tokens=max_new,
                           temperature=0.8 if temps and uid % 2 else 0.0))
    return {r.uid: r.generated for r in eng.run()}


def test_paged_engine_bit_identical_to_dense(setup):
    """The paged layout is a storage change, not a numerics change: greedy
    AND sampled outputs match the dense engine token-for-token."""
    cfg, fns, params = setup
    prompts = _mixed_workload(cfg, n=10, seed=5)
    dense = _serve_all(ServingEngine(cfg, fns, params,
                                     _paged_ecfg(page_size=0)), prompts)
    paged_eng = ServingEngine(cfg, fns, params, _paged_ecfg())
    paged = _serve_all(paged_eng, prompts)
    assert paged == dense
    # drained engine leaks no pages: host view full, device live zero
    ps = paged_eng.page_stats()
    assert ps["host_free"] == ps["pool_pages"] and ps["device_live"] == 0


def test_paged_engine_through_pallas_kernel(setup, monkeypatch):
    """REPRO_DECODE_ATTN=interpret drives the engine through the paged
    pallas decode kernel (page-table walk, pl.when page skipping) in
    interpret mode; greedy tokens must match the ref paged path."""
    from dataclasses import replace

    cfg, fns, _ = setup
    pcfg = replace(cfg, attn_impl="pallas")
    params = fns.init(jax.random.PRNGKey(2), pcfg)
    prompts = _mixed_workload(cfg, n=3, seed=9)

    def serve():
        eng = ServingEngine(pcfg, fns, params, _paged_ecfg(max_batch=2))
        return _serve_all(eng, prompts, max_new=5, temps=False)

    ref = serve()
    monkeypatch.setenv("REPRO_DECODE_ATTN", "interpret")
    assert serve() == ref


def test_engine_device_commits_state_and_imported_rows(setup):
    """device= commits params and slot state to that device; generations
    migrated in from an engine without one are copied onto it and finish
    with the tokens of an unmigrated run."""
    cfg, fns, params = setup
    dev = jax.devices()[-1]
    prompts = _mixed_workload(cfg, n=3, seed=4)
    want = _serve_all(ServingEngine(cfg, fns, params, _paged_ecfg(
        page_size=0)), prompts, max_new=20)

    src = ServingEngine(cfg, fns, params, _paged_ecfg(page_size=0))
    dst = ServingEngine(cfg, fns, params, _paged_ecfg(page_size=0),
                        device=dev)
    held = jax.tree.leaves((dst.params, dst.cache, dst.state))
    assert all(x.committed and x.devices() == {dev} for x in held)
    for uid, p in enumerate(prompts):
        src.submit(Request(uid=uid, prompt=p, max_new_tokens=20,
                           temperature=0.8 if uid % 2 else 0.0))
    src.step()
    dst.import_slots(src.export_slots(
        [i for i, r in enumerate(src.slots) if r is not None]))
    assert all(x.devices() == {dev}
               for x in jax.tree.leaves((dst.cache, dst.state)))
    got = {r.uid: r.generated for r in dst.run()}
    assert got == want


def test_paged_continuous_admission_undersized_pool(setup):
    """A pool too small for all slots at once gates admission on free
    pages (head-of-line stall), recycles a finishing request's pages into
    later admissions, completes everything, and stays bit-identical."""
    cfg, fns, params = setup
    prompts = _mixed_workload(cfg, n=10, seed=5)
    dense = _serve_all(ServingEngine(cfg, fns, params,
                                     _paged_ecfg(page_size=0)), prompts)
    eng = ServingEngine(cfg, fns, params, _paged_ecfg(pool_pages=8))
    got = _serve_all(eng, prompts)
    assert got == dense
    assert eng.stats["admission_stalls"] > 0
    ps = eng.page_stats()
    assert ps["host_free"] == ps["pool_pages"] and ps["device_live"] == 0


def test_paged_prefix_sharing_refcounts_pages(setup):
    """Requests repeating an already-served prompt head map its whole
    pages from the prefix cache instead of re-allocating: shared pages
    show up in stats and in a lower live-page peak."""
    cfg, fns, params = setup
    head = np.arange(32, dtype=np.int32)           # two whole 16-tok pages
    tails = [np.concatenate([head, np.full(4 + i, i, np.int32)])
             for i in range(4)]
    eng = ServingEngine(cfg, fns, params,
                        _paged_ecfg(max_batch=2, prefix_cache=4))
    # first request stores the head; later ones (separate prefill calls,
    # since max_batch=2 < len(tails)) consume it
    got = _serve_all(eng, tails, max_new=4, temps=False)
    dense = _serve_all(ServingEngine(cfg, fns, params,
                                     _paged_ecfg(max_batch=2, page_size=0)),
                       tails, max_new=4, temps=False)
    assert got == dense
    assert eng.stats["prefix_stores"] >= 1
    assert eng.stats["prefix_hits"] >= 1
    assert eng.stats["pages_shared"] >= 2
    ps = eng.page_stats()
    # pinned prefix pages stay resident after drain; nothing else does
    assert ps["device_live"] == 2 * eng.stats["prefix_stores"]


def test_paged_trace_count_bounded(setup):
    """Continuous admission at page granularity must not add traces: the
    paged engine compiles at most len(buckets) + 1 (prefill buckets + one
    fused decode block) for a mixed-length workload."""
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params, _paged_ecfg(max_batch=2))
    got = _serve_all(eng, _mixed_workload(cfg, n=9, seed=3), max_new=6,
                     temps=False)
    assert len(got) == 9
    traces = eng.trace_count()
    if traces < 0:
        pytest.skip("jit cache introspection unavailable in this jax")
    assert traces <= len(eng.buckets()) + 1


# --------------------------------------------- submit boundary + buckets --

def test_submit_rejects_prompt_at_max_len(setup):
    """A prompt of exactly max_len fills the row with no room for even one
    decoded token: submit must reject it with a clear error, and max_len-1
    must still be admittable."""
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=1, max_len=32))
    with pytest.raises(ValueError, match="must be < max_len"):
        eng.submit(Request(uid=0, prompt=np.zeros(32, np.int32),
                           max_new_tokens=1))
    with pytest.raises(ValueError, match="must be < max_len"):
        eng.submit(Request(uid=1, prompt=np.zeros(40, np.int32),
                           max_new_tokens=1))
    eng.submit(Request(uid=2, prompt=np.zeros(31, np.int32),
                       max_new_tokens=4))
    done = eng.run()
    assert len(done) == 1 and len(done[0].generated) == 1  # row cap at 32


def test_prefill_bucket_edges(setup):
    cfg, fns, params = setup

    def mk(min_bucket, max_len):
        return ServingEngine(cfg, fns, params,
                             EngineConfig(max_batch=1, max_len=max_len,
                                          min_bucket=min_bucket))

    # pow2 max_len: the doubling ladder lands exactly on it, no duplicate
    assert mk(16, 64).buckets() == [16, 32, 64]
    # non-pow2 max_len: final bucket is max_len itself
    assert mk(16, 48).buckets() == [16, 32, 48]
    # min_bucket above max_len degenerates to a single max_len bucket
    assert mk(128, 64).buckets() == [64]


def test_eos_frees_slot(setup):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=1, max_len=64))
    # run once to find the greedy token, then use it as eos
    eng.submit(Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=8))
    first = eng.run()[0].generated[0]
    eng2 = ServingEngine(cfg, fns, params,
                         EngineConfig(max_batch=1, max_len=64))
    eng2.submit(Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=8, eos_id=first))
    done = eng2.run()
    assert len(done[0].generated) <= 8


SCOPES = ("embed", "layers", "attention", "mlp", "head", "sample", "pages")


def _op_scopes(hlo_text):
    """(opcode, innermost named scope or "other", whether the op is the
    layer scan's own, output shape) of every instruction of a compiled HLO
    module that carries a metadata op_name."""
    out = []
    for shape, op, path in re.findall(
            r"= (\S+) ([\w-]+)\(.*?op_name=\"([^\"]*)\"", hlo_text):
        inner = [p for p in path.split("/") if p in SCOPES]
        # an op of the layer scan itself: ".../layers/while/body/<op>"
        own = path.rsplit("/", 1)[0].endswith("/layers/while/body")
        out.append((op, inner[-1] if inner else "other", own, shape))
    return out


@pytest.mark.parametrize("page_size", [0, 16])
def test_programs_carry_named_scopes(setup, page_size):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64,
                                     decode_block=2, page_size=page_size))
    b, lb = 2, 16
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    page_ops = {k: i32(b) for k in ("pf_entry", "pf_n", "pf_store",
                                    "pf_store_n")}
    progs = {
        "decode": eng._engine_step.lower(eng.params, eng.cache, eng.state),
        "prefill": eng._prefill.lower(
            eng.params, eng.cache, eng.state, i32(b, lb), i32(b),
            jnp.zeros((b,), bool), jnp.zeros((b,), jnp.float32), i32(b),
            i32(b), i32(b), page_ops),
    }
    want = set(SCOPES) - ({"pages"} if not page_size else set())
    for name, lowered in progs.items():
        ops = _op_scopes(lowered.compile().as_text())
        assert want <= {sc for _, sc, _, _ in ops}, name
        # the layer scan's own slices and write-backs sit under `layers`
        # and in no child scope
        scan = {(op, sc) for op, sc, own, _ in ops if own}
        assert ("dynamic-slice", "layers") in scan, name
        assert {sc for _, sc in scan} == {"layers"}, name
        if name == "decode" and not page_size:
            assert ("dynamic-update-slice", "layers") in scan
        if name == "decode" and page_size:
            # the layer scan carries the stacked pools and writes each
            # layer's new rows in place: no op, under `layers` or
            # elsewhere, has the shape of one layer's pool
            one = ",".join(map(str, eng.cache["kp"].shape[1:]))
            pool = {(op, sc) for op, sc, _, shape in ops
                    if re.search(rf"\[(1,)?{one}\]", shape)}
            assert not pool, pool
            # the paged decode gathers each row's whole page table into a
            # dense row: every op of that shape sits under `attention`
            (rows, mp), kp = eng.cache["ptab"].shape, eng.cache["kp"].shape
            table = f"[{rows},{mp},{kp[2]},{kp[3]},{kp[4]}]"
            gather = {(op, sc) for op, sc, _, shape in ops if table in shape}
            assert ("select", "attention") in gather, gather
            assert {sc for _, sc in gather} == {"attention"}, gather


def test_prefill_counters_for_a_known_admission(setup):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=4, max_len=64))
    for uid, n in enumerate((5, 10, 20)):           # buckets 16, 16, 32
        eng.submit(Request(uid=uid, prompt=np.arange(n, dtype=np.int32),
                           max_new_tokens=3))
    eng.step()
    s = eng.stats
    assert (s["prefill_calls"], s["prefill_rows"]) == (2, 3)
    assert s["prefill_tokens"] == 5 + 10 + 20
    assert s["prefill_slot_tokens"] == 4 * 16 + 4 * 32
    for gone in ("decode_blocks", "exported_slots", "imported_slots"):
        assert gone not in s


def test_request_stamps_are_ordered(setup):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64))
    rng = np.random.default_rng(3)
    given = Request(uid=99, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=4, arrival=0.0)
    eng.submit(given)
    for uid in range(4):
        eng.submit(Request(uid=uid, max_new_tokens=int(rng.integers(1, 9)),
                           prompt=rng.integers(0, cfg.vocab_size, size=int(
                               rng.integers(3, 40))).astype(np.int32)))
    done = eng.run()
    assert len(done) == 5 and given.arrival == 0.0
    for r in done:
        assert r.arrival <= r.admitted_at <= r.first_token_at, r.uid


def _engine_spans(logdir):
    """[(name, start ns, end ns)] of the `engine.*` host spans of the
    profiler trace written under `logdir`."""
    import glob

    from jax.profiler import ProfileData

    out = []
    for path in glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for line in plane.lines for ev in line.events
                        if ev.name.startswith("engine.")]
    return out


@pytest.mark.parametrize("page_size", [0, 16])
def test_engine_spans_nest_in_a_profiler_trace(setup, tmp_path, page_size):
    cfg, fns, params = setup
    eng = ServingEngine(cfg, fns, params,
                        EngineConfig(max_batch=2, max_len=64,
                                     decode_block=2, page_size=page_size))
    for uid, n in enumerate((5, 20)):
        eng.submit(Request(uid=uid, prompt=np.arange(n, dtype=np.int32),
                           max_new_tokens=4))
    eng.step()                                    # compile outside the trace
    eng.submit(Request(uid=2, prompt=np.arange(7, dtype=np.int32),
                       max_new_tokens=4))
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    spans = _engine_spans(tmp_path)
    parents = {"engine.fill": {"engine.step"},
               "engine.prefill": {"engine.fill"},
               "engine.decode_block": {"engine.step"},
               "engine.drain": {"engine.fill", "engine.decode_block"},
               "engine.emit": {"engine.fill", "engine.decode_block"}}
    assert {name for name, _, _ in spans} == {"engine.step"} | set(parents)
    for name, start, end in spans:
        if name in parents:
            assert any(p in parents[name] and s <= start and end <= e
                       for p, s, e in spans), (name, start, end)


def test_launcher_admission_summary_reads_counters_and_stamps():
    from types import SimpleNamespace as NS

    from repro.launch.serve import admission_summary

    stats = {"prefill_calls": 2, "prefill_rows": 3, "prefill_tokens": 35,
             "prefill_slot_tokens": 4 * 16 + 4 * 32}
    done = [NS(arrival=0.0, admitted_at=0.01 * k,
               first_token_at=0.01 * k + 0.002) for k in range(11)]
    line = admission_summary(stats, done)
    assert "2 prefill calls, 1.5 rows/call" in line
    assert "18.2% of prefill token rows useful" in line
    assert "p90 queue wait 90.0 ms" in line
    assert "admission to first token 2.0 ms" in line
