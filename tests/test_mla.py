"""Latent attention and held-share experts (models/mla.py, models/moe.py),
through the one trunk (models/transformer.py), at the reduced Moonlight
size on the CPU, against the plain reference (bench/references/mla_moe.py)
on seeded random weights."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import mla_moe as ref
from repro.models import mla, moe, registry, transformer
from repro.models.decode_state import decode_spec, paged_spec
from repro.models.moe import held_experts_ffn, moe_ffn, sigmoid_route

ARCH = "moonlight-16b-a3b"
F32 = dict(compute_dtype="float32", param_dtype="float32")


def _cfg(**kw):
    return registry.get_reduced_config(ARCH, **kw)


CFG = _cfg(experts_held=8, expert_offset=4, **F32)   # a share: 8 of 16
WHOLE = _cfg(**F32)                                   # all 16 held


def _ref_cfg(cfg):
    """The reference's configuration dict for a program config."""
    keys = ("n_layers", "d_model", "n_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "d_ff",
            "first_dense_layers", "moe_d_ff", "num_experts", "top_k",
            "n_shared_experts", "routed_scaling", "expert_offset",
            "vocab_size", "rope_base", "rms_norm_eps", "param_dtype")
    out = {k: getattr(cfg, k) for k in keys}
    out["experts_held"] = cfg.n_held
    return out


@functools.cache
def _ref_logits(cfg):
    rc = _ref_cfg(cfg)
    return jax.jit(lambda w, toks: ref.logits(w, toks, rc))


@functools.cache
def _weights(cfg, seed=3):
    w = ref.make_weights(_ref_cfg(cfg), seed)
    want = jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                          jax.random.PRNGKey(0))
    assert jax.tree.structure(w) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(w)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
    return w


def test_forward_matches_the_reference():
    cfg = CFG
    w = _weights(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(0), (20,), 0, cfg.vocab_size)
    got = jax.jit(transformer.forward, static_argnums=2)(w, toks[None],
                                                      cfg)[0]
    want = _ref_logits(cfg)(w, toks)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("paged", [False, True])
def test_prefill_then_latent_decode_matches_the_reference(paged):
    """Bucketed prefill of two ragged prompts through the DecodeState spec,
    then decode steps fed the reference's own tokens: the logits of every
    step are the reference's full forward at that position."""
    cfg = CFG
    w = _weights(cfg)
    spec = decode_spec(cfg)
    if paged:
        spec = paged_spec(spec, page_size=8, max_batch=3, max_len=64)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg.vocab_size, n) for n in (30, 22)]
    lens = np.array([9, 14, 0])
    st = spec.init_state(3, 64)
    toks = np.zeros((3, 16), np.int32)
    for i, n in enumerate(lens[:2]):
        toks[i, :n] = seqs[i][:n]
    admit = jnp.asarray([True, True, False])
    logits, st = jax.jit(spec.prefill)(w, st, jnp.asarray(toks),
                                       jnp.asarray(lens), admit)
    step = jax.jit(spec.decode)
    full = [_ref_logits(cfg)(w, jnp.asarray(s)) for s in seqs]
    for t in range(8):
        for i in range(2):
            np.testing.assert_allclose(logits[i], full[i][lens[i] - 1 + t],
                                       atol=3e-4, rtol=3e-4)
        nxt = [seqs[i][lens[i] + t] for i in range(2)] + [0]
        logits, st = step(w, st, jnp.asarray(nxt, jnp.int32)[:, None])
    if paged:
        ctr = spec.counters(st)
        assert int(ctr["decode_steps"]) == 8
        assert int(ctr["expert_tokens"]) > 0
        assert int(ctr["latent_positions"]) == sum(
            sum(n + t + 1 for t in range(8)) for n in lens[:2])


def test_absorbed_attention_is_the_naive_one():
    """One layer: the decode token's attention over cached rows, absorbed
    (q into latent space, W_UV after) against naive (c_kv expanded into
    per-head K and V)."""
    cfg = CFG
    w = _weights(cfg)
    lp = jax.tree.map(lambda a: a[0], w["layers"])
    b, m = 3, 24
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    rows = jax.random.normal(ks[0], (b, m, mla.row_width(cfg)))
    q_nope = jax.random.normal(ks[1], (b, 1, cfg.n_heads,
                                       cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(ks[2], (b, 1, cfg.n_heads,
                                     cfg.qk_rope_head_dim))
    kv_len = jnp.asarray([5, 24, 17])
    mask = (jnp.arange(m)[None, None] < kv_len[:, None, None])
    naive = mla.naive_attention(cfg, lp, q_nope, q_pe, rows, mask)
    q = mla.latent_query(cfg, lp, q_nope, q_pe)[:, 0]
    absorbed = mla.latent_out(cfg, lp, mla.latent_attention(
        cfg, q, rows, kv_len)[:, None])
    np.testing.assert_allclose(absorbed, naive, atol=1e-5, rtol=1e-5)


def _layer(cfg):
    w = _weights(cfg)
    lp = jax.tree.map(lambda a: a[0], w["layers"])
    return {"router": lp["router"], "bias": lp["router_bias"],
            "wi_gate": lp["moe_wi_gate"], "wi_up": lp["moe_wi_up"],
            "wo": lp["moe_wo"]}, lp


@pytest.mark.parametrize("t", [40, 2048])
def test_the_shares_add_up_to_the_uncut_layer(t, monkeypatch):
    """Eight chips' shares of the experts, each computing its own part
    with the routing over all of them, plus the shared expert counted
    once, give the uncut layer: at a decode's token count (every held
    expert on every row) and at a prefill's (grouped products over the
    sorted assignments, in two chunks)."""
    cfg = WHOLE
    whole, lp = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (t, cfg.d_model))
    share = cfg.num_experts // 8
    monkeypatch.setattr(moe, "PREFILL_CHUNK", 1024)
    layer = jax.jit(functools.partial(
        held_experts_ffn, top_k=cfg.top_k, scaling=cfg.routed_scaling))
    parts, counts = [], []
    for c in range(8):
        sl = {k: (v[c * share:(c + 1) * share] if k.startswith("w") else v)
              for k, v in whole.items()}
        out, n = layer(x, sl, offset=c * share)
        parts.append(out)
        counts.append(n)
    uncut, n_all = layer(x, whole, offset=0)
    shared = jax.nn.silu(x @ lp["shared_wi_gate"]) * (x @ lp["shared_wi_up"])
    shared = shared @ lp["shared_wo"]
    np.testing.assert_allclose(sum(parts) + shared, uncut + shared,
                               atol=2e-5, rtol=2e-5)
    assert int(sum(c.sum() for c in counts)) == int(n_all.sum()) == \
        t * cfg.top_k
    # the uncut layer is the plain sum of each token's gated experts
    gates, ex = sigmoid_route(x, whole["router"], whole["bias"],
                              top_k=cfg.top_k, scaling=cfg.routed_scaling)
    y = jax.vmap(lambda xe, wg, wu, wo: (jax.nn.silu(xe @ wg) * (xe @ wu))
                 @ wo, in_axes=(None, 0, 0, 0))(
        x, whole["wi_gate"], whole["wi_up"], whole["wo"])       # (E, T, D)
    want = jnp.sum(jnp.take_along_axis(
        y.transpose(1, 0, 2), ex[..., None], 1) * gates[..., None], 1)
    np.testing.assert_allclose(uncut, want, atol=2e-5, rtol=2e-5)


def test_a_rows_output_does_not_depend_on_its_batch():
    """Dropless: a token's expert output is the same alone and among 63
    others. The capacity layer (`moe_ffn`) drops assignments past its
    capacity, so its output for the same token moves with the batch."""
    cfg = WHOLE
    held, _ = _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model))
    layer = jax.jit(functools.partial(
        held_experts_ffn, top_k=cfg.top_k, scaling=cfg.routed_scaling,
        offset=0))
    alone, _ = layer(x[:1], held)
    among, _ = layer(x, held)
    np.testing.assert_allclose(among[0], alone[0], atol=1e-6, rtol=1e-6)
    old = {"router": held["router"], "wi_gate": held["wi_gate"],
           "wi_up": held["wi_up"], "wo": held["wo"]}
    cap = jax.jit(functools.partial(moe_ffn, num_experts=cfg.num_experts,
                                    top_k=cfg.top_k))
    one, many = cap(x[:1], old), cap(jnp.tile(x[:1], (64, 1)), old)
    assert float(jnp.max(jnp.abs(many[-1] - one[0]))) > 1e-3


def test_served_logits_do_not_depend_on_the_batch():
    """Through the paged engine path: a request's decode logits are the
    same alone and beside others (bf16, the cell's precision)."""
    cfg = _cfg()
    w = _weights(cfg)
    spec = paged_spec(decode_spec(cfg), page_size=8, max_batch=4,
                      max_len=64)
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (4, 12)).astype(np.int32)

    prefill, decode = jax.jit(spec.prefill), jax.jit(spec.decode)

    def run(n):
        st = spec.init_state(4, 64)
        lens = jnp.asarray([12] * n + [0] * (4 - n))
        admit = jnp.arange(4) < n
        out, st = prefill(w, st, jnp.asarray(prompts), lens, admit)
        got = [out[0]]
        for t in range(4):
            out, st = decode(w, st, jnp.full((4, 1), t + 1, jnp.int32))
            got.append(out[0])
        return jnp.stack(got)

    np.testing.assert_array_equal(run(1), run(4))


def test_latent_state_shapes_and_kind():
    cfg = registry.get_config(ARCH, experts_held=8)
    spec = decode_spec(cfg)
    assert spec.state_kind == "latent+experts"
    st = jax.eval_shape(lambda: paged_spec(
        spec, page_size=16, max_batch=64, max_len=2048,
        pool_pages=8192).init_state(64, 2048))
    assert st["ckvp"].shape == (26, 8193, 16, 576)
    assert st["ckv0p"].shape == (1, 8193, 16, 576)
    assert "vp" not in st and "kp" not in st
    assert cfg.param_count() == ref_param_count(cfg)


def ref_param_count(cfg):
    from bench.counts import mla_moe as counts
    return counts.param_count(_ref_cfg(cfg))


def test_engine_paged_equals_dense_and_any_block_size():
    """The serving engine on the latent model: paged and dense latent rows,
    decode blocks of 8 and of 1, serve the same tokens."""
    from repro.serving import EngineConfig, Request, ServingEngine

    cfg = _cfg(experts_held=8)
    fns = registry.model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 16, 9, 12)]                  # one bucket

    def run(**kw):
        eng = ServingEngine(cfg, fns, params,
                            EngineConfig(max_batch=3, max_len=32, **kw))
        reqs = [Request(uid=i, prompt=p, max_new_tokens=10)
                for i, p in enumerate(prompts)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return [r.generated for r in reqs]

    dense = run(decode_block=8)
    assert run(decode_block=8, page_size=16) == dense
    assert run(decode_block=1, page_size=16) == dense


def test_routing_flips_counts_choices_that_bfloat16_moves():
    """The reference's count of expert choices that rounding its matmul
    operands to bfloat16 changes, over every MoE layer and position."""
    rc = _ref_cfg(CFG)
    w = _weights(CFG)
    toks = np.random.default_rng(3).integers(0, CFG.vocab_size, 48)
    got = ref.routing_flips(w, rc, toks)
    moe_layers = CFG.n_layers - CFG.first_dense_layers
    assert got["choices"] == moe_layers * 48
    assert 0 <= got["flipped_held"] <= got["flipped"] <= got["choices"]
    assert got["tokens_with_a_flip"] <= 48
