"""Focused model-math tests: decode==forward parity, chunked==parallel
mLSTM, chunked==full cross-entropy, attention impl equivalence, M-RoPE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import registry
from repro.models.layers import attention_chunked, attention_ref
from repro.models.losses import chunked_lm_loss, softmax_xent
from repro.models.transformer import TransformerConfig
from repro.models.xlstm import _mlstm_chunked, _mlstm_parallel


class TestAttentionImpls:
    @pytest.mark.parametrize("window", [None, 16])
    @pytest.mark.parametrize("s", [64, 100])
    def test_chunked_matches_ref(self, window, s):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (2, s, 4, 32))
        k = jax.random.normal(kk, (2, s, 2, 32))
        v = jax.random.normal(kv, (2, s, 2, 32))
        ref = attention_ref(q, k, v, causal=True, window=window)
        out = attention_chunked(q, k, v, causal=True, window=window,
                                kv_block=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_chunked_grads_match_ref(self):
        kq, kk = jax.random.split(jax.random.PRNGKey(1))
        q = jax.random.normal(kq, (1, 64, 2, 16))
        k = jax.random.normal(kk, (1, 64, 2, 16))
        v = jax.random.normal(kk, (1, 64, 2, 16))
        g1 = jax.grad(lambda q_: attention_ref(
            q_, k, v, causal=True).astype(jnp.float32).sum())(q)
        g2 = jax.grad(lambda q_: attention_chunked(
            q_, k, v, causal=True, kv_block=16).astype(
                jnp.float32).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)


class TestMLSTMChunked:
    @pytest.mark.parametrize("s,chunk", [(128, 32), (96, 24), (100, 32)])
    def test_matches_parallel(self, s, chunk):
        kq, kk, kv, ki = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(kq, (2, s, 4, 32))
        k = jax.random.normal(kk, (2, s, 4, 32))
        v = jax.random.normal(kv, (2, s, 4, 32))
        ifg = jax.random.normal(ki, (2, s, 8)) * 2.0
        ref = _mlstm_parallel(q, k, v, ifg)
        out = _mlstm_chunked(q, k, v, ifg, chunk)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=5e-4, rtol=5e-3)

    def test_gradients_finite(self):
        kq, ki = jax.random.split(jax.random.PRNGKey(2))
        q = jax.random.normal(kq, (1, 64, 2, 16))
        ifg = jax.random.normal(ki, (1, 64, 4))

        def loss(q_):
            return _mlstm_chunked(q_, q_, q_, ifg, 16).astype(
                jnp.float32).sum()
        g = jax.grad(loss)(q)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestChunkedLoss:
    def test_matches_full_xent(self):
        key = jax.random.PRNGKey(0)
        kh, kw, kl = jax.random.split(key, 3)
        hidden = jax.random.normal(kh, (2, 64, 32))
        head = jax.random.normal(kw, (32, 101))
        labels = jax.random.randint(kl, (2, 64), 0, 101)
        full = jnp.mean(softmax_xent(hidden @ head, labels))
        chunked = chunked_lm_loss(hidden, head, labels, chunk=16)
        assert float(full) == pytest.approx(float(chunked), rel=1e-5)

    def test_gradients_match(self):
        key = jax.random.PRNGKey(1)
        kh, kw, kl = jax.random.split(key, 3)
        hidden = jax.random.normal(kh, (2, 32, 16))
        head = jax.random.normal(kw, (16, 53))
        labels = jax.random.randint(kl, (2, 32), 0, 53)
        g1 = jax.grad(lambda h: jnp.mean(
            softmax_xent(h @ head, labels)))(hidden)
        g2 = jax.grad(lambda h: chunked_lm_loss(
            h, head, labels, chunk=8))(hidden)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-5, rtol=1e-4)

    def test_transformer_loss_chunk_config_equivalence(self):
        from dataclasses import replace
        cfg = registry.get_reduced_config("suncatcher-lm-100m")
        fns = registry.model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        kt, kl = jax.random.split(jax.random.PRNGKey(1))
        batch = {"tokens": jax.random.randint(kt, (2, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(kl, (2, 32), 0,
                                              cfg.vocab_size)}
        full = fns.loss_fn(params, batch, cfg)
        chunked = fns.loss_fn(params, batch, replace(cfg, loss_chunk=8))
        assert float(full) == pytest.approx(float(chunked), rel=1e-3)


class TestDecodeParity:
    """Step-by-step decode must equal the parallel forward pass."""

    @pytest.mark.parametrize("arch", ["suncatcher-lm-100m", "xlstm-350m",
                                      "recurrentgemma-2b", "qwen2-vl-2b",
                                      "moonlight-16b-a3b"])
    def test_decode_matches_forward(self, arch):
        # f32 compute: the test checks algorithmic parity of the two paths,
        # not bf16 accumulation-order noise (which also made the outcome
        # depend on whether an earlier test module enabled x64 globally)
        cfg = registry.get_reduced_config(arch,
                                          compute_dtype="float32")
        fns = registry.model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                                  cfg.vocab_size)
        cache = fns.init_cache(cfg, 2, 16)
        for t in range(10):
            lg, cache = fns.decode_step(params, cache, toks[:, t:t + 1], cfg)
        ref = fns.forward(params, toks, cfg)[:, -1]
        np.testing.assert_allclose(
            np.asarray(lg, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)

    def test_rglru_ring_buffer_wraps(self):
        """Decode past the window: ring buffer must overwrite oldest slots
        and still match the windowed parallel forward."""
        cfg = registry.get_reduced_config("recurrentgemma-2b")  # window=16
        fns = registry.model_fns(cfg)
        params = fns.init(jax.random.PRNGKey(0), cfg)
        n = 24   # > window
        toks = jax.random.randint(jax.random.PRNGKey(2), (1, n), 0,
                                  cfg.vocab_size)
        cache = fns.init_cache(cfg, 1, n)
        for t in range(n):
            lg, cache = fns.decode_step(params, cache, toks[:, t:t + 1], cfg)
        ref = fns.forward(params, toks, cfg)[:, -1]
        np.testing.assert_allclose(
            np.asarray(lg, np.float32), np.asarray(ref, np.float32),
            atol=3e-2, rtol=3e-2)


class TestMRoPE:
    def test_mrope_reduces_to_rope_on_equal_positions(self):
        from repro.models.layers import mrope_cos_sin, rope_cos_sin
        p = jnp.arange(8)[None]
        pos = jnp.stack([p, p, p])
        c1, s1 = mrope_cos_sin(pos, 16, (4, 2, 2))
        c2, s2 = rope_cos_sin(p, 16)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)
