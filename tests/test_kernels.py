"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU) + hypothesis properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_reference,
                                            gather_pages,
                                            paged_decode_attention,
                                            paged_decode_attention_reference)
from repro.kernels.decode_attention.paged import (paged_decode_attention_fwd,
                                                 pages_per_block)
from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.rglru_scan import (rglru_scan, rglru_scan_associative,
                                      rglru_scan_reference)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[dt]


class TestFlashAttention:
    @pytest.mark.parametrize("b,h,hkv,s,dh", [
        (1, 4, 4, 256, 64),     # MHA
        (2, 8, 2, 256, 128),    # GQA 4:1
        (1, 4, 1, 512, 64),     # MQA
        (1, 2, 2, 128, 256),    # wide head
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_sweep_vs_oracle(self, b, h, hkv, s, dh, dtype, causal):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, h, s, dh), dtype)
        k = jax.random.normal(kk, (b, hkv, s, dh), dtype)
        v = jax.random.normal(kv, (b, hkv, s, dh), dtype)
        out = flash_attention_fwd(q, k, v, causal=causal, interpret=True)
        ref = attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))

    def test_unpadded_shapes_via_wrapper(self):
        kq, kk = jax.random.split(jax.random.PRNGKey(1))
        q = jax.random.normal(kq, (2, 200, 4, 64))
        k = jax.random.normal(kk, (2, 200, 2, 64))
        v = jax.random.normal(kk, (2, 200, 2, 64))
        out = flash_attention(q, k, v, causal=True, interpret=True)
        ref = attention_reference(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=True).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_flow(self):
        """custom_vjp backward (remat'd oracle) produces oracle gradients."""
        kq, kk = jax.random.split(jax.random.PRNGKey(2))
        q = jax.random.normal(kq, (1, 4, 128, 64))
        k = jax.random.normal(kk, (1, 2, 128, 64))
        v = jax.random.normal(kk, (1, 2, 128, 64))
        g1 = jax.grad(lambda q_: flash_attention(
            q_, k, v, causal=True, layout="bhsd", interpret=True).sum())(q)
        g2 = jax.grad(lambda q_: attention_reference(
            q_, k, v, causal=True).astype(jnp.float32).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-4, rtol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 3), st.sampled_from([1, 2, 4]),
           st.sampled_from([128, 256]), st.sampled_from([64, 128]))
    def test_property_rows_sum_to_convex_combination(self, b, hkv, s, dh):
        """Attention output rows lie in the convex hull of V rows: with
        V = const c, output must equal c everywhere."""
        h = hkv * 2
        kq, kk = jax.random.split(jax.random.PRNGKey(b * 7 + s))
        q = jax.random.normal(kq, (b, h, s, dh))
        k = jax.random.normal(kk, (b, hkv, s, dh))
        v = jnp.full((b, hkv, s, dh), 3.25)
        out = flash_attention_fwd(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 3.25, atol=1e-5)


class TestDecodeAttention:
    """Caches are in the model's (B, M, Hkv, dh) layout — the kernel
    consumes them with no transpose/pad on the serving hot path."""

    @pytest.mark.parametrize("b,h,hkv,m,dh", [
        (2, 8, 8, 1024, 64),
        (4, 8, 2, 2048, 128),
        (1, 4, 1, 512, 64),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_oracle(self, b, h, hkv, m, dh, dtype):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(kq, (b, h, dh), dtype)
        kc = jax.random.normal(kk, (b, m, hkv, dh), dtype)
        vc = jax.random.normal(kv, (b, m, hkv, dh), dtype)
        kv_len = m // 2 + 17                    # scalar broadcasts
        from repro.kernels.decode_attention.kernel import decode_attention_fwd
        out = decode_attention_fwd(q, kc, vc, kv_len, interpret=True)
        ref = decode_attention_reference(q, kc, vc, kv_len)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))

    @pytest.mark.parametrize("b,h,hkv,m,dh", [
        (4, 8, 2, 1024, 64),
        (3, 4, 4, 512, 128),
    ])
    def test_ragged_per_row_kv_len(self, b, h, hkv, m, dh):
        """Each slot masks only its own cache tail — including an empty
        slot (kv_len=0 -> exact zeros) and a nearly-full one (max_len-1)."""
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(kq, (b, h, dh))
        kc = jax.random.normal(kk, (b, m, hkv, dh))
        vc = jax.random.normal(kv, (b, m, hkv, dh))
        lens = jnp.asarray([0, 1, m - 1, m // 2 + 3][:b], jnp.int32)
        from repro.kernels.decode_attention.kernel import decode_attention_fwd
        out = decode_attention_fwd(q, kc, vc, lens, interpret=True)
        ref = decode_attention_reference(q, kc, vc, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert np.all(np.asarray(out[0]) == 0.0)     # empty slot

    def test_ragged_rows_match_scalar_per_row(self):
        """Row i of a ragged call == a scalar-kv_len call at lens[i]."""
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(12), 3)
        b, h, hkv, m, dh = 3, 4, 2, 512, 64
        q = jax.random.normal(kq, (b, h, dh))
        kc = jax.random.normal(kk, (b, m, hkv, dh))
        vc = jax.random.normal(kv, (b, m, hkv, dh))
        lens = [37, 256, 511]
        from repro.kernels.decode_attention.kernel import decode_attention_fwd
        ragged = decode_attention_fwd(q, kc, vc,
                                      jnp.asarray(lens, jnp.int32),
                                      interpret=True)
        for i, n in enumerate(lens):
            solo = decode_attention_fwd(q[i:i + 1], kc[i:i + 1],
                                        vc[i:i + 1], n, interpret=True)
            np.testing.assert_array_equal(np.asarray(ragged[i]),
                                          np.asarray(solo[0]))

    def test_model_layout_wrapper(self):
        kq, kk = jax.random.split(jax.random.PRNGKey(4))
        q = jax.random.normal(kq, (2, 1, 8, 64))
        kc = jax.random.normal(kk, (2, 777, 2, 64))     # unpadded M
        vc = jax.random.normal(kk, (2, 777, 2, 64))
        out = decode_attention(q, kc, vc, 400, interpret=True)
        ref = decode_attention_reference(q[:, 0], kc, vc, 400)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_kv_len_masking_exact(self):
        """Entries beyond each row's kv_len must not influence the output."""
        kq, kk = jax.random.split(jax.random.PRNGKey(5))
        q = jax.random.normal(kq, (2, 4, 64))
        kc = jax.random.normal(kk, (2, 512, 2, 64))
        vc = jax.random.normal(kk, (2, 512, 2, 64))
        lens = jnp.asarray([100, 300], jnp.int32)
        from repro.kernels.decode_attention.kernel import decode_attention_fwd
        out1 = decode_attention_fwd(q, kc, vc, lens, interpret=True)
        kc2 = kc.at[0, 100:].set(1e4).at[1, 300:].set(1e4)
        vc2 = vc.at[0, 100:].set(-1e4).at[1, 300:].set(-1e4)
        out2 = decode_attention_fwd(q, kc2, vc2, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def _paged_setup(key, b, hkv, dh, page_size, max_pages, pool_pages,
                 dtype=jnp.float32):
    """Random pool + a permuted (non-contiguous) page table per row; the
    trash page id is pool_pages and fills every unmapped entry."""
    kk, kv, kp = jax.random.split(key, 3)
    kpool = jax.random.normal(kk, (pool_pages + 1, page_size, hkv, dh),
                              dtype)
    vpool = jax.random.normal(kv, (pool_pages + 1, page_size, hkv, dh),
                              dtype)
    perm = jax.random.permutation(kp, pool_pages)[:b * max_pages]
    ptab = perm.reshape(b, max_pages).astype(jnp.int32)
    return kpool, vpool, ptab


class TestPagedDecodeAttention:
    """The paged kernel walks a per-row page table over a shared physical
    pool; outputs must match the gather-to-dense oracle bitwise-closely and
    be exactly independent of trash-page / unmapped-pool garbage."""

    @pytest.mark.parametrize("b,h,hkv,ps,mp,dh,lens", [
        pytest.param(2, 8, 8, 16, 8, 64, None, id="2-8-8-16-8-64"),  # MHA
        pytest.param(3, 8, 2, 32, 4, 128, None,
                     id="3-8-2-32-4-128"),                          # GQA 4:1
        pytest.param(1, 4, 1, 64, 4, 64, None, id="1-4-1-64-4-64"),  # MQA
        # many-head MHA over blocks of 8 pages: an empty row, one ending
        # mid-page, one on the first block's end, the full table
        pytest.param(4, 12, 12, 16, 20, 64, (0, 16 * 5 + 7, 128, 320),
                     id="mha12-ragged-multipage"),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_oracle(self, b, h, hkv, ps, mp, dh, lens, dtype):
        kq, kkv = jax.random.split(jax.random.PRNGKey(20))
        q = jax.random.normal(kq, (b, h, dh), dtype)
        kpool, vpool, ptab = _paged_setup(kkv, b, hkv, dh, ps, mp,
                                          pool_pages=b * mp + 3, dtype=dtype)
        # a scalar broadcasts to every row
        kv_len = (ps * mp) // 2 + 7 if lens is None else jnp.asarray(
            lens, jnp.int32)
        out = paged_decode_attention_fwd(q, kpool, vpool, ptab, kv_len,
                                         interpret=True)
        ref = paged_decode_attention_reference(q, kpool, vpool, ptab,
                                               kv_len)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))
        if lens is not None:
            assert np.all(np.asarray(out[0]) == 0.0)  # empty row: zeros

    def test_ragged_lens_including_empty_row(self):
        b, h, hkv, ps, mp, dh = 4, 4, 2, 16, 8, 64
        kq, kkv = jax.random.split(jax.random.PRNGKey(21))
        q = jax.random.normal(kq, (b, h, dh))
        kpool, vpool, ptab = _paged_setup(kkv, b, hkv, dh, ps, mp,
                                          pool_pages=b * mp)
        lens = jnp.asarray([0, 1, ps * mp - 1, ps + 3], jnp.int32)
        out = paged_decode_attention_fwd(q, kpool, vpool, ptab, lens,
                                         interpret=True)
        ref = paged_decode_attention_reference(q, kpool, vpool, ptab, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        assert np.all(np.asarray(out[0]) == 0.0)    # empty row: exact zeros

    def test_trash_page_poison_is_bitwise_invariant(self):
        """Unmapped table entries alias the trash page; poisoning it (and
        every unreferenced pool page) to huge values must not change ANY
        output bit — masking happens before the exp. The second row spans
        two blocks of pages, the first leaves most of its block dead."""
        b, h, hkv, ps, mp, dh = 2, 4, 2, 16, 12, 64
        pool_pages = 24
        kq, kkv = jax.random.split(jax.random.PRNGKey(22))
        q = jax.random.normal(kq, (b, h, dh))
        kpool, vpool, ptab = _paged_setup(kkv, b, hkv, dh, ps, mp,
                                          pool_pages=pool_pages)
        lens = jnp.asarray([ps * 2 + 5, ps * mp - 2], jnp.int32)
        # map entries past each row's last live page to the trash id
        live = -(-lens // ps)                    # pages per row
        col = jnp.arange(mp)[None, :]
        ptab = jnp.where(col < live[:, None], ptab, pool_pages)
        out1 = paged_decode_attention_fwd(q, kpool, vpool, ptab, lens,
                                          interpret=True)
        referenced = np.zeros(pool_pages + 1, bool)
        referenced[np.asarray(ptab).ravel()] = True
        poison = jnp.asarray(~referenced)[:, None, None, None]
        kpool2 = jnp.where(poison, 1e4, kpool).at[pool_pages].set(1e4)
        vpool2 = jnp.where(poison, -1e4, vpool).at[pool_pages].set(-1e4)
        out2 = paged_decode_attention_fwd(q, kpool2, vpool2, ptab, lens,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_paged_matches_dense_kernel_on_same_logical_cache(self):
        """Gathering the paged pool to the dense layout and running the
        dense kernel at the paged kernel's block size (its pages per block
        times the page size) gives bitwise the result of the paged kernel
        directly: both fold the same blocks through the same online-softmax
        code."""
        b, h, hkv, ps, mp, dh = 3, 8, 2, 32, 8, 64
        kq, kkv = jax.random.split(jax.random.PRNGKey(23))
        q = jax.random.normal(kq, (b, h, dh))
        kpool, vpool, ptab = _paged_setup(kkv, b, hkv, dh, ps, mp,
                                          pool_pages=b * mp)
        block = pages_per_block(ps, mp) * ps
        assert ps * mp == 2 * block                 # two blocks a row
        lens = jnp.asarray([ps * 3 + 9, block, ps * mp], jnp.int32)
        from repro.kernels.decode_attention.kernel import decode_attention_fwd
        dense = decode_attention_fwd(q, gather_pages(kpool, ptab),
                                     gather_pages(vpool, ptab), lens,
                                     block_k=block, interpret=True)
        paged = paged_decode_attention_fwd(q, kpool, vpool, ptab, lens,
                                           interpret=True)
        np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))

    def test_model_layout_wrapper(self):
        b, h, hkv, ps, mp, dh = 2, 8, 2, 16, 4, 64
        kq, kkv = jax.random.split(jax.random.PRNGKey(24))
        q = jax.random.normal(kq, (b, 1, h, dh))        # (B, 1, H, dh)
        kpool, vpool, ptab = _paged_setup(kkv, b, hkv, dh, ps, mp,
                                          pool_pages=b * mp)
        out = paged_decode_attention(q, kpool, vpool, ptab, ps * 2 + 1,
                                     interpret=True)
        ref = paged_decode_attention_reference(q[:, 0], kpool, vpool, ptab,
                                               ps * 2 + 1)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 3), st.sampled_from([1, 2]),
           st.sampled_from([16, 32]), st.integers(1, 63))
    def test_property_shared_pages_give_identical_rows(self, b, hkv, ps,
                                                       kv_len):
        """Prefix sharing aliases physical pages across rows: rows with
        identical tables and lengths must produce bitwise-identical
        outputs for identical queries."""
        h, dh, mp = hkv * 2, 64, 2
        kq, kkv = jax.random.split(jax.random.PRNGKey(kv_len * 31 + b))
        q1 = jax.random.normal(kq, (1, h, dh))
        q = jnp.broadcast_to(q1, (b, h, dh))
        kpool, vpool, ptab = _paged_setup(kkv, 1, hkv, dh, ps, mp,
                                          pool_pages=mp + 2)
        shared = jnp.broadcast_to(ptab[:1], (b, mp))
        out = paged_decode_attention_fwd(q, kpool, vpool, shared,
                                         min(kv_len, ps * mp),
                                         interpret=True)
        for i in range(1, b):
            np.testing.assert_array_equal(np.asarray(out[0]),
                                          np.asarray(out[i]))


class TestPagedLatentAttention:
    """The latent pool: 16 query heads over one KV head of 576 (the
    Moonlight MLA row [c_kv | k_pe]), values its first 512 columns, the
    scale from the caller (192 ** -0.5, not 576 ** -0.5)."""

    H, DK, DV, SCALE = 16, 576, 512, 192 ** -0.5

    def _setup(self, key, b, ps, mp, pool_pages, dtype):
        kq, kp, kt = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, self.H, self.DK), dtype)
        pool = jax.random.normal(kp, (pool_pages + 1, ps, self.DK), dtype)
        ptab = jax.random.permutation(kt, pool_pages)[:b * mp]
        return q, pool, ptab.reshape(b, mp).astype(jnp.int32)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_latent_shape_vs_oracle(self, dtype):
        """Rows ending mid-page, on a block's end, across two blocks, and
        an empty row (exact zeros)."""
        from repro.kernels.decode_attention.paged import (
            latent_attention_reference, paged_latent_attention)
        b, ps, mp = 4, 16, 12
        q, pool, ptab = self._setup(jax.random.PRNGKey(26), b, ps, mp,
                                    b * mp + 2, dtype)
        lens = jnp.asarray([ps * 3 + 5, 128, ps * mp - 1, 0], jnp.int32)
        out = paged_latent_attention(q, pool, ptab, lens,
                                     sm_scale=self.SCALE, v_dim=self.DV,
                                     interpret=True)
        ref = latent_attention_reference(q, gather_pages(pool, ptab), lens,
                                         sm_scale=self.SCALE, v_dim=self.DV)
        assert out.shape == (b, self.H, self.DV)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype), rtol=_tol(dtype))
        assert np.all(np.asarray(out[3]) == 0.0)

    def test_values_are_the_keys_leading_columns(self):
        """The same pool as separate K and V (V = K[..., :512]) through the
        naive per-head oracle, with q's scale folded in."""
        from repro.kernels.decode_attention.paged import (
            paged_latent_attention)
        b, ps, mp = 2, 16, 4
        q, pool, ptab = self._setup(jax.random.PRNGKey(27), b, ps, mp,
                                    b * mp, jnp.float32)
        lens = jnp.asarray([ps * 2 + 1, ps * mp], jnp.int32)
        out = paged_latent_attention(q, pool, ptab, lens,
                                     sm_scale=self.SCALE, v_dim=self.DV,
                                     interpret=True)
        keys = gather_pages(pool, ptab)                     # (B, M, 576)
        s = jnp.einsum("bhd,bmd->bhm", q, keys) * self.SCALE
        s = jnp.where(jnp.arange(keys.shape[1]) < lens[:, None, None], s,
                      -jnp.inf)
        want = jnp.einsum("bhm,bmd->bhd", jax.nn.softmax(s, -1),
                          keys[..., :self.DV])
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kind", ["mha", "latent"])
def test_stacked_pools_read_their_layer(kind, layer):
    """Either paged kernel, given three layers' pools stacked (L, P+1, ps,
    ...) and a layer index, returns bitwise what it returns on that
    layer's pool alone, and poisoning every other layer's pages (trash
    pages included) changes no bit."""
    n_layers, b, ps, mp, pool_pages = 3, 2, 16, 12, 24
    kq, kp, kt = jax.random.split(jax.random.PRNGKey(31), 3)
    ptab = jax.random.permutation(kt, pool_pages)[:b * mp]
    ptab = ptab.reshape(b, mp).astype(jnp.int32)
    lens = jnp.asarray([ps * 2 + 5, ps * mp - 2], jnp.int32)
    if kind == "mha":
        h, hkv, dh = 4, 2, 64
        q = jax.random.normal(kq, (b, h, dh))
        pools = tuple(jax.random.normal(
            kp, (2, n_layers, pool_pages + 1, ps, hkv, dh)))

        def run(pools, *at):
            return paged_decode_attention_fwd(q, *pools, ptab, lens, *at,
                                              interpret=True)
    else:
        from repro.kernels.decode_attention.paged import (
            paged_latent_attention)
        c = TestPagedLatentAttention
        q = jax.random.normal(kq, (b, c.H, c.DK), jnp.bfloat16)
        pools = (jax.random.normal(kp, (n_layers, pool_pages + 1, ps, c.DK),
                                   jnp.bfloat16),)

        def run(pools, *at):
            return paged_latent_attention(q, *pools, ptab, lens, *at,
                                          sm_scale=c.SCALE, v_dim=c.DV,
                                          interpret=True)
    alone = run(tuple(p[layer] for p in pools))
    stacked = run(pools, layer)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(alone))
    other = jnp.arange(n_layers) != layer
    poisoned = tuple(
        jnp.where(other.reshape(-1, *[1] * (p.ndim - 1)), sign * 1e4, p)
        .astype(p.dtype) for p, sign in zip(pools, (1, -1)))
    np.testing.assert_array_equal(np.asarray(run(poisoned, layer)),
                                  np.asarray(alone))


class TestRGLRUScan:
    @pytest.mark.parametrize("b,s,d", [(2, 256, 128), (1, 512, 256),
                                       (3, 128, 384)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_oracle(self, b, s, d, dtype):
        ka, kx = jax.random.split(jax.random.PRNGKey(6))
        a = jax.random.uniform(ka, (b, s, d), dtype, 0.2, 0.999)
        x = jax.random.normal(kx, (b, s, d), dtype)
        out = rglru_scan(a, x, interpret=True)
        ref = rglru_scan_reference(a, x)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dtype) * 5, rtol=_tol(dtype) * 5)

    def test_unpadded_shapes(self):
        ka, kx = jax.random.split(jax.random.PRNGKey(7))
        a = jax.random.uniform(ka, (2, 100, 70), jnp.float32, 0.5, 0.99)
        x = jax.random.normal(kx, (2, 100, 70))
        out = rglru_scan(a, x, interpret=True)
        ref = rglru_scan_reference(a, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_associative_matches_sequential(self):
        """The XLA associative-scan path is itself validated vs sequential."""
        ka, kx = jax.random.split(jax.random.PRNGKey(8))
        a = jax.random.uniform(ka, (2, 333, 64), jnp.float32, 0.1, 0.999)
        x = jax.random.normal(kx, (2, 333, 64))
        np.testing.assert_allclose(np.asarray(rglru_scan_associative(a, x)),
                                   np.asarray(rglru_scan_reference(a, x)),
                                   atol=1e-5, rtol=1e-5)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 1000))
    def test_property_zero_a_is_identity(self, seed):
        """a == 0 -> h == x (no history); a == 1 -> h == cumsum(x)."""
        x = jax.random.normal(jax.random.PRNGKey(seed), (1, 128, 128))
        h0 = rglru_scan(jnp.zeros_like(x), x, interpret=True)
        np.testing.assert_allclose(np.asarray(h0), np.asarray(x), atol=1e-6)
        h1 = rglru_scan(jnp.ones_like(x), x, interpret=True)
        np.testing.assert_allclose(np.asarray(h1),
                                   np.asarray(jnp.cumsum(x, axis=1)),
                                   atol=1e-4, rtol=1e-4)

    def test_gradients_flow(self):
        ka, kx = jax.random.split(jax.random.PRNGKey(9))
        a = jax.random.uniform(ka, (1, 128, 128), jnp.float32, 0.5, 0.99)
        x = jax.random.normal(kx, (1, 128, 128))
        g1 = jax.grad(lambda x_: rglru_scan(a, x_, interpret=True).sum())(x)
        g2 = jax.grad(lambda x_: rglru_scan_associative(a, x_).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=1e-5, rtol=1e-5)
