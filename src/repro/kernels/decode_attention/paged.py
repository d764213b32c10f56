"""Paged decode attention: the kernel walks a per-row page table instead of
a contiguous per-slot cache row.

KV lives in a shared pool of physical pages (P+1, page_size, Hkv, dh) — the
last page id (P) is a trash page that absorbs writes/reads for unmapped
table entries. Each batch row owns a (max_pages,) int32 row of the page
table; entries past ceil(kv_len / page_size) are the trash id. HBM cost now
tracks *allocated* pages, not max_len: the pool is sized for live tokens
across the whole batch, and prefix-shared pages appear in several rows'
tables at once.

Grid = (B, max_pages) with the page axis innermost/sequential; each step
takes one (page_size, Hkv, dh) page of K and V, all KV heads at once, as
the dense kernel takes a cache block. kv_lens and the page table ride in as
scalar-prefetch operands (`PrefetchScalarGridSpec`), so the k/v index_map
resolves the physical page id *before* the DMA is issued — the pool is
streamed through the dense kernel's own body (`kernel.decode_kernel`) and
VMEM scratch. `pl.when` skips pages past
a row's kv_len, and because every unmapped entry aliases the one trash
page, the pipeline's consecutive-identical-block dedup collapses the
unmapped tail into a single redundant fetch.

Masking is bit-compatible with the dense kernel: scores past kv_len go to
-1e30 before the exp, so trash-page garbage contributes exact 0.0 to the
softmax, and paged output == dense-kernel output (at block_k = page_size)
bitwise for the same cache contents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import decode_kernel, scratch_shapes
from .ref import decode_attention_reference


def _paged_decode_kernel(lens_ref, ptab_ref, *refs, **kw):
    # the page table only steers the k/v index_map (the DMA); the block
    # arithmetic is the dense kernel's
    decode_kernel(lens_ref, *refs, **kw)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, kv_lens, *,
                               interpret: bool = False):
    """q: (B, H, dh); k/v_pages: (P+1, page_size, Hkv, dh) pool (last page
    is trash); page_table: (B, max_pages) int32 physical page ids (unmapped
    entries point at the trash page); kv_lens: (B,) int32 logical lengths
    (a scalar broadcasts to all rows)."""
    b, h, dh = q.shape
    ps, hkv = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    assert h % hkv == 0
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (b,))
    page_table = page_table.astype(jnp.int32)

    kernel = functools.partial(_paged_decode_kernel, block_k=ps, hkv=hkv,
                               group=h // hkv, sm_scale=dh ** -0.5)
    page_spec = pl.BlockSpec((1, ps, hkv, dh),
                             lambda bi, pi, lens, ptab:
                             (ptab[bi, pi], 0, 0, 0))
    row_spec = pl.BlockSpec((1, h, dh),
                            lambda bi, pi, lens, ptab: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=scratch_shapes(h, dh),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens, page_table, q, k_pages, v_pages)


def gather_pages(pool, page_table):
    """Materialize the logical dense layout from a pool + page table.

    pool: (P+1, page_size, Hkv, dh); page_table: (B, max_pages) int32.
    Returns (B, max_pages * page_size, Hkv, dh) — the reference/CPU path;
    the pallas kernel never builds this.
    """
    b, mp = page_table.shape
    ps = pool.shape[1]
    dense = jnp.take(pool, page_table, axis=0)      # (B, MP, ps, Hkv, dh)
    return dense.reshape(b, mp * ps, *pool.shape[2:])


def paged_decode_attention_reference(q, k_pages, v_pages, page_table,
                                     kv_len):
    """Pure-jnp oracle: gather pages to the logical dense layout and run the
    dense reference. Positions >= kv_len (incl. all trash-page content) are
    masked to exact-zero probability, so the result is independent of pool
    garbage."""
    return decode_attention_reference(
        q, gather_pages(k_pages, page_table),
        gather_pages(v_pages, page_table), kv_len)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len, *,
                           interpret: bool = False):
    """q: (B, 1, H, dh) or (B, H, dh); pools: (P+1, page_size, Hkv, dh);
    page_table: (B, max_pages); kv_len: scalar or (B,)."""
    squeeze = q.ndim == 4
    if squeeze:  # repro-lint: allow[RT001] rank normalization is trace-time static; two shapes total
        q = q[:, 0]
    out = paged_decode_attention_fwd(q, k_pages, v_pages, page_table,
                                     kv_len, interpret=interpret)
    return out[:, None] if squeeze else out
