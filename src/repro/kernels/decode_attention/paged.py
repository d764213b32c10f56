"""Paged decode attention: the kernel walks a per-row page table instead of
a contiguous per-slot cache row.

KV lives in a shared pool of physical pages (P+1, page_size, Hkv, dh) — the
last page id (P) is a trash page that absorbs writes/reads for unmapped
table entries. Each batch row owns a (max_pages,) int32 row of the page
table; entries past ceil(kv_len / page_size) are the trash id. HBM cost now
tracks *allocated* pages, not max_len: the pool is sized for live tokens
across the whole batch, and prefix-shared pages appear in several rows'
tables at once.

Grid = (B, cdiv(max_pages, ppb)), blocks of ppb = `pages_per_block` pages
(BLOCK_TOKENS positions) with the block axis innermost/sequential. The
pool is passed ppb times, one input slot per page of a block, and each
slot's index_map reads the physical page id from a fetch schedule that
rides in as a scalar-prefetch operand with kv_lens
(`PrefetchScalarGridSpec`): the pipeline fetches the next step's pages
while this step folds, across row boundaries too. A dead slot (at or past
its row's kv_len) repeats the page it already holds, and the pipeline skips
a copy whose block index did not change, so only live pages are fetched.
(The pipeline's copies are used rather than manual DMAs because Mosaic
refuses a manual copy of a page whose (Hkv, dh) is not a multiple of the
tiling, e.g. 36 heads of 64.) Each page is folded through the dense
kernel's `kernel.fold_block`, all KV heads at once.

Both kernels also take the pools stacked over layers, (L, P+1, ...), with
a layer index as a third scalar-prefetch operand: each page slot's
index_map leads with the layer on a squeezed dimension, so the body sees
the same page ref and a decode layer scan can keep the stacked pools in
one buffer, written in place, instead of slicing a layer's pool out for
the kernel. An unstacked pool is the L = 1 case.

Masking is bit-compatible with the dense kernel: scores past kv_len go to
-1e30 before the exp, so trash-page or stale contents contribute exact 0.0
to the softmax, and since both kernels fold the same 16-position chunks in
order, paged output == dense-kernel output bitwise for the same cache
contents.

`paged_latent_attention` reads a latent pool (P+1, page_size, dk): one KV
head shared by every query head, whose first `v_dim` columns are also the
values (multi-head latent attention in its absorbed form), with the
softmax scale from the caller. Same fetch schedule; each grid step lays
its pages side by side and runs the scores and the weighted sum as matrix
products for all query heads at once, where the MHA fold would spend one
elementwise product per query head on a single KV head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernel import (NEG_INF, finalize, fold_block, group_heads, init_state,
                     scratch_shapes, ungroup_heads)
from .ref import decode_attention_reference

BLOCK_TOKENS = 128     # positions fetched from the pool per grid step


def pages_per_block(page_size: int, max_pages: int) -> int:
    """Pages fetched per grid step: BLOCK_TOKENS positions, at least one
    page, at most the whole table. The dense kernel at block_k = this many
    pages' positions folds the same blocks in the same order."""
    return max(1, min(max_pages, BLOCK_TOKENS // page_size))


def fetch_schedule(page_table, kv_lens, page_size: int, ppb: int):
    """(B * nblk * ppb,) int32: the pool page that input slot i of grid step
    (row, block) holds. A live page (below its row's kv_len) is its table
    entry; a dead one repeats what that slot held at the step before (the
    first live page of the slot, before any), so the pipeline, which skips
    a block whose index did not change, fetches live pages only — a row
    at kv_len 0 fetches nothing."""
    b, max_pages = page_table.shape
    nblk = pl.cdiv(max_pages, ppb)
    tab = jnp.pad(page_table, ((0, 0), (0, nblk * ppb - max_pages)),
                  mode="edge").reshape(b * nblk, ppb)
    live_pages = pl.cdiv(kv_lens, page_size)[:, None]
    live = (jnp.arange(nblk * ppb)[None] < live_pages).reshape(b * nblk, ppb)
    step = jnp.arange(b * nblk, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    first = jax.lax.cummin(jnp.where(live, step, b * nblk), axis=0,
                           reverse=True)
    src = jnp.clip(jnp.where(last >= 0, last, first), 0, b * nblk - 1)
    return jnp.take_along_axis(tab, src, axis=0).reshape(-1)


def _stacked(pool, ndim: int):
    """The pool with its layer axis: a pool of `ndim` dims is one layer's
    (a reshape, so no copy), one of ndim + 1 is already stacked."""
    return pool[None] if pool.ndim == ndim else pool


def _paged_decode_kernel(lens_ref, fetch_ref, layer_ref, q_ref, *refs,
                         ppb: int, sm_scale: float):
    """Step (row, block): fold the block's live pages, one input slot per
    page, through the dense kernel's `fold_block`."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * ppb:]
    bi = pl.program_id(0)
    j = pl.program_id(1)
    ps = k_refs[0].shape[1]

    @pl.when(j == 0)
    def _init():
        init_state(acc_ref, m_ref, l_ref)

    kv_len = lens_ref[bi]

    @pl.when(j * ppb * ps < kv_len)        # ragged early-exit per row
    def _compute():
        for i in range(ppb):
            fold_block(q_ref, k_refs[i], v_refs[i], acc_ref,
                       m_ref, l_ref, k_start=(j * ppb + i) * ps,
                       kv_len=kv_len, sm_scale=sm_scale)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref, kv_len)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, kv_lens,
                               layer=0, *, interpret: bool = False):
    """q: (B, H, dh); k/v_pages: (P+1, page_size, Hkv, dh) pool (last page
    is trash), or the pools of L layers stacked, (L, P+1, page_size, Hkv,
    dh), read at `layer`; page_table: (B, max_pages) int32 physical page
    ids (unmapped entries point at the trash page); kv_lens: (B,) int32
    logical lengths (a scalar broadcasts to all rows)."""
    k_pages, v_pages = _stacked(k_pages, 4), _stacked(v_pages, 4)
    b, h, dh = q.shape
    ps, hkv = k_pages.shape[2], k_pages.shape[3]
    max_pages = page_table.shape[1]
    assert h % hkv == 0
    group = h // hkv
    ppb = pages_per_block(ps, max_pages)
    nblk = pl.cdiv(max_pages, ppb)
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (b,))
    fetch = fetch_schedule(page_table.astype(jnp.int32), kv_lens, ps, ppb)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def page_spec(i):
        return pl.BlockSpec(
            (None, 1, ps, hkv, dh),
            lambda bi, j, lens, fetch, layer: (
                layer[0], fetch[(bi * nblk + j) * ppb + i], 0, 0, 0))

    row_spec = pl.BlockSpec((1, group, hkv, dh),
                            lambda bi, j, lens, fetch, layer: (bi, 0, 0, 0))
    pages = [page_spec(i) for i in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblk),
        in_specs=[row_spec, *pages, *pages],
        out_specs=row_spec,
        scratch_shapes=scratch_shapes(group, hkv, dh),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, ppb=ppb,
                          sm_scale=dh ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, group, hkv, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens, fetch, layer, group_heads(q, hkv), *[k_pages] * ppb,
      *[v_pages] * ppb)
    return ungroup_heads(out)


def gather_pages(pool, page_table, layer=None):
    """Materialize the logical dense layout from a pool + page table.

    pool: (P+1, page_size, Hkv, dh), or with `layer` the stacked (L, P+1,
    page_size, ...) read at that layer; page_table: (B, max_pages) int32.
    Returns (B, max_pages * page_size, Hkv, dh) — the reference/CPU path;
    the pallas kernel never builds this.
    """
    if layer is not None:       # one gather from the flattened layers
        page_table = page_table + layer * pool.shape[1]
        pool = pool.reshape(-1, *pool.shape[2:])
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
def paged_decode_attention(q, k_pages, v_pages, page_table, kv_len,
                           layer=0, *, interpret: bool = False):
    """q: (B, 1, H, dh) or (B, H, dh); pools: (P+1, page_size, Hkv, dh), or
    (L, P+1, page_size, Hkv, dh) read at `layer`; page_table: (B,
    max_pages); kv_len: scalar or (B,)."""
    squeeze = q.ndim == 4
    if squeeze:  # repro-lint: allow[RT001] rank normalization is trace-time static; two shapes total
        q = q[:, 0]
    out = paged_decode_attention_fwd(q, k_pages, v_pages, page_table,
                                     kv_len, layer, interpret=interpret)
    return out[:, None] if squeeze else out


# --------------------------------------------------------------------------
# latent pages: one KV head whose leading columns are also its values
# --------------------------------------------------------------------------
def _paged_latent_kernel(lens_ref, fetch_ref, layer_ref, q_ref, *refs,
                         ppb: int, sm_scale: float, v_dim: int):
    """Step (row, block): the block's ppb pages side by side as one
    (ppb * ps, dk) slab; scores of all H query heads against it in one
    matrix product, values its first `v_dim` columns."""
    k_refs = refs[:ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[ppb:]
    bi = pl.program_id(0)
    j = pl.program_id(1)
    ps = k_refs[0].shape[1]

    @pl.when(j == 0)
    def _init():
        init_state(acc_ref, m_ref, l_ref)

    kv_len = lens_ref[bi]

    @pl.when(j * ppb * ps < kv_len)        # ragged early-exit per row
    def _compute():
        k = jnp.concatenate([r[0] for r in k_refs], axis=0)  # (ppb*ps, dk)
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                      # (H, ppb*ps)
        kpos = j * ppb * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(k.dtype), k[:, :v_dim],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        finalize(o_ref, acc_ref, l_ref, kv_len)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "v_dim", "interpret"))
def paged_latent_attention(q, pages, page_table, kv_lens, layer=0, *,
                           sm_scale: float, v_dim: int,
                           interpret: bool = False):
    """Decode attention over a latent pool, where every query head shares
    one KV head and the values are the keys' first `v_dim` columns (MLA in
    its absorbed form: K = [c_kv | k_pe], V = c_kv).

    q: (B, H, dk); pages: (P+1, page_size, dk) pool (last page is trash),
    or (L, P+1, page_size, dk) read at `layer`; page_table, kv_lens as in
    `paged_decode_attention_fwd`. Returns (B, H, v_dim). Pages are fetched
    once each, by the same schedule as the MHA kernel; the scores and the
    weighted sum run on the matrix unit, bf16 operands with float32
    sums."""
    pages = _stacked(pages, 3)
    b, h, dk = q.shape
    ps = pages.shape[2]
    max_pages = page_table.shape[1]
    ppb = pages_per_block(ps, max_pages)
    nblk = pl.cdiv(max_pages, ppb)
    kv_lens = jnp.broadcast_to(
        jnp.asarray(kv_lens, jnp.int32).reshape(-1), (b,))
    fetch = fetch_schedule(page_table.astype(jnp.int32), kv_lens, ps, ppb)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def page_spec(i):
        return pl.BlockSpec(
            (None, 1, ps, dk),
            lambda bi, j, lens, fetch, layer: (
                layer[0], fetch[(bi * nblk + j) * ppb + i], 0, 0))

    row = lambda bi, j, lens, fetch, layer: (bi, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nblk),
        in_specs=[pl.BlockSpec((1, h, dk), row),
                  *[page_spec(i) for i in range(ppb)]],
        out_specs=pl.BlockSpec((1, h, v_dim), row),
        scratch_shapes=[pltpu.VMEM((h, v_dim), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, ppb=ppb, sm_scale=sm_scale,
                          v_dim=v_dim),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_lens, fetch, layer, q, *[pages] * ppb)


def latent_attention_reference(q, rows, kv_len, *, sm_scale: float,
                               v_dim: int):
    """Pure-jnp latent decode attention over dense rows: q (B, H, dk), rows
    (B, M, dk), kv_len () or (B,). Positions >= kv_len get exact-zero
    weight; the oracle of `paged_latent_attention` (through `gather_pages`)
    and the path off the TPU. Returns (B, H, v_dim)."""
    s = jnp.einsum("bhd,bmd->bhm", q, rows,
                   preferred_element_type=jnp.float32) * sm_scale
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len), (q.shape[0],))
    valid = jnp.arange(rows.shape[1])[None, None] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1)
    out = jnp.einsum("bhm,bmd->bhd", p.astype(rows.dtype), rows[..., :v_dim],
                     preferred_element_type=jnp.float32)
    return jnp.where(kv_len[:, None, None] > 0, out, 0.0).astype(q.dtype)
