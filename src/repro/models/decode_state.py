"""DecodeState: one per-slot decode-state abstraction for every model family.

The serving engine keeps a fixed pool of `max_batch` decode slots whose
per-slot model state used to be hard-coded to the transformer KV layout
(cache["k"]/["v"]/["pos"]).  This module is the family boundary: each
architecture implements one spec describing

  * how to allocate the state       (`init_state`)   — per-row "pos" (B,)
  * how to advance it one token     (`decode`)       — per-row positions
  * how to prefill a ragged bucket  (`prefill`)      — admit-masked merge
  * how inactive rows hold          (`freeze`)
  * where the slot axis lives       (`batch_axes`)   — pytree of ints
  * which leaves grow with seq len  (`length_axes`)  — pytree of ints,
                                                       -1 = O(1) carry leaf

and the engine's migration machinery (export/import, delta replication,
standby promote, clear) becomes four generic tree operations over those
axis declarations: `state_rows`, `merge_rows`, `delta_since`,
`delta_apply`.  A `state_kind` tag ("kv" | "carry" | "kv+experts") plus
the derived `windowed` flag tell the router what the replication cursor
means: windowed KV states ship `width`-row cache deltas, carry states
ship the whole O(1) state every sync (cursor jumps straight to pos).

Everything here is shape-polymorphic but trace-static: index vectors are
full-width (max_batch,) and the delta window width is a static argument,
so repeated migrations/syncs of any size are jit cache hits on every
family (`trace_count()` flat — same proof obligation as the KV plane).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import rglru as _rglru
from . import transformer as _transformer
from . import xlstm as _xlstm
from .rglru import RGLRUConfig
from .transformer import TransformerConfig
from .xlstm import XLSTMConfig

# The generic gather/scatters below are the bodies of the engine's jitted
# export/import/delta/standby roots; `python -m repro.analysis.lint
# --budgets` (entries "engine-serve" / "engine-serve-rglru") asserts they
# lower with zero host callbacks for both a KV and a carry family.
LINT_BUDGET = {"host_callbacks": 0}


def _bcast(vec, ndim, ax):
    """Reshape a (B,) vector to broadcast against a leaf with slot axis
    `ax`."""
    shape = [1] * ndim
    shape[ax] = vec.shape[0]
    return vec.reshape(shape)


def admit_merge(state, fresh, axes, admit):
    """Overwrite `admit`-masked slot rows of `state` with `fresh` rows."""
    return jax.tree.map(
        lambda o, n, ax: jnp.where(_bcast(admit, o.ndim, ax), n, o),
        state, fresh, axes)


def state_rows(state, axes, idx):
    """Gather slot rows `idx` from every leaf into fresh buffers.

    Full-width (`idx` is (max_batch,)): one trace covers every export
    size, so repeated migrations are jit cache hits."""
    return jax.tree.map(lambda x, ax: jnp.take(x, idx, axis=ax), state, axes)


def merge_rows(state, bundle, axes, src_for_dst, mask):
    """Scatter bundle rows into `mask`-ed slots: row d takes bundle row
    `src_for_dst[d]`; unmasked rows are untouched, so resident
    generations cannot be perturbed by an import."""
    def leaf(old, b, ax):
        g = jnp.take(b, src_for_dst, axis=ax)
        return jnp.where(_bcast(mask, old.ndim, ax), g, old)
    return jax.tree.map(leaf, state, bundle, axes)


def delta_since(state, axes, laxes, idx, starts, width):
    """Gather rows `idx`, windowed to [starts, starts + width) along each
    leaf's length axis.  Leaves with laxis < 0 (recurrent carries, ring
    buffers, pos) ship whole — they are O(1)/O(window) in sequence
    length, which is the point of the carry families."""
    def leaf(x, ax, lax_):
        g = jnp.take(x, idx, axis=ax)
        if lax_ < 0:
            return g
        assert ax < lax_, "slot axis must precede the length axis"
        cols = starts[:, None] + jnp.arange(width)              # (B, W)
        colc = jnp.clip(cols, 0, g.shape[lax_] - 1)
        shape = [1] * g.ndim
        shape[ax], shape[lax_] = colc.shape
        return jnp.take_along_axis(g, colc.reshape(shape), axis=lax_)
    return jax.tree.map(leaf, state, axes, laxes)


def delta_apply(state, bundle, axes, laxes, src_for_dst, starts, mask):
    """Scatter a `delta_since` bundle into `mask`-ed standby rows: row r
    takes bundle row `src_for_dst[r]` — windowed leaves at
    [starts[r], starts[r] + W) clipped to the rows the source actually
    wrote (its pos), carry leaves whole.  The standby "pos" becomes the
    replication cursor: min(starts + W, source pos) when any leaf is
    windowed, the source pos itself otherwise (whole state shipped, so
    the standby is promotable after every sync)."""
    pos = jnp.take(bundle["pos"], src_for_dst)
    rest = lambda t: {k: v for k, v in t.items() if k != "pos"}
    widths = [b.shape[l] for b, l in
              zip(jax.tree.leaves(rest(bundle)), jax.tree.leaves(rest(laxes)))
              if l >= 0]

    def leaf(old, b, ax, lax_):
        g = jnp.take(b, src_for_dst, axis=ax)
        if lax_ < 0:
            return jnp.where(_bcast(mask, old.ndim, ax), g, old)
        W = b.shape[lax_]
        M = old.shape[lax_]
        pend = jnp.clip(pos - starts, 0, W)                     # rows to copy
        rel = jnp.arange(M)[None, :] - starts[:, None]          # (B, M)
        in_win = (rel >= 0) & (rel < pend[:, None]) & mask[:, None]
        shape = [1] * old.ndim
        shape[ax], shape[lax_] = rel.shape
        relc = jnp.clip(rel, 0, W - 1).reshape(shape)
        return jnp.where(in_win.reshape(shape),
                         jnp.take_along_axis(g, relc, axis=lax_), old)

    out = jax.tree.map(leaf, rest(state), rest(bundle), rest(axes),
                       rest(laxes))
    cursor = jnp.minimum(starts + widths[0], pos) if widths else pos
    out["pos"] = jnp.where(mask, cursor, state["pos"])
    return out


# --------------------------------------------------------------------------
# paged-pool primitives (page-table KV cache; see PagedTransformerDecodeState)
# --------------------------------------------------------------------------
def _alloc_rows(ptab, free, top, ref, take):
    """Pop one page per True entry of `take` (B, max_pages) off the free
    stack into the matching page-table entries, setting their refcount to
    1.  Fully in-graph: entries are numbered row-major by an exclusive
    cumsum, so a whole batch's worth of allocations is one gather + one
    scatter — no host round-trip, no data-dependent shapes.  The caller
    (host-side admission gating) guarantees the stack holds enough pages,
    so `top` never goes negative."""
    t32 = take.astype(jnp.int32)
    flat = t32.reshape(-1)
    off = (jnp.cumsum(flat) - flat).reshape(take.shape)
    pool = free.shape[0]
    pid = free[jnp.clip(top - 1 - off, 0, pool - 1)]
    ptab2 = jnp.where(take, pid, ptab)
    ref2 = ref.at[jnp.where(take, pid, pool)].add(t32)   # pool id == trash
    # dtype= pins the accumulator: under jax_enable_x64 a bare jnp.sum
    # promotes int32 -> int64, silently changing the persisted stack
    # pointer's aval and forcing a retrace of every fused jit
    return ptab2, ref2, top - jnp.sum(t32, dtype=jnp.int32)


def _release_rows(ptab, free, top, ref, drop):
    """Decref every mapped page of `drop`-masked rows; pages whose count
    hits zero are pushed back on the free stack (deduplicated per page —
    two dropped rows sharing a prefix page release it once) and the rows'
    table entries reset to the trash id.  Prefix-cache pins hold an extra
    reference, so published pages survive their publisher."""
    pool = free.shape[0]
    trash = pool
    dec = drop[:, None] & (ptab != trash)
    ref2 = ref.at[jnp.where(dec, ptab, trash)].add(-dec.astype(jnp.int32))
    pages = jnp.arange(pool + 1)
    became = (ref2 == 0) & (ref > 0) & (pages < pool)
    b32 = became.astype(jnp.int32)
    rank = jnp.cumsum(b32) - b32
    dst = jnp.where(became, top + rank, pool)            # pool -> dropped
    free2 = free.at[dst].set(pages.astype(free.dtype), mode="drop")
    ptab2 = jnp.where(drop[:, None], trash, ptab)
    return ptab2, free2, top + jnp.sum(b32, dtype=jnp.int32), ref2


def _gather_logical(pool, ptab):
    """(L, P+1, ps, Hkv, dh) pool + (B, max_pages) table -> the logical
    dense layout (L, B, max_pages*ps, Hkv, dh).  Positions in unmapped
    (trash) pages carry garbage — every consumer masks by kv_len/pos."""
    g = jnp.take(pool, ptab, axis=1)            # (L, B, MP, ps, Hkv, dh)
    b, mp = ptab.shape
    return g.reshape(pool.shape[0], b, mp * pool.shape[2], *pool.shape[3:])


def _scatter_logical(pool, ptab, vals, write):
    """Scatter logical rows `vals` (L, B, M, Hkv, dh) into mapped pages:
    position t of row b lands at (ptab[b, t//ps], t%ps).  Entries with
    write == False are routed to the trash page, so a single full-width
    scatter covers ragged prefill widths."""
    ps = pool.shape[2]
    b, m = write.shape
    t = jnp.arange(m)
    pid = jnp.where(write, ptab[:, t // ps], pool.shape[1] - 1)
    off = jnp.broadcast_to(t % ps, (b, m))
    return pool.at[:, pid, off].set(vals.astype(pool.dtype))


# --------------------------------------------------------------------------
# family specs
# --------------------------------------------------------------------------
class DecodeStateSpec:
    """Base: carry-family defaults; shared derived properties."""

    state_kind = "carry"

    def __init__(self, cfg):
        self.cfg = cfg

    @property
    def windowed(self) -> bool:
        """True when any leaf grows with sequence length (KV families) —
        the router then replicates in `width`-row deltas and tracks a
        cursor; carry planes sync whole-state and are fresh every sync."""
        return any(l >= 0 for l in jax.tree.leaves(self.length_axes()))

    def freeze(self, new, old, active):
        """Hold inactive rows across a decode sub-step.  Recurrent
        carries advance in place every sub-step, so inactive rows must
        hold their whole tree — bit-stable rows are what keep exports
        and standby syncs of neighbours deterministic."""
        return jax.tree.map(
            lambda n, o, ax: jnp.where(_bcast(active, n.ndim, ax), n, o),
            new, old, self.batch_axes())

    # --- migration/replication hooks (the engine's jit-root bodies) -------
    # The default implementations are the four generic tree ops over the
    # spec's axis declarations; a family whose physical layout is not
    # row-partitioned (the paged pool) overrides them while keeping the
    # WIRE format identical — the engine and router never see the
    # difference, and the bit-exactness proofs carry over.
    def export_rows(self, state, idx):
        return state_rows(state, self.batch_axes(), idx)

    def import_rows(self, state, bundle, src_for_dst, mask):
        return merge_rows(state, bundle, self.batch_axes(), src_for_dst,
                          mask)

    def export_delta_rows(self, state, idx, starts, width):
        return delta_since(state, self.batch_axes(), self.length_axes(),
                           idx, starts, width)

    def apply_delta_rows(self, state, bundle, src_for_dst, starts, mask):
        return delta_apply(state, bundle, self.batch_axes(),
                           self.length_axes(), src_for_dst, starts, mask)

    def init_standby(self, state):
        """Allocate the warm-standby store mirroring `state`'s wire
        format (zeroed)."""
        return jax.tree.map(jnp.zeros_like, state)

    def advance(self, state, active):
        """Pre-decode bookkeeping for `active` rows (paged: map the next
        page when a row crosses a page boundary).  Identity for
        row-partitioned families."""
        return state

    def release(self, state, drop):
        """Return per-row resources of `drop`-masked rows (paged: decref
        + free the rows' pages).  Identity for row-partitioned families,
        whose rows own fixed storage."""
        return state

    def counters(self, state):
        """{name: device scalar} of the counters the family's decode keeps
        in its state (cumulative); fetched with each block's tokens."""
        return {}

    def row_wire_bytes(self, max_len):
        """Actual wire cost of one slot row, from the axis declarations:
        (full_bytes, per_pos_bytes, carry_bytes).  full = one row's whole
        state tree (a full export / non-incremental sync); per_pos =
        bytes per cache position summed over windowed leaves (a width-W
        delta ships W * per_pos of them); carry = the non-windowed
        leaves, shipped whole on EVERY sync — for carry families this is
        the entire row (per_pos == 0), which is what plane_stats must
        report instead of pretending a sync moved one KV row."""
        st = jax.eval_shape(lambda: self.init_state(1, max_len))
        laxes = self.length_axes()
        full = per_pos = windowed_bytes = 0
        for leaf, lax_ in zip(jax.tree.leaves(st), jax.tree.leaves(laxes)):
            nb = int(leaf.size) * leaf.dtype.itemsize
            full += nb
            if lax_ >= 0:
                per_pos += nb // leaf.shape[lax_]
                windowed_bytes += nb
        return full, per_pos, full - windowed_bytes


class TransformerDecodeState(DecodeStateSpec):
    """KV family: (L, B, M, Hkv, dh) cache rows + per-row pos.  Covers the
    dense, MoE ("kv+experts": expert-sharded FFN via models/moe.py — the
    decode state itself is still per-slot KV rows), VLM and audio configs,
    and latent attention ("latent", models/mla.py), whose rows are
    (L, B, M, r + rope) per layer stack under its own keys.
    """

    def __init__(self, cfg: TransformerConfig):
        super().__init__(cfg)
        self.state_kind = _transformer.state_kind(cfg)
        self.names = _transformer.cache_names(cfg)

    def init_state(self, batch, max_len, dtype=None):
        st = _transformer.init_cache(self.cfg, batch, max_len, dtype)
        st["pos"] = jnp.zeros((batch,), jnp.int32)
        return st

    def batch_axes(self):
        return {**dict.fromkeys(self.names, 1), "pos": 0}

    def length_axes(self):
        return {**dict.fromkeys(self.names, 2), "pos": -1}

    def decode(self, params, state, last):
        return _transformer.decode_step(params, state, last, self.cfg)

    def prefill(self, params, state, tokens, lens, admit, page_ops=None):
        cfg = self.cfg
        b, lb = tokens.shape
        tmp = self.init_state(b, lb)
        logits, tmp = _transformer.decode_step(
            params, tmp, tokens, cfg, last_idx=jnp.maximum(lens - 1, 0))
        # merge admitted rows' fresh cache prefix into the shared cache
        new = dict(state)
        for nm in self.names:
            w = tmp[nm].shape[2]               # bucket len, block-aligned
            adm = admit.reshape((1, b) + (1,) * (tmp[nm].ndim - 2))
            new[nm] = state[nm].at[:, :, :w].set(
                jnp.where(adm, tmp[nm][:, :, :w], state[nm][:, :, :w]))
        new["pos"] = jnp.where(admit, lens, state["pos"])
        return logits, new

    def freeze(self, new, old, active):
        # KV rows of inactive slots only ever write into the masked tail
        # (pos is held), so only pos needs the select — the full-tree
        # where the carry families pay is skipped on the KV hot path.
        return {**new, "pos": jnp.where(active, new["pos"], old["pos"])}


class RGLRUDecodeState(DecodeStateSpec):
    """Griffin/RecurrentGemma carry: per-layer (h, conv) RG-LRU states
    plus an O(window) local-attention ring.  The ring has a length axis of
    fixed size `window`, but its slots are position-modular, not
    cursor-contiguous — it ships whole (laxis = -1), which is O(window),
    not O(seq): still the sub-quadratic migration story."""

    def init_state(self, batch, max_len, dtype=None):
        st = _rglru.init_cache(self.cfg, batch, max_len, dtype)
        st["pos"] = jnp.zeros((batch,), jnp.int32)
        return st

    def batch_axes(self):
        ax = {"rec_a": (1, 1), "rec_b": (1, 1), "attn": (1, 1), "pos": 0}
        if self.cfg.n_tail_rec:
            ax["tail"] = (1, 1)
        return ax

    def length_axes(self):
        return jax.tree.map(lambda _: -1, self.batch_axes())

    def decode(self, params, state, last):
        return _rglru.decode_step(params, state, last, self.cfg)

    def prefill(self, params, state, tokens, lens, admit, page_ops=None):
        logits, fresh = _rglru.prefill_cells(params, tokens, lens, self.cfg)
        return logits, admit_merge(state, fresh, self.batch_axes(), admit)


class XLSTMDecodeState(DecodeStateSpec):
    """xLSTM carry: sLSTM (c, n, m, h) scalar memories + mLSTM matrix
    memory (C, n, m) per pair — all O(1) in sequence length."""

    def init_state(self, batch, max_len, dtype=None):
        st = _xlstm.init_cache(self.cfg, batch, max_len, dtype)
        st["pos"] = jnp.zeros((batch,), jnp.int32)
        return st

    def batch_axes(self):
        return {"slstm": (1, 1, 1, 1), "mlstm": (1, 1, 1), "pos": 0}

    def length_axes(self):
        return jax.tree.map(lambda _: -1, self.batch_axes())

    def decode(self, params, state, last):
        return _xlstm.decode_step(params, state, last, self.cfg)

    def prefill(self, params, state, tokens, lens, admit, page_ops=None):
        logits, fresh = _xlstm.prefill_cells(params, tokens, lens, self.cfg)
        return logits, admit_merge(state, fresh, self.batch_axes(), admit)


class PagedTransformerDecodeState(TransformerDecodeState):
    """Paged KV family: the per-slot (B, M) cache rows become a shared
    pool of physical pages (L, P+1, page_size, Hkv, dh) addressed through
    a per-row (B, max_pages) int32 page table.  HBM scales with *live
    tokens* (pages allocated), not max_batch * max_len, and identical
    prompt prefixes share physical pages via refcounts.

    Allocator state rides in the tree (free-list stack + top + per-page
    refcounts), so alloc/free run INSIDE the engine's fused jits — zero
    host callbacks on the allocator path (budget entry
    "engine-serve-paged").  Invariants:
      * pages covering [0, pos) of an active row are always mapped;
        entries past ceil(pos/ps) hold the trash id (= pool_pages)
      * a page is on the free stack iff its refcount is 0
      * prefix-published pages carry a +1 pin from the pf table, so they
        outlive their publisher; a row's release never frees a page
        another row (or the prefix cache) still references
      * host-side admission gating reserves worst-case pages per request,
        so the in-graph stack never underflows

    The WIRE format (export/import/delta bundles) stays the dense logical
    {"k", "v", "pos"} layout, gathered through the table on the way out
    and re-paged on the way in — the router, standby store, and every
    bit-exactness proof from the dense plane carry over unchanged.
    Bit-identity with the dense engine holds because masked positions
    contribute exact-zero probability (-1e30 before the exp), and mapped
    positions hold bit-identical values by induction over writes.
    """

    def __init__(self, cfg: TransformerConfig, *, page_size: int,
                 max_batch: int, max_len: int, pool_pages=None,
                 prefix_entries: int = 0):
        super().__init__(cfg)
        self.state_kind += "-paged"
        if cfg.window is not None:
            raise ValueError("paged KV serving does not support local "
                             "(windowed) attention yet")
        if cfg.n_codebooks > 1:
            raise ValueError("paged KV serving supports single-codebook "
                             "token streams only")
        m = -(-max_len // 128) * 128       # same padding as init_cache
        if m % page_size:
            raise ValueError(
                f"page_size {page_size} must divide the padded cache "
                f"length {m} (max_len {max_len} rounded up to 128)")
        self.page_size = page_size
        self.padded_len = m
        self.max_pages = m // page_size
        self.pool_pages = (pool_pages if pool_pages is not None
                           else max_batch * self.max_pages)
        if self.pool_pages < self.max_pages:
            raise ValueError(
                f"pool_pages {self.pool_pages} cannot hold even one "
                f"max_len row ({self.max_pages} pages)")
        self.prefix_entries = prefix_entries
        self.max_batch = max_batch
        self.max_len = max_len
        self._dense = TransformerDecodeState(cfg)

    def init_state(self, batch, max_len, dtype=None):
        dtype = dtype or self.cfg.cdtype
        trash = self.pool_pages
        st = {
            **_transformer.init_paged_pools(self.cfg, self.pool_pages,
                                            self.page_size, dtype),
            "ptab": jnp.full((batch, self.max_pages), trash, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
            "free": jnp.arange(self.pool_pages, dtype=jnp.int32),
            "top": jnp.asarray(self.pool_pages, jnp.int32),
            "ref": jnp.zeros((self.pool_pages + 1,), jnp.int32),
        }
        if self.prefix_entries:
            st["pf_tab"] = jnp.full((self.prefix_entries, self.max_pages),
                                    trash, jnp.int32)
            st["pf_len"] = jnp.zeros((self.prefix_entries,), jnp.int32)
        if self.cfg.moe_d_ff:
            st.update(dict.fromkeys(_transformer.COUNTERS,
                                    jnp.zeros((), jnp.int32)))
        return st

    def counters(self, state):
        return {k: state[k] for k in _transformer.COUNTERS if k in state}

    # axis declarations describe the WIRE format (the dense logical
    # layout every bundle travels in), not the pool — all physical-layout
    # ops are overridden below. `decode` is the dense spec's: the trunk
    # reads a state holding "ptab" through its page table.
    def advance(self, state, active):
        """Map one fresh page for each active row whose next write
        position starts a new page (pos % ps == 0)."""
        ps = self.page_size
        pos = state["pos"]
        with jax.named_scope("pages"):
            col = jnp.clip(pos // ps, 0, self.max_pages - 1)
            need = active & (pos % ps == 0) & (pos // ps < self.max_pages)
            b = pos.shape[0]
            take = jnp.zeros((b, self.max_pages), bool)
            take = take.at[jnp.arange(b), col].set(need)
            ptab, ref, top = _alloc_rows(state["ptab"], state["free"],
                                         state["top"], state["ref"], take)
        return {**state, "ptab": ptab, "ref": ref, "top": top}

    def release(self, state, drop):
        with jax.named_scope("pages"):
            ptab, free, top, ref = _release_rows(
                state["ptab"], state["free"], state["top"], state["ref"],
                drop)
        return {**state, "ptab": ptab, "free": free, "top": top,
                "ref": ref}

    def live_pages(self, state):
        """Currently-allocated page count (device scalar)."""
        return self.pool_pages - state["top"]

    def prefill(self, params, state, tokens, lens, admit, page_ops=None):
        """Bucketed prefill into the pool: the model half runs on a dense
        temporary bucket cache (bit-identical logits to the dense
        engine), then the admitted rows' fresh KV is re-paged — shared
        prefix pages are mapped from the pf table (+refcount) instead of
        re-allocated, fresh pages come off the free stack, and rows
        flagged for publication pin their head pages into the pf table.

        `page_ops` (from host-side prefix matching): (B,) int32 vectors
        pf_entry (-1 = no shared prefix), pf_n (shared page count),
        pf_store (-1 = don't publish), pf_store_n (pages to publish)."""
        cfg = self.cfg
        b, lb = tokens.shape
        tmp = self._dense.init_state(b, lb)
        logits, tmp = _transformer.decode_step(
            params, tmp, tokens, cfg, last_idx=jnp.maximum(lens - 1, 0))

        # the page half: prefix-page mapping, allocation, re-paging and
        # publication
        with jax.named_scope("pages"):
            ps, mp, trash = self.page_size, self.max_pages, self.pool_pages
            cols = jnp.arange(mp)[None]                     # (1, MP)
            ptab = jnp.where(admit[:, None], trash, state["ptab"])
            ref, top = state["ref"], state["top"]
            if page_ops is None:
                zeros = jnp.zeros((b,), jnp.int32)
                page_ops = {"pf_entry": zeros - 1, "pf_n": zeros,
                            "pf_store": zeros - 1, "pf_store_n": zeros}
            pf_entry, pf_n = page_ops["pf_entry"], page_ops["pf_n"]
            pf_store, pf_store_n = page_ops["pf_store"], page_ops["pf_store_n"]

            new = dict(state)
            shared = jnp.where(admit & (pf_entry >= 0), pf_n, 0)
            if self.prefix_entries:
                # map shared prefix pages from the pf table + take a reference
                src = state["pf_tab"][jnp.clip(pf_entry, 0,
                                               self.prefix_entries - 1)]
                use = (admit & (pf_entry >= 0))[:, None] & \
                    (cols < shared[:, None])
                ptab = jnp.where(use, src, ptab)
                ref = ref.at[jnp.where(use, src, trash)].add(
                    use.astype(jnp.int32))

            # allocate the non-shared remainder of ceil(lens / ps) pages
            pages_needed = -(-lens // ps)
            take = admit[:, None] & (cols >= shared[:, None]) & \
                (cols < pages_needed[:, None])
            ptab, ref, top = _alloc_rows(ptab, state["free"], top, ref, take)

            # re-page the freshly prefilled KV (skip shared pages — their
            # contents are already resident and bit-identical by the
            # prefill length-independence proof)
            t = jnp.arange(tmp[self.names[0]].shape[2])[None]  # lb up to 128
            write = admit[:, None] & (t >= (shared * ps)[:, None]) & \
                (t < lens[:, None])
            for nm in self.names:
                new[nm + "p"] = _scatter_logical(state[nm + "p"], ptab,
                                                 tmp[nm], write)

            if self.prefix_entries:
                # publish flagged rows' head pages (+1 pin so they outlive
                # the publishing request)
                store = admit & (pf_store >= 0)
                ents = jnp.where(store, pf_store, self.prefix_entries)
                vals = jnp.where(cols < pf_store_n[:, None], ptab, trash)
                new["pf_tab"] = state["pf_tab"].at[ents].set(vals, mode="drop")
                new["pf_len"] = state["pf_len"].at[ents].set(pf_store_n,
                                                             mode="drop")
                pin = store[:, None] & (cols < pf_store_n[:, None])
                ref = ref.at[jnp.where(pin, ptab, trash)].add(
                    pin.astype(jnp.int32))

        new.update(ptab=ptab, ref=ref, top=top,
                   pos=jnp.where(admit, lens, state["pos"]))
        return logits, new

    # --- migration/replication: dense-logical wire format -----------------
    def export_rows(self, state, idx):
        ptab = jnp.take(state["ptab"], idx, axis=0)
        return {**{nm: _gather_logical(state[nm + "p"], ptab)
                   for nm in self.names},
                "pos": jnp.take(state["pos"], idx)}

    def import_rows(self, state, bundle, src_for_dst, mask):
        state = self.release(state, mask)      # targets drop their pages
        rows = {nm: jnp.take(bundle[nm], src_for_dst, axis=1)
                for nm in self.names}
        pos = jnp.where(mask, jnp.take(bundle["pos"], src_for_dst), 0)
        ps = self.page_size
        cols = jnp.arange(self.max_pages)[None]
        take = mask[:, None] & (cols < (-(-pos // ps))[:, None])
        ptab, ref, top = _alloc_rows(state["ptab"], state["free"],
                                     state["top"], state["ref"], take)
        t = jnp.arange(rows[self.names[0]].shape[2])[None]
        write = mask[:, None] & (t < pos[:, None])
        return {**state, "ptab": ptab, "ref": ref, "top": top,
                **{nm + "p": _scatter_logical(state[nm + "p"], ptab, r, write)
                   for nm, r in rows.items()},
                "pos": jnp.where(mask, pos, state["pos"])}

    def export_delta_rows(self, state, idx, starts, width):
        ptab = jnp.take(state["ptab"], idx, axis=0)
        cols = jnp.clip(starts[:, None] + jnp.arange(width), 0,
                        self.padded_len - 1)            # (B, W)
        pid = jnp.take_along_axis(ptab, cols // self.page_size, axis=1)
        off = cols % self.page_size
        return {**{nm: state[nm + "p"][:, pid, off] for nm in self.names},
                "pos": jnp.take(state["pos"], idx)}

    def init_standby(self, state):
        # the standby store holds the wire format: dense logical rows.
        # (Paged standby pools — pool-sized warm replicas — are a
        # follow-up; the delta/promote path is already layout-agnostic.)
        return self._dense.init_state(self.max_batch, self.max_len)

    def row_wire_bytes(self, max_len):
        return self._dense.row_wire_bytes(max_len)


def paged_spec(spec: DecodeStateSpec, *, page_size: int, max_batch: int,
               max_len: int, pool_pages=None,
               prefix_entries: int = 0) -> "PagedTransformerDecodeState":
    """Wrap a family spec's config in the paged-KV spec.  Only the
    transformer KV families page their state; carry families keep O(1)
    rows and have nothing to page."""
    if type(spec) is not TransformerDecodeState:
        raise ValueError(
            f"page_size > 0 requires a transformer KV family; "
            f"{type(spec).__name__} (state_kind={spec.state_kind!r}) "
            f"does not page")
    return PagedTransformerDecodeState(
        spec.cfg, page_size=page_size, max_batch=max_batch,
        max_len=max_len, pool_pages=pool_pages,
        prefix_entries=prefix_entries)


_FAMILIES = {
    TransformerConfig: TransformerDecodeState,
    RGLRUConfig: RGLRUDecodeState,
    XLSTMConfig: XLSTMDecodeState,
}


def decode_spec(cfg) -> DecodeStateSpec:
    """Config dataclass -> its family's DecodeState spec."""
    for klass, spec in _FAMILIES.items():
        if isinstance(cfg, klass):
            return spec(cfg)
    raise KeyError(
        f"no decode-state family registered for config type "
        f"{type(cfg).__name__}; registered families: "
        f"{sorted(k.__name__ for k in _FAMILIES)}")
