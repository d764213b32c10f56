"""Device-resident continuous-batching serving engine.

A fixed pool of `max_batch` decode slots shares one batched, block-aligned
KV cache. The decode hot path is a single fused jit (`engine_step`) that
runs admit-free decode->sample->bookkeeping for up to `decode_block` tokens
per host round-trip: per-slot state (last token, remaining budget, active /
eos / temperature, per-request PRNG streams) lives on device, sub-steps are
a `lax.scan`, finished rows are masked out (early-exit) inside the scan,
and the host drains one `(B, N)` token block + emit/done masks in a single
transfer. Host syncs per token drop from O(max_batch) to 1/N.

Prefill is power-of-two length-bucketed and full-batch: prompts are padded
to their bucket, always traced at the engine's (max_batch, bucket) shape
with an admit mask, and the per-row first token is sampled on device — a
mixed-length workload compiles at most len(buckets) prefill traces plus one
decode trace, instead of one trace per distinct prompt length.

Determinism: each request owns a PRNG stream (fold_in(base, submit_seq))
that advances once per decode sub-step and is sampled per-row (vmap'd
categorical), so outputs are bit-identical across decode_block settings,
slot placements, and co-batched traffic.

Hot-swap (serving/training co-residency): `swap_params` stages a new
param pytree (same treedef/shapes/dtypes — enforced, so the jitted hot
path gets a cache hit and `trace_count()` stays flat) and the engine
applies it at the next idle slot boundary. In-flight requests keep
decoding against the snapshot they were admitted under — admission is
held while a swap is pending, active slots drain, then the reference is
swapped atomically — so every request's full generation (prefill + all
decode blocks) is a pure function of ONE param snapshot and is
bit-identical to a fresh engine built on that snapshot.

Migration (constellation serving plane): `export_slots`/`import_slots`
move in-flight generations between engine replicas bit-exactly. Export is
one jitted device->device gather of the per-slot state pytree (last token,
budgets, eos/temps, PRNG streams) plus the slot's KV rows and position;
import is the matching scatter into free slots of another engine built on
the SAME param snapshot (enforced via params_version). The resumed decode
continues the request's PRNG stream and ragged KV length exactly where the
source left them, so the token sequence is bit-identical to an unmigrated
run — and both directions are fixed-shape (full-width, index+mask driven),
so repeated migrations are jit cache hits (`trace_count()` stays flat).
serving/router.py drives this from the constellation liveness mask.

The engine speaks the DecodeState protocol (models/decode_state.py), not
any one cache layout: every model family (transformer KV, RG-LRU carry,
xLSTM carry, MoE) supplies a spec with `init_state`/`decode`/`prefill`/
`freeze` plus batch/length axis declarations, and every migration
primitive here is a generic tree gather/scatter over those declarations —
carry migration carries the same bit-exactness proof as KV migration.

What operators can read, with the profiler on or off (no option turns any
of it on): host spans, `jax.profiler.TraceAnnotation`s on the same clock as
the device ops in a profiler trace — `engine.step` (all of `step`),
`engine.fill` (admission; arg `rows`), `engine.prefill` (one prefill
program call; args `bucket`, `rows`), `engine.decode_block`,
`engine.drain` (the host waiting on `jax.device_get` of a fill or a block)
and `engine.emit` (the host loop after a drain: tokens appended, requests
finished, pages returned). Counters in `stats`, per prefill call:
`prefill_calls`, `prefill_rows`, `prefill_tokens` (prompt tokens) and
`prefill_slot_tokens` (max_batch x bucket, the token rows the program
computes), so prefill_tokens / prefill_slot_tokens is the useful share of
prefill work. A model whose decode state keeps counters of its own
(`DecodeStateSpec.counters`; models/transformer.py's `COUNTERS`:
`decode_steps`, `expert_tokens`, `expert_tokens_peak` and
`latent_positions`) has them
copied into `stats` at each block's drain, fetched with the block's
tokens. Stamps on each `Request`, on `time.perf_counter()`:
`arrival` (the caller's, else `submit`'s), `admitted_at` (taken into a
slot) and `first_token_at` (first token on the host). Device ops carry
`jax.named_scope` names in their op metadata: `embed`, `layers` (the scan
over blocks: its own ops are the weight and cache slices and the cache
write-back), `attention`, `mlp`, `head`, `sample` and `pages` (paged
allocator and prefix-page mapping); the latent-attention model adds
`latent` (its decode attention) and `experts` (routing and the held
experts' products), models/mla.py.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import decode_state as ds


@dataclass
class Request:
    """One generation request.

    Fields:
      uid: caller-chosen id, echoed back on the finished request.
      prompt: (S,) int32 token ids; S must be <= EngineConfig.max_len.
      max_new_tokens: decode budget; generation stops after this many
        tokens even without an eos hit.
      temperature: 0 = greedy argmax; > 0 samples top-k at this
        temperature from the request's own PRNG stream.
      eos_id: stop token (None = budget/max_len only).
      arch: arch-group label (a model config name) on a heterogeneous
        ConstellationRouter plane; None = the plane's default group.
        Ignored by a bare ServingEngine.
      generated: output token ids (filled in by the engine).
      done: set once the request left its slot (eos/budget/out-of-room).
      arrival: when the request arrived, on time.perf_counter(); the
        caller's, else stamped by `submit`.
      admitted_at: when a fill took it off the queue into a slot.
      first_token_at: when its first token reached the host.
    """
    uid: int
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0        # 0 = greedy
    eos_id: Optional[int] = None
    arch: Optional[str] = None
    # outputs
    generated: list = field(default_factory=list)
    done: bool = False
    arrival: Optional[float] = None
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    # engine-internal: submission order, keys the request's PRNG stream
    _seq: int = -1
    # engine-internal: params_version the request was admitted (and will
    # fully decode) under — the co-residency determinism witness
    _params_version: int = -1


# Enforced by `python -m repro.analysis.lint --budgets` (entry
# "engine-serve"): the fused decode block and every prefill bucket must
# compile with zero host callbacks and zero collectives (decode is
# pod-local by design), and decode+prefill lowerings stay bounded by the
# pow2 bucket count.
LINT_BUDGET = {
    "host_callbacks": 0,
    "decode_collective_wire_bytes": 0,
    "max_traces": 4,  # 3 prefill buckets (16/32/64 on the smoke config) + decode
}


@dataclass(frozen=True)
class EngineConfig:
    """Serving-engine knobs.

    Fields:
      max_batch: decode-slot count — the fixed batch of the shared KV
        cache; also the prefill batch (continuous batching admits into
        free slots).
      max_len: KV-cache length per slot; prompt_len + generated tokens
        are truncated to it (out-of-room rows finish early).
      top_k: sampling pool size for temperature > 0 requests.
      seed: base PRNG key; each request's stream is
        fold_in(seed, submit_order).
      decode_block: tokens decoded per fused device call (and per host
        round-trip) — host syncs per token are ~1/decode_block.
      min_bucket: smallest power-of-two prefill bucket; prompts pad up
        to their bucket so traces stay bounded by len(buckets) + 1.
      page_size: 0 = dense per-slot KV rows (the default); > 0 switches
        transformer KV families to the paged layout — KV lives in a
        shared pool of physical pages addressed through per-row page
        tables, HBM tracks live tokens instead of max_batch * max_len,
        and identical prompt heads share pages via refcounts.
      pool_pages: physical page-pool size (paged only); None sizes the
        pool dense-equivalent (max_batch * max_len worth of pages).
        Undersizing it is the point: admission gates on free pages, so
        slots can oversubscribe the pool safely.
      prefix_cache: number of prefix-cache entries (paged only; 0 = off).
        Whole-page prompt heads are published here and later prompts
        with an identical head map the SAME physical pages (+refcount)
        instead of recomputing/duplicating them.
    """
    max_batch: int = 8
    max_len: int = 512
    top_k: int = 50
    seed: int = 0
    decode_block: int = 8           # tokens decoded per host round-trip
    min_bucket: int = 16            # smallest prefill bucket (pow2)
    page_size: int = 0              # 0 = dense layout
    pool_pages: Optional[int] = None
    prefix_cache: int = 0           # prefix-cache entries (paged only)

    def __post_init__(self):
        if self.decode_block < 1:
            raise ValueError(f"decode_block must be >= 1, "
                             f"got {self.decode_block}")
        if self.min_bucket < 1:
            raise ValueError(f"min_bucket must be >= 1, "
                             f"got {self.min_bucket}")
        if self.page_size < 0:
            raise ValueError(f"page_size must be >= 0, "
                             f"got {self.page_size}")
        if not self.page_size and self.pool_pages is not None:
            raise ValueError("pool_pages requires page_size > 0")
        if not self.page_size and self.prefix_cache:
            raise ValueError("prefix_cache requires page_size > 0 "
                             "(prefix sharing is page-granular)")


def check_swap_compatible(old_params, new_params):
    """Raise unless `new_params` can replace `old_params` on a jit cache
    hit: identical tree structure, shapes, and dtypes. Shared by
    `ServingEngine.swap_params` and the router's plane-wide staging."""
    old, new = jax.tree.structure(old_params), jax.tree.structure(new_params)
    if old != new:
        raise ValueError(f"swap_params: tree structure mismatch "
                         f"({new} != {old})")
    for o, n in zip(jax.tree.leaves(old_params), jax.tree.leaves(new_params)):
        if o.shape != n.shape or o.dtype != n.dtype:
            raise ValueError(
                f"swap_params: leaf mismatch {n.shape}/{n.dtype} != "
                f"{o.shape}/{o.dtype} — a swap must be re-trace-free")


class ServingEngine:
    """`device` commits params and all device state to one device (e.g.
    one replica per chip behind a ConstellationRouter); the jitted steps
    then run there, and rows imported from another engine are copied
    onto it first. None leaves placement to JAX's default device."""

    def __init__(self, cfg, fns, params, ecfg: EngineConfig, device=None):
        self.model_cfg = cfg
        self.fns = fns
        self.device = device
        self.params = self._place(params)
        self.ecfg = ecfg
        spec_fn = getattr(fns, "decode_spec", None) or ds.decode_spec
        self.spec = spec_fn(cfg)
        if ecfg.page_size:
            self.spec = ds.paged_spec(
                self.spec, page_size=ecfg.page_size,
                max_batch=ecfg.max_batch, max_len=ecfg.max_len,
                pool_pages=ecfg.pool_pages,
                prefix_entries=ecfg.prefix_cache)
        self.cache = self._place(
            self.spec.init_state(ecfg.max_batch, ecfg.max_len))
        self._axes = self.spec.batch_axes()
        self._laxes = self.spec.length_axes()
        b = ecfg.max_batch
        self.state = self._place({
            "last": jnp.zeros((b,), jnp.int32),
            "active": jnp.zeros((b,), bool),
            "remaining": jnp.zeros((b,), jnp.int32),
            "temp": jnp.zeros((b,), jnp.float32),
            "eos": jnp.full((b,), -1, jnp.int32),
            "rkey": jnp.zeros((b, 2), jnp.uint32),
        })
        self._base_key = jax.random.PRNGKey(ecfg.seed)
        self._next_seq = 0
        self.slots: list[Optional[Request]] = [None] * b
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.params_version = 0
        self._pending_params = None
        self.standby = None          # lazily allocated warm-standby store
        self.stats = {"tokens": 0, "host_syncs": 0, "swaps": 0,
                      "standby_syncs": 0, "promoted_slots": 0,
                      "prefill_calls": 0, "prefill_rows": 0,
                      "prefill_tokens": 0, "prefill_slot_tokens": 0}
        # host-side conservative page accounting (paged layout only):
        # admission reserves worst-case pages per request so the in-graph
        # allocator's free stack can never underflow.  Invariant:
        # device free pages >= self._pool_free >= 0.
        self._pool_free = getattr(self.spec, "pool_pages", 0)
        self._reserved: dict[int, tuple[int, int]] = {}  # slot -> (pages, pinned)
        self._prefix_index: dict[bytes, tuple[int, int]] = {}  # hash -> (entry, n_pages)
        self._prefix_staged: dict[bytes, tuple[int, int]] = {}
        self._next_prefix_entry = 0
        if ecfg.page_size:
            self.stats.update(pages_reserved=0, pages_shared=0,
                              prefix_hits=0, prefix_stores=0,
                              admission_stalls=0)
        # the model's own counters (cumulative, kept in its decode state)
        self.stats.update(dict.fromkeys(self.spec.counters(self.cache), 0))

        self._prefill = jax.jit(self._prefill_impl)
        self._engine_step = jax.jit(self._engine_step_impl)
        self._export = jax.jit(self._export_impl)
        self._import = jax.jit(self._import_impl)
        self._delta_export = jax.jit(self._delta_export_impl,
                                     static_argnums=(4,))
        self._standby_apply = jax.jit(self._standby_apply_impl)
        self._deactivate = jax.jit(self._deactivate_impl)

    def _place(self, tree):
        """Commit `tree` to this engine's device (identity without one)."""
        return tree if self.device is None else jax.device_put(tree,
                                                               self.device)

    # --- bucketing ---------------------------------------------------------
    def buckets(self) -> list[int]:
        """Power-of-two prefill bucket lengths up to max_len."""
        out, b = [], self.ecfg.min_bucket
        while b < self.ecfg.max_len:
            out.append(b)
            b *= 2
        out.append(self.ecfg.max_len)
        return out

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets():
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds max_len "
                         f"{self.ecfg.max_len}")

    # --- device-side sampling ---------------------------------------------
    def _sample(self, logits, keys, temps):
        """Per-row top-k temperature sampling (greedy where temp == 0).

        `keys` is (B, 2): each row draws from its own request stream, so
        the result is independent of slot placement and co-batched rows."""
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1)
            k = min(self.ecfg.top_k, logits.shape[-1])
            vals, idx = jax.lax.top_k(logits, k)
            scaled = vals / jnp.maximum(temps[:, None], 1e-6)
            draw = jax.vmap(jax.random.categorical)(keys, scaled)
            sampled = jnp.take_along_axis(idx, draw[:, None], -1)[:, 0]
            return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    # --- fused decode block (the hot path) --------------------------------
    def _engine_step_impl(self, params, cache, state):
        """Decode up to N tokens for every active slot with zero host syncs.

        Each sub-step: spec.advance (paged: map a fresh page for rows
        crossing a page boundary; dense/carry: identity) -> batched
        spec.decode -> per-row sample -> masked bookkeeping ->
        spec.release (paged: finished rows' pages go back on the free
        stack IN-SCAN, so they are admissible to the very next fill at
        this block's boundary; dense/carry: identity). Rows that finish
        (eos / budget / out of room) are deactivated in-scan; inactive
        rows hold their state via spec.freeze (KV: pos frozen so stale
        cache writes land in the masked tail — paged: in the trash page,
        since a released row's table is all-trash; carry: the whole row
        tree holds) and their PRNG stream idles deterministically."""
        n = self.ecfg.decode_block
        max_len = self.ecfg.max_len

        def sub(carry, _):
            cache, st = carry
            was = st["active"]
            cache = self.spec.advance(cache, was)
            logits, cache2 = self.spec.decode(params, cache,
                                              st["last"][:, None])
            pair = jax.vmap(jax.random.split)(st["rkey"])
            tok = self._sample(logits, pair[:, 1], st["temp"])
            tok = jnp.where(was, tok, st["last"])
            cache2 = self.spec.freeze(cache2, cache, was)
            pos = cache2["pos"]
            remaining = st["remaining"] - was.astype(jnp.int32)
            done = was & ((tok == st["eos"]) | (remaining <= 0)
                          | (pos + 1 >= max_len))
            cache2 = self.spec.release(cache2, done)
            st2 = {"last": tok, "active": was & ~done,
                   "remaining": remaining, "temp": st["temp"],
                   "eos": st["eos"],
                   "rkey": jnp.where(was[:, None], pair[:, 0], st["rkey"])}
            return (cache2, st2), (tok, was, done)

        (cache, state), (toks, emit, done) = jax.lax.scan(
            sub, (cache, state), None, length=n)
        return cache, state, toks.T, emit.T, done.T      # (B, N) each

    # --- bucketed prefill --------------------------------------------------
    def _prefill_impl(self, params, cache, state, tokens, lens, admit,
                      temps, eos, budgets, seqs, page_ops):
        """Prefill `admit`-masked rows of a (max_batch, bucket_len) token
        block into the shared cache and sample each row's first token.

        Always traced at the full engine batch: the number of distinct
        traces is bounded by the number of buckets, not by (group size x
        prompt length) combinations. The model half (ragged prefill +
        admit-masked merge into the shared state) is the family's
        spec.prefill; the sampler half below is family-agnostic.
        `page_ops` carries the host's per-row prefix-cache plan (paged
        layout only; the dense families ignore it): which pf entry to
        map shared head pages from, and which rows publish theirs."""
        logits, new_cache = self.spec.prefill(params, cache, tokens, lens,
                                              admit, page_ops=page_ops)

        # per-request PRNG streams: fold_in(base, submit_seq) — admission
        # order and slot placement cannot perturb sampling
        rkeys = jax.vmap(lambda s: jax.random.fold_in(self._base_key, s))(
            seqs).astype(jnp.uint32)
        pair = jax.vmap(jax.random.split)(rkeys)
        first = self._sample(logits, pair[:, 1], temps)
        done0 = admit & ((first == eos) | (budgets <= 1)
                         | (lens + 1 >= self.ecfg.max_len))
        # rows that finish at admission free their pages immediately
        # (paged; identity otherwise)
        new_cache = self.spec.release(new_cache, done0)

        def sel(new, old):
            return jnp.where(admit if new.ndim == 1 else admit[:, None],
                             new, old)
        new_state = {
            "last": sel(first, state["last"]),
            "active": jnp.where(admit, ~done0, state["active"]),
            "remaining": sel(budgets - 1, state["remaining"]),
            "temp": sel(temps, state["temp"]),
            "eos": sel(eos, state["eos"]),
            "rkey": sel(pair[:, 0], state["rkey"]),
        }
        return new_cache, new_state, first, done0

    # --- slot migration (constellation serving plane) ----------------------
    def _export_impl(self, cache, state, idx, drop):
        """Gather rows `idx` of the slot state + model state tree into
        fresh device buffers and deactivate `drop`-masked rows on the
        source. One generic tree gather over the spec's batch axes.

        Always full-width (idx/drop are (max_batch,)): one trace covers
        every export size, so repeated migrations are jit cache hits.

        The bundle travels in the spec's WIRE format — for the paged
        layout that is the dense logical row (gathered through the page
        table on the way out), so physical page ids never leave the pod
        and the receiver may run any layout with the same max_len.
        Dropped rows hand their pages back to the pool (spec.release;
        identity for dense/carry)."""
        bundle_cache = self.spec.export_rows(cache, idx)
        bundle_state = jax.tree.map(lambda x: jnp.take(x, idx, axis=0),
                                    state)
        new_cache = self.spec.release(cache, drop)
        new_state = {**state, "active": state["active"] & ~drop}
        return bundle_cache, bundle_state, new_cache, new_state

    def _import_impl(self, cache, state, bcache, bstate, src_for_dst, mask):
        """Scatter bundle rows into `mask`-ed destination slots; row d
        receives bundle row `src_for_dst[d]`. One generic tree scatter
        over the spec's batch axes; unmasked rows are untouched, so
        resident generations cannot be perturbed by an import."""
        new_cache = self.spec.import_rows(cache, bcache, src_for_dst,
                                          mask)

        def sel(b, old):
            g = jnp.take(b, src_for_dst, axis=0)
            w = mask if old.ndim == 1 else mask[:, None]
            return jnp.where(w, g, old)

        return new_cache, jax.tree.map(sel, bstate, state)

    def export_slots(self, slot_ids) -> dict:
        """Extract the in-flight generations in `slot_ids` for migration.

        Returns a bundle holding the slots' device state (last token,
        remaining budget, temperature, eos, PRNG stream), their KV-cache
        rows + per-row positions (fresh buffers — the source may keep
        decoding its other slots), the Request objects, and the source's
        params_version. The exported rows are deactivated and their slots
        freed; everything device-side is ONE jitted gather, no re-trace
        after the first call and no device->host transfer."""
        slot_ids = list(slot_ids)
        if not slot_ids:
            raise ValueError("export_slots: empty slot list")
        b = self.ecfg.max_batch
        idx = np.zeros((b,), np.int32)
        drop = np.zeros((b,), bool)
        reqs = []
        for j, s in enumerate(slot_ids):
            req = self.slots[s]
            if req is None:
                raise ValueError(f"export_slots: slot {s} is empty")
            idx[j] = s
            drop[s] = True
            reqs.append(req)
        bcache, bstate, self.cache, self.state = self._export(
            self.cache, self.state, jnp.asarray(idx), jnp.asarray(drop))
        for s in slot_ids:
            self.slots[s] = None
            self._return_pages(s)
        return {"cache": bcache, "state": bstate, "requests": reqs,
                "params_version": self.params_version,
                "max_len": self.ecfg.max_len}

    def import_slots(self, bundle) -> list[int]:
        """Resume a bundle of exported generations on this engine.

        Bit-exactness contract: this engine must serve the SAME param
        snapshot the requests were decoding under at export (the bundle
        carries the source's params_version — a mismatch raises instead of
        silently mixing snapshots mid-generation) and share max_len (the
        KV row length). Rows land in this engine's free slots via ONE
        jitted scatter; decode then continues each request's PRNG stream
        and ragged KV length exactly where the source stopped. Returns the
        destination slot ids."""
        if bundle["max_len"] != self.ecfg.max_len:
            raise ValueError(
                f"import_slots: max_len mismatch {bundle['max_len']} != "
                f"{self.ecfg.max_len} — replicas must share the KV layout")
        if bundle["params_version"] != self.params_version:
            raise ValueError(
                f"import_slots: param snapshot mismatch (bundle v"
                f"{bundle['params_version']} != engine v"
                f"{self.params_version}) — a migrated generation must "
                "resume on its admission snapshot")
        reqs = bundle["requests"]
        free = [i for i, s in enumerate(self.slots) if s is None]
        if len(free) < len(reqs):
            raise ValueError(f"import_slots: {len(reqs)} rows but only "
                             f"{len(free)} free slots")
        b = self.ecfg.max_batch
        src = np.zeros((b,), np.int32)
        mask = np.zeros((b,), bool)
        dst_slots = free[:len(reqs)]
        for j, d in enumerate(dst_slots):
            src[d] = j
            mask[d] = True
        self._reserve_for_resume(dst_slots, reqs)
        bcache, bstate = self._place((bundle["cache"], bundle["state"]))
        self.cache, self.state = self._import(
            self.cache, self.state, bcache, bstate,
            jnp.asarray(src), jnp.asarray(mask))
        for d, req in zip(dst_slots, reqs):
            self.slots[d] = req
        return dst_slots

    # --- warm-standby replication (tuple-space serving grid) ---------------
    def _delta_export_impl(self, cache, state, idx, starts, width):
        """Gather each `idx` slot's state delta: leaves with a length axis
        (KV rows) windowed to [starts, starts + width) from the per-row
        replication cursor, carry leaves whole (they are O(1)/O(window) —
        the whole carry IS the delta). Only rows written since the last
        sync cross the (simulated) wire, not the whole max_len cache row.
        Full-width (idx/starts are (max_batch,)) so every sync size
        shares one trace. Paged sources gather the window through the
        page table — the delta bundle is layout-agnostic dense rows."""
        bcache = self.spec.export_delta_rows(cache, idx, starts, width)
        bstate = jax.tree.map(lambda x: jnp.take(x, idx, axis=0), state)
        return bcache, bstate

    def _standby_apply_impl(self, sb_cache, sb_state, bcache, bstate,
                            src_for_dst, starts, mask):
        """Scatter a delta bundle into `mask`-ed standby rows: row r takes
        bundle row `src_for_dst[r]` — windowed leaves at [starts[r],
        starts[r] + W) clipped to the rows actually written (the source's
        pos), carry leaves whole. standby `pos` tracks the replication
        cursor — when it reaches the source's pos the standby is
        promotable (a pointer-flip failover target); carry planes land
        there after every sync."""
        new_cache = self.spec.apply_delta_rows(sb_cache, bcache,
                                               src_for_dst, starts, mask)

        def sel(b, old):
            g = jnp.take(b, src_for_dst, axis=0)
            return jnp.where(mask if old.ndim == 1 else mask[:, None],
                             g, old)

        return new_cache, jax.tree.map(sel, bstate, sb_state)

    def _deactivate_impl(self, cache, state, drop):
        cache = self.spec.release(cache, drop)
        return cache, {**state, "active": state["active"] & ~drop}

    def ensure_standby(self):
        """Allocate the warm-standby store: a full-width mirror of the
        slot state + KV cache holding replicas of OTHER pods' in-flight
        generations. Lazy — engines outside a replicated grid never pay
        the memory."""
        if self.standby is None:
            self.standby = self._place({
                "cache": self.spec.init_standby(self.cache),
                "state": jax.tree.map(jnp.zeros_like, self.state),
            })

    def export_delta(self, entries, width: int) -> dict:
        """Delta-export `entries` = [(slot, cursor), ...]: each slot's
        windowed state delta [cursor, cursor + width) (whole carry for
        carry families) + its sampler state row, in ONE jitted gather.
        Unlike `export_slots` this does NOT deactivate or free anything —
        the source keeps decoding; this is the background replication
        feed, off the decode critical path (no host sync)."""
        b = self.ecfg.max_batch
        if not 0 < len(entries) <= b:
            raise ValueError(f"export_delta: {len(entries)} entries for "
                             f"{b} slots")
        idx = np.zeros((b,), np.int32)
        starts = np.zeros((b,), np.int32)
        for j, (s, c) in enumerate(entries):
            if self.slots[s] is None:
                raise ValueError(f"export_delta: slot {s} is empty")
            idx[j] = s
            starts[j] = c
        bcache, bstate = self._delta_export(
            self.cache, self.state, jnp.asarray(idx), jnp.asarray(starts),
            int(width))
        return {"cache": bcache, "state": bstate,
                "starts": starts, "params_version": self.params_version,
                "max_len": self.ecfg.max_len}

    def standby_apply(self, bundle, placements):
        """Apply a delta bundle to this engine's standby store.
        `placements` = [(bundle_row, standby_row), ...]; ONE jitted
        scatter, no host sync. The bundle must come from an engine on the
        same param snapshot and KV layout (a standby is only ever
        promoted into THIS engine, so the import invariants apply at
        write time, not just at failover)."""
        if bundle["max_len"] != self.ecfg.max_len:
            raise ValueError(
                f"standby_apply: max_len mismatch {bundle['max_len']} != "
                f"{self.ecfg.max_len}")
        if bundle["params_version"] != self.params_version:
            raise ValueError(
                f"standby_apply: param snapshot mismatch (bundle v"
                f"{bundle['params_version']} != engine v"
                f"{self.params_version})")
        self.ensure_standby()
        b = self.ecfg.max_batch
        src = np.zeros((b,), np.int32)
        starts = np.zeros((b,), np.int32)
        mask = np.zeros((b,), bool)
        for j, r in placements:
            src[r] = j
            starts[r] = bundle["starts"][j]
            mask[r] = True
        bcache, bstate = self._place((bundle["cache"], bundle["state"]))
        sc, ss = self._standby_apply(
            self.standby["cache"], self.standby["state"], bcache, bstate,
            jnp.asarray(src), jnp.asarray(starts), jnp.asarray(mask))
        self.standby = {"cache": sc, "state": ss}
        self.stats["standby_syncs"] += 1

    def promote_standby(self, pairs) -> list[int]:
        """Pointer-flip failover: resume `pairs` = [(standby_row,
        Request), ...] from this engine's OWN standby store into its free
        slots. The replica is already resident — no export from the (dead)
        source pod, no cross-pod transfer on the critical path; the only
        device work is the same one jitted scatter `import_slots` uses
        (cache hit). The caller (the router) must only promote FRESH
        standbys (cursor == source pos, state synced after the source's
        last decode block) — that is what makes the continuation
        bit-identical."""
        if self.standby is None:
            raise ValueError("promote_standby: no standby store")
        reqs = [r for _, r in pairs]
        free = [i for i, s in enumerate(self.slots) if s is None]
        if len(free) < len(reqs):
            raise ValueError(f"promote_standby: {len(reqs)} rows but only "
                             f"{len(free)} free slots")
        b = self.ecfg.max_batch
        src = np.zeros((b,), np.int32)
        mask = np.zeros((b,), bool)
        dst_slots = free[:len(reqs)]
        for (row, _), d in zip(pairs, dst_slots):
            src[d] = row
            mask[d] = True
        self._reserve_for_resume(dst_slots, reqs)
        self.cache, self.state = self._import(
            self.cache, self.state, self.standby["cache"],
            self.standby["state"], jnp.asarray(src), jnp.asarray(mask))
        for d, req in zip(dst_slots, reqs):
            self.slots[d] = req
        self.stats["promoted_slots"] += len(reqs)
        return dst_slots

    def clear_rows(self, slot_ids):
        """Deactivate device rows whose generations now live elsewhere
        (pointer-flipped off this pod, or shed). On a masked pod this is
        deferred to rejoin — it models the reboot wiping slot memory —
        so the flip itself never touches the dead engine."""
        b = self.ecfg.max_batch
        drop = np.zeros((b,), bool)
        for s in slot_ids:
            drop[s] = True
            self._return_pages(s)
        self.cache, self.state = self._deactivate(self.cache, self.state,
                                                  jnp.asarray(drop))

    # --- param hot-swap (serving/training co-residency) --------------------
    def swap_params(self, new_params):
        """Stage `new_params` as the next param snapshot to serve from.

        The swap is applied at the next moment no request is in flight
        (`step` holds admissions while a swap is pending, so active slots
        drain in at most max_new_tokens decode blocks): a request admitted
        under snapshot v decodes its WHOLE generation against v, never a
        mix. Applying the swap is a host-side reference assignment — no
        cache reset, no device sync — and the new tree must match the old
        one's structure/shapes/dtypes exactly, so the jitted prefill /
        decode hot path re-runs on a jit cache HIT (`trace_count()` is
        flat across swaps; asserted in tests).

        Staging twice before the swap applies keeps only the newest
        params (the older staged snapshot was never served).

        Returns the version number the new params will serve under.
        """
        check_swap_compatible(self.params, new_params)
        self._pending_params = self._place(new_params)
        self._maybe_apply_swap()
        return self.params_version + (self._pending_params is not None)

    def _maybe_apply_swap(self):
        """Apply a staged swap once no generation is in flight."""
        if self._pending_params is not None and \
                all(s is None for s in self.slots):
            self.params = self._pending_params
            self._pending_params = None
            self.params_version += 1
            self.stats["swaps"] += 1

    # --- host-side page accounting (paged layout only) ---------------------
    @property
    def _paged(self) -> bool:
        return bool(self.ecfg.page_size)

    def _return_pages(self, slot: int):
        """A slot left the engine (finished / exported / cleared): its
        worst-case reservation minus any permanently-pinned prefix pages
        goes back to the host's free-page count."""
        if not self._paged:
            return
        reserve, pinned = self._reserved.pop(slot, (0, 0))
        self._pool_free += reserve - pinned

    def _reserve_for_resume(self, dst_slots, reqs):
        """Reserve pages for rows arriving via import/promote: worst case
        = every page the resumed generation can still touch. Raises if
        the pool cannot cover it (the caller keeps the bundle)."""
        if not self._paged:
            return
        ps = self.ecfg.page_size
        plans = []
        for req in reqs:
            kv = len(req.prompt) + len(req.generated)
            left = req.max_new_tokens - len(req.generated)
            need = -(-min(kv + max(left, 0), self.ecfg.max_len) // ps)
            plans.append(need)
        if sum(plans) > self._pool_free:
            raise ValueError(
                f"import: {sum(plans)} pages needed but only "
                f"{self._pool_free} free in the pool")
        for d, need in zip(dst_slots, plans):
            self._reserved[d] = (need, 0)
            self._pool_free -= need
            self.stats["pages_reserved"] += need

    def _page_plan(self, req: Request):
        """Host half of admission for the paged layout: worst-case page
        reservation + the prefix-cache plan.

        Returns (reserve, pinned, ops) where ops = (pf_entry, pf_n,
        pf_store, pf_store_n) for this row, or None if the pool cannot
        cover the reservation right now.

        Prefix matching is whole-page and longest-match over already
        PUBLISHED entries (entries staged earlier in this same fill are
        not yet resident on device, so they only become matchable after
        their prefill call was issued). A complete miss publishes the
        prompt's whole-page head if entries remain — pinned pages are
        paid for by this request's reservation and never returned."""
        ps = self.ecfg.page_size
        s = len(req.prompt)
        total = -(-min(s + req.max_new_tokens, self.ecfg.max_len) // ps)
        prompt = np.asarray(req.prompt, np.int32)
        entry, shared = -1, 0
        store, store_n = -1, 0
        if self.ecfg.prefix_cache:
            for j in range(s // ps, 0, -1):
                hit = self._prefix_index.get(prompt[:j * ps].tobytes())
                if hit is not None:
                    entry, shared = hit[0], j
                    self.stats["prefix_hits"] += 1
                    break
            j_store = s // ps
            if entry < 0 and j_store > 0 and \
                    self._next_prefix_entry < self.ecfg.prefix_cache and \
                    prompt[:j_store * ps].tobytes() not in self._prefix_staged:
                # (a head already staged by an earlier row in this same
                # fill is being published by THAT row — don't burn a
                # second entry on it)
                store = self._next_prefix_entry
                store_n = j_store
                self._next_prefix_entry += 1
                for j in range(1, j_store + 1):
                    key = prompt[:j * ps].tobytes()
                    if key not in self._prefix_index and \
                            key not in self._prefix_staged:
                        self._prefix_staged[key] = (store, j)
                self.stats["prefix_stores"] += 1
        reserve = total - shared
        if reserve > self._pool_free:
            # roll back the store claim — the request stays queued
            if store >= 0:
                self._next_prefix_entry -= 1
                self._prefix_staged = {
                    k: v for k, v in self._prefix_staged.items()
                    if v[0] != store}
                self.stats["prefix_stores"] -= 1
            if entry >= 0:
                self.stats["prefix_hits"] -= 1
            return None
        pinned = store_n if store >= 0 else 0
        self.stats["pages_reserved"] += reserve
        self.stats["pages_shared"] += shared
        return reserve, pinned, (entry, shared, store, store_n)

    def page_stats(self) -> dict:
        """Paged-pool occupancy: host-side conservative view plus the
        device allocator's live-page count (one device scalar read — a
        diagnostics call, not the hot path)."""
        if not self._paged:
            return {}
        live = int(jax.device_get(self.spec.live_pages(self.cache)))
        return {"pool_pages": self.spec.pool_pages,
                "host_free": self._pool_free,
                "device_live": live,
                "page_size": self.ecfg.page_size,
                "prefix_entries_used": self._next_prefix_entry}

    # --- host-side slot management ----------------------------------------
    def submit(self, req: Request):
        if len(req.prompt) >= self.ecfg.max_len:
            # == max_len is rejected too: the cache row would be full at
            # admission with zero room for even one decoded token
            raise ValueError(
                f"request {req.uid}: prompt length {len(req.prompt)} "
                f"must be < max_len {self.ecfg.max_len} (a prompt that "
                f"fills the whole cache row leaves no room to decode)")
        if req._seq < 0:
            # a router may pre-assign plane-level seqs so each request's
            # PRNG stream is independent of which replica it lands on
            req._seq = self._next_seq
            self._next_seq += 1
        if req.arrival is None:
            req.arrival = time.perf_counter()
        self.queue.append(req)

    def _fill_slots(self):
        """Admit queued requests into free slots via bucketed prefill.

        Paged layout: admission also gates on free PAGES — each request
        reserves its worst-case page count (prompt + full decode budget,
        minus prefix-shared pages) against the host's conservative pool
        counter, so the in-graph allocator never underflows even with
        slots oversubscribing an undersized pool. The queue is FIFO:
        a head request that does not fit stalls admission (no reorder,
        no starvation) until a decode block recycles enough pages."""
        with jax.profiler.TraceAnnotation("engine.fill") as span:
            admitted = self._admit()
            span.set_metadata(rows=len(admitted))
            if not admitted:
                return
            results = self._prefill_buckets(admitted)
            # one transfer for all admission rounds in this fill
            with jax.profiler.TraceAnnotation("engine.drain"):
                flat = jax.device_get([(f, d) for _, f, d in results])  # repro-lint: allow[HS001] the single batched admission drain; counted in stats["host_syncs"]
            now = time.perf_counter()
            self.stats["host_syncs"] += 1
            with jax.profiler.TraceAnnotation("engine.emit"):
                for (grp, _, _), (first, done0) in zip(results, flat):
                    for slot, req, _ in grp:
                        req.generated.append(int(first[slot]))
                        req.first_token_at = now
                        self.stats["tokens"] += 1
                        if done0[slot]:
                            req.done = True
                            self.finished.append(req)
                            self.slots[slot] = None
                            self._return_pages(slot)

    def _admit(self):
        """Take queued requests into free slots (FIFO, page-gated when
        paged): [(slot, request, page ops)]."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        admitted = []
        while free and self.queue:
            if self._paged:
                plan = self._page_plan(self.queue[0])
                if plan is None:
                    self.stats["admission_stalls"] += 1
                    break
                slot = free.pop(0)
                self._reserved[slot] = plan[:2]
                self._pool_free -= plan[0]
                admitted.append((slot, self.queue.pop(0), plan[2]))
            else:
                admitted.append((free.pop(0), self.queue.pop(0), None))
        now = time.perf_counter()
        for _, req, _ in admitted:
            req.admitted_at = now
        return admitted

    def _prefill_buckets(self, admitted):
        """One full-batch prefill call per bucket the admitted rows use:
        [(rows, first tokens, done-at-admission)], still on device."""
        groups = defaultdict(list)
        for slot, req, ops in admitted:
            groups[self._bucket_for(len(req.prompt))].append(
                (slot, req, ops))

        b = self.ecfg.max_batch
        results = []
        for lb in sorted(groups):
            grp = groups[lb]
            tokens = np.zeros((b, lb), np.int32)
            lens = np.zeros((b,), np.int32)
            admit = np.zeros((b,), bool)
            temps = np.zeros((b,), np.float32)
            eos = np.full((b,), -1, np.int32)
            budgets = np.ones((b,), np.int32)
            seqs = np.zeros((b,), np.int32)
            page_ops = {"pf_entry": np.full((b,), -1, np.int32),
                        "pf_n": np.zeros((b,), np.int32),
                        "pf_store": np.full((b,), -1, np.int32),
                        "pf_store_n": np.zeros((b,), np.int32)}
            for slot, req, ops in grp:
                req._params_version = self.params_version
                tokens[slot, :len(req.prompt)] = req.prompt
                lens[slot] = len(req.prompt)
                admit[slot] = True
                temps[slot] = req.temperature
                eos[slot] = -1 if req.eos_id is None else req.eos_id
                budgets[slot] = req.max_new_tokens
                seqs[slot] = req._seq
                self.slots[slot] = req
                if ops is not None:
                    (page_ops["pf_entry"][slot], page_ops["pf_n"][slot],
                     page_ops["pf_store"][slot],
                     page_ops["pf_store_n"][slot]) = ops
            with jax.profiler.TraceAnnotation("engine.prefill", bucket=lb,
                                              rows=len(grp)):
                self.cache, self.state, first, done0 = self._prefill(
                    self.params, self.cache, self.state,
                    jnp.asarray(tokens), jnp.asarray(lens),
                    jnp.asarray(admit), jnp.asarray(temps),
                    jnp.asarray(eos), jnp.asarray(budgets),
                    jnp.asarray(seqs), jax.tree.map(jnp.asarray, page_ops))
            self.stats["prefill_calls"] += 1
            self.stats["prefill_rows"] += len(grp)
            self.stats["prefill_tokens"] += int(lens.sum())
            self.stats["prefill_slot_tokens"] += b * lb
            results.append((grp, first, done0))
        # prefix entries published by the calls above are now resident
        # on device — matchable from the next fill on
        if self._prefix_staged:
            self._prefix_index.update(self._prefix_staged)
            self._prefix_staged.clear()
        return results

    def _decode_block(self):
        """One fused device block; drain results in a single transfer."""
        with jax.profiler.TraceAnnotation("engine.decode_block"):
            self.cache, self.state, toks, emit, done = self._engine_step(
                self.params, self.cache, self.state)
            with jax.profiler.TraceAnnotation("engine.drain"):
                toks, emit, done, ctr = jax.device_get((toks, emit, done, self.spec.counters(self.cache)))  # repro-lint: allow[HS001] THE per-block drain the 0.047 syncs/token budget is built on
            self.stats["host_syncs"] += 1
            self.stats.update({k: int(v) for k, v in ctr.items()})
            with jax.profiler.TraceAnnotation("engine.emit"):
                for i, req in enumerate(self.slots):
                    if req is None:
                        continue
                    row = toks[i][emit[i]]
                    req.generated.extend(int(t) for t in row)
                    self.stats["tokens"] += int(emit[i].sum())
                    if done[i].any():
                        req.done = True
                        self.finished.append(req)
                        self.slots[i] = None
                        self._return_pages(i)

    def step(self):
        """Admit new requests, then decode one block for all active slots.
        Returns the number of active slots decoded this block.

        While a param swap is staged, admission is held (queued requests
        wait) so the in-flight generation drains against its original
        snapshot; the swap applies at the first empty-slot boundary and
        admission resumes under the new version."""
        with jax.profiler.TraceAnnotation("engine.step"):
            self._maybe_apply_swap()
            if self._pending_params is None:
                self._fill_slots()
            n_active = sum(s is not None for s in self.slots)
            if n_active:
                self._decode_block()
                self._maybe_apply_swap()   # the block may have drained it
            return n_active

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(s is not None for s in self.slots)) \
                and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    def trace_count(self) -> int:
        """Number of distinct XLA traces compiled by the serving hot path,
        or -1 when jax's (private) jit-cache introspection is unavailable."""
        total = 0
        for fn in (self._prefill, self._engine_step, self._export,
                   self._import, self._delta_export, self._standby_apply,
                   self._deactivate):
            size = getattr(fn, "_cache_size", None)
            if size is None:
                return -1
            total += int(size())
        return total
