"""The serving engine's KV cache: a pool of fixed-size pages.

The paged layout (PagedAttention, Kwon et al., SOSP 2023) stores K/V as
a pool of fixed-size pages, ``(L, P, H_kv, page, Dh)`` (K and V, or
whatever the model's kinds of layer page: :func:`init_page_pool`); each
of the S
slots owns an int32 page-table row, resolved INSIDE the compiled decode
tick (:func:`~horovod_tpu.models.transformer.decode_step_paged`), and a
per-slot ``(S,)`` write position, because every slot holds a different
request at a different depth.  Page tables and the active mask are
DATA, not structure, so requests coming, going, growing and sharing
prefix pages never recompile anything.  Page 0 is the reserved
NULL/trash page: never granted, the routing target for inactive rows'
writes and unpopulated table entries.

The host side (:class:`PagedSlotCache`) is free-list bookkeeping: slots
are allocated lowest-index-first and freed on retirement, pages are
granted on demand, refcounted for prefix sharing and copied on write.
Nothing freed is scrubbed: a page's next owner writes every position
before first attending it (``tests/test_paged.py`` exercises it).
Every write into the pool, from the tick, the speculative verify and
the landing alike, is :func:`~horovod_tpu.ops.paged_attention.write_pages`.
WHICH arrays a pool holds is the model's table of layer kinds
(:data:`~horovod_tpu.models.transformer.LAYER_KINDS`).

A kind of layer may keep a per-SLOT state under the same manager (a
conv layer's last ``taps`` gated inputs ``conv`` ``(L_conv, S, taps,
D)``; a hybrid layer's taps and, MBs a slot and layer where those are
KBs, its state-space mixer's matrix state ``ssm`` ``(L, S, H, P, N)``; a
linear-attention layer's matrix state ``lin`` ``(L_lin, S, H, Dh, Dh)``,
in FLOAT32 whatever the pool's dtype)
— fixed in size where a slot's pages grow — beside the page arrays in
the pool dict, so it is donated, carried and written in place with
them.  It is granted with the slot and ZEROED then (:meth:`PagedSlotCache
.alloc`), written by the tick for the active rows and by a landing for
the landed rows (:func:`paged_insert`), and read back for a prompt's
next chunk (:meth:`PagedSlotCache.slot_state`).

(Until PR 28 a slot-contiguous ``(L, S, H_kv, T, Dh)`` cache stood
beside this one; no workload ran it.)
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu.models import transformer as T
from horovod_tpu.ops.paged_attention import write_pages
from horovod_tpu.serving.scheduler import CacheOutOfPagesError

NULL_PAGE = 0

_KV_DTYPES = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
              "f32": jnp.float32, "float32": jnp.float32,
              "int8": jnp.int8}


def resolve_kv_dtype(cfg: "T.TransformerConfig", kv_dtype):
    """``(storage dtype, quantized?)`` for a ``kv_dtype`` spec: None =
    the model's compute dtype, "bf16" halves f32 cache bytes, "int8"
    quarters them (per-vector scales ride alongside;
    dequantize-on-attend in the tick)."""
    if kv_dtype is None:
        return cfg.dtype, False
    if isinstance(kv_dtype, str):
        if kv_dtype not in _KV_DTYPES:
            raise ValueError(f"unknown kv_dtype {kv_dtype!r}; expected "
                             f"one of {sorted(_KV_DTYPES)} or None")
        kv_dtype = _KV_DTYPES[kv_dtype]
    return kv_dtype, jnp.dtype(kv_dtype) == jnp.int8


def _arrays(pool: Dict, role: str) -> List[str]:
    """The names of ``pool``'s arrays that the table declares ``role``
    (``paged`` | ``scales`` | ``page_rows`` | ``state``), in the table's
    order."""
    return [n for n in dict.fromkeys(
        n for k in T.LAYER_KINDS.values() for n in getattr(k, role))
        if n in pool]


def init_page_pool(cfg: "T.TransformerConfig", n_slots: int, n_pages: int,
                   page_size: int, kv_dtype=None, n_layers=None,
                   max_pages: int = 0) -> Dict:
    """The paged device cache: what the configuration's kinds of layer
    declare (:data:`~horovod_tpu.models.transformer.LAYER_KINDS`) —
    every paged array a page pool ``(L, P, heads, page, width)`` (``P``
    counts the NULL page; ``k``/``v``, or a latent model's ONE array of
    rows and an indexer's keys under the same page table), every array
    of a row a page of a SLOT's table ``(L_kind, S, heads, max_pages,
    width)`` (a block-sparse layer's compressed keys, by the page's
    logical index; ``max_pages`` is the table's width, 0 = what
    ``cfg.max_seq`` takes), every per-slot state ``(L_kind, S, ...)``
    (in the pool's dtype, or float32 where the kind says so) — and
    ``pos``, the per-slot ``(S,)`` logical write position.
    int8 storage adds ``k_scale``/``v_scale`` ``(L, P, H_kv, page)``
    per-vector f32 scales.  The page table itself is HOST state
    (:attr:`PagedSlotCache.table`), uploaded as data each tick.
    ``n_layers`` overrides the pages' depth: the pool of ONE kind of
    layer (a window layer's own is laid out as a full layer's)."""
    dt, quant = resolve_kv_dtype(cfg, kv_dtype)
    L = cfg.n_layers if n_layers is None else n_layers
    kinds = [k for k in {"full": cfg.kind("full"), **cfg.kinds}.values()
             if not k.window]   # a window layer's pool: a full layer's
    stateful = any(k.state for k in kinds)
    pool = {"pos": jnp.zeros((n_slots,), jnp.int32)}
    for kind in kinds:
        scale = dict(zip(kind.paged, kind.scales))
        if (quant and not scale) or (stateful and any(
                cfg.layers_with(n) != L for n in kind.paged)):
            raise T.UnsupportedModelConfigError(
                "int8 pages (per-vector scales) are written for pages of "
                "K and V alone: not for a latent pool, nor beside a "
                "per-slot state (conv or hybrid layers), whose pool is "
                "its attention layers' pages and that state")
        for name, row in kind.paged.items():
            if name not in pool:    # (a hybrid layer's are a full layer's:
                heads, width = row(cfg)  # never a pool's array twice)
                pool[name] = jnp.zeros(
                    (L, n_pages, heads, page_size, width), dt)
                if quant:
                    pool[scale[name]] = jnp.zeros(
                        (L, n_pages, heads, page_size), jnp.float32)
        for name, row in kind.page_rows.items():   # a row a page of a SLOT
            heads, width = row(cfg)
            pool[name] = jnp.zeros(
                (cfg.layers_with(name), n_slots, heads,
                 max_pages or -(-cfg.max_seq // page_size), width), dt)
        for name, shape in kind.state.items():
            pool[name] = jnp.zeros(
                (cfg.layers_with(name), n_slots) + shape(cfg),
                jnp.float32 if name in kind.f32 else dt)
    return pool


def _bucket_pages(x, first, n_pg: int, ps: int):
    """A prefilled block ``(L, K, H_kv, Tb, ...)`` as the pages it lands
    in, ``(L, K, n_pg, H_kv, page, ...)``: column ``t`` sits at offset
    ``(first + t) % page`` of page ``(first + t) // page`` — ``first``
    (traced) is where column 0 falls in its page, 0 unless a suffix
    starts mid-page.  Offsets no column reaches hold padding."""
    tb = x.shape[3]
    pad = [(0, 0)] * x.ndim
    pad[3] = (ps, n_pg * ps - tb)
    x = lax.dynamic_slice_in_dim(jnp.pad(x, pad), ps - first, n_pg * ps, 3)
    x = x.reshape(x.shape[:3] + (n_pg, ps) + x.shape[4:])
    return jnp.moveaxis(x, 3, 2)


def landing_pages(bucket: int, page_size: int) -> int:
    """Pages a landed block of ``bucket`` columns can touch in one row,
    wherever in a page its first column falls."""
    return -(-(bucket + page_size - 1) // page_size)


@jax.named_scope("kv_land")  # T.DEVICE_SCOPES
def paged_insert(pool: Dict, slots, new_pos, pages, first, lens,
                 block: Dict) -> Dict:
    """Land a prefilled ``block`` — what :func:`~horovod_tpu.models.
    transformer.prefill` / ``prefill_with_prefix`` hand back, under the
    pool's names (``pos`` apart: ``new_pos`` is it) — into the pool.

    A PAGED array ``(L, K, heads, Tb, width)`` goes into pages: column
    ``t`` of row ``i`` is logical position ``start + t``; with ``first
    = start % page`` it goes to offset ``(first + t) % page`` of
    ``pages[i, (first + t) // page]`` if ``t < lens[i]``, and nowhere
    otherwise (bucket padding).  ``pages`` ``(K, landing_pages(Tb,
    page))``, ``first`` and ``lens`` are host-built DATA, so one
    executable per ``(K, bucket)`` shape serves every page assignment
    and every bucket alignment (suffix landings start mid-page after a
    COW: the positions before ``start`` stay), and a page that takes no
    column is the NULL page.  int8 pools quantize per vector on the way
    in; payload and scale go through the same :func:`write_pages`.  A
    pool whose rows several narrow KV heads share takes the block a row
    a head and lays them side by side.  An array of a row a page of a
    SLOT's table takes ``(L, K, heads, landing pages, width)``, the row
    of each page the landing touches, and keeps those of the pages it
    FILLS, at the page's logical index ``(new_pos - lens) // page + j``
    of its slot's rows; the others' go to the slot's row 0, which is
    never read.  A per-slot STATE ``(L_kind, K, ...)`` — each row's at
    its new position — replaces its slot's.
    ``slots`` / ``new_pos`` adopt the per-row positions (empty for
    slotless landings — prefix registration)."""
    paged, scales = _arrays(pool, "paged"), _arrays(pool, "scales")
    ps, n_pg = pool[paged[0]].shape[3], pages.shape[1]
    first = jnp.asarray(first, jnp.int32)
    col = (jnp.arange(n_pg * ps, dtype=jnp.int32) - first).reshape(n_pg, ps)
    take = (col >= 0) & (col < jnp.asarray(lens, jnp.int32)[:, None, None])
    layer = jnp.arange(pool[paged[0]].shape[0], dtype=jnp.int32)[:, None, None]

    def land(name, x):
        return write_pages(pool[name], layer, pages[None],
                           _bucket_pages(x, first, n_pg, ps), take[None])

    rows = {n: T._pack_heads(block[n], pool[n].shape[-1]
                             // block[n].shape[-1]) for n in paged}
    if scales:
        quant = {n: T.kv_quantize(rows[n]) for n in paged}
        rows = {n: q for n, (q, _) in quant.items()}
        rows.update(zip(scales, (s for _, s in quant.values())))
    out = {**pool, **{n: land(n, x) for n, x in rows.items()}}
    for n in _arrays(pool, "page_rows"):
        if len(slots) != len(lens):
            raise T.UnsupportedModelConfigError(
                f"a slotless landing (prefix registration) has no slot "
                f"whose rows would take {n!r}: no page of such a pool is "
                f"shared")
        # ``(L, K, heads, n_pg, width)``: the row of each page this
        # landing FILLS (its last offset is a landed column) at the
        # page's logical index; the others' to the slot's row 0
        end = jnp.asarray(new_pos, jnp.int32)[:, None]
        page = ((end - jnp.asarray(lens, jnp.int32)[:, None]) // ps
                + jnp.arange(n_pg, dtype=jnp.int32))
        at = jnp.where((page + 1) * ps <= end, page, 0)
        L, _, heads = pool[n].shape[:3]
        out[n] = pool[n].at[
            jnp.arange(L, dtype=jnp.int32)[:, None, None, None],
            jnp.asarray(slots, jnp.int32)[:, None, None],
            jnp.arange(heads, dtype=jnp.int32)[:, None],
            at[:, None]].set(block[n].astype(pool[n].dtype))
    for n in _arrays(pool, "state"):
        out[n] = pool[n].at[:, slots].set(block[n].astype(pool[n].dtype))
    out["pos"] = pool["pos"].at[slots].set(new_pos)
    return out


@jax.named_scope("kv_write")  # T.DEVICE_SCOPES
def copy_page(pool: Dict, src, dst) -> Dict:
    """Copy one physical page (all layers, payload + scales) — the
    copy-on-write primitive.  ``src``/``dst`` are traced scalars, so
    one compile covers every copy."""
    out = dict(pool)
    for name in _arrays(pool, "paged") + _arrays(pool, "scales"):
        a = pool[name]
        layer = jnp.arange(a.shape[0], dtype=jnp.int32)
        out[name] = write_pages(a, layer, dst, a[layer, src],
                                jnp.ones((1, a.shape[3]), bool))
    # (an array of a row a page lies by slot: no page of a pool that
    # holds one is shared, so none is ever copied)
    return out


@jax.named_scope("landed_gather")  # T.DEVICE_SCOPES
def gather_prefix_pages(pool: Dict, pages) -> Dict:
    """Materialize ``pages`` (a ``(n,)`` id vector) of every paged array
    as contiguous ``(L, heads, n * page, width)``, under the pool's
    names — the landed prefix handed to :func:`~horovod_tpu.models.
    transformer.prefill_with_prefix` (``k`` and ``v``; a latent pool's
    ``k`` alone, with an indexer ``ik`` beside it).  int8 pools
    dequantize here (f32), so the suffix prefill attends real values."""
    paged = _arrays(pool, "paged")
    scale = dict(zip(paged, _arrays(pool, "scales")))
    out = {}
    for name in paged:
        a = pool[name][:, pages]                  # (L, n, heads, ps, width)
        L, n, H, ps, D = a.shape
        out[name] = jnp.moveaxis(a, 1, 2).reshape(L, H, n * ps, D)
        if name in scale:
            s = jnp.moveaxis(pool[scale[name]][:, pages], 1, 2)
            out[name] = T.kv_dequantize(out[name], s.reshape(L, H, n * ps),
                                        jnp.float32)
    return out


class PagedSlotCache:
    """Host-side page allocator + slot bookkeeping over one device page
    pool.  The slot surface is what the engine's admission and
    retirement touch (alloc/free/active_mask/occupancy/...); the paging
    surface is per-slot page tables (:attr:`table`, uploaded as tick
    data; :attr:`table_version` bumps on every change so the engine
    re-uploads only then), a heapq free list of pages, REFCOUNTED pages
    for prefix sharing (:meth:`attach` / :meth:`grant_raw`), and
    copy-on-write (:meth:`cow`) so a shared page is copied only when a
    slot must write into it.

    Freed pages are NOT scrubbed: a page's next owner writes every
    position before first attending it (prefill landing covers the
    prompt span; decode writes position ``p`` the same tick it first
    attends ``p``) — the write-before-attend argument, proven per page
    by the no-contamination test in ``tests/test_paged.py``.

    A configuration with window layers holds TWO instances side by side
    (``serving.engine``): the full layers' (``n_layers`` = their count)
    and, told its ``window``, the window layers' — the same allocator,
    whose slot gives a page back once every position in it lies behind
    the next query's window (:meth:`release_behind`), so a slot never
    holds more than :attr:`window_pages_bound` of them whatever its
    context."""

    def __init__(self, cfg: "T.TransformerConfig", n_slots: int,
                 max_len: int = 0, *, page_size: int = 16,
                 n_pages: int = 0, kv_dtype=None, mesh=None,
                 n_layers=None, window: int = 0):
        if n_slots < 1:
            raise ValueError(f"need at least one slot, got {n_slots}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len or cfg.max_seq
        self.page_size = page_size
        self.max_pages = -(-self.max_len // page_size)
        self.n_layers = cfg.n_layers if n_layers is None else n_layers
        self.window = window
        # 0 = every slot can grow to max_len (or to its window's
        # bound) at once; a
        # smaller pool is the whole point — mixed-length traffic rarely
        # needs worst case, and the admission back-pressure handles the
        # tail.
        self.n_pages = n_pages or n_slots * (
            self.window_pages_bound if window else self.max_pages)
        self.kv_dtype = kv_dtype
        # Tensor-parallel serving (docs/serving.md "Tensor-parallel
        # replicas"): with a mesh, the pool is allocated with an
        # EXPLICIT device sharding — payload (and int8 scales) split by
        # kv head over tp, per-slot pos replicated.  Everything
        # host-side below (tables, grants, refcounts, COW) is
        # sharding-OBLIVIOUS: pages are split by head, never by page
        # id, so the allocator's view of a page is unchanged.
        self.mesh = mesh
        self._storage_dtype, self.quantized = resolve_kv_dtype(
            cfg, kv_dtype)
        self.cache = init_page_pool(cfg, n_slots, self.n_pages + 1,
                                    page_size, kv_dtype, self.n_layers,
                                    self.max_pages)
        self.slot_pages_max = 0  # most pages one slot ever held at once
        if mesh is not None:
            self.cache = T.shard_kv_pool(self.cache, mesh)
        self.table = np.zeros((n_slots, self.max_pages), np.int32)
        self.table_version = 0
        self._ref = np.zeros(self.n_pages + 1, np.int64)
        self._ref[NULL_PAGE] = 1  # never granted
        self._free_pages: List[int] = list(range(1, self.n_pages + 1))
        self._min_free = self.n_pages
        self._active = np.zeros(n_slots, bool)
        self._free: List[int] = list(range(n_slots))  # heap (sorted)
        # jax.jit caches one executable per input shape, so single
        # callables cover every (K, bucket) landing, every copy, and
        # every prefix-gather length.
        self._insert = jax.jit(paged_insert, donate_argnums=(0,))
        self._copy = jax.jit(copy_page, donate_argnums=(0,))
        self._gather = jax.jit(gather_prefix_pages)
        self._set_pos = jax.jit(
            lambda pool, s, v: {**pool, "pos": pool["pos"].at[s].set(v)},
            donate_argnums=(0,))
        # the per-slot state arrays (conv layers' taps; a hybrid
        # model's taps and matrix states): zeroed with the grant, read
        # back (L, 1, ...) for a prompt's next chunk
        self.state_arrays = state = _arrays(self.cache, "state")
        self._zero_state = jax.jit(
            lambda pool, s: {**pool, **{n: pool[n].at[:, s].set(0)
                                        for n in state}},
            donate_argnums=(0,))
        self._slot_state = jax.jit(
            lambda array, s: lax.dynamic_slice_in_dim(array, s, 1, 1))

    # -- slot allocation: lowest free index first, O(log S) an op ------------

    def alloc(self) -> Optional[int]:
        """Lowest free slot index, or ``None`` when every slot is held."""
        if not self._free:
            return None
        slot = heapq.heappop(self._free)
        self._active[slot] = True
        if self.state_arrays:
            # a request starts from zeros, whatever the last tenant (or
            # a tick still in flight for it) left here
            self.cache = self._zero_state(self.cache, np.int32(slot))
        return slot

    def acquire(self, slot: int) -> None:
        """Mark a SPECIFIC slot active — the paired-pool primitive: a
        draft model's page pool mirrors the target pool slot-for-slot
        (same slot ids, same retirement), so its allocator follows the
        target's choices instead of making its own.  Refcount/COW rules
        are unchanged; :meth:`free` releases as usual."""
        if self._active[slot]:
            raise ValueError(f"slot {slot} is already active")
        self._free.remove(slot)
        heapq.heapify(self._free)
        self._active[slot] = True

    def free(self, slot: int) -> None:
        """Retire a slot: every page its table references is
        dereferenced (a page reaching refcount 0 returns to the free
        heap — shared prefix pages survive until their last reference,
        including the registry's own pin, drops)."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._active[slot] = False
        heapq.heappush(self._free, slot)
        for pg in self.table[slot]:
            self._decref(int(pg))
        self.table[slot, :] = NULL_PAGE
        self.table_version += 1

    def release_all(self) -> None:
        """Host-side reset of slots AND pages (terminal/restart paths).
        Any prefix-registry pins die with this — the engine invalidates
        its registry whenever it resets the cache."""
        self._active[:] = False
        self._free = list(range(self.n_slots))
        self.table[:, :] = NULL_PAGE
        self.table_version += 1
        self._ref[:] = 0
        self._ref[NULL_PAGE] = 1
        self._free_pages = list(range(1, self.n_pages + 1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return int(self._active.sum())

    @property
    def occupancy(self) -> float:
        return self.active_count / self.n_slots

    def active_mask(self) -> np.ndarray:
        """(S,) bool — a COPY, safe to hand to jit."""
        return self._active.copy()

    def positions(self) -> np.ndarray:
        return np.asarray(self.cache["pos"])

    # -- page accounting ----------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def pages_shared(self) -> int:
        """Pages referenced more than once (prefix sharing in effect)."""
        return int((self._ref[1:] > 1).sum())

    @property
    def pages_high_water(self) -> int:
        """Most pages ever simultaneously allocated."""
        return self.n_pages - self._min_free

    def _bytes(self, names, *per) -> int:
        """Bytes of the pool's arrays among ``names`` a unit of dims ``per``."""
        return sum(a.nbytes // int(np.prod([a.shape[d] for d in per]))
                   for a in map(self.cache.get, names) if a is not None)

    @property
    def bytes_per_token(self) -> int:
        """KV bytes one token costs in this pool (the quantization
        lever made legible): every paged array's payload across layers
        (k + v; a latent pool's one stored row and its index key), plus
        the per-vector scales for int8."""
        return self._bytes(_arrays(self.cache, "paged")
                           + _arrays(self.cache, "scales"), 1, 3)

    @property
    def conv_state_bytes_per_slot(self) -> int:
        """What a slot holds beside its pages, whatever its context:
        the last ``taps`` inputs of every layer that keeps a short
        convolution's (0: none does)."""
        return self._bytes(T.LAYER_KINDS["conv"].state, 1)

    @property
    def ssm_state_bytes_per_slot(self) -> int:
        """... and every state-space mixer's matrix state (0: the
        model has none)."""
        return self._bytes(set(T.LAYER_KINDS["hybrid"].state)
                           - set(T.LAYER_KINDS["conv"].state), 1)

    @property
    def lin_state_bytes_per_slot(self) -> int:
        """... and every linear-attention layer's float32 matrix state
        (0: the model has none)."""
        return self._bytes(T.LAYER_KINDS["linear"].state, 1)

    @property
    def compressed_bytes_per_page(self) -> int:
        """What a page holds beside its tokens' rows: every
        block-sparse layer's compressed key a KV head (0: none), in
        its slot's rows."""
        return self._bytes(T.LAYER_KINDS["block_sparse"].page_rows, 1, 3)

    @property
    def latent_bytes_per_token(self) -> int:
        """What a token leaves in a latent pool's rows, every layer's
        (``cfg.latent_row`` as stored; 0 for a pool of K and V)."""
        return (self._bytes(T.LAYER_KINDS["latent"].paged, 1, 3)
                if self.cfg.latent else 0)

    @property
    def index_bytes_per_token(self) -> int:
        """... and in a sparse model's index-key array beside them."""
        return self._bytes(set(T.LAYER_KINDS["sparse"].paged)
                           - set(T.LAYER_KINDS["latent"].paged), 1, 3)

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size) if n_tokens > 0 else 0

    def _pop_page(self) -> int:
        if not self._free_pages:
            raise CacheOutOfPagesError(
                f"page pool exhausted ({self.n_pages} pages, "
                f"{self.pages_shared} shared)")
        pg = heapq.heappop(self._free_pages)
        self._min_free = min(self._min_free, len(self._free_pages))
        return pg

    def _decref(self, pg: int) -> None:
        if pg == NULL_PAGE:
            return
        self._ref[pg] -= 1
        if self._ref[pg] == 0:
            heapq.heappush(self._free_pages, pg)
        elif self._ref[pg] < 0:  # pragma: no cover - allocator invariant
            raise AssertionError(f"page {pg} refcount underflow")

    # -- grants / sharing / COW --------------------------------------------

    def grant(self, slot: int, idx: int) -> int:
        """Grant a fresh PRIVATE page at table index ``idx`` (on-demand
        growth at a tick boundary).  Raises
        :class:`CacheOutOfPagesError` on an empty pool — the engine
        turns that into preemption or back-pressure, never silent
        over-allocation."""
        if self.table[slot, idx] != NULL_PAGE:
            raise ValueError(
                f"slot {slot} already has page {self.table[slot, idx]} "
                f"at index {idx}")
        pg = self._pop_page()
        self._ref[pg] = 1
        self.table[slot, idx] = pg
        self.table_version += 1
        self.slot_pages_max = max(self.slot_pages_max,
                                  int(np.count_nonzero(self.table[slot])))
        return pg

    # -- a window layer's pages --------------------------------------------

    @property
    def window_pages_bound(self) -> int:
        """The most pages a slot of a windowed cache holds at once: the
        window's span plus one page of rounding."""
        return min(self.max_pages, -(-self.window // self.page_size) + 1)

    def first_live(self, pos: int) -> int:
        """The first table index a query at position ``pos`` (or later)
        can still read: it sees positions ``> pos - window``.  0 for a
        cache with no window."""
        if not self.window:
            return 0
        return max(0, pos - self.window + 1) // self.page_size

    def release_behind(self, slot: int, pos: int) -> None:
        """Give back every page of ``slot`` that lies wholly behind the
        window of a query at ``pos``: the next position the slot writes
        and attends (decode), or the first query of the chunk after the
        one just planned (ingestion).  The entries become NULL pages —
        the kernel's walk starts past them, and a landing routes what
        falls there to the trash page."""
        row = self.table[slot, :self.first_live(pos)]
        held = np.nonzero(row)[0]
        if held.size:
            for idx in held:
                self._decref(int(row[idx]))
            row[held] = NULL_PAGE
            self.table_version += 1

    def grant_raw(self, n: int) -> List[int]:
        """``n`` pages owned by the CALLER (the prefix registry's pin),
        refcount 1 each, bound to no slot.  All-or-nothing."""
        if len(self._free_pages) < n:
            raise CacheOutOfPagesError(
                f"need {n} pages for prefix registration, "
                f"{len(self._free_pages)} free of {self.n_pages}")
        pages = []
        for _ in range(n):
            pg = self._pop_page()
            self._ref[pg] = 1
            pages.append(pg)
        return pages

    def release_raw(self, pages: Sequence[int]) -> None:
        """Drop a :meth:`grant_raw` pin (prefix unregistration)."""
        for pg in pages:
            self._decref(int(pg))

    def attach(self, slot: int, pages: Sequence[int]) -> None:
        """Reference shared pages from table indices ``0..len-1`` —
        prefix sharing: refcount++ per page, no copy, no compute."""
        for i, pg in enumerate(pages):
            if self.table[slot, i] != NULL_PAGE:
                raise ValueError(f"slot {slot} index {i} already mapped")
            self.table[slot, i] = pg
            self._ref[pg] += 1
        self.table_version += 1

    def cow(self, slot: int, idx: int) -> int:
        """Copy-on-write: make the page at table index ``idx`` PRIVATE
        to ``slot``.  A no-op if it already is; otherwise a fresh page
        is granted, the shared page's payload is copied on device, the
        table repointed, and the shared page dereferenced.  Called
        before ANY write can target a shared page — suffix landing
        into a partially-filled prefix page, or decode growing into
        one."""
        src = int(self.table[slot, idx])
        if src == NULL_PAGE:
            raise ValueError(f"slot {slot} has no page at index {idx}")
        if self._ref[src] <= 1:
            return src
        dst = self._pop_page()
        self._ref[dst] = 1
        self.cache = self._copy(self.cache, jnp.int32(src), jnp.int32(dst))
        self.table[slot, idx] = dst
        self._decref(src)
        self.table_version += 1
        return dst

    # -- device ops ---------------------------------------------------------

    def _land_pages(self, rows: Sequence[Sequence[int]], start: int,
                    true_lens, bucket: int) -> np.ndarray:
        """Host-built landing targets: for each row, the physical pages
        its ``bucket`` columns from logical position ``start`` fall in,
        in order — ``landing_pages`` of them, whatever ``start %
        page_size`` is, so the executable's shape depends on the bucket
        alone.  A page that takes no column (past ``true_lens[i]``:
        bucket padding; past the table) is the NULL page."""
        ps = self.page_size
        c = np.arange(landing_pages(bucket, ps))
        idx = start // ps + c
        pages = np.zeros((len(rows), c.size), np.int32)
        for i, row in enumerate(rows):
            row = np.asarray(row, np.int32)
            live = (c * ps - start % ps < int(true_lens[i])) & (
                idx < row.size)
            pages[i, live] = row[idx[live]]
        return pages

    def _land(self, slots, new_pos, rows, prefilled: Dict, true_lens,
              start: int) -> None:
        # what THIS pool holds of it (``pos`` travels as ``new_pos``)
        block = {n: a for n, a in prefilled.items()
                 if n in self.cache and n != "pos"}
        bucket = block[_arrays(block, "paged")[0]].shape[3]
        self.cache = self._insert(
            self.cache, np.asarray(slots, np.int32), new_pos,
            self._land_pages(rows, start, true_lens, bucket),
            np.int32(start % self.page_size),
            np.asarray(true_lens, np.int32), block)

    def land(self, slots: Sequence[int], prefilled: Dict,
             true_lens, start: int = 0) -> None:
        """Land a prefilled (or suffix-prefilled) K/V block into the
        slots' granted pages with ONE page-granular write
        (:func:`paged_insert`), and adopt the per-row positions from
        ``prefilled["pos"]``.  ``start`` is the logical position of
        bucket column 0 (0 for full prompts, the shared prefix length
        for suffix landings)."""
        for s in slots:
            if not self._active[s]:
                raise ValueError(f"slot {s} is not allocated")
        self._land(slots, prefilled["pos"].astype(jnp.int32),
                   [self.table[s] for s in slots], prefilled, true_lens,
                   start)

    def land_raw(self, pages: Sequence[int], prefilled: Dict,
                 true_len: int) -> None:
        """Slotless landing into raw pages (prefix registration): the
        prefix block fills ``pages`` in order; no slot position is
        touched."""
        self._land((), jnp.zeros((0,), jnp.int32), [pages], prefilled,
                   [true_len], 0)

    def set_pos(self, slots: Sequence[int], vals: Sequence[int]) -> None:
        """Adopt positions without landing (attach-only admission — the
        whole prompt already lives in shared pages)."""
        self.cache = self._set_pos(
            self.cache, np.asarray(slots, np.int32),
            np.asarray(vals, np.int32))

    def slot_state(self, slot: int, name: Optional[str] = None):
        """One slot's state in the per-slot array ``name`` (None: the
        pool's first) — the taps ``conv`` ``(L, 1, taps, C)``, a hybrid
        model's matrix states ``ssm`` ``(L, 1, H, P, N)``, a linear
        layer's ``lin`` ``(L, 1, H, Dh, Dh)`` — as :func:`~horovod_tpu.
        models.transformer.prefill_with_prefix` takes it for the slot's
        next chunk."""
        return self._slot_state(self.cache[name or self.state_arrays[0]],
                                np.int32(slot))

    def gather_prefix(self, pages: Sequence[int]):
        """A shared prefix's pages, contiguous, by the pool's names
        (:func:`gather_prefix_pages`)."""
        return self._gather(self.cache, np.asarray(pages, np.int32))
