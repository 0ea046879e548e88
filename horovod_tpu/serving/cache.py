"""The serving engine's KV cache: a pool of fixed-size pages.

The paged layout (PagedAttention, Kwon et al., SOSP 2023) stores K/V as
a pool of fixed-size pages, ``(L, P, H_kv, page, Dh)`` (K and V; a
latent-attention model's ONE array ``(L, P, 1, page, 640)``, the rows
its heads share, and beside it, where the model has an indexer, its
keys ``(L, P, 1, page, 128)`` under the same page table:
:func:`init_page_pool`); each of the S
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
the landing alike, is :func:`write_pages`.

A model with conv layers (gated short convolutions) keeps a SECOND kind
of per-request state under the same manager: ``conv`` ``(L_conv, S,
taps, D)``, every conv layer's last ``taps`` gated inputs of every SLOT
— fixed in size where a slot's pages grow — beside the page arrays in
the pool dict, so it is donated, carried and written in place with
them.  It is granted with the slot and ZEROED then (:meth:`PagedSlotCache
.alloc`), written by the tick for the active rows and by a landing for
the landed rows (:func:`paged_insert`), and read back for a prompt's
next chunk (:meth:`PagedSlotCache.slot_state`).  A model of hybrid
layers (attention and a state-space mixer side by side) keeps BOTH in
every layer: pages, the mixer's short convolution's taps in ``conv``
(``[x | B | C]`` wide, not ``D``) and a THIRD array ``ssm`` ``(L, S, H,
P, N)``, the mixer's matrix state a head — MBs a slot and layer where
the taps are KBs — under the same grant, zeroing, landing and read-back.

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


def init_page_pool(cfg: "T.TransformerConfig", n_slots: int, n_pages: int,
                   page_size: int, kv_dtype=None, n_layers=None) -> Dict:
    """The paged device cache: ``k``/``v`` are ``(L, P, H_kv, page,
    Dh)`` page pools (``P`` counts the NULL page), ``pos`` is the
    per-slot ``(S,)`` logical write position, and int8 storage adds
    ``k_scale``/``v_scale`` ``(L, P, H_kv, page)`` per-vector f32
    scales.  The page table itself is HOST state
    (:attr:`PagedSlotCache.table`), uploaded as data each tick.
    ``n_layers`` overrides the depth: the pool of ONE kind of layer."""
    dt, quant = resolve_kv_dtype(cfg, kv_dtype)
    L = cfg.n_layers if n_layers is None else n_layers
    if cfg.latent:
        # latent attention: ONE array.  A token leaves one row a layer,
        # ``[ckv | k_rope | 0]`` (cfg.latent_row: 576 values in 640
        # lanes) — the key of one kv "head" every query head shares,
        # whose first kv_lora_rank lanes are the value too — so
        # the pool is ``k`` with H_kv = 1 and no ``v``; everything that
        # addresses pages (write_pages, the landing, COW, the gather)
        # reads its layout from the array, as before.
        if quant:
            raise T.UnsupportedModelConfigError(
                "int8 pages (per-vector scales) are not written for a "
                "latent pool")
        pool = {"k": jnp.zeros((L, n_pages, 1, page_size,
                                cfg.latent_row), dt),
                "pos": jnp.zeros((n_slots,), jnp.int32)}
        if cfg.sparse:
            # ... and a SECOND array under the same page table: the
            # indexer's key, one ``index_head_dim`` vector a token and
            # layer (128: exactly one lane group, no padding).  A page
            # of it is granted, landed, copied and released WITH the
            # latent page of the same id.
            pool["ik"] = jnp.zeros((L, n_pages, 1, page_size,
                                    cfg.index_head_dim), dt)
        return pool
    # a row a KV head, or ``kv_pack`` narrow heads side by side in one
    Hkv, Dh = cfg.kv_heads // cfg.kv_pack, cfg.head_dim * cfg.kv_pack
    pool = {
        "k": jnp.zeros((L, n_pages, Hkv, page_size, Dh), dt),
        "v": jnp.zeros((L, n_pages, Hkv, page_size, Dh), dt),
        "pos": jnp.zeros((n_slots,), jnp.int32),
    }
    if cfg.has_state:
        if quant or cfg.layers_with("k") != L:
            raise T.UnsupportedModelConfigError(
                "the pool of a model with a per-slot state (conv or "
                "hybrid layers) is its attention layers' pages, "
                "unquantized, and that state")
        pool["conv"] = jnp.zeros((cfg.layers_with("conv"), n_slots,
                                  cfg.conv_taps, cfg.conv_width), dt)
    if cfg.has_ssm:
        pool["ssm"] = jnp.zeros(
            (cfg.layers_with("ssm"), n_slots, cfg.ssm_heads,
             cfg.ssm_head_dim, cfg.ssm_state), dt)
    if quant:
        pool["k_scale"] = jnp.zeros((L, n_pages, Hkv, page_size),
                                    jnp.float32)
        pool["v_scale"] = jnp.zeros((L, n_pages, Hkv, page_size),
                                    jnp.float32)
    return pool


def write_pages(stack, layer, phys, new, take):
    """THE write into a page pool: whole pages, addressed by the pool's
    two leading dims and nothing else.

    ``stack`` is one pool array, ``(L, P, H_kv, page, ...)`` (payload
    with its trailing ``Dh``, or an int8 pool's scales without one);
    ``layer`` and ``phys`` are int32 arrays that broadcast to one batch
    shape ``B``; ``new`` broadcasts to ``B + (H_kv, page, ...)`` and
    ``take`` ``B + (page,)`` says which offsets of each page take it.
    The ``B`` target pages are read, the taken offsets replaced, and the
    pages written back at ``[layer, phys]``: the scatter's indices are
    the leading dims and its window the whole page, which is the pool's
    own layout, so the compiler updates a donated (or loop-carried) pool
    in place and no operation has a result the size of a layer of it.
    What is not taken keeps its contents — the positions before a
    suffix's ``start``, a page's tail.

    A page may appear ONCE among the targets: of two whole-page updates
    of one page the later would undo the earlier, so callers merge the
    rows that share a page first.  The NULL page alone is exempt:
    inactive rows, padding and rejected drafts all go there, and what
    it holds is never attended."""
    idx = (jnp.asarray(layer, jnp.int32), jnp.asarray(phys, jnp.int32))
    take = take.reshape(take.shape[:-1] + (1, take.shape[-1])
                        + (1,) * (stack.ndim - 4))
    pages = jnp.where(take, new.astype(stack.dtype), stack[idx])
    return stack.at[idx].set(pages)


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
                 prefilled_k, prefilled_v=None, prefilled_ik=None,
                 prefilled_conv=None, prefilled_ssm=None) -> Dict:
    """Land a prefilled K/V block ``(L, K, H_kv, Tb, Dh)`` into pages.
    Column ``t`` of row ``i`` is logical position ``start + t``; with
    ``first = start % page`` it goes to offset ``(first + t) % page``
    of ``pages[i, (first + t) // page]`` if ``t < lens[i]``, and
    nowhere otherwise (bucket padding).  ``pages`` ``(K,
    landing_pages(Tb, page))``, ``first`` and ``lens`` are host-built
    DATA, so one executable per ``(K, bucket)`` shape serves every page
    assignment and every bucket alignment (suffix landings start
    mid-page after a COW: the positions before ``start`` stay), and a
    page that takes no column is the NULL page.  ``slots`` /
    ``new_pos`` adopt the per-row positions (empty for slotless
    landings — prefix registration).  int8 pools quantize per vector
    on the way in; payload and scale go through the same
    :func:`write_pages`.  A latent pool has ``k`` alone
    (``prefilled_v`` None): the block is the latent rows; a sparse
    model's index keys ``prefilled_ik`` land in ``ik`` at the same
    pages and offsets.  A pool whose rows several narrow KV heads share
    takes the block a row a head and lays them side by side.  A conv
    model's ``prefilled_conv`` ``(L_conv, K, taps, D)`` — each row's
    state at its new position — replaces its slot's; so a hybrid
    model's ``prefilled_ssm`` ``(L, K, H, P, N)``."""
    ps = pool["k"].shape[3]
    L, n_pg = pool["k"].shape[0], pages.shape[1]
    first = jnp.asarray(first, jnp.int32)
    col = (jnp.arange(n_pg * ps, dtype=jnp.int32) - first).reshape(n_pg, ps)
    take = (col >= 0) & (col < jnp.asarray(lens, jnp.int32)[:, None, None])
    layer = jnp.arange(L, dtype=jnp.int32)[:, None, None]

    def land(name, x):
        return write_pages(pool[name], layer, pages[None],
                           _bucket_pages(x, first, n_pg, ps), take[None])

    out = dict(pool)
    k, v = prefilled_k, prefilled_v
    pack = pool["k"].shape[-1] // k.shape[-1]
    if pack > 1:
        k, v = T._pack_heads(k, pack), T._pack_heads(v, pack)
    if "k_scale" in pool:
        k, sk = T.kv_quantize(k)
        v, sv = T.kv_quantize(v)
        out["k_scale"], out["v_scale"] = land("k_scale", sk), land("v_scale", sv)
    out["k"] = land("k", k)
    if v is not None:
        out["v"] = land("v", v)
    if prefilled_ik is not None:
        out["ik"] = land("ik", prefilled_ik)
    if prefilled_conv is not None:
        out["conv"] = pool["conv"].at[:, slots].set(
            prefilled_conv.astype(pool["conv"].dtype))
    if prefilled_ssm is not None:
        out["ssm"] = pool["ssm"].at[:, slots].set(
            prefilled_ssm.astype(pool["ssm"].dtype))
    out["pos"] = pool["pos"].at[slots].set(new_pos)
    return out


@jax.named_scope("kv_write")  # T.DEVICE_SCOPES
def copy_page(pool: Dict, src, dst) -> Dict:
    """Copy one physical page (all layers, payload + scales) — the
    copy-on-write primitive.  ``src``/``dst`` are traced scalars, so
    one compile covers every copy."""
    out = dict(pool)
    for name in ("k", "v", "k_scale", "v_scale", "ik"):
        if name in pool:
            a = pool[name]
            layer = jnp.arange(a.shape[0], dtype=jnp.int32)
            out[name] = write_pages(a, layer, dst, a[layer, src],
                                    jnp.ones((1, a.shape[3]), bool))
    return out


@jax.named_scope("landed_gather")  # T.DEVICE_SCOPES
def gather_prefix_pages(pool: Dict, pages):
    """Materialize ``pages`` (a ``(n,)`` id vector) as contiguous
    ``(k, v)`` of shape ``(L, H_kv, n * page, Dh)`` — the shared-prefix
    K/V handed to :func:`~horovod_tpu.models.transformer.
    prefill_with_prefix`.  int8 pools dequantize here (f32), so the
    suffix prefill attends real values.  A latent pool gives ``(rows,
    None)``, or with an indexer ``(rows, index keys)``."""
    k = pool["k"][:, pages]                   # (L, n, H_kv, ps, Dh)
    L, n, Hkv, ps, Dh = k.shape
    k = jnp.moveaxis(k, 1, 2).reshape(L, Hkv, n * ps, Dh)
    if "v" not in pool:                       # a latent pool's rows
        if "ik" not in pool:
            return k, None
        return k, jnp.moveaxis(pool["ik"][:, pages], 1, 2).reshape(
            L, 1, n * ps, -1)
    v = jnp.moveaxis(pool["v"][:, pages], 1, 2).reshape(L, Hkv, n * ps, Dh)
    if "k_scale" in pool:
        ks = jnp.moveaxis(pool["k_scale"][:, pages], 1, 2
                          ).reshape(L, Hkv, n * ps)
        vs = jnp.moveaxis(pool["v_scale"][:, pages], 1, 2
                          ).reshape(L, Hkv, n * ps)
        k = T.kv_dequantize(k, ks, jnp.float32)
        v = T.kv_dequantize(v, vs, jnp.float32)
    return k, v


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
                                    page_size, kv_dtype, self.n_layers)
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
        state = tuple(n for n in ("conv", "ssm") if n in self.cache)
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
        if "conv" in self.cache:
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

    @property
    def bytes_per_token(self) -> int:
        """KV bytes one token costs in this pool (the quantization
        lever made legible): payload for k+v across layers, plus the
        per-vector scales for int8."""
        if self.cfg.latent:    # one stored row a layer, and its index key
            return self.latent_bytes_per_token + self.index_bytes_per_token
        elem = jnp.dtype(self._storage_dtype).itemsize
        n = self.n_layers * self.cfg.kv_heads
        b = 2 * n * self.cfg.head_dim * elem
        if self.quantized:
            b += 2 * n * 4  # f32 scale per (layer, head, token) vector
        return b

    @property
    def conv_state_bytes_per_slot(self) -> int:
        """What a slot holds beside its pages, whatever its context:
        the last ``taps`` inputs of every layer that keeps a short
        convolution's (0: none does)."""
        a = self.cache.get("conv")
        return 0 if a is None else a.nbytes // self.n_slots

    @property
    def ssm_state_bytes_per_slot(self) -> int:
        """... and every state-space mixer's matrix state (0: the
        model has none)."""
        a = self.cache.get("ssm")
        return 0 if a is None else a.nbytes // self.n_slots

    @property
    def latent_bytes_per_token(self) -> int:
        """What a token leaves in a latent pool's rows, every layer's
        (``cfg.latent_row`` as stored; 0 for a pool of K and V)."""
        return (self.n_layers * self.cfg.latent_row
                * jnp.dtype(self._storage_dtype).itemsize
                if self.cfg.latent else 0)

    @property
    def index_bytes_per_token(self) -> int:
        """... and in a sparse model's index-key array beside them."""
        return (self.n_layers * self.cfg.index_head_dim
                * jnp.dtype(self._storage_dtype).itemsize
                if self.cfg.sparse else 0)

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
        bucket = prefilled["k"].shape[3]
        self.cache = self._insert(
            self.cache, np.asarray(slots, np.int32), new_pos,
            self._land_pages(rows, start, true_lens, bucket),
            np.int32(start % self.page_size),
            np.asarray(true_lens, np.int32), prefilled["k"],
            prefilled.get("v"), prefilled.get("ik"), prefilled.get("conv"),
            prefilled.get("ssm"))

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

    def slot_state(self, slot: int, name: str = "conv"):
        """One slot's state in the per-slot array ``name`` — the taps
        ``conv`` ``(L, 1, taps, C)``, a hybrid model's matrix states
        ``ssm`` ``(L, 1, H, P, N)`` — as :func:`~horovod_tpu.models.
        transformer.prefill_with_prefix` takes it for the slot's next
        chunk."""
        return self._slot_state(self.cache[name], np.int32(slot))

    def gather_prefix(self, pages: Sequence[int]):
        """Contiguous ``(k, v)`` for a shared prefix's pages (see
        :func:`gather_prefix_pages`)."""
        return self._gather(self.cache, np.asarray(pages, np.int32))
