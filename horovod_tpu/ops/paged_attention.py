"""Fused Pallas paged-attention decode kernel (flash-decoding over the
page table, int8 dequant in the load).

The unfused paged decode path (:func:`horovod_tpu.models.transformer.
_attention_decode_paged`) runs gather-pages -> ``kv_dequantize`` ->
attend as separate XLA ops, materializing every active slot's FULL
logical K/V view (``(S, H_kv, max_pages * page, Dh)`` at compute dtype)
each tick.  Decode is cache-bandwidth-bound, so that materialization is
pure overhead — the paper's fusion-buffer insight applied to serving:
collapse the many small memory-bound steps into one resident pass.

This kernel performs the whole resolve-dequant-attend in one Pallas
program per SLOT, walking only the pages the slot can attend:

* the grid is ``(slot,)``; the pools (and the int8 scale pools) stay in
  HBM (``memory_space=pl.ANY``) and the page table row and the per-slot
  ``limit`` live in SMEM via scalar prefetch
  (``PrefetchScalarGridSpec``);
* per slot a ``fori_loop`` runs over ``ceil(limit / block_tokens)``
  blocks of the table (:func:`walk` — the trip count is DATA, read
  from SMEM): a slot with ``limit == 0`` walks nothing, and table
  entries past the live pages are never read, let alone fetched;
* a block is :func:`block_pages` pages (a function of the pool's
  layout alone: 128 tokens for bf16 pages of 16 x 8 heads x 128).  The
  pool is the STACK of every layer's, ``(L, P, H_kv, page, Dh)``, and
  the layer's index one more scalar in SMEM — a custom call's operand
  is cut out of a layer scan's stack whole, so one layer's pool is
  never the operand — and ``pool.at[layer, page]`` is ONE contiguous
  region holding every KV head of the page: the kernel issues one
  ``make_async_copy`` per live page for K and one for V, into one of
  two VMEM buffers, and attends the other meanwhile;
* the block is attended for all KV heads at once (dots batched over
  the head): int8 dequant is fused into the load — the pages' int8
  payload and their per-vector scales are combined in-register (f32
  compute, then cast to the compute dtype — the exact
  :func:`~horovod_tpu.models.transformer.kv_dequantize` contract, see
  :data:`DEQUANT_COMPUTE`);
* masking is by LOGICAL position against ``limit`` (positions
  ``< limit[s]`` attend) — a partial last page, page-tail junk, the
  places of a block's dead pages and inactive slots (``limit == 0``)
  all fall out of the same comparison; a window layer also gives
  ``lower`` (positions ``>= lower[s]``), and its walk starts at the
  block holding ``lower[s]``: pages wholly behind the window are never
  fetched, so their table entries may be released;
* cross-block combination is the standard flash-decoding online
  softmax (running max / sum / accumulator with rescale, carried by
  the loop), and the kernel emits per-row ``logsumexp`` so a caller
  can merge the result with attention over OTHER sources (the
  speculative VERIFY path combines committed-page attention with
  in-window attention by LSE).

Conventions shared with :mod:`~horovod_tpu.ops.attention` via
:mod:`~horovod_tpu.ops._pallas_util`: compiled on TPU, interpreted on
CPU (tier-1 CPU tests exercise the REAL kernel body).  The pure-JAX
:func:`paged_attend_reference` is the test oracle of a table a slot
(of a table a slot and KV head it is the unfused tick's attend: its
docstring) — :func:`paged_attend` never substitutes it; a caller asks
:func:`kernel_supported` first (the engine does, once, at
construction) and takes the unfused XLA tick for layouts the compiler
cannot tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.ops._pallas_util import (
    NEG_INF,
    pl,
    pltpu,
    use_interpret,
)

__all__ = ["DEQUANT_COMPUTE", "UnsupportedPagedLayoutError", "paged_attend",
           "paged_attend_reference", "mla_decode", "mla_decode_reference",
           "kernel_supported", "walk", "first_block", "index_scores",
           "index_scores_reference", "index_scores_rows", "index_block_pages",
           "select_topk", "pages_of",
           "selected_attend"]


# The pinned dequant compute dtype.  ``kv_dequantize`` promotes int8
# payloads and their scales through f32 — even when the compute dtype is
# bf16 — and only THEN casts to the target dtype.  The fused kernel
# mirrors the same f32-multiply-then-cast in its load so the unfused
# fallback and the fused path round identically; any change here must
# change both (tests/test_paged.py pins the contract).
DEQUANT_COMPUTE = jnp.float32


def _dequant_col(q, scale_col, dtype):
    """The mirror of ``kv_dequantize``: f32 multiply, then a single cast
    to ``dtype`` (see :data:`DEQUANT_COMPUTE`).  ``scale_col`` already
    carries the trailing unit dim (the kernel reads it as a column)."""
    return (q.astype(DEQUANT_COMPUTE)
            * scale_col.astype(DEQUANT_COMPUTE)).astype(dtype)


def _dequant(q, scale, dtype):
    """:func:`_dequant_col` for a scale lacking the trailing dim."""
    return _dequant_col(q, scale[..., None], dtype)


# Minimum sublane tile (second-to-last dim) per STORED dtype on TPU: a
# head's share of a page is a ``(page_size, head_dim)`` slab of a VMEM
# buffer, and Mosaic tiles the last two dims in (sublane, 128-lane)
# units.
_MIN_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32}


class UnsupportedPagedLayoutError(ValueError):
    """The fused kernel was demanded for a pool layout the TPU compiler
    cannot tile (see :func:`kernel_supported`)."""


def kernel_supported(storage_dtype, page_size: int, head_dim: int,
                     v_dim=None) -> bool:
    """Whether the COMPILED kernel can serve a pool of this layout.

    This is the TPU compiler's rule, whatever backend asks: the page
    must fill whole dtype tiles — ``head_dim`` a lane multiple (128)
    and ``page_size`` a sublane multiple of the stored dtype (8 f32 /
    16 bf16 / 32 int8).  On CPU the interpreter runs any shape, so
    :func:`paged_attend` itself does not consult this; the serving
    engine does, at construction, when it decides whether its ticks use
    the kernel (``/stats`` ``paged_kernel_engaged``).  ``v_dim``: a
    latent pool (:func:`mla_decode`), ``head_dim`` its row's width."""
    sub = _MIN_SUBLANE.get(jnp.dtype(storage_dtype).name)
    # a latent pool's VALUE, its row's first v_dim lanes, is cut at a
    # lane boundary too
    return (sub is not None and head_dim % 128 == 0
            and page_size % sub == 0 and (v_dim or 0) % 128 == 0)


#: The kernel's name on a device trace (``pl.pallas_call(name=)``):
#: readers of a profile find the call by it, not by operand shapes.
KERNEL_NAME = "hvd_paged_attend"
#: ... and the name the same walk carries over a LATENT pool
#: (:func:`mla_decode`).
MLA_KERNEL_NAME = "hvd_mla_decode"
#: ... and over a table a slot AND KV head (block-sparse attention: the
#: chosen blocks' pages, compacted).
BSA_KERNEL_NAME = "hvd_bsa_attend"

# What one step of a slot's walk holds of K (and as much of V) in VMEM,
# counted at the width it is computed at: an int8 page is widened to the
# compute dtype on arrival, so it is budgeted at two bytes an element.
# Two buffers each of K and V make four of these.  On a v5e the kernel
# alone moves 250-280 GB/s at 64 KB, 360-430 at 128, 490-620 at 256,
# 430-665 at 512 (PERF.md, PR 25): past 256 KB a longer step gains
# long contexts what its rounding costs short ones.
_BLOCK_BYTES = 256 * 1024

# A step of the walk over a LATENT pool (one array, two buffers of
# this).  Every fetched row is attended by all 64 heads, so a step
# carries more arithmetic than a dense pool's, and its fixed costs (the
# accumulator's rescale: 64 x 512 floats) want longer steps: on a v5e
# the kernel alone, 32 slots at 2-18 k of context, moves 184 GB/s of
# needed bytes at 96 tokens a step, 219 at 128, 335 at 384, 364 at 512,
# 388 at 768, 400 at 1152 (PERF.md, PR 30); past 768 a longer step
# gains less than its rounding costs contexts of a few thousand.
_LATENT_BLOCK_BYTES = 1024 * 1024


def block_pages(page_size: int, n_kv_heads: int, head_dim: int,
                storage_dtype, max_pages: int, latent: bool = False) -> int:
    """Pages one step of the walk fetches and attends: as many whole
    pages (every KV head of each) as fit :data:`_BLOCK_BYTES`, never
    more than a slot's table holds.  A function of the pool's layout
    alone — 8 pages (128 tokens) for bf16 pages of 16 x 8 heads x 128,
    4 pages for int8 pages of 32, 32 for a tp shard's 2 heads.

    ``latent``: a latent pool's one array (:func:`mla_decode`) takes
    :data:`_LATENT_BLOCK_BYTES` a step, cut to whole 128-token lane
    groups (the scores' last dim): 48 pages (768 tokens, 983 KB) for
    bf16 pages of 16 x 640."""
    width = max(jnp.dtype(storage_dtype).itemsize, 2)
    page_bytes = n_kv_heads * page_size * head_dim * width
    if not latent:
        return max(1, min(_BLOCK_BYTES // page_bytes, max_pages))
    n = max(1, min(_LATENT_BLOCK_BYTES // page_bytes, max_pages))
    group = max(128 // page_size, 1)
    return n - n % group if n > group else n


def walk(limit, block_tokens: int, lower=None):
    """``(blocks, tokens)`` the kernel walks for a slot that attends
    positions ``< limit``: whole blocks up to the last live position,
    none for ``limit == 0``.  With ``lower`` (a window layer: positions
    ``>= lower`` only) the walk starts at the block that holds
    ``lower`` — :func:`first_block` — so blocks wholly behind the
    window are neither fetched nor counted.  The ONE statement of the
    bound: it is the kernel's trip count (a traced scalar read from
    SMEM) and, on the host's ``_page_pos + 1`` (a numpy array), the
    engine's ``paged_walked_tokens`` counter."""
    blocks = (limit + block_tokens - 1) // block_tokens
    if lower is not None:
        # an empty window (lower >= limit) walks nothing: every block
        # that IS walked holds a visible position, as without a bound
        blocks = (blocks - first_block(lower, block_tokens)) * (lower < limit)
    return blocks, blocks * block_tokens


def first_block(lower, block_tokens: int):
    """The block a walk bounded below by ``lower`` starts at."""
    return lower // block_tokens


def _kernel_body(table_ref, limit_ref, *refs, page_size, n_pages,
                 compute_dtype, quantized, windowed, v_dim=None,
                 sm_scale=None, heads=0):
    """One grid step: slot ``s``, every KV head, the slot's live pages.

    ``heads`` (a table a slot AND KV head: block-sparse attention): the
    grid's row ``s`` is slot ``s // heads``, KV head ``s % heads``,
    whose own table row it walks; a page's transfer is that head's
    ``(page, Dh)`` share alone.

    ``v_dim`` (a latent pool): there is no V pool — a fetched row is the
    key, and its first ``v_dim`` lanes the value.

    ``k_hbm``/``v_hbm`` (every layer's pool, read at ``layer_ref[0]``;
    and this layer's scale pools) stay in HBM; the loop
    fetches block ``b`` of the slot's table — ``n_pages`` pages, each
    ONE contiguous ``(H_kv, page, Dh)`` transfer — into one of two VMEM
    buffers while the other is attended.  The online softmax rides the
    loop's carry.  Only pages holding a position ``< limit`` are
    fetched: table entries past them are never read.
    """
    lower_ref = None
    if windowed:                  # a third scalar-prefetch operand
        lower_ref, refs = refs[0], refs[1:]
    layer_ref, refs = refs[0], refs[1:]   # the last scalar-prefetch one
    latent = v_dim is not None
    if latent:
        q_ref, k_hbm, o_ref, lse_ref, k_buf, sems = refs
        v_hbm = v_buf = None
    elif quantized:
        q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, lse_ref, k_buf, v_buf, \
            ks_buf, vs_buf, sems = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, lse_ref, k_buf, v_buf, sems = refs
    s = pl.program_id(0)
    layer = layer_ref[0]
    Hkv, R, Dh = q_ref.shape[1:]
    Dv = v_dim if latent else Dh
    block_tokens = n_pages * page_size
    # A limit past the table's capacity would index the table out of
    # bounds; the reference attends nothing there either.
    limit = jnp.minimum(limit_ref[s], table_ref.shape[1] * page_size)
    if windowed:
        lower = jnp.clip(lower_ref[s], 0, limit)
        b0 = first_block(lower, block_tokens)
        n_blocks = b0 + walk(limit, block_tokens, lower)[0]
    else:
        b0 = 0
        n_blocks, _ = walk(limit, block_tokens)

    @pl.when(s == 0)
    def _clear():
        # A dead page's place in a buffer keeps what was there before:
        # an earlier live page (finite), or, before the first fetch,
        # whatever VMEM held.  Its weights are exactly 0, and 0 * junk
        # must stay 0 in the PV product.
        values = k_buf if latent else v_buf
        values[...] = jnp.zeros_like(values)
        if quantized:
            vs_buf[...] = jnp.zeros_like(vs_buf)

    def fetch(b, buf, wait):
        """Start (or wait for) the transfers of block ``b``'s live
        pages into buffer ``buf``."""
        for i in range(n_pages):
            idx = b * n_pages + i

            live = idx * page_size < limit
            if windowed:          # the page's last position is in reach
                live &= (idx + 1) * page_size > lower

            @pl.when(live)
            def _page():
                # a wait needs the descriptor's shape, not its source
                at = (0, 0) if wait else (layer, table_ref[s, idx])
                to = slice(None)
                if heads:         # this row's head of the page alone
                    at, to = at + ((0,) if wait else (s % heads,)), 0
                pairs = [(k_hbm.at[at], k_buf.at[buf, to, i])]
                if not latent:
                    pairs.append((v_hbm.at[at], v_buf.at[buf, to, i]))
                if quantized:         # one layer's scales: (P, H_kv, lanes)
                    pairs += [(ks_hbm.at[at[1]], ks_buf.at[buf, i]),
                              (vs_hbm.at[at[1]], vs_buf.at[buf, i])]
                for src, dst in pairs:
                    dma = pltpu.make_async_copy(src, dst, sems.at[buf])
                    dma.wait() if wait else dma.start()

    def scale_col(sc_buf, buf):
        """The block's ``(n_pages, H_kv, page)`` scales as the column
        ``(H_kv, block_tokens, 1)`` the payload is multiplied by."""
        sc = sc_buf[buf][:, :, :page_size]
        return jnp.concatenate(
            [sc[i].reshape(Hkv, page_size, 1) for i in range(n_pages)],
            axis=1)

    @pl.when(n_blocks > b0)
    def _first():
        fetch(b0, 0, wait=False)

    q = q_ref[0].astype(compute_dtype if quantized else k_buf.dtype)

    def block(b, carry):
        m_prev, l_prev, acc = carry
        buf = (b - b0) % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            fetch(b + 1, 1 - buf, wait=False)

        fetch(b, buf, wait=True)
        k = k_buf[buf].reshape(Hkv, block_tokens, Dh)
        v = (k[:, :, :Dv] if latent
             else v_buf[buf].reshape(Hkv, block_tokens, Dh))
        if quantized:  # fused dequant: int8 payload * f32 scale, in-reg
            k = _dequant_col(k, scale_col(ks_buf, buf), compute_dtype)
            v = _dequant_col(v, scale_col(vs_buf, buf), compute_dtype)
        s_blk = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        s_blk = (s_blk / np.sqrt(Dh) if sm_scale is None
                 else s_blk * sm_scale)
        # Logical-position mask: page-tail junk, the block's dead pages
        # and a partial last page all sit at positions >= limit.
        col = b * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 2)
        vis = col < limit
        if windowed:
            vis &= col >= lower
        s_blk = jnp.where(vis, s_blk, NEG_INF)            # (Hkv, R, T)

        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)                        # f32
        l_new = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        # _cache_attend discipline: weights cast to V's dtype before the
        # dot, f32 MXU accumulation.
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)           # (Hkv, R, Dh)
        return m_new, l_new, acc * alpha + pv

    m, l, acc = jax.lax.fori_loop(
        b0, n_blocks, block,
        (jnp.full((Hkv, R, 1), NEG_INF, jnp.float32),
         jnp.zeros((Hkv, R, 1), jnp.float32),
         jnp.zeros((Hkv, R, Dv), jnp.float32)))
    empty = l <= 0.0              # fully-masked row (limit == 0)
    l_safe = jnp.where(empty, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse = jnp.where(empty, NEG_INF, m + jnp.log(l_safe))  # (Hkv, R, 1)
    lse_ref[0] = jnp.broadcast_to(lse.reshape(Hkv, 1, R), lse_ref.shape[1:])


def _pallas_paged_attend(qg, k_pool, v_pool, k_scale, v_scale, table,
                         limit, compute_dtype, lower, layer, v_dim=None,
                         sm_scale=None, name=None):
    heads = 0
    if table.ndim == 3:     # a table a slot AND KV head: rows (slot, head)
        assert k_scale is None and lower is None and v_dim is None
        heads, name = qg.shape[1], name or BSA_KERNEL_NAME
        qg = qg.reshape((-1, 1) + qg.shape[2:])
        table = table.reshape(-1, table.shape[2])
        limit = jnp.broadcast_to(limit.reshape(-1, 1) if limit.ndim == 1
                                 else limit, (qg.shape[0] // heads, heads)
                                 ).reshape(-1)
    S, Hkv, R, Dh = qg.shape
    _, _, _, ps, _ = k_pool.shape
    quantized = k_scale is not None
    latent = v_dim is not None
    Dv = v_dim if latent else Dh
    n_pages = block_pages(ps, Hkv, Dh, k_pool.dtype, table.shape[1], latent)

    # Pad query rows up to a sublane tile so tiny G (or G*W) widths
    # still compile on real hardware; padded rows cost only VPU lanes
    # and are sliced off below.
    R_pad = max(8, -(-R // 8) * 8)
    if R_pad != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R_pad - R), (0, 0)))

    # The table and the limits are scalar-prefetched into SMEM: the
    # kernel reads the slot's live entries and issues the page fetches
    # itself, so the pools never get a BlockSpec's pipeline.
    hbm = pl.BlockSpec(memory_space=pl.ANY)

    # The table stays the call's FIRST operand (readers of a device
    # trace find the kernel's events by it); the layer's index goes last.
    windowed = lower is not None
    scalars = [table.astype(jnp.int32), limit.astype(jnp.int32)]
    if windowed:
        scalars.append(lower.astype(jnp.int32))
    layer = jnp.asarray(layer, jnp.int32)
    scalars.append(layer.reshape(1))

    def of_slot(s, *scalars):
        return (s, 0, 0, 0)

    in_specs = [pl.BlockSpec((1, Hkv, R_pad, Dh), of_slot), hbm]
    operands = [qg, k_pool]
    # Buffers are head-major so a block reads as (H_kv, tokens, Dh)
    # with no relayout: page i of a block lands at [:, i].
    buf = (2, Hkv, n_pages, ps, Dh)
    scratch = [pltpu.VMEM(buf, k_pool.dtype)]
    if not latent:
        in_specs.append(hbm)
        operands.append(v_pool)
        scratch.append(pltpu.VMEM(buf, v_pool.dtype))
    if quantized:
        # Mosaic slices an HBM ref in whole 128-lane rows only, so a
        # page's (H_kv, page) scales travel padded to the lane width.
        # Only this layer's are padded: 1 / Dh of the layer's payload.
        lanes = -(-ps // 128) * 128
        pad = ((0, 0), (0, 0), (0, lanes - ps))
        in_specs += [hbm, hbm]
        operands += [jnp.pad(jax.lax.dynamic_index_in_dim(
            sc, layer, 0, keepdims=False), pad)
            for sc in (k_scale, v_scale)]
        sc_buf = (2, n_pages, Hkv, lanes)
        scratch += [pltpu.VMEM(sc_buf, k_scale.dtype),
                    pltpu.VMEM(sc_buf, v_scale.dtype)]
    scratch.append(pltpu.SemaphoreType.DMA((2,)))         # one a buffer

    o_shape = jax.ShapeDtypeStruct((S, Hkv, R_pad, Dv), jnp.float32)
    # lse rides a sublane-replicated (…, 8, R) layout, like the flash
    # kernel's — callers read row 0.
    lse_shape = jax.ShapeDtypeStruct((S, Hkv, 8, R_pad), jnp.float32)
    out_specs = [
        pl.BlockSpec((1, Hkv, R_pad, Dv), of_slot),
        pl.BlockSpec((1, Hkv, 8, R_pad), of_slot),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    o, lse = pl.pallas_call(
        functools.partial(_kernel_body, page_size=ps, n_pages=n_pages,
                          compute_dtype=compute_dtype, quantized=quantized,
                          windowed=windowed, v_dim=v_dim, sm_scale=sm_scale,
                          heads=heads),
        grid_spec=grid_spec,
        out_shape=[o_shape, lse_shape],
        # the buffers' zeroing at slot 0 must come first
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=use_interpret(),
        name=KERNEL_NAME if not (latent or name) else name or MLA_KERNEL_NAME,
    )(*scalars, *operands)
    o, lse = o[:, :, :R, :], lse[:, :, 0, :R]
    if heads:
        o = o.reshape(S // heads, heads, R, Dv)
        lse = lse.reshape(S // heads, heads, R)
    return o, lse


def paged_attend_reference(qg, k_pool, v_pool, k_scale, v_scale, table,
                           limit, *, compute_dtype=None, lower=None,
                           v_dim=None, sm_scale=None, layer=None):
    """Pure-JAX reference for :func:`paged_attend` — gather, dequant,
    masked softmax — mirroring the unfused decode path's op-for-op
    rounding (``kv_dequantize``'s f32 contract, ``_cache_attend``'s
    stored-dtype dots with f32 accumulation, normalize-then-cast
    weights).  For a table a slot (``(S, n)``) the oracle in tests and
    in ``chip_smoke.py``, never a substitute for the kernel.  For a
    table a slot AND KV head (``(S, H_kv, n)``, ``limit`` ``(S, H_kv)``:
    block-sparse attention, with ``layer`` the stacked pool's) it is NOT
    an oracle but the tick's own attend wherever the kernel is not
    engaged (the CPU), as :func:`mla_decode_reference` is: the ``heads``
    kernel is held to ``plain_reference.sala_forward`` instead, which
    shares nothing with either (``tests/test_linear_sparse_layers.py``,
    ``chip_smoke.py``'s linear-sparse phase)."""
    S, Hkv, R, Dh = qg.shape
    if table.ndim == 3:
        kg, vg = (pool[layer, table, jnp.arange(Hkv)[None, :, None]].reshape(
            S, Hkv, -1, Dh) for pool in (k_pool, v_pool))
        s = jnp.einsum("skrd,sktd->skrt", qg.astype(kg.dtype), kg,
                       preferred_element_type=jnp.float32) / np.sqrt(Dh)
        col = jax.lax.broadcasted_iota(jnp.int32, (kg.shape[2],), 0)
        vis = (col < limit[..., None])[:, :, None]        # (S, Hkv, 1, T)
        m = jnp.max(jnp.where(vis, s, NEG_INF), axis=-1, keepdims=True)
        p = jnp.where(vis, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("skrt,sktd->skrd",
                       (p / jnp.where(l > 0, l, 1.0)).astype(vg.dtype), vg,
                       preferred_element_type=jnp.float32)
        return o, jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(
            jnp.where(l > 0, l, 1.0))[..., 0], NEG_INF)
    max_pages = table.shape[1]
    ps = k_pool.shape[2]
    if compute_dtype is None:
        compute_dtype = k_pool.dtype

    def gather(pool_l):                       # (P,Hkv,ps,Dh) -> logical
        g = pool_l[table]                     # (S, max_pages, Hkv, ps, Dh)
        return jnp.moveaxis(g, 1, 2).reshape(S, Hkv, max_pages * ps, Dh)

    if k_scale is not None:
        def gather_sc(scale_l):
            g = scale_l[table]
            return jnp.moveaxis(g, 1, 2).reshape(S, Hkv, max_pages * ps)

        kg = _dequant(gather(k_pool), gather_sc(k_scale), compute_dtype)
        vg = _dequant(gather(v_pool), gather_sc(v_scale), compute_dtype)
    else:
        kg = gather(k_pool)
        # a latent pool: the value is the key's first v_dim lanes
        vg = gather(v_pool) if v_dim is None else kg[..., :v_dim]
    s = jnp.einsum("skrd,sktd->skrt", qg.astype(kg.dtype), kg,
                   preferred_element_type=jnp.float32)
    s = s / np.sqrt(Dh) if sm_scale is None else s * sm_scale
    T = max_pages * ps
    col = jax.lax.broadcasted_iota(jnp.int32, (T,), 0)[None, :]
    vis = col < limit[:, None]                # (S, T)
    if lower is not None:
        vis &= col >= lower[:, None]
    s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    any_vis = jnp.any(vis, axis=-1)[:, None, None, None]
    p = jnp.exp(s - jnp.where(any_vis, m, 0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    w = jnp.where(any_vis, p / l, 0.0)
    o = jnp.einsum("skrt,sktd->skrd", w.astype(vg.dtype), vg,
                   preferred_element_type=jnp.float32)
    lse = jnp.where(any_vis[..., 0], m[..., 0] + jnp.log(l[..., 0]),
                    NEG_INF)
    return o, lse


def paged_attend(qg, k_pool, v_pool, k_scale, v_scale, table, limit, *,
                 compute_dtype=None, lower=None, layer=None, sm_scale=None):
    """Fused decode attention directly against a paged KV pool.

    Args:
      qg: ``(S, H_kv, R, Dh)`` grouped queries — ``R = G`` (GQA group)
        for a one-token decode tick, ``R = G * W`` for a W-wide VERIFY
        window (rows ``g * W + j``).
      k_pool / v_pool: the pool in the stored dtype (f32 / bf16 / int8):
        every layer's, ``(L, P, H_kv, page, Dh)``, with ``layer``; or
        ONE layer's, ``(P, H_kv, page, Dh)``, with ``layer=None`` (a
        stack of one: a reshape, no copy).
      k_scale / v_scale: f32 per-vector scales for int8 pools, shaped as
        the pool less its last dim, else ``None``.
      table: ``(S, max_pages)`` int32 physical page ids (host data —
        any allocation pattern, one executable); or ``(S, H_kv, n)``, a
        table a slot AND KV head (block-sparse attention: the pages of
        the blocks that head's queries selected, compacted) — the grid
        is then a (slot, head) a step, a page's transfer that head's
        ``(page, Dh)`` alone, ``limit`` ``(S, H_kv)`` counts positions
        of the COMPACTED order; unquantized, no window.
      limit: ``(S,)`` int32 — attend logical positions ``< limit[s]``
        (``pos + 1`` for decode-at-``pos``, ``pos`` for VERIFY over
        committed pages; ``0`` masks a slot entirely).
      compute_dtype: dtype int8 pages are dequantized TO (the model's
        ``cfg.dtype``); ignored for unquantized pools, which are dotted
        in their stored dtype per ``_cache_attend``.
      lower: optional ``(S,)`` int32 — a window layer's lower bound:
        attend positions ``lower[s] <= t < limit[s]`` only, and start
        the walk at the block that holds ``lower[s]``.  ``None`` (a
        full layer) compiles the kernel with no such operand.
      layer: int32 scalar (traced in a layer scan) — which layer of the
        stacked pool to attend.  The kernel reads ``pool[layer, page]``
        in place; the caller never slices the layer out.
      sm_scale: the scores' scale where it is not ``Dh ** -0.5`` of the
        STORED row: a pool whose 128-lane rows hold two 64-wide heads
        side by side (``TransformerConfig.kv_lane_dense``) is attended
        as ``H_kv / 2`` heads of 128 — head ``a``'s queries zero outside
        its lanes, so a row's product is that head's alone, and of the
        output's lanes each query reads its own head's — at ``64 **
        -0.5``.  The kernel is the one heads of 128 run.

    Returns:
      ``(o, lse)``: ``o`` ``(S, H_kv, R, Dh)`` f32 attention output
      (zeros for fully-masked rows), ``lse`` ``(S, H_kv, R)`` f32 per-
      row logsumexp of the masked scores (``NEG_INF`` when fully
      masked) for cross-source combining.
    """
    if compute_dtype is None:
        compute_dtype = k_pool.dtype
    if layer is None:
        layer = 0
        k_pool, v_pool = k_pool[None], v_pool[None]
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    return _pallas_paged_attend(qg, k_pool, v_pool, k_scale, v_scale,
                                table, limit, compute_dtype, lower, layer,
                                sm_scale=sm_scale)


def mla_decode(q, pool, table, limit, *, v_dim: int, sm_scale: float,
               layer=None, name: str = MLA_KERNEL_NAME):
    """Absorbed latent attention of one token a slot, directly against
    a paged LATENT pool: kernel :data:`MLA_KERNEL_NAME`, which is
    :func:`paged_attend`'s walk (table, limits, :func:`walk`,
    :func:`block_pages`, the double-buffered block loop and its online
    softmax — one body, ``_kernel_body``) over ONE array: a fetched row
    ``[ckv | k_rope | 0]`` is the key of all ``H`` heads, and its first
    ``v_dim`` lanes their value.

    Args:
      q: ``(S, H, W)`` absorbed queries ``[q_nope W_k^T | q_rope | 0]``,
        ``W`` the STORED row: ``kv_lora_rank + qk_rope_head_dim`` (576)
        rounded up to whole 128-lane groups (640), zeros behind.
      pool: every layer's latent rows ``(L, P, 1, page, W)`` with
        ``layer`` (traced in a layer scan), or one layer's ``(P, 1,
        page, W)`` with ``layer=None``.
      table, limit: as :func:`paged_attend`.
      v_dim: ``kv_lora_rank`` (512).
      sm_scale: the softmax scale (``TransformerConfig.mla_scale``).

    Returns ``(o_lat (S, H, v_dim) float32, lse (S, H))``: each head's
    weighted sum of latents — ``W_v`` and ``W_o`` are applied outside.
    ``name``: the call's name on a device trace (:func:`selected_attend`
    runs this body over gathered rows under a name of its own).

    Per cached token the mathematics needs 576 x 2 bytes (the walk
    fetches the stored 640) and ``H x 2 x (576 + v_dim)`` FLOPs: 1 152 B
    against 139 kFLOP at the published sizes, 121 FLOPs a byte — under a v5e's 240, so bound by bytes with
    the MXU half busy."""
    if layer is None:
        layer, pool = 0, pool[None]
    o, lse = _pallas_paged_attend(
        q[:, None], pool, None, None, None, table, limit, pool.dtype, None,
        layer, v_dim=v_dim, sm_scale=sm_scale, name=name)
    return o[:, 0], lse[:, 0]


def mla_decode_reference(q, pool, table, limit, *, v_dim: int,
                         sm_scale: float, layer=None):
    """:func:`mla_decode`'s unfused twin (gather, masked softmax, in
    XLA): the test oracle, and the tick's attend where the kernel is not
    engaged (the CPU).  ``pool[layer, table]`` is gathered, so no layer
    is cut out of the stack."""
    if layer is not None:
        S, max_pages = table.shape
        g = pool[layer, table]             # (S, max_pages, 1, page, W)
        # already the slots' logical rows: a pool of S * max_pages pages
        pool = g.reshape((S * max_pages,) + g.shape[2:])
        table = jnp.arange(S * max_pages, dtype=jnp.int32).reshape(
            S, max_pages)
    o, lse = paged_attend_reference(
        q[:, None], pool, None, None, None, table, limit, v_dim=v_dim,
        sm_scale=sm_scale)
    return o[:, 0], lse[:, 0]


# --- learned sparse attention: the index walk, the selection, the attend ------
#
# A sparse latent model (``TransformerConfig.sparse``) keeps ONE index
# key a token and layer beside the latent row — the pool's second array
# ``ik`` ``(L, P, 1, page, Di)`` under the same page table — and a tick
# does three things with it: scores every live token of every slot
# (:func:`index_scores`, the walk above emitting a score a token instead
# of a softmax), picks each slot's ``k`` best positions
# (:func:`select_topk`: exact, no sort), and attends those rows alone
# (:func:`selected_attend`).

#: The index walk's name on a device trace.
INDEX_KERNEL_NAME = "hvd_dsa_score"
#: ... and the selected attend's (``mla_decode``'s body over the
#: gathered rows).
SELECT_ATTEND_NAME = "hvd_dsa_attend"

# What one step of the index walk holds of the keys (two buffers of
# this): 1024 tokens of 128 bf16 values.  The scores of a step are
# (index heads x tokens) float32 — 256 KB at 64 heads.
_INDEX_BLOCK_BYTES = 256 * 1024


def index_block_pages(page_size: int, index_dim: int, storage_dtype,
                      max_pages: int) -> int:
    """Pages one step of the index walk fetches and scores: as many as
    fit :data:`_INDEX_BLOCK_BYTES`, in whole 128-token lane groups (the
    scores' last dim), never more than a slot's table holds — 64 pages
    (1024 tokens) for bf16 pages of 16 x 128."""
    page_bytes = page_size * index_dim * max(
        jnp.dtype(storage_dtype).itemsize, 2)
    n = max(1, min(_INDEX_BLOCK_BYTES // page_bytes, max_pages))
    group = max(128 // page_size, 1)
    return n - n % group if n > group else n


def _index_kernel_body(table_ref, limit_ref, layer_ref, q_ref, w_ref, k_hbm,
                       o_ref, k_buf, sems, *, page_size, n_pages):
    """One grid step of the index walk: slot ``s``'s live pages of index
    keys, block by block (``_kernel_body``'s fetch discipline: one
    transfer a live page into one of two buffers while the other is
    scored), each block's ``(Hi, tokens)`` dots through ReLU, times the
    heads' weights, summed over the heads into one score a token.
    Positions ``>= limit`` read ``NEG_INF``; blocks past the last live
    position are never fetched."""
    s = pl.program_id(0)
    layer = layer_ref[0]
    block_tokens = n_pages * page_size
    limit = jnp.minimum(limit_ref[s], table_ref.shape[1] * page_size)
    n_blocks, _ = walk(limit, block_tokens)
    o_ref[...] = jnp.full(o_ref.shape, NEG_INF, o_ref.dtype)

    def fetch(b, buf, wait):
        for i in range(n_pages):
            idx = b * n_pages + i

            @pl.when(idx * page_size < limit)
            def _page():
                at = (0, 0) if wait else (layer, table_ref[s, idx])
                dma = pltpu.make_async_copy(
                    k_hbm.at[at], k_buf.at[buf, :, i], sems.at[buf])
                dma.wait() if wait else dma.start()

    @pl.when(n_blocks > 0)
    def _first():
        fetch(0, 0, wait=False)

    q = q_ref[0].astype(k_buf.dtype)                      # (Hi, Di)
    w = w_ref[0]                                          # (Hi, 1) f32

    def block(b, carry):
        buf = b % 2

        @pl.when(b + 1 < n_blocks)
        def _next():
            fetch(b + 1, 1 - buf, wait=False)

        fetch(b, buf, wait=True)
        k = k_buf[buf].reshape(block_tokens, k_buf.shape[-1])
        dots = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (Hi, tokens)
        score = jnp.sum(jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
        col = b * block_tokens + jax.lax.broadcasted_iota(
            jnp.int32, score.shape, 1)
        o_ref[0, pl.ds(b, 1), :] = jnp.where(col < limit, score, NEG_INF)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)


def index_scores(qi, w, ik_pool, table, limit, *, layer=None):
    """The INDEX WALK: every slot's index score of each of its live
    tokens, directly against the paged index keys — kernel
    :data:`INDEX_KERNEL_NAME`, :func:`paged_attend`'s walk (table,
    limits, :func:`walk`, a block of :func:`index_block_pages` pages,
    two buffers) with a score a token where that one keeps a softmax.

    Args:
      qi: ``(S, Hi, Di)`` index queries (roped), one token a slot.
      w: ``(S, Hi)`` float32 head weights, the two scales folded in.
      ik_pool: every layer's index keys ``(L, P, 1, page, Di)`` with
        ``layer``, or one layer's ``(P, 1, page, Di)`` with
        ``layer=None``.
      table, limit: as :func:`paged_attend`.

    Returns ``(S, max_pages * page)`` float32: ``sum_j w[s, j] * relu(qi[s, j] . k[t])`` at live
    positions ``t < limit[s]``, ``NEG_INF`` elsewhere.

    Per live token the mathematics needs ``Di`` x 2 bytes and ``Hi x Di
    x 2`` FLOPs: 256 B against 16 kFLOP at the published sizes, 64
    FLOPs a byte — bound by bytes."""
    if layer is None:
        layer, ik_pool = 0, ik_pool[None]
    S, Hi, Di = qi.shape
    ps = ik_pool.shape[3]
    max_pages = table.shape[1]
    n_pages = index_block_pages(ps, Di, ik_pool.dtype, max_pages)
    n_blocks = -(-max_pages // n_pages)
    bt = n_pages * ps
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scalars = [table.astype(jnp.int32), limit.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape(1)]

    def of_slot(s, *scalars):
        return (s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars), grid=(S,),
        in_specs=[pl.BlockSpec((1, Hi, Di), of_slot),
                  pl.BlockSpec((1, Hi, 1), of_slot), hbm],
        out_specs=pl.BlockSpec((1, n_blocks, bt), of_slot),
        scratch_shapes=[pltpu.VMEM((2, 1, n_pages, ps, Di), ik_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    out = pl.pallas_call(
        functools.partial(_index_kernel_body, page_size=ps, n_pages=n_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_blocks, bt), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=use_interpret(),
        name=INDEX_KERNEL_NAME,
    )(*scalars, qi, w.astype(jnp.float32)[..., None], ik_pool)
    return out.reshape(S, n_blocks * bt)[:, :max_pages * ps]


def index_scores_dense(qi, w, keys):
    """Index scores against keys that lie in order: ``qi`` ``(R, Hi,
    Di)``, ``w`` ``(R, Hi)`` float32, ``keys`` ``(R, T, Di)`` (or ``(T,
    Di)``, every row's) -> ``(R, T)`` float32.  The products take the
    keys' dtype and accumulate in float32, as the kernel's do."""
    eq = "rhd,rtd->rht" if keys.ndim == 3 else "rhd,td->rht"
    dots = jnp.einsum(eq, qi.astype(keys.dtype), keys,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(dots, 0.0)
                   * w.astype(jnp.float32)[..., None], axis=1)


def _rows_kernel_body(q_ref, w_ref, k_ref, o_ref):
    tq, Hi, Di = q_ref.shape
    dots = jax.lax.dot_general(
        q_ref[...].reshape(tq * Hi, Di), k_ref[...],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    dots = jnp.maximum(dots, 0.0) * w_ref[...].reshape(tq * Hi, 1)
    o_ref[...] = jnp.sum(dots.reshape(tq, Hi, dots.shape[-1]), axis=1)


def index_scores_rows(qi, w, keys, *, kernel: bool):
    """A CHUNK's index scores, every query against every key that lies
    in order: ``qi`` ``(Q, Hi, Di)``, ``w`` ``(Q, Hi)``, ``keys`` ``(T,
    Di)`` -> ``(Q, T)`` float32, unmasked (the selection is told how
    many positions each query sees).  ``kernel``: one Pallas program
    (:data:`INDEX_KERNEL_NAME`) a tile of 32 queries x 512 keys — the
    ``(queries, heads, keys)`` dots live in VMEM alone (4.3 GB of them
    for 512 queries against 32 k keys) — else
    :func:`index_scores_dense`."""
    if not kernel:
        return index_scores_dense(qi, w, keys)
    Q, Hi, Di = qi.shape
    T = keys.shape[0]
    tq, tk = min(32, Q), 512
    Qp, Tp = -(-Q // tq) * tq, -(-T // tk) * tk
    qi = jnp.pad(qi.astype(keys.dtype), ((0, Qp - Q), (0, 0), (0, 0)))
    w = jnp.pad(w.astype(jnp.float32), ((0, Qp - Q), (0, 0)))[..., None]
    keys = jnp.pad(keys, ((0, Tp - T), (0, 0)))
    out = pl.pallas_call(
        _rows_kernel_body,
        grid=(Qp // tq, Tp // tk),
        in_specs=[pl.BlockSpec((tq, Hi, Di), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((tq, Hi, 1), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((tk, Di), lambda i, j: (j, 0))],
        out_specs=pl.BlockSpec((tq, tk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Tp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=use_interpret(),
        name=INDEX_KERNEL_NAME,
    )(qi, w, keys)
    return out[:Q, :T]


def index_scores_reference(qi, w, ik_pool, table, limit, *, layer=None):
    """:func:`index_scores`' unfused twin (gather, dots, mask, in XLA):
    the test oracle, and the tick's index walk where the kernel is not
    engaged (the CPU).  ``(S, max_pages * page)``."""
    if layer is None:
        layer, ik_pool = 0, ik_pool[None]
    S, max_pages = table.shape
    g = ik_pool[layer, table]              # (S, max_pages, 1, page, Di)
    keys = g.reshape(S, max_pages * g.shape[3], g.shape[4])
    col = jax.lax.broadcasted_iota(jnp.int32, keys.shape[:2], 1)
    return jnp.where(col < limit[:, None],
                     index_scores_dense(qi, w, keys), NEG_INF)


def _order_keys(x):
    """float32 -> uint32 whose unsigned order is the floats' order
    (``-0.0 < +0.0``; NaNs at the ends)."""
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


_LANES = 128


def _block_prefix(mask):
    """Inclusive running count of a ``(R, nb, 128)`` bool ``mask``
    inside each 128-wide block, float32: a product with a triangle of
    ones on the MXU (counts to 128 are exact in bfloat16 operands and
    float32 sums), not a scan."""
    tri = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1))
    return jnp.einsum("rbl,lm->rbm", mask.astype(jnp.bfloat16),
                      tri.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def select_topk(scores, n_valid, k: int, width: int = 0):
    """Each row's ``min(k, n_valid)`` best-scored positions among its
    first ``n_valid`` — EXACTLY ``lax.top_k``'s set (ties to the lower
    position), with no sort: the set is what attention needs, not its
    order.

    ``scores`` ``(R, T)`` float32, ``n_valid`` ``(R,)`` int32 (positions
    ``>= n_valid[r]`` are never picked, whatever they hold).  Returns
    ``(idx (R, width) int32, count (R,) int32)``, ``width`` (0 = ``k``)
    at least ``k``: row ``r``'s picks are ``idx[r, :count[r]]``,
    ascending; the places behind hold 0.

    Three steps, all of them counting passes and small dense products:
    (1) the ``count``-th largest score by BISECTION over the floats'
    bit pattern — 32 passes, each counting the row's scores at or over
    a candidate; (2) everything over it is in, and of the scores EQUAL
    to it the lowest positions, as many as are still owed (a running
    count); (3) COMPACTION of the picked mask into positions, two
    levels of 128: the block that holds the row's ``j``-th pick from
    the blocks' running totals, the lane inside it from the block's
    running count, the block's row fetched by a one-hot product."""
    R, T = scores.shape
    width = width or k
    assert width >= k, (width, k)
    Tp = -(-T // _LANES) * _LANES
    n_valid = jnp.minimum(n_valid.astype(jnp.int32), T)
    count = jnp.minimum(n_valid, k)
    valid = jax.lax.broadcasted_iota(jnp.int32, (R, Tp), 1) < n_valid[:, None]
    keys = jnp.pad(_order_keys(scores), ((0, 0), (0, Tp - T)))
    keys = jnp.where(valid, keys, jnp.uint32(0))

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum((keys >= cand[:, None]) & valid, axis=1,
                    dtype=jnp.int32)
        return jnp.where(n >= count, cand, tau)

    # the largest tau with at least ``count`` valid scores at or over it
    tau = jax.lax.fori_loop(0, 32, bit, jnp.zeros((R,), jnp.uint32))
    over = valid & (keys > tau[:, None])
    tie = (valid & (keys == tau[:, None])).reshape(R, -1, _LANES)
    owed = (count - jnp.sum(over, axis=1, dtype=jnp.int32)
            ).astype(jnp.float32)
    tie_in = _block_prefix(tie)
    tie_before = jnp.cumsum(tie_in[..., -1], axis=1) - tie_in[..., -1]
    picked = over.reshape(tie.shape) | (
        tie & (tie_in + tie_before[..., None] <= owed[:, None, None]))

    # compaction: pick j of a row lies in the block whose running total
    # first passes j, at the lane whose running count is j less the
    # blocks' before it, plus one
    rank = _block_prefix(picked)
    in_block = rank[..., -1]                              # (R, nb)
    rank = rank * picked                                  # 0 = not picked
    total = jnp.cumsum(in_block, axis=1)
    j = jnp.arange(width, dtype=jnp.float32)
    blk = jnp.sum(total[:, None, :] <= j[None, :, None], axis=-1,
                  dtype=jnp.int32)                        # (R, k)
    nb = Tp // _LANES
    hot = blk[..., None] == jnp.arange(nb, dtype=jnp.int32)   # (R, k, nb)
    before = total - in_block
    want = j[None, :] - jnp.sum(jnp.where(hot, before[:, None, :], 0.0),
                                axis=-1) + 1.0
    row = jnp.einsum("rkb,rbl->rkl", hot.astype(jnp.bfloat16),
                     rank.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    lane = jnp.sum(jnp.where(row == want[..., None],
                             jnp.arange(_LANES, dtype=jnp.int32), 0),
                   axis=-1)
    idx = jnp.where(jnp.arange(width)[None, :] < count[:, None],
                    blk * _LANES + lane, 0)
    return idx.astype(jnp.int32), count


def pages_of(table, idx, page_size: int):
    """The physical page of each picked position: ``table`` ``(R,
    max_pages)`` int32, ``idx`` ``(R, K)`` logical positions ``<
    max_pages * page_size`` -> ``(R, K)`` int32, EXACTLY
    ``jnp.take_along_axis(table, idx // page_size, axis=1)`` — which
    XLA:TPU lowers to a transfer an ELEMENT (~10 ns each on the v5e).

    The two moves of :func:`select_topk`'s compaction instead: the
    table row in groups of ``128 // page_size`` pages, the pick's group
    fetched by a one-hot product on the MXU, its page of the group
    chosen by a compare-and-sum.  The ids go through the product a BYTE
    at a time (a one-hot row times an integer under 256 is exact in
    bfloat16 operands and float32 sums), so any int32 id comes back as
    it went in."""
    R, max_pages = table.shape
    per = max(_LANES // page_size, 1)          # pages a group
    ng = -(-max_pages // per)
    groups = jnp.pad(table, ((0, 0), (0, ng * per - max_pages))
                     ).reshape(R, ng, per)
    planes = jnp.concatenate([(groups >> s) & 0xFF for s in (0, 8, 16, 24)],
                             axis=-1)                     # (R, ng, 4 * per)
    page = idx // page_size
    hot = (page // per)[..., None] == jnp.arange(ng, dtype=jnp.int32)
    got = jnp.einsum("rkg,rgp->rkp", hot.astype(jnp.bfloat16),
                     planes.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    # column c of the product is byte c // per of the group's page c % per
    col = jnp.arange(4 * per, dtype=jnp.int32)
    mine = (page % per)[..., None] == col % per
    return jnp.sum(jnp.where(mine, got.astype(jnp.int32) << 8 * (col // per),
                             0), axis=-1)


def selected_attend(q, rows, count, *, v_dim: int, sm_scale: float,
                    kernel: bool):
    """Absorbed latent attention of each query over ITS OWN gathered
    rows: ``q`` ``(R, H, W)``, ``rows`` ``(R, K, W)`` (row ``r``'s
    selected cache rows, the first ``count[r]`` of them real), ``K`` a
    multiple of 16.  The rows are read as a pool of ``R * K / 16`` pages
    under an identity table, so the kernel is :func:`mla_decode`'s body
    (one walk, one online softmax) under the name
    :data:`SELECT_ATTEND_NAME`; ``kernel=False`` is its unfused twin.
    -> ``(o_lat (R, H, v_dim) float32, lse (R, H))``."""
    R, K, W = rows.shape
    ps = 16
    assert K % ps == 0, K
    pool = rows.reshape(R * K // ps, 1, ps, W)
    table = jnp.arange(R * K // ps, dtype=jnp.int32).reshape(R, K // ps)
    if kernel:
        return mla_decode(q, pool, table, count, v_dim=v_dim,
                          sm_scale=sm_scale, name=SELECT_ATTEND_NAME)
    return mla_decode_reference(q, pool, table, count, v_dim=v_dim,
                                sm_scale=sm_scale)


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
