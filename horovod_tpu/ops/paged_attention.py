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
program per ``(slot, kv-head)``:

* the grid walks ``(slot, kv_head, page_block)`` with the PAGE BLOCK
  innermost, so the online-softmax scratch carries across a slot's
  pages;
* the page table row lives in SMEM via scalar prefetch
  (``PrefetchScalarGridSpec``) — each K/V BlockSpec's index_map reads
  ``table[s, b]`` to stream the REFERENCED physical page straight from
  the pool, so the gather never materializes;
* int8 dequant is fused into the load: the page's int8 payload and its
  per-vector scales are combined in-register (f32 compute, then cast to
  the compute dtype — the exact :func:`~horovod_tpu.models.transformer.
  kv_dequantize` contract, see :data:`DEQUANT_COMPUTE`);
* masking is by LOGICAL position against a per-slot ``limit``
  (positions ``< limit[s]`` attend) — partial last pages, page-tail
  junk, NULL-page trash, and inactive slots (``limit == 0``) all fall
  out of the same comparison;
* cross-block combination is the standard flash-decoding online
  softmax (running max / sum / accumulator with rescale), and the
  kernel emits per-row ``logsumexp`` so a caller can merge the result
  with attention over OTHER sources (the speculative VERIFY path
  combines committed-page attention with in-window attention by LSE).

Conventions shared with :mod:`~horovod_tpu.ops.attention` via
:mod:`~horovod_tpu.ops._pallas_util`: compiled on TPU, interpreted on
CPU (tier-1 CPU tests exercise the REAL kernel body).  The pure-JAX
:func:`paged_attend_reference` is the test oracle only —
:func:`paged_attend` never substitutes it; a caller asks
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
           "paged_attend_reference", "kernel_supported"]


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
# page is one ``(page_size, head_dim)`` VMEM block, and Mosaic tiles the
# last two dims in (sublane, 128-lane) units.
_MIN_SUBLANE = {"float32": 8, "bfloat16": 16, "int8": 32}


class UnsupportedPagedLayoutError(ValueError):
    """The fused kernel was demanded for a pool layout the TPU compiler
    cannot tile (see :func:`kernel_supported`)."""


def kernel_supported(storage_dtype, page_size: int, head_dim: int) -> bool:
    """Whether the COMPILED kernel can serve a pool of this layout.

    This is the TPU compiler's rule, whatever backend asks: the page
    must fill whole dtype tiles — ``head_dim`` a lane multiple (128)
    and ``page_size`` a sublane multiple of the stored dtype (8 f32 /
    16 bf16 / 32 int8).  On CPU the interpreter runs any shape, so
    :func:`paged_attend` itself does not consult this; the serving
    engine does, at construction, when it decides whether its ticks use
    the kernel (``/stats`` ``paged_kernel_engaged``)."""
    sub = _MIN_SUBLANE.get(jnp.dtype(storage_dtype).name)
    return (sub is not None and head_dim % 128 == 0
            and page_size % sub == 0)


def _kernel_body(table_ref, limit_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, *, page_size, num_blocks,
                 compute_dtype, quantized, ks_ref=None, vs_ref=None):
    """One grid step: slot ``s``, kv-head ``h``, page block ``b``.

    The BlockSpec index_maps already routed ``k_ref``/``v_ref`` (and the
    scale refs) at PHYSICAL page ``table[s, b]`` — in here the block is
    simply "this slot's pages ``b*page .. (b+1)*page`` in logical
    order".  Scratch (``acc``/``m``/``l``) persists across the innermost
    grid dim, carrying the online softmax over the slot's pages.
    """
    s, h, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    limit = limit_ref[s]

    @pl.when(b == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(b * page_size < limit)
    def _step():
        k = k_ref[0, 0]                                   # (page, Dh)
        v = v_ref[0, 0]
        if quantized:  # fused dequant: int8 payload * f32 scale, in-reg
            # The scale block holds ALL of the page's heads (see the
            # sc_spec note below); take head h's row as a column.
            k = _dequant_col(
                k, ks_ref[0, pl.ds(h, 1), :].reshape(page_size, 1),
                compute_dtype)
            v = _dequant_col(
                v, vs_ref[0, pl.ds(h, 1), :].reshape(page_size, 1),
                compute_dtype)
        q = q_ref[0, 0].astype(k.dtype)                   # (R, Dh)
        Dh = q.shape[-1]
        s_blk = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / np.sqrt(Dh)  # (R, page)
        # Logical-position mask: page-tail junk / NULL-page trash /
        # partial last page all sit at positions >= limit.
        col = b * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s_blk.shape, 1)
        s_blk = jnp.where(col < limit, s_blk, NEG_INF)

        m_prev = m_ref[:, :1]                             # (R, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s_blk - m_new)                        # (R, page) f32
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # _cache_attend discipline: weights cast to V's dtype before the
        # dot, f32 MXU accumulation.
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # (R, Dh)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(b == num_blocks - 1)
    def _finalize():
        m = m_ref[:, :1]
        l = l_ref[:, :1]
        empty = l <= 0.0          # fully-masked row (limit == 0)
        l_safe = jnp.where(empty, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(empty, NEG_INF, m + jnp.log(l_safe))  # (R, 1)
        lse_ref[0, 0] = jnp.broadcast_to(lse.reshape(1, -1),
                                         lse_ref.shape[2:])


#: The kernel's name on a device trace (``pl.pallas_call(name=)``):
#: readers of a profile find the call by it, not by operand shapes.
KERNEL_NAME = "hvd_paged_attend"


def grid_extent(n_slots: int, n_kv_heads: int, max_pages: int):
    """The kernel's grid for one call: one step per (slot, KV head,
    page block of the slot's table) — EVERY block of the table, whatever
    the slot's ``limit`` (blocks past it are masked, not skipped).  The
    ONE statement of the extent: :func:`_pallas_paged_attend` builds its
    ``grid_spec`` from it and :func:`grid_tokens` counts from it, so a
    change that shrinks the grid moves the engine's
    ``paged_walked_tokens`` counter with it."""
    return (n_slots, n_kv_heads, max_pages)


def grid_tokens(n_slots: int, max_pages: int, page_size: int) -> int:
    """Logical positions one call's grid visits (per KV head and
    layer): the denominator of "live over walked"."""
    slots, _, blocks = grid_extent(n_slots, 1, max_pages)
    return slots * blocks * page_size


def _pallas_paged_attend(qg, k_pool, v_pool, k_scale, v_scale, table,
                         limit, compute_dtype):
    S, Hkv, R, Dh = qg.shape
    _, _, ps, _ = k_pool.shape
    max_pages = table.shape[1]
    quantized = k_scale is not None

    # Pad query rows up to a sublane tile so tiny G (or G*W) widths
    # still compile on real hardware; padded rows cost only VPU lanes
    # and are sliced off below.
    R_pad = max(8, -(-R // 8) * 8)
    if R_pad != R:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, R_pad - R), (0, 0)))

    kernel = functools.partial(
        _kernel_body, page_size=ps, num_blocks=max_pages,
        compute_dtype=compute_dtype, quantized=quantized)
    if quantized:
        def kernel(t, lim, q, k, v, ks, vs, o, lse, acc, m, l):  # noqa: F811
            return _kernel_body(
                t, lim, q, k, v, o, lse, acc, m, l, page_size=ps,
                num_blocks=max_pages, compute_dtype=compute_dtype,
                quantized=True, ks_ref=ks, vs_ref=vs)

    # Scalar-prefetch args (table, limit) arrive as trailing index_map
    # operands: the K/V specs use the TABLE ROW to stream the referenced
    # physical page — the "gather" is just block routing.
    q_spec = pl.BlockSpec((1, 1, R_pad, Dh), lambda s, h, b, t, lim: (s, h, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, ps, Dh),
                           lambda s, h, b, t, lim: (t[s, b], h, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qg, k_pool, v_pool]
    if quantized:
        # Mosaic wants a block's last two dims in whole (8, 128) tiles
        # or equal to the array's: a per-head (1, 1, ps) block over
        # (P, H_kv, ps) is neither, so the block carries every head of
        # the page (H_kv * ps f32 — a few hundred bytes) and the kernel
        # picks its row.
        sc_spec = pl.BlockSpec((1, Hkv, ps),
                               lambda s, h, b, t, lim: (t[s, b], 0, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    o_shape = jax.ShapeDtypeStruct((S, Hkv, R_pad, Dh), jnp.float32)
    # lse rides a sublane-replicated (…, 8, R) layout, like the flash
    # kernel's — callers read row 0.
    lse_shape = jax.ShapeDtypeStruct((S, Hkv, 8, R_pad), jnp.float32)
    out_specs = [
        pl.BlockSpec((1, 1, R_pad, Dh), lambda s, h, b, t, lim: (s, h, 0, 0)),
        pl.BlockSpec((1, 1, 8, R_pad), lambda s, h, b, t, lim: (s, h, 0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid_extent(S, Hkv, max_pages),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((R_pad, Dh), jnp.float32),    # acc
            pltpu.VMEM((R_pad, 128), jnp.float32),   # running max
            pltpu.VMEM((R_pad, 128), jnp.float32),   # running sum
        ],
    )
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[o_shape, lse_shape],
        interpret=use_interpret(),
        name=KERNEL_NAME,
    )(table.astype(jnp.int32), limit.astype(jnp.int32), *operands)
    return o[:, :, :R, :], lse[:, :, 0, :R]


def paged_attend_reference(qg, k_pool, v_pool, k_scale, v_scale, table,
                           limit, *, compute_dtype=None):
    """Pure-JAX reference for :func:`paged_attend` — gather, dequant,
    masked softmax — mirroring the unfused decode path's op-for-op
    rounding (``kv_dequantize``'s f32 contract, ``_cache_attend``'s
    stored-dtype dots with f32 accumulation, normalize-then-cast
    weights).  The oracle in tests and in ``chip_smoke.py``; never a
    silent substitute for the kernel."""
    S, Hkv, R, Dh = qg.shape
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
        vg = gather(v_pool)
    s = jnp.einsum("skrd,sktd->skrt", qg.astype(kg.dtype), kg,
                   preferred_element_type=jnp.float32) / np.sqrt(Dh)
    T = max_pages * ps
    vis = (jax.lax.broadcasted_iota(jnp.int32, (T,), 0)[None, :]
           < limit[:, None])                  # (S, T)
    s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    any_vis = (limit > 0)[:, None, None, None]
    p = jnp.exp(s - jnp.where(any_vis, m, 0.0))
    l = jnp.sum(p, axis=-1, keepdims=True)
    w = jnp.where(any_vis, p / l, 0.0)
    o = jnp.einsum("skrt,sktd->skrd", w.astype(vg.dtype), vg,
                   preferred_element_type=jnp.float32)
    lse = jnp.where(any_vis[..., 0], m[..., 0] + jnp.log(l[..., 0]),
                    NEG_INF)
    return o, lse


def paged_attend(qg, k_pool, v_pool, k_scale, v_scale, table, limit, *,
                 compute_dtype=None):
    """Fused decode attention directly against a paged KV pool.

    Args:
      qg: ``(S, H_kv, R, Dh)`` grouped queries — ``R = G`` (GQA group)
        for a one-token decode tick, ``R = G * W`` for a W-wide VERIFY
        window (rows ``g * W + j``).
      k_pool / v_pool: ONE layer's pool, ``(P, H_kv, page, Dh)`` in the
        stored dtype (f32 / bf16 / int8).
      k_scale / v_scale: ``(P, H_kv, page)`` f32 per-vector scales for
        int8 pools, else ``None``.
      table: ``(S, max_pages)`` int32 physical page ids (host data —
        any allocation pattern, one executable).
      limit: ``(S,)`` int32 — attend logical positions ``< limit[s]``
        (``pos + 1`` for decode-at-``pos``, ``pos`` for VERIFY over
        committed pages; ``0`` masks a slot entirely).
      compute_dtype: dtype int8 pages are dequantized TO (the model's
        ``cfg.dtype``); ignored for unquantized pools, which are dotted
        in their stored dtype per ``_cache_attend``.

    Returns:
      ``(o, lse)``: ``o`` ``(S, H_kv, R, Dh)`` f32 attention output
      (zeros for fully-masked rows), ``lse`` ``(S, H_kv, R)`` f32 per-
      row logsumexp of the masked scores (``NEG_INF`` when fully
      masked) for cross-source combining.
    """
    if compute_dtype is None:
        compute_dtype = k_pool.dtype
    return _pallas_paged_attend(qg, k_pool, v_pool, k_scale, v_scale,
                                table, limit, compute_dtype)
