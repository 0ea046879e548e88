"""Attention kernels: Pallas flash attention + ring attention (sequence
parallelism over the mesh).

The reference framework has no attention ops at all — sequence length is
invisible to it (SURVEY.md §5.7: tensors are opaque byte buffers, and the
op set is allreduce/allgather/broadcast/join).  These are the TPU-native
extensions the rebuild is required to treat as first-class: long-context
attention as a fused-VMEM Pallas kernel, and context parallelism as
``lax.ppermute`` rotations of K/V shards over the ICI ring — the
collective pattern the reference could only have expressed as NCCL
point-to-points.

Layout convention: ``(batch, heads, seq, head_dim)`` f32/bf16.

* :func:`flash_attention` — online-softmax tiled attention, one Pallas
  kernel; O(block) VMEM, saves the logsumexp for the backward.  The
  backward is a pair of fused Pallas kernels (dk/dv with Q innermost,
  dq with K innermost) computing the analytic flash gradients from the
  saved LSE — no (S, block) score materialization in HBM; untileable
  shapes fall back to the same math expressed blockwise in XLA.
  :func:`flash_attention_with_lse` additionally exposes the LSE as a
  differentiable output (dlse folds in as ``delta -= dlse``).
* :func:`flash_attention_shifted` — the same kernels with the mask as a
  RUNTIME scalar: allowed iff ``col + shift <= row``, ``shift`` an int32
  operand staged into SMEM.  ``shift = 0`` is ordinary causal,
  ``shift <= -T`` is unmasked, ``shift >= S`` masks everything (the
  kernel then yields o=0, lse=-inf, which vanishes in a logsumexp
  merge).  This is what lets ring attention call ONE kernel per chunk
  instead of dispatching through ``lax.switch`` (whose pallas-in-switch-
  in-scan nesting trips a jax lowering-cache bug, see ``ring_attention``).
* :func:`ring_attention` — each device holds a contiguous sequence shard;
  K/V shards rotate around the ring with ``lax.ppermute`` while the local
  Q accumulates partial attention, merged by logsumexp weighting.  Each
  chunk runs the Pallas flash kernel with ``shift = (src - me) * S_kv``:
  earlier shards come out fully attended, the diagonal shard causally,
  later shards fully masked — one code path, no per-kind dispatch.
* :func:`ulysses_attention` — the all-to-all flavor of sequence
  parallelism (DeepSpeed-Ulysses pattern): one ``lax.all_to_all``
  reshards from sequence-sharded to head-sharded, every device computes
  FULL-sequence attention for its head subset (so the flash kernel and
  plain causal masking apply unchanged), and a second all-to-all reshards
  back.  Two collectives per attention instead of P ppermute rounds —
  cheaper when heads divide evenly over the axis and the ICI all-to-all
  bandwidth is good; ring wins when S_local is huge and overlap matters.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

NEG_INF = -1e30  # finite mask value: exp(NEG_INF - anything_real) == 0


def _sm_scale(q, sm_scale):
    return 1.0 / np.sqrt(q.shape[-1]) if sm_scale is None else sm_scale


def expand_kv(kv, n_heads: int):
    """Grouped-query attention: repeat K/V heads up to ``n_heads``.

    The kernels are MHA; GQA expands at the call site with
    ``jnp.repeat`` — whose VJP is exactly the per-group sum, so
    gradients w.r.t. the shared KV heads are exact under autodiff.  The
    bandwidth win is preserved where it matters: ring attention rotates
    the UNEXPANDED (B, H_kv, S, D) shards around the ICI ring and
    expands per chunk, so ppermute traffic shrinks by H/H_kv."""
    H_kv = kv.shape[1]
    if H_kv == n_heads:
        return kv
    if n_heads % H_kv != 0:
        raise ValueError(
            f"n_heads ({n_heads}) must be a multiple of kv heads ({H_kv})")
    return jnp.repeat(kv, n_heads // H_kv, axis=1)


def _float0_like(x):
    """Cotangent for an integer-dtype primal (custom_vjp convention)."""
    return np.zeros(np.shape(x), dtype=jax.dtypes.float0)


# --- reference (oracle) -------------------------------------------------------


def _reference_attention_lse(q, k, v, shift, scale, window: int = 0):
    """One O(S^2) score computation -> (output, logsumexp).

    ``shift``: None for unmasked, else a (traced or static) int scalar —
    position (row, col) is attended iff ``col + shift <= row``.  shift=0
    is standard causal.  ``window`` > 0 also asks ``row - col <
    window``."""
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if shift is not None:
        S, T = scores.shape[-2], scores.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (S, T), 0)
        cols = lax.broadcasted_iota(jnp.int32, (S, T), 1)
        vis = cols + shift <= rows
        if window:
            vis &= rows - cols < window
        scores = jnp.where(vis, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # fully-masked rows: stay finite
    p = jnp.where(scores > NEG_INF * 0.5, jnp.exp(scores - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l > 0, l, 1.0)
    o = jnp.einsum("bhst,bhtd->bhsd", (p / l_safe).astype(v.dtype), v)
    lse = jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(l_safe[..., 0]),
                    NEG_INF)
    return o, lse


def reference_attention(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None, window: int = 0):
    """O(S^2)-memory oracle used by tests and as the small-shape fallback
    (``window`` > 0 with ``causal``: a sliding-window layer)."""
    o, _ = _reference_attention_lse(q, k, v, 0 if causal else None,
                                    _sm_scale(q, sm_scale), window)
    return o


# --- Pallas forward kernel ----------------------------------------------------


def _flash_fwd_kernel(shift_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                      acc_ref, m_ref, l_ref,
                      *, block_q: int, block_k: int, masked: bool,
                      scale: float, num_k: int, window: int = 0):
    """Grid: (batch*heads, num_q_blocks, num_k_blocks); K innermost, so the
    (acc, m, l) scratch carries the online softmax across K steps.

    ``shift_ref`` is a (1,) int32 in SMEM: position (row, col) attends iff
    ``col + shift <= row`` (only read when ``masked``).  ``window`` > 0
    (static) also asks ``row - col < window``: K blocks wholly behind
    the window of the block's first row are skipped like those above
    the diagonal."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # K blocks entirely above the shifted diagonal contribute nothing.
    run = True
    if masked:
        run = ik * block_k + shift_ref[0] <= iq * block_q + block_q - 1
        if window:
            run &= iq * block_q - (ik * block_k + block_k - 1) < window

    @pl.when(run)
    def _step():
        # Keep inputs in their native dtype (bf16 rides the MXU at full
        # rate) and accumulate in f32 via preferred_element_type.
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (block_q, block_k)
        if masked:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            vis = cols + shift_ref[0] <= rows
            if window:
                vis &= rows - cols < window
            s = jnp.where(vis, s, NEG_INF)
        m_prev = m_ref[:, :1]                               # (block_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)          # (block_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        if masked:
            # Rows fully masked so far have m_new == NEG_INF; exp(s-m_new)
            # would be exp(0)=1 garbage — zero those lanes explicitly.
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - m_new), 0.0)
        else:
            p = jnp.exp(s - m_new)                          # (block_q, block_k)
        alpha = jnp.exp(m_prev - m_new)                     # (block_q, 1)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        # P·V in the value dtype (bf16 MXU) with f32 accumulation; exact
        # for f32 inputs, standard flash practice for bf16.
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == num_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # LSE layout (BH, 8, S): 8 replicated sublanes satisfy the TPU
        # (÷8, ÷128) tile constraint; caller reads sublane 0.  Fully
        # masked rows (l == 0) report -inf so they vanish in merges.
        lse = jnp.where(l[:, 0] > 0, m_ref[:, 0] + jnp.log(l_safe[:, 0]),
                        NEG_INF)  # (block_q,)
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


# Shared Pallas plumbing (ops/_pallas_util.py): the interpret rule,
# vma-inheriting out shapes, and the SMEM scalar spec are shared with
# the fused paged-attention decode kernel (ops/paged_attention.py) so
# the conventions cannot fork.
from horovod_tpu.ops._pallas_util import (  # noqa: E402
    out_sds as _out_sds,
    pl,
    pltpu,
    scalar_operand as _shift_operand,
    smem_spec as _smem_spec,
    use_interpret as _use_interpret,
)


def _tileable(S: int, T: int, D: int, block_q: int, block_k: int) -> bool:
    """THE shape rule of the flash kernels (forward and backward share
    it): whole blocks along both sequence dims and a head dim in whole
    sublanes.  Anything else takes the same math in XLA."""
    return S % block_q == 0 and T % block_k == 0 and D % 8 == 0


def _flash_fwd(q, k, v, shift, sm_scale, block_q: int, block_k: int,
               window: int = 0):
    """shift: None (no mask) or int scalar (traced ok) — shifted causal;
    window: 0, or the static span a row may look back over."""
    B, H, S, D = q.shape
    T, Dv = k.shape[2], v.shape[3]   # a value may be narrower than a key
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    scale = _sm_scale(q, sm_scale)
    if not (_tileable(S, T, D, block_q, block_k) and Dv % 8 == 0):
        return _reference_attention_lse(q, k, v, shift, scale, window)
    nq, nk = S // block_q, T // block_k
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k,
        masked=shift is not None, scale=scale, num_k=nk, window=window)
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, Dv)
    o, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            _smem_spec(),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _out_sds((B * H, S, Dv), q.dtype, q),
            _out_sds((B * H, 8, S), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=_use_interpret(),
        name="hvd_flash_fwd",
    )(_shift_operand(shift, q), qr, kr, vr)
    return o.reshape(B, H, S, Dv), lse[:, 0, :].reshape(B, H, S)


def _flash_bwd_dkdv_kernel(shift_ref, q_ref, do_ref, lse_ref, delta_ref,
                           k_ref, v_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                           *, block_q: int, block_k: int, masked: bool,
                           scale: float, num_q: int):
    """Grid: (BH, num_k_blocks, num_q_blocks); Q innermost so the dk/dv
    scratch accumulates across Q steps for one K block."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if masked:  # Q blocks entirely above the shifted diagonal: nothing
        run = iq * block_q + block_q - 1 >= ik * block_k + shift_ref[0]

    @pl.when(run)
    def _step():
        q = q_ref[0]                      # (block_q, d) native dtype
        do = do_ref[0]                    # (block_q, d)
        k = k_ref[0]                      # (block_k, d)
        v = v_ref[0]
        lse = lse_ref[0, 0, :]            # (block_q,) f32
        delta = delta_ref[0, 0, :]        # (block_q,) f32
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols + shift_ref[0] <= rows, s, NEG_INF)
            # exp(NEG_INF - NEG_INF) == 1 for rows whose lse is -inf
            # (fully masked): their cotangents are exactly zero, but keep
            # p finite-clean anyway.
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse[:, None]), 0.0)
        else:
            p = jnp.exp(s - lse[:, None])  # (block_q, block_k) f32
        # dv_j += p^T do_i
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do v^T ; ds = p * (dp - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        # dk_j += ds^T q_i
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(shift_ref, q_ref, do_ref, lse_ref, delta_ref,
                         k_ref, v_ref, dq_ref, dq_acc,
                         *, block_q: int, block_k: int, masked: bool,
                         scale: float, num_k: int):
    """Grid: (BH, num_q_blocks, num_k_blocks); K innermost, dq scratch
    accumulates across K steps for one Q block."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = True
    if masked:
        run = ik * block_k + shift_ref[0] <= iq * block_q + block_q - 1

    @pl.when(run)
    def _step():
        q = q_ref[0]
        do = do_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = iq * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols + shift_ref[0] <= rows, s, NEG_INF)
            p = jnp.where(s > NEG_INF * 0.5, jnp.exp(s - lse[:, None]), 0.0)
        else:
            p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_pallas(shift, scale, block_q, block_k, q, k, v, o, lse, do,
                      dlse=None):
    """Fused Pallas backward: two tiled kernels (dk/dv then dq), O(block)
    VMEM, no (S, block_k) f32 materialization in HBM.

    ``dlse``: optional cotangent of the LSE output (when the caller
    differentiates through the logsumexp too, e.g. ring attention's
    merge).  ∂lse_i/∂s_ij = p_ij, so it folds into the kernels as
    ``delta_i -= dlse_i`` — the same place the o-path's rowsum(do·o)
    enters."""
    B, H, S, D = q.shape
    T = k.shape[2]
    nq, nk = S // block_q, T // block_k
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, T, D)
    vr = v.reshape(B * H, T, D)
    dor = do.reshape(B * H, S, D).astype(q.dtype)
    # delta_i = rowsum(do * o); same (BH, 8, S) sublane-replicated layout
    # as the forward's LSE output.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(
        delta.reshape(B * H, 1, S), (B * H, 8, S)).astype(jnp.float32)
    lse_t = jnp.broadcast_to(
        lse.reshape(B * H, 1, S), (B * H, 8, S)).astype(jnp.float32)

    q_spec_by_q = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    q_spec_by_k = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    k_spec_by_q = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    k_spec_by_k = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    row_by_q = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))
    row_by_k = pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i))

    masked = shift is not None
    sh = _shift_operand(shift, q)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, block_q=block_q,
                          block_k=block_k, masked=masked, scale=scale,
                          num_q=nq),
        grid=(B * H, nk, nq),
        in_specs=[_smem_spec(), q_spec_by_k, q_spec_by_k, row_by_k, row_by_k,
                  k_spec_by_k, k_spec_by_k],
        out_specs=[k_spec_by_k, k_spec_by_k],
        out_shape=[_out_sds((B * H, T, D), k.dtype, q),
                   _out_sds((B * H, T, D), v.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=_use_interpret(),
        name="hvd_flash_bwd_dkv",
    )(sh, qr, dor, lse_t, delta, kr, vr)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_q=block_q,
                          block_k=block_k, masked=masked, scale=scale,
                          num_k=nk),
        grid=(B * H, nq, nk),
        in_specs=[_smem_spec(), q_spec_by_q, q_spec_by_q, row_by_q, row_by_q,
                  k_spec_by_q, k_spec_by_q],
        out_specs=q_spec_by_q,
        out_shape=_out_sds((B * H, S, D), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=_use_interpret(),
        name="hvd_flash_bwd_dq",
    )(sh, qr, dor, lse_t, delta, kr, vr)

    return (dq.reshape(B, H, S, D), dk.reshape(B, H, T, D),
            dv.reshape(B, H, T, D))


def _flash_bwd(shift, sm_scale, block_q, block_k, res, do, dlse=None):
    """Flash backward from the saved LSE.

    Tileable shapes run the fused Pallas kernels (above): O(block) VMEM,
    no (S, block) f32 score materialization in HBM.  Untileable shapes
    fall back to the analytic XLA form scanned over K blocks:

        p_ij = exp(q_i k_j^T * scale - lse_i)
        dv_j = p^T do ;  dp = do v^T ;  ds = p * (dp - rowsum(do * o))
        dq_i += ds k_j * scale ;  dk_j = ds^T q_i * scale

    ``dlse`` (cotangent of the LSE output) folds in as delta -= dlse.
    ``shift``: None for unmasked, else the shifted-causal int scalar.
    """
    q, k, v, o, lse = res
    B, H, S, D = q.shape
    T = k.shape[2]
    if v.shape[3] != D:
        raise NotImplementedError(
            f"the flash backward is written for one head size; q/k are "
            f"{D} wide and v {v.shape[3]} (latent attention serves only)")
    scale = _sm_scale(q, sm_scale)
    bq = min(block_q, S)
    bk = min(block_k, T)
    if _tileable(S, T, D, bq, bk):
        return _flash_bwd_pallas(shift, scale, bq, bk, q, k, v, o, lse, do,
                                 dlse=dlse)
    if T % bk:  # analytic fallback: widen to one K block
        bk = T
    nk = T // bk

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (B,H,S)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    rows = lax.broadcasted_iota(jnp.int32, (S, bk), 0)

    def kblock(carry, jb):
        dq = carry
        ks = lax.dynamic_slice_in_dim(k, jb * bk, bk, axis=2).astype(jnp.float32)
        vs = lax.dynamic_slice_in_dim(v, jb * bk, bk, axis=2).astype(jnp.float32)
        s = jnp.einsum("bhsd,bhtd->bhst", qf, ks) * scale  # (B,H,S,bk)
        if shift is not None:
            cols = jb * bk + lax.broadcasted_iota(jnp.int32, (S, bk), 1)
            s = jnp.where(cols + shift <= rows, s, NEG_INF)
            p = jnp.where(s > NEG_INF * 0.5,
                          jnp.exp(s - lse[..., None]), 0.0)
        else:
            p = jnp.exp(s - lse[..., None])                 # (B,H,S,bk)
        dv = jnp.einsum("bhst,bhsd->bhtd", p, dof)
        dp = jnp.einsum("bhsd,bhtd->bhst", dof, vs)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhst,bhtd->bhsd", ds, ks)
        dk = jnp.einsum("bhst,bhsd->bhtd", ds, qf)
        return dq, (dk, dv)

    dq0 = jnp.zeros_like(qf)
    dq, (dks, dvs) = lax.scan(kblock, dq0, jnp.arange(nk))
    dk = jnp.moveaxis(dks, 0, 2).reshape(B, H, T, D)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(B, H, T, D)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 512):
    """Fused tiled attention.  ``(B, H, S, D) x (B, H, T, D) -> (B, H, S, D)``.

    Forward runs as one Pallas TPU kernel (online softmax, O(block) VMEM);
    on CPU it runs the same kernel under the Pallas interpreter.  Shapes
    that can't tile (S % block, D % 8) silently use the XLA reference.
    """
    o, _ = _flash_fwd(q, k, v, 0 if causal else None, sm_scale,
                      block_q, block_k)
    return o


# The names a checkpoint policy keeps the forward kernel's two results
# under (models/transformer.py _remat, "dots"): saved, the backward pass
# reads them; not saved, it runs the whole kernel again to get them back.
FLASH_OUT_NAME = "hvd_flash_out"
FLASH_LSE_NAME = "hvd_flash_lse"


def _flash_fwd_saved(q, k, v, shift, sm_scale, block_q, block_k):
    """:func:`_flash_fwd` for the forward RULES below: ``(o, lse)`` named,
    so the arrays a rule returns AND keeps as residuals are ones a
    ``save_only_these_names`` policy can hold.  Outside ``jax.checkpoint``
    the names do nothing; the primal bodies (serving) never get here."""
    o, lse = _flash_fwd(q, k, v, shift, sm_scale, block_q, block_k)
    return (checkpoint_name(o, FLASH_OUT_NAME),
            checkpoint_name(lse, FLASH_LSE_NAME))


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    o, lse = _flash_fwd_saved(q, k, v, 0 if causal else None, sm_scale,
                              block_q, block_k)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, res, do):
    return _flash_bwd(0 if causal else None, sm_scale, block_q, block_k,
                      res, do)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_windowed(q, k, v, window: int,
                             sm_scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 512):
    """Causal :func:`flash_attention` in which row ``i`` sees column
    ``j`` iff ``j <= i`` and ``i - j < window`` — a sliding-window
    layer's prefill.  Forward only (serving): the kernel carries no
    gradient rule for the window."""
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    o, _ = _flash_fwd(q, k, v, 0, sm_scale, block_q, block_k, window)
    return o


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal: bool = False,
                             sm_scale: Optional[float] = None,
                             block_q: int = 1024, block_k: int = 512):
    """:func:`flash_attention` that also returns the per-row logsumexp as
    a DIFFERENTIABLE output ``(o, lse)`` — the building block for merge-
    based compositions (ring attention) whose gradients flow through the
    lse weights; the backward folds the lse cotangent in as
    ``delta -= dlse``."""
    return _flash_fwd(q, k, v, 0 if causal else None, sm_scale,
                      block_q, block_k)


def _fal_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    o, lse = _flash_fwd_saved(q, k, v, 0 if causal else None, sm_scale,
                              block_q, block_k)
    return (o, lse), (q, k, v, o, lse)


def _fal_bwd(causal, sm_scale, block_q, block_k, res, ct):
    do, dlse = ct
    return _flash_bwd(0 if causal else None, sm_scale, block_q, block_k,
                      res, do, dlse=dlse)


flash_attention_with_lse.defvjp(_fal_fwd, _fal_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_shifted(q, k, v, shift,
                            sm_scale: Optional[float] = None,
                            block_q: int = 1024, block_k: int = 512):
    """Flash attention with a RUNTIME shifted-causal mask -> ``(o, lse)``.

    ``shift`` is an int32 scalar (traced values welcome): position
    (row, col) attends iff ``col + shift <= row``.  shift=0 is ordinary
    causal; shift <= -T allows everything; shift >= S masks everything
    and yields o=0, lse=NEG_INF (a no-op under logsumexp merging).  The
    scalar rides to the kernel through SMEM, so ONE compiled kernel
    serves every chunk kind of ring attention — full, diagonal, and dead
    — with no ``lax.switch`` wrapper (pallas-in-switch-in-scan trips a
    jax lowering-cache bug; a data-dependent mask sidesteps it).  Both
    outputs are differentiable; dlse folds in as ``delta -= dlse``.
    """
    return _flash_fwd(q, k, v, shift, sm_scale, block_q, block_k)


def _fas_fwd(q, k, v, shift, sm_scale, block_q, block_k):
    o, lse = _flash_fwd_saved(q, k, v, shift, sm_scale, block_q, block_k)
    return (o, lse), (q, k, v, o, lse, shift)


def _fas_bwd(sm_scale, block_q, block_k, res, ct):
    q, k, v, o, lse, shift = res
    do, dlse = ct
    dq, dk, dv = _flash_bwd(shift, sm_scale, block_q, block_k,
                            (q, k, v, o, lse), do, dlse=dlse)
    return dq, dk, dv, _float0_like(shift)


flash_attention_shifted.defvjp(_fas_fwd, _fas_bwd)


# --- chunk attention with LSE (building block for ring) -----------------------


def _chunk_attn(q, k, v, mask, scale):
    """Attention of local q over one K/V chunk with an additive bool mask
    (True = allowed); returns per-chunk normalized output + LSE."""
    s = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)  # fully-masked rows stay at NEG_INF
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l > 0, l, 1.0)
    o = jnp.einsum("bhst,bhtd->bhsd", p / l_safe, v.astype(jnp.float32))
    lse = (m + jnp.log(l_safe))[..., 0]  # (B,H,S)
    return o, lse


def ring_attention(q, k, v, *, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   impl: str = "flash",
                   block_q: int = 1024, block_k: int = 512):
    """Sequence-parallel attention inside ``shard_map``: every device holds
    a contiguous sequence shard of q/k/v ``(B, H, S_local, D)``; K/V rotate
    around the mesh-axis ring via ``lax.ppermute`` (ICI neighbor exchange)
    while partial attention accumulates with logsumexp merging.

    Each chunk is computed by the Pallas flash kernel
    (:func:`flash_attention_shifted`) with ``shift = (src - me) * S_kv``:
    the globally-causal mask restricted to the (me, src) shard pair IS a
    shifted-causal mask, so earlier shards come out fully attended, the
    diagonal shard causally, and later shards fully masked (o=0,
    lse=-inf, which the merge annihilates) — one kernel call per step,
    no ``lax.switch`` chunk dispatch (whose pallas-in-switch-in-scan
    nesting trips a jax lowering-cache bug, the r2 blocker).  Dead-chunk
    blocks are still skipped inside the kernel: the ``pl.when`` grid
    predicate compares against the runtime shift.

    ``impl="reference"`` keeps the masked-XLA chunk path (used by tests
    as a second oracle and by shapes that can't tile — though the flash
    path falls back internally too).  Differentiable end-to-end; the VJP
    rides the transposed ``ppermute``s back around the ring.

    GQA: pass k/v with ``H_kv < H`` heads (``H % H_kv == 0``) — the ring
    rotates the small shards (ICI traffic ÷ H/H_kv) and each chunk
    expands to full heads before the kernel.
    """
    P = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    scale = _sm_scale(q, sm_scale)
    B, H, S, D = q.shape
    T = k.shape[2]
    perm = [(i, (i + 1) % P) for i in range(P)]
    use_flash = impl == "flash"

    def step(carry, s_idx):
        o, lse, ks_kv, vs_kv = carry
        src = (me - s_idx) % P  # which shard's K/V we hold this step
        last = s_idx == P - 1
        # GQA: the carry rotates the small (B, H_kv, T, D) shards; the
        # chunk compute expands to full heads (jnp.repeat — VJP is the
        # group-sum, so the transposed ring carries exact KV grads).
        ks = expand_kv(ks_kv, H)
        vs = expand_kv(vs_kv, H)
        if use_flash:
            if causal:
                shift = ((src - me) * T).astype(jnp.int32)
                o_c, lse_c = flash_attention_shifted(
                    q, ks, vs, shift, scale, block_q, block_k)
            else:
                o_c, lse_c = flash_attention_with_lse(
                    q, ks, vs, False, scale, block_q, block_k)
            o_c = o_c.astype(jnp.float32)
            lse_c = lse_c.astype(jnp.float32)
        elif causal:
            shift = (src - me) * T
            rows = lax.broadcasted_iota(jnp.int32, (S, T), 0)
            cols = lax.broadcasted_iota(jnp.int32, (S, T), 1)
            o_c, lse_c = _chunk_attn(
                q, ks, vs, (cols + shift <= rows)[None, None], scale)
        else:
            o_c, lse_c = _chunk_attn(q, ks, vs, None, scale)
        lse_new = jnp.logaddexp(lse, lse_c)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_c * jnp.exp(lse_c - lse_new)[..., None])
        if not last:  # the final rotation's result is never read
            ks_kv = lax.ppermute(ks_kv, axis_name, perm)
            vs_kv = lax.ppermute(vs_kv, axis_name, perm)
        return o, lse_new, ks_kv, vs_kv

    # Derive the initial carry from q so it inherits q's varying-over-axis
    # type under shard_map (a plain literal would mismatch the carry-out).
    o0 = jnp.zeros_like(q, jnp.float32) * 0.0
    lse0 = q[..., 0].astype(jnp.float32) * 0.0 + NEG_INF
    # The ring loop is UNROLLED (P is the static mesh-axis size): each
    # step is one kernel call + a ppermute, so XLA can overlap step i's
    # neighbor exchange with step i-1's compute — a lax.scan would
    # serialize them behind the carry.  Unrolling also keeps the Pallas
    # call out of scan-in-scan nesting, which the interpret-mode
    # lowering used on CPU can't cache correctly (KeyError: closed_call).
    carry = (o0, lse0, k, v)
    for s_idx in range(P):
        carry = step(carry, s_idx)
    o = carry[0]
    return o.astype(q.dtype)


def zigzag_perm(S: int, P: int):
    """Column permutation mapping a CONTIGUOUS global sequence to the
    zigzag layout: device i holds global chunks ``(i, 2P-1-i)`` of size
    ``S/(2P)`` — pairing an early and a late chunk so every device owns
    the same amount of causal work.  Returns (perm, inv): permute data
    columns by ``perm`` before sharding contiguously over the axis;
    ``inv`` restores original order."""
    if S % (2 * P):
        raise ValueError(f"sequence {S} must divide into 2*{P} chunks")
    Sc = S // (2 * P)
    idx = np.arange(S).reshape(2 * P, Sc)
    perm = np.concatenate(
        [np.concatenate([idx[i], idx[2 * P - 1 - i]]) for i in range(P)])
    inv = np.argsort(perm)
    return perm, inv


def zigzag_positions(S_local: int, axis_name: str):
    """Global position ids for this device's zigzag rows (feed to RoPE):
    ``[me*Sc + 0..Sc-1, (2P-1-me)*Sc + 0..Sc-1]``."""
    P = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    Sc = S_local // 2
    ar = jnp.arange(Sc, dtype=jnp.float32)
    return jnp.concatenate([me * Sc + ar, (2 * P - 1 - me) * Sc + ar])


def zigzag_ring_attention(q, k, v, *, axis_name: str,
                          sm_scale: Optional[float] = None,
                          impl: str = "flash",
                          block_q: int = 1024, block_k: int = 512):
    """CAUSAL ring attention with the ZIGZAG chunk layout — the causal
    load-balance fix for sequence parallelism.

    Plain ring + causal is imbalanced: device i's rows attend i+1 of the
    P shard-pairs, so early devices idle while the last device computes
    every step — the lockstep ring pays the max every rotation.  Zigzag
    pairs chunk ``i`` with chunk ``2P-1-i`` on device i (q/k/v rows in
    zigzag layout — :func:`zigzag_perm`; RoPE positions from
    :func:`zigzag_positions`), which makes the alive work EXACTLY half
    the block pairs on every device at every step:

      step with kv from src = chunks (src, 2P-1-src); my q = (me, 2P-1-me)
        q_early × k_early : alive iff src <= me   (shift-causal kernel)
        q_early × k_late  : ALWAYS dead           (never issued)
        q_late  × k_early : always fully alive
        q_late  × k_late  : alive iff src >= me   (shift-causal kernel)

    Exactly 2 of 4 quarter-blocks compute per device per step — ~2×
    the causal ring's steady-state throughput at large P.  Dead blocks
    in the two conditional calls are skipped inside the shifted flash
    kernel (the ``pl.when`` grid predicate against the runtime shift).
    ``impl="reference"`` uses one masked-XLA chunk attention over the
    exact global-position causal mask (the oracle).  Differentiable
    end-to-end (the VJP rides the transposed ppermutes); GQA supported
    like :func:`ring_attention`.
    """
    P = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    scale = _sm_scale(q, sm_scale)
    B, H, S, D = q.shape
    if S % 2:
        raise ValueError("zigzag shard length must be even (two chunks)")
    Sc = S // 2
    perm = [(i, (i + 1) % P) for i in range(P)]
    use_flash = impl == "flash"

    qa, qb = q[:, :, :Sc], q[:, :, Sc:]

    # Global chunk ids of my q rows.
    my_a = me           # early chunk
    my_b = 2 * P - 1 - me  # late chunk

    def merge(o, lse, o_c, lse_c):
        lse_new = jnp.logaddexp(lse, lse_c)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_c * jnp.exp(lse_c - lse_new)[..., None])
        return o, lse_new

    def block(qx, my_chunk, ks, vs, src_chunk):
        """(o, lse) of one q-half over one kv-half chunk, with the
        global-causal relation expressed as a shifted-causal mask."""
        if use_flash:
            shift = ((src_chunk - my_chunk) * Sc).astype(jnp.int32)
            o_c, lse_c = flash_attention_shifted(
                qx, ks, vs, shift, scale, block_q, block_k)
            return o_c.astype(jnp.float32), lse_c.astype(jnp.float32)
        shift = (src_chunk - my_chunk) * Sc
        rows = lax.broadcasted_iota(jnp.int32, (Sc, Sc), 0)
        cols = lax.broadcasted_iota(jnp.int32, (Sc, Sc), 1)
        return _chunk_attn(qx, ks, vs,
                           (cols + shift <= rows)[None, None], scale)

    def step(carry, s_idx):
        oa, lsea, ob, lseb, ks_kv, vs_kv = carry
        src = (me - s_idx) % P
        last = s_idx == P - 1
        ks = expand_kv(ks_kv, H)
        vs = expand_kv(vs_kv, H)
        ka, va = ks[:, :, :Sc], vs[:, :, :Sc]   # src's early chunk
        kb, vb = ks[:, :, Sc:], vs[:, :, Sc:]   # src's late chunk
        src_a = src
        src_b = 2 * P - 1 - src
        # q_early x k_early (alive iff src <= me; dead blocks kernel-skip)
        o_c, l_c = block(qa, my_a, ka, va, src_a)
        oa, lsea = merge(oa, lsea, o_c, l_c)
        # q_late x k_early (always fully alive)
        o_c, l_c = block(qb, my_b, ka, va, src_a)
        ob, lseb = merge(ob, lseb, o_c, l_c)
        # q_late x k_late (alive iff src >= me)
        o_c, l_c = block(qb, my_b, kb, vb, src_b)
        ob, lseb = merge(ob, lseb, o_c, l_c)
        # q_early x k_late: provably dead for every (me, src) — not issued.
        if not last:
            ks_kv = lax.ppermute(ks_kv, axis_name, perm)
            vs_kv = lax.ppermute(vs_kv, axis_name, perm)
        return oa, lsea, ob, lseb, ks_kv, vs_kv

    def zeros_like_half(qx):
        o0 = jnp.zeros_like(qx, jnp.float32) * 0.0
        lse0 = qx[..., 0].astype(jnp.float32) * 0.0 + NEG_INF
        return o0, lse0

    oa, lsea = zeros_like_half(qa)
    ob, lseb = zeros_like_half(qb)
    carry = (oa, lsea, ob, lseb, k, v)
    for s_idx in range(P):  # unrolled like ring_attention (see note there)
        carry = step(carry, s_idx)
    out = jnp.concatenate([carry[0], carry[2]], axis=2)
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str, causal: bool = False,
                      sm_scale: Optional[float] = None,
                      impl: str = "flash"):
    """All-to-all sequence parallelism inside ``shard_map`` (the
    DeepSpeed-Ulysses pattern; SURVEY.md §5.7 lists it as the alltoall
    resharding flavor of context parallelism).

    Every device holds a sequence shard ``(B, H, S_local, D)``.  One
    ``lax.all_to_all`` redistributes to ``(B, H/P, S_global, D)`` — full
    sequence, head subset — so local attention (including the Pallas
    flash kernel via the default ``impl="flash"``, and ordinary causal
    masking) runs unchanged; the inverse all_to_all restores sequence
    sharding.  Requires ``H %% axis_size == 0``.  Differentiable
    end-to-end: the VJP of ``all_to_all`` is the transposed all_to_all.
    """
    P = lax.axis_size(axis_name)
    B, H, S, D = q.shape
    if H % P != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"'{axis_name}' axis size ({P}); use ring_attention otherwise")

    def seq_to_heads(x):  # (B,h,S_local,D) -> (B,h/P,S_global,D)
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    # GQA: reshard K/V at their small head count when it still divides
    # the axis (all_to_all moves H_kv/P heads per link), expanding to
    # full heads only after the reshard; otherwise expand first.
    if k.shape[1] % P == 0:
        kh = expand_kv(seq_to_heads(k), H // P)
        vh = expand_kv(seq_to_heads(v), H // P)
    else:
        kh = seq_to_heads(expand_kv(k, H))
        vh = seq_to_heads(expand_kv(v, H))
    qh = seq_to_heads(q)
    if impl == "flash":
        oh = flash_attention(qh, kh, vh, causal, sm_scale=sm_scale)
    else:
        oh = reference_attention(qh, kh, vh, causal=causal,
                                 sm_scale=sm_scale)
    # (B,H/P,S_global,D) -> (B,H,S_local,D)
    return lax.all_to_all(oh, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)
