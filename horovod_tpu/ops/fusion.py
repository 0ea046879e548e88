"""Tensor fusion: batch many small tensors into few large collectives.

Reference: the fusion buffer + response fusion
(``common/fusion_buffer_manager.cc:21-50``, ``Controller::FuseResponses``
``controller.cc:631-752``), with the 64 MB default threshold set at
``operations.cc:408`` and the atomic-unit rounding at
``controller.cc:349-367``.

TPU re-design: there is no persistent byte buffer or memcpy in/out.  Fusion
is a *functional transform*: leaves are grouped by dtype into buckets of at
most ``threshold`` bytes, each bucket is flattened and concatenated, ONE
collective runs per bucket, and results are split and reshaped back.  Under
``jit`` the concat and split are the compiler's to place; they are NOT
free: on four v5e chips the ``grad_allreduce`` scope (collectives, packing
and unpacking) is 34.35 ms of a 494 ms step at Mistral-7B's widths, 34.13 of
it exposed (``m7b-train-dp4``: ledger, PR 40).  What the static form saves
over the reference is the runtime machinery (negotiation, a persistent
buffer), not the bytes moved.  The bucket size is the main autotuning knob
(:mod:`horovod_tpu.autotune`).
"""

from __future__ import annotations

import os
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024  # bytes; operations.cc:408


def fusion_threshold_bytes() -> int:
    """Threshold resolution order: live autotuner (the tuned value, applied
    each sample window) → HOROVOD_FUSION_THRESHOLD env → 64 MB default.
    In-graph callers bucket with this value at TRACE time, so the tuned
    threshold affects steps built after tuning; the eager path consults it
    on every call."""
    from horovod_tpu import basics

    if basics.is_initialized():
        at = getattr(basics._ctx(), "autotuner", None)
        if at is not None:
            return int(at.fusion_threshold)
    v = os.environ.get("HOROVOD_FUSION_THRESHOLD")
    if v:
        return int(v)
    return DEFAULT_FUSION_THRESHOLD


def make_buckets(
    leaves: Sequence[Any], threshold: int
) -> List[List[int]]:
    """Greedy dtype-grouped bucketing; returns lists of leaf indices.

    Keeps submission order within a dtype group (the reference fuses
    responses in controller arrival order).
    """
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        a = jnp.asarray(leaf) if not hasattr(leaf, "dtype") else leaf
        by_dtype.setdefault(jnp.asarray(a).dtype if not hasattr(a, "dtype") else a.dtype, []).append(i)
    buckets: List[List[int]] = []
    for _, idxs in by_dtype.items():
        cur: List[int] = []
        cur_bytes = 0
        for i in idxs:
            a = leaves[i]
            nbytes = int(np.prod(np.asarray(a).shape if not hasattr(a, "shape") else a.shape) or 1) * jnp.asarray(a).dtype.itemsize if not hasattr(a, "nbytes") else int(a.nbytes)
            if cur and cur_bytes + nbytes > threshold:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            buckets.append(cur)
    return buckets


def _flatten_bucket(leaves: Sequence[Any]):
    flats = [jnp.ravel(jnp.asarray(l)) for l in leaves]
    return jnp.concatenate(flats) if len(flats) > 1 else flats[0]


def _split_bucket(buf, leaves: Sequence[Any]):
    out = []
    off = 0
    for l in leaves:
        a = jnp.asarray(l)
        n = int(np.prod(a.shape)) if a.ndim else 1
        out.append(jnp.reshape(buf[off : off + n], a.shape))
        off += n
    return out


def fused_allreduce_tree(tree, op=None, *, axis_name=None, threshold: int = None):
    """In-graph fused allreduce of a pytree: bucket → concat → one
    ``psum`` per bucket → split.  The JAX-transform equivalent of the
    reference's fusion buffer cycle
    (``MemcpyInFusionBuffer → ncclAllReduce → MemcpyOutFusionBuffer``,
    ``ops/nccl_operations.cc:122-156``)."""
    from horovod_tpu.ops import collectives as C

    op = op or C.Average
    threshold = threshold if threshold is not None else fusion_threshold_bytes()
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    buckets = make_buckets(leaves, threshold)
    out_leaves: List[Any] = [None] * len(leaves)
    for idxs in buckets:
        group = [leaves[i] for i in idxs]
        buf = _flatten_bucket(group)
        red = C.allreduce(buf, op, axis_name=axis_name)
        for i, piece in zip(idxs, _split_bucket(red, group)):
            out_leaves[i] = piece
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def fused_eager_allreduce(tensors: Sequence[Any], op=None) -> List[Any]:
    """Eager grouped allreduce through host-side buckets — the eager
    analogue of one fusion-buffer cycle."""
    from horovod_tpu.ops import collectives as C

    op = op or C.Average
    arrs = [np.asarray(t) for t in tensors]
    if not arrs:
        return []
    threshold = fusion_threshold_bytes()
    buckets = make_buckets(arrs, threshold)
    out: List[Any] = [None] * len(arrs)
    for idxs in buckets:
        group = [arrs[i] for i in idxs]
        flat = np.concatenate([a.ravel() for a in group]) if len(group) > 1 else group[0].ravel()
        red = C._eager_allreduce(flat, op, None, None)
        off = 0
        for i in idxs:
            n = arrs[i].size
            out[i] = red[off : off + n].reshape(arrs[i].shape)
            off += n
    return out
