"""Tensor fusion: batch many small tensors into few large collectives.

Reference: the fusion buffer + response fusion
(``common/fusion_buffer_manager.cc:21-50``, ``Controller::FuseResponses``
``controller.cc:631-752``), with the 64 MB default threshold set at
``operations.cc:408`` and the atomic-unit rounding at
``controller.cc:349-367``.

TPU re-design: there is no persistent byte buffer or memcpy in/out.  Fusion
is a *functional transform*: leaves are grouped by dtype into buckets of at
most ``threshold`` bytes, and ONE collective runs per bucket.  A bucket of
several leaves is PACKED: each leaf raveled, the lot concatenated, reduced,
split and reshaped back.  A leaf alone in its bucket — one the threshold
leaves alone, or the only one of its dtype — is reduced in its own shape.

The packing is NOT free, and under ``jit`` it does not "disappear": on a
TPU an array lies in tiles (a ``(4096, 14336)`` float32 leaf as
``T(8,128)``, the flat array as ``T(1024)``), so a ravel is a relayout
``copy`` of the whole leaf and the reshape back is another.  Read out of
the four-chip step compiled for the v5e at Mistral-7B's widths (ISSUE 43;
``tests/test_tpu_aot.py``): while a big leaf was raveled too, each weight
gradient was copied to the flat form in front of its ``all-reduce``, AdamW
ran in two passes (the moments' increments on the flat array, then
parameter, ``mu`` and ``nu`` in the leaf's shape) with two ``reshape``s a
leaf between them: 33.3 GB moved a step around the update where one chip
moves 13.6.  With the leaf kept in its shape there is no copy, no reshape
and one pass.  A packed bucket still pays its packing (there ``wv`` with
the final norm, and the layer's two norms: 17 MB), which is what tensor
fusion buys its fewer collectives with.  What the static form
saves over the reference is the runtime machinery (negotiation, a
persistent buffer), not the bytes moved.  The bucket size is the main
autotuning knob (:mod:`horovod_tpu.autotune`).
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
    """Several leaves packed into one flat array.  A leaf alone stays as
    it lies: raveling a tiled array is a relayout on a TPU (module
    docstring)."""
    if len(leaves) == 1:
        return jnp.asarray(leaves[0])
    return jnp.concatenate([jnp.ravel(jnp.asarray(l)) for l in leaves])


def _split_bucket(buf, leaves: Sequence[Any]):
    if len(leaves) == 1:
        return [buf]
    out = []
    off = 0
    for l in leaves:
        a = jnp.asarray(l)
        n = int(np.prod(a.shape)) if a.ndim else 1
        out.append(jnp.reshape(buf[off : off + n], a.shape))
        off += n
    return out


def fused_allreduce_tree(tree, op=None, *, axis_name=None, threshold: int = None):
    """In-graph fused allreduce of a pytree: bucket → one ``psum`` per
    bucket; a bucket of several leaves is concatenated in front of it and
    split behind it, a leaf alone keeps its shape.  The JAX-transform
    equivalent of the reference's fusion buffer cycle
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
