"""High-level gradient-averaging API: ``DistributedOptimizer`` and
``DistributedGradientTape`` equivalents.

Reference: ``horovod/tensorflow/__init__.py:230-531`` (``_make_allreduce_
grads_fn``, ``_DistributedOptimizer``, ``DistributedGradientTape``) and
``horovod/torch/__init__.py:61-216`` (per-parameter hook optimizer with
``backward_passes_per_step`` accumulation).

TPU re-design: the optimizer is an **optax gradient transformation** — the
allreduce is a pure function inside the compiled train step, scheduled
statically where the reference's background thread scheduled dynamically.
There are no hooks, handles, or ``synchronize()``: data dependencies
express completion.

What is MEASURED of that schedule (four v5e chips, Mistral-7B's widths,
``m7b-train-dp4``: ledger, PR 40): XLA:TPU issues each gradient's
``all-reduce`` right behind the product that makes the gradient, but as a
BLOCKING operation — 34.13 of the 34.35 ms a step under ``grad_allreduce``
are exposed, the core waits them out in seven pieces, and nothing of the
backward pass runs meanwhile (ROADMAP.md S11).  The reference's overlap
is not had for free.

Gradients enter the reduction as VALUES (``_as_values`` says why: one
``lax.optimization_barrier`` a leaf, in traced code), so that the compiler
cannot fuse the wrapped optimizer into the matmul that makes its gradient.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.ops import collectives as C
from horovod_tpu.ops import fusion as F
from horovod_tpu.ops.compression import Compression


def _as_values(grads):
    """Traced gradients as MATERIALISED values, leaf by leaf.

    With nothing between a gradient and its update — a world of one, where
    the reduction emits no operation — XLA:TPU puts the optimizer's outputs
    (AdamW: parameter, ``mu``, ``nu``) into the epilogue of the matmul that
    makes the gradient, and that product then runs at 41-52 % of the MXU
    where it reaches 83-87 % alone (``m7b-train-1chip`` against
    ``m7b-train-dp4``: ledger, PR 40; PERF.md section 6, PR 41).  On
    several chips the allreduce already stands there, and the program is
    the same with the barrier.  One barrier a LEAF: no leaf's reduction or
    update waits for another leaf's gradient.  Eager arrays are values
    already."""
    return jax.tree_util.tree_map(
        lambda g: jax.lax.optimization_barrier(g)
        if isinstance(g, jax.core.Tracer) else g, grads)


def distributed_gradients(
    grads,
    op: str = C.Average,
    *,
    axis_name=None,
    compression=Compression.none,
    fuse: bool = True,
    fusion_threshold: Optional[int] = None,
    sparse_keys=(),
):
    """Allreduce a gradient pytree (the reference's
    ``_make_allreduce_grads_fn``, ``tensorflow/__init__.py:230-251``).

    ``fuse=True`` buckets leaves into large flat collectives
    (:mod:`horovod_tpu.ops.fusion`); compression casts to 16-bit for the
    wire and restores dtype after (``tensorflow/compression.py``).

    ``sparse_keys``: tree-path substrings (e.g. ``("embed",)``) whose
    EAGER leaves reduce by allgathering touched rows instead of the
    dense allreduce — the reference's IndexedSlices path
    (``tensorflow/__init__.py:74-89``), re-created for JAX's dense
    lookup VJPs by row-sparsity detection
    (:func:`horovod_tpu.ops.sparse.sparse_allreduce`).  Traced leaves
    (inside jit) always reduce dense — static shapes; compression is
    not applied to the sparse leaves (their values ride the wire
    already-small)."""
    if sparse_keys and op in (C.Average, C.Sum):
        from horovod_tpu.ops import sparse as SP

        treedef, dense, sparse = SP.split_sparse_leaves(
            grads, tuple(sparse_keys))
        if sparse:
            idx = [i for i, l in enumerate(dense) if l is not None]
            reduced = distributed_gradients(
                [dense[i] for i in idx], op, axis_name=axis_name,
                compression=compression, fuse=fuse,
                fusion_threshold=fusion_threshold)
            out = [None] * len(dense)
            for i, r in zip(idx, reduced):
                out[i] = r
            red_sparse = [
                (i, SP.sparse_allreduce(leaf, op, name=f"sparse.{i}"))
                for i, _key, leaf in sparse
            ]
            return SP.merge_sparse_leaves(treedef, out, red_sparse)
    # "grad_allreduce": the device scope a profile attributes the
    # compiled collectives (and their casts and packing) to.
    with jax.named_scope("grad_allreduce"):
        grads, ctx = compression.compress(_as_values(grads))
        if fuse and op in (C.Average, C.Sum):
            out = F.fused_allreduce_tree(
                grads, op, axis_name=axis_name, threshold=fusion_threshold
            )
        else:
            out = C.allreduce(grads, op, axis_name=axis_name)
        return compression.decompress(out, ctx)


class _AccumState(NamedTuple):
    inner: Any
    acc: Any
    counter: jnp.ndarray


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: str = C.Average,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    average_aggregated_gradients: bool = True,
    axis_name=None,
    fuse: bool = True,
    fusion_threshold: Optional[int] = None,
    sparse_keys=(),
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates are computed from
    cross-worker-reduced gradients.

    Reference semantics matched:

    * ``op=Average|Sum|Adasum`` (``tensorflow/__init__.py:410-471``).
    * ``backward_passes_per_step`` accumulates gradients locally and only
      allreduces (and steps) every k-th call; non-boundary calls return zero
      updates (``torch/__init__.py:95-157``).
    * ``average_aggregated_gradients`` divides the accumulated sum by k
      before reduction (``tensorflow/__init__.py:328-365``).
    * ``sparse_keys`` — embedding-shaped leaves reduce sparsely on the
      eager path (see :func:`distributed_gradients`; the reference's
      IndexedSlices allgather, ``tensorflow/__init__.py:74-89``).
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")

    def _reduce(grads):
        return distributed_gradients(
            grads,
            op,
            axis_name=axis_name,
            compression=compression,
            fuse=fuse,
            fusion_threshold=fusion_threshold,
            sparse_keys=sparse_keys,
        )

    if backward_passes_per_step == 1:

        def init_fn(params):
            return optimizer.init(params)

        def update_fn(grads, state, params=None, **extra):
            reduced = _reduce(grads)
            with jax.named_scope("opt_update"):
                return optimizer.update(reduced, state, params, **extra)

        return optax.GradientTransformation(init_fn, update_fn)

    k = backward_passes_per_step

    def init_fn(params):
        return _AccumState(
            inner=optimizer.init(params),
            acc=jax.tree_util.tree_map(jnp.zeros_like, params),
            counter=jnp.zeros((), jnp.int32),
        )

    def update_fn(grads, state, params=None, **extra):
        acc = jax.tree_util.tree_map(lambda a, g: a + g, state.acc, grads)
        count = state.counter + 1
        boundary = count >= k

        def do_step(operands):
            acc, inner, params = operands
            scale = 1.0 / k if average_aggregated_gradients else 1.0
            scaled = jax.tree_util.tree_map(
                lambda a: a * jnp.asarray(scale, a.dtype), acc
            )
            reduced = _reduce(scaled)
            with jax.named_scope("opt_update"):
                updates, inner2 = optimizer.update(
                    reduced, inner, params, **extra)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return updates, inner2, zeroed

        def skip_step(operands):
            acc, inner, _params = operands
            updates = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return updates, inner, acc

        updates, inner, acc = jax.lax.cond(
            boundary, do_step, skip_step, (acc, state.inner, params)
        )
        counter = jnp.where(boundary, 0, count)
        return updates, _AccumState(inner=inner, acc=acc, counter=counter)

    return optax.GradientTransformation(init_fn, update_fn)


class _AdasumDeltaState(NamedTuple):
    inner: Any
    start: Any       # params at the last sync (None when k == 1)
    counter: jnp.ndarray


def DistributedAdasumOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    axis_name=None,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
) -> optax.GradientTransformation:
    """Delta-model Adasum: combine LOCAL OPTIMIZER UPDATES, not gradients.

    The published Adasum usage mode (reference
    ``tensorflow/__init__.py:313-407`` ``_DistributedAdasumOptimizer``,
    ``torch/__init__.py:219-407``): each worker applies its own optimizer
    step, and the resulting parameter delta — which already carries the
    optimizer's adaptive scaling — is Adasum-allreduced, so the
    scale-insensitive pairwise combination operates on actual model
    movement:

        start  = params at the last sync
        local  = params + inner_update(grads)          (optimizer logic)
        delta  = local - start
        global = adasum_allreduce(delta)
        params = start + global

    In optax terms the inner update IS the per-step delta, so with
    ``backward_passes_per_step == 1`` no snapshot is needed: the returned
    update is ``adasum(inner_update)``.  With k > 1, updates apply
    locally for k-1 steps (workers drift) and the k-th step reduces the
    CUMULATIVE drift from ``start``, mirroring the reference's
    ``_is_comm_step`` handling.
    """
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    from horovod_tpu.ops import adasum as AD

    def _adasum(tree):
        with jax.named_scope("grad_allreduce"):
            tree, ctx = compression.compress(tree)
            out = AD.adasum_allreduce(tree, axis_name=axis_name)
            return compression.decompress(out, ctx)

    def _inner_update(grads, state, params, extra):
        with jax.named_scope("opt_update"):
            return optimizer.update(grads, state, params, **extra)

    if backward_passes_per_step == 1:

        def init_fn(params):
            return optimizer.init(params)

        def update_fn(grads, state, params=None, **extra):
            updates, inner = _inner_update(grads, state, params, extra)
            return _adasum(updates), inner

        return optax.GradientTransformation(init_fn, update_fn)

    k = backward_passes_per_step

    def init_fn(params):
        return _AdasumDeltaState(
            inner=optimizer.init(params),
            start=jax.tree_util.tree_map(jnp.asarray, params),
            counter=jnp.zeros((), jnp.int32),
        )

    def update_fn(grads, state, params=None, **extra):
        if params is None:
            raise ValueError(
                "DistributedAdasumOptimizer with backward_passes_per_step "
                "> 1 needs params passed to update()")
        local_updates, inner = _inner_update(
            grads, state.inner, params, extra)
        count = state.counter + 1
        boundary = count >= k

        def do_sync(operands):
            local_updates, params, start = operands
            # Cumulative drift since the last sync, including this step's
            # local update.
            delta = jax.tree_util.tree_map(
                lambda p, u, s: p + u - s, params, local_updates, start)
            global_delta = _adasum(delta)
            new_start = jax.tree_util.tree_map(
                lambda s, g: s + g, start, global_delta)
            updates = jax.tree_util.tree_map(
                lambda ns, p: ns - p, new_start, params)
            return updates, new_start

        def skip_sync(operands):
            local_updates, _params, start = operands
            return local_updates, start

        updates, start = jax.lax.cond(
            boundary, do_sync, skip_sync, (local_updates, params, state.start)
        )
        counter = jnp.where(boundary, 0, count)
        return updates, _AdasumDeltaState(
            inner=inner, start=start, counter=counter)

    return optax.GradientTransformation(init_fn, update_fn)


def DistributedGradientTape(
    fun,
    *,
    op: str = C.Average,
    compression=Compression.none,
    axis_name=None,
    has_aux: bool = False,
    fuse: bool = True,
    sparse_keys=(),
):
    """Return ``value_and_grad(fun)`` whose gradients are allreduced.

    JAX analogue of ``hvd.DistributedGradientTape``
    (``tensorflow/__init__.py:474-531``): TF tapes record eagerly, JAX
    differentiates functionally, so the "tape" is a transformed
    ``value_and_grad``.  ``sparse_keys`` routes embedding-shaped leaves
    through the sparse (indices, values) allgather on the eager path —
    the IndexedSlices analogue.

        loss, grads = hvd.DistributedGradientTape(loss_fn)(params, batch)
    """
    vg = jax.value_and_grad(fun, has_aux=has_aux)

    def wrapped(*args, **kwargs):
        val, grads = vg(*args, **kwargs)
        grads = distributed_gradients(
            grads, op, axis_name=axis_name, compression=compression,
            fuse=fuse, sparse_keys=sparse_keys
        )
        return val, grads

    return wrapped
