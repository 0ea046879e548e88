"""SPMD step construction: the compiled replacement for the reference's
entire runtime hot path.

Where the reference enqueues each gradient to a background thread that
negotiates, fuses and launches NCCL (call stack SURVEY.md §3.2), here the
whole train step — forward, backward, allreduce, optimizer — is ONE jitted
SPMD program over the horovod mesh, with the gradient collectives scheduled
statically on ICI.  That schedule does NOT hide them today: on four v5e
chips at Mistral-7B's widths each ``all-reduce`` follows the product that
makes its gradient but blocks the core, 34.13 of 34.35 ms a step exposed
(``m7b-train-dp4``: ledger, PR 40; ROADMAP.md S11) — the reference's
async background thread overlapped, this compiled step does not yet.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import basics

def shard(fn, *, in_specs, out_specs, mesh=None, check_replication: bool = False):
    """``jax.shard_map`` over the horovod mesh."""
    return jax.shard_map(
        fn,
        mesh=mesh or basics.mesh(),
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=check_replication,
    )


def run(fn, *args, in_specs, out_specs, mesh=None):
    """Run ``fn`` once under shard_map (eagerly jitted)."""
    return jax.jit(shard(fn, in_specs=in_specs, out_specs=out_specs, mesh=mesh))(
        *args
    )


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    mesh=None,
    axis: Optional[str] = None,
    donate: bool = True,
    has_aux: bool = False,
    hierarchical: Optional[bool] = None,
):
    """Build the canonical data-parallel train step.

    ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux``); ``optimizer`` is typically
    ``hvd.DistributedOptimizer(optax...)`` so the gradient allreduce is
    inside.  Batch arrays are sharded on dim 0 over the worker axis; params
    and optimizer state are replicated.  Returns
    ``step(params, opt_state, batch) -> (params, opt_state, loss[, aux])``.

    This is the compiled equivalent of the reference's
    ``DistributedGradientTape`` + ``apply_gradients`` hot path
    (SURVEY.md §3.2) with negotiation/fusion/cache made unnecessary by
    SPMD compilation.

    ``hierarchical=True`` (default: the ``HOROVOD_HIERARCHICAL_ALLREDUCE``/
    ``ALLGATHER`` env flags, i.e. the launcher's ``--hierarchical-*``)
    builds the step over the 2-D ``(cross, local)`` mesh so collectives can
    use the two-level algorithms — the wiring for the reference's
    ``NCCLHierarchicalAllreduce`` configuration knob (``common.h:76-77``).
    """
    from horovod_tpu.ops import collectives as _C

    if hierarchical is None:
        hierarchical = (
            _C.hierarchical_allreduce_enabled()
            or _C.hierarchical_allgather_enabled()
        )
    if hierarchical and mesh is None and axis is None:
        hier = basics.hierarchical_mesh()
        if hier is not None:
            mesh = hier
            axis = (basics.CROSS_AXIS, basics.LOCAL_AXIS)
    mesh = mesh or basics.mesh()
    axis = axis or basics.axis_name()

    def _step(params, opt_state, batch):
        vg = jax.value_and_grad(loss_fn, has_aux=has_aux)
        val, grads = vg(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if has_aux:
            loss, aux = val
        else:
            loss = val
        loss = lax.pmean(loss, axis)
        if has_aux:
            return params, opt_state, loss, aux
        return params, opt_state, loss

    batch_spec = P(axis)
    sharded = shard(
        _step,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()) + ((batch_spec,) if has_aux else ()),
        mesh=mesh,
    )
    donate_argnums = (0, 1) if donate else ()
    return jax.jit(sharded, donate_argnums=donate_argnums)


def make_gspmd_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    mesh,
    param_spec,
    batch_spec,
    donate: bool = True,
):
    """Build a train step in GSPMD style: parameters/batch carry
    NamedShardings over an N-D mesh (dp/fsdp/tp/sp/pp/ep — see
    :mod:`horovod_tpu.parallel.meshes`), and XLA's sharding propagation
    inserts every collective — gradient psums over dp/fsdp, tp
    all-gathers/reduce-scatters, sp/pp permutes.

    This is the second (TPU-idiomatic) face of the framework: where
    :func:`make_train_step` expresses Horovod's explicit-collective
    programming model, this one expresses "pick a mesh, annotate shardings,
    let XLA insert collectives" for arbitrary multi-axis parallelism the
    reference never had (SURVEY.md §2.6 extensions).
    """
    p_shard = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, s), param_spec
    )
    b_shard = jax.tree_util.tree_map(
        lambda s: jax.sharding.NamedSharding(mesh, s), batch_spec
    )
    repl = jax.sharding.NamedSharding(mesh, P())

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(
        _step,
        in_shardings=(p_shard, None, b_shard),
        out_shardings=(p_shard, None, repl),
        donate_argnums=(0, 1) if donate else (),
    )


def init_replicated(params, mesh=None):
    """Place a pytree replicated across the mesh (host → devices)."""
    mesh = mesh or basics.mesh()
    sharding = jax.sharding.NamedSharding(mesh, P())
    return jax.device_put(params, sharding)


def shard_batch(batch, mesh=None, axis: Optional[str] = None):
    """Place host batch arrays sharded on dim 0 over the worker axis."""
    mesh = mesh or basics.mesh()
    axis = axis or basics.axis_name()
    sharding = jax.sharding.NamedSharding(mesh, P(axis))
    return jax.tree_util.tree_map(lambda b: jax.device_put(b, sharding), batch)
