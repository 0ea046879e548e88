"""Collective operations: allreduce / allgather / broadcast / alltoall /
reducescatter, synchronous and handle-based async.

Re-design of the reference's op layer (``horovod/common/ops/*``,
``horovod/torch/mpi_ops.py:72-508``, ``horovod/tensorflow/mpi_ops.py``) for
TPU.  Two execution paths replace the reference's seven backends:

* **In-graph (compiled) path** — when called under a trace with the worker
  axis bound (``shard_map``/``pmap`` over the horovod mesh), each op lowers
  directly to the XLA collective (``psum`` / ``all_gather`` / ``ppermute`` /
  ``all_to_all`` / ``psum_scatter``) over ICI/DCN.  Negotiation
  (``controller.cc:55-347``), tensor fusion (``controller.cc:631-752``) and
  the response cache (``response_cache.h``) are unnecessary here: SPMD
  compilation gives every process an identical collective schedule, and XLA's
  combiner does the batching the fusion buffer did.
* **Eager path** — concrete arrays outside any trace.  Ops run as tiny cached
  compiled programs over a one-device-per-process mesh (the CROSS
  communicator), i.e. the replacement for the reference's CPU backends
  (MPI/Gloo/CCL ops).  Multiple eager ops issued back-to-back are fused by
  the bucketing layer in :mod:`horovod_tpu.ops.fusion`.

All processes must issue eager collectives in the same order — the same
contract the reference enforces dynamically via its coordinator; here it is a
documented SPMD requirement, with the native runtime's stall inspector
(``native/src/stall_inspector.cc``) flagging violations when it is active.
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import basics

# --- Reduce ops (reference: horovod_reduce_op_* in common/operations.cc and
# the Average/Sum/Adasum constants re-exported per framework) ----------------

Average = "Average"
Sum = "Sum"
Adasum = "Adasum"
Min = "Min"      # TPU extension (reference v0.19 has only the three above)
Max = "Max"
Product = "Product"

_REDUCE_OPS = (Average, Sum, Adasum, Min, Max, Product)


def _check_op(op: str) -> None:
    if op not in _REDUCE_OPS:
        raise ValueError(f"Unknown reduce op {op!r}; expected one of {_REDUCE_OPS}")


def _is_traced(tree: Any) -> bool:
    return any(
        isinstance(leaf, jax.core.Tracer) for leaf in jax.tree_util.tree_leaves(tree)
    )


def _axis_names(axis_name) -> tuple:
    if axis_name is None:
        axis_name = basics.axis_name() if basics.is_initialized() else basics.AXIS
        if isinstance(axis_name, str):
            # Probe the trace's axis environment: a step built over the
            # hierarchical (cross, local) mesh binds those axes instead of
            # the flat worker axis, and collectives called with
            # axis_name=None should resolve to whichever is live.
            try:
                lax.axis_size(axis_name)
            except NameError:
                try:
                    lax.axis_size(basics.CROSS_AXIS)
                    lax.axis_size(basics.LOCAL_AXIS)
                    return (basics.CROSS_AXIS, basics.LOCAL_AXIS)
                except NameError:
                    pass
    if isinstance(axis_name, (tuple, list)):
        return tuple(axis_name)
    return (axis_name,)


# --- hierarchical-collective config (reference knobs: common/common.h:76-77,
# HOROVOD_HIERARCHICAL_ALLREDUCE / HOROVOD_HIERARCHICAL_ALLGATHER; exported
# by the launcher's --hierarchical-* flags via runner/config_parser.py) ------

import os as _os


def _env_flag(name: str) -> bool:
    return _os.environ.get(name, "0").lower() not in ("", "0", "false")


def hierarchical_allreduce_enabled() -> bool:
    """True when HOROVOD_HIERARCHICAL_ALLREDUCE requests the two-level
    reduce (psum_scatter over `local`/ICI → psum over `cross`/DCN →
    all_gather over `local`) instead of a flat psum over both axes."""
    return _env_flag("HOROVOD_HIERARCHICAL_ALLREDUCE")


def hierarchical_allgather_enabled() -> bool:
    """True when HOROVOD_HIERARCHICAL_ALLGATHER requests staged gathers
    (local axis first, then cross) instead of one joint-axis all_gather."""
    return _env_flag("HOROVOD_HIERARCHICAL_ALLGATHER")


def _axis_size(axes: tuple) -> int:
    try:
        n = 1
        for a in axes:
            n *= lax.axis_size(a)
        return n
    except (NameError, AttributeError):
        # Fallback: psum of ones — XLA constant-folds this for a static mesh.
        return lax.psum(jnp.ones((), jnp.int32), axes)


def _reraise_unbound(err: NameError) -> None:
    raise RuntimeError(
        "horovod_tpu collective called inside jit without the worker axis "
        "bound. Wrap the computation in jax.shard_map over horovod_tpu.mesh() "
        "(or use horovod_tpu.spmd.run_step), or call the op eagerly."
    ) from err


# --- in-graph implementations ----------------------------------------------


def _hier_psum(t, axes: tuple):
    """Two-level allreduce over the (cross, local) mesh — the compiled
    re-design of ``NCCLHierarchicalAllreduce``
    (``ops/nccl_operations.cc:162-354``): reduce-scatter within the node,
    allreduce of the scattered shard across nodes, allgather within the
    node.  Here `local` rides ICI and `cross` rides DCN, so the cross-host
    hop moves 1/local_size of the tensor per chip."""
    cross, local = axes
    n_local = lax.axis_size(local)
    flat = t.reshape(-1)
    pad = (-flat.shape[0]) % n_local
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    shard = lax.psum_scatter(flat, local, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, cross)
    full = lax.all_gather(shard, local, axis=0, tiled=True)
    if pad:
        full = full[: t.size]
    return full.reshape(t.shape)


def _injit_allreduce(tensor, op: str, axes: tuple, prescale, postscale):
    if op == Adasum:
        from horovod_tpu.ops import adasum as _adasum

        return _adasum.adasum_allreduce(tensor, axis_name=axes)
    if prescale is not None and prescale != 1.0:
        tensor = jax.tree_util.tree_map(lambda t: t * prescale, tensor)
    if op in (Average, Sum):
        if len(axes) == 2 and hierarchical_allreduce_enabled():
            out = jax.tree_util.tree_map(lambda t: _hier_psum(t, axes), tensor)
        else:
            out = jax.tree_util.tree_map(lambda t: lax.psum(t, axes), tensor)
        if op == Average:
            n = _axis_size(axes)
            out = jax.tree_util.tree_map(lambda t: t / jnp.asarray(n, t.dtype), out)
    elif op == Min:
        out = jax.tree_util.tree_map(lambda t: lax.pmin(t, axes), tensor)
    elif op == Max:
        out = jax.tree_util.tree_map(lambda t: lax.pmax(t, axes), tensor)
    elif op == Product:
        # XLA has no pprod; take it through logs? No — all_gather+reduce is
        # exact for small worker counts and rare use.  Reference lacks
        # Product entirely, so the simple form is acceptable.
        def _prod(t):
            g = lax.all_gather(t, axes[-1])
            for a in axes[:-1]:
                g = lax.all_gather(g, a)
            return jnp.prod(g.reshape((-1,) + t.shape), axis=0)

        out = jax.tree_util.tree_map(_prod, tensor)
    else:  # pragma: no cover
        raise AssertionError(op)
    if postscale is not None and postscale != 1.0:
        out = jax.tree_util.tree_map(lambda t: t * postscale, out)
    return out


def _injit_broadcast(tensor, root_rank: int, axes: tuple):
    """Broadcast by masked psum: select(rank==root, x, 0) then sum.

    One allreduce on ICI — the compiled replacement for
    ``NCCLBroadcast::Execute`` (``ops/nccl_operations.cc:366-396``).
    """
    if len(axes) == 1:
        idx = lax.axis_index(axes[0])
    else:
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)

    def _bc(t):
        masked = jnp.where(idx == root_rank, t, jnp.zeros_like(t))
        return lax.psum(masked, axes)

    return jax.tree_util.tree_map(_bc, tensor)


def _injit_allgather(tensor, axes: tuple):
    def _ag(t):
        if len(axes) == 2 and hierarchical_allgather_enabled():
            # MPIHierarchicalAllgather analogue (ops/mpi_operations.cc):
            # gather within the node first (ICI), then gather node blocks
            # across hosts (DCN).  Worker order is (cross, local)-major on
            # both paths.
            g = lax.all_gather(t, axes[1], axis=0, tiled=True)
            return lax.all_gather(g, axes[0], axis=0, tiled=True)
        # Flat path: ONE gather over the (possibly joint) axis — XLA emits a
        # single all-gather over the full device set.
        return lax.all_gather(t, axes if len(axes) > 1 else axes[0],
                              axis=0, tiled=True)

    return jax.tree_util.tree_map(_ag, tensor)


def _injit_alltoall(tensor, axes: tuple):
    if len(axes) != 1:
        raise ValueError("alltoall supports a single mesh axis")

    def _a2a(t):
        return lax.all_to_all(t, axes[0], split_axis=0, concat_axis=0, tiled=True)

    return jax.tree_util.tree_map(_a2a, tensor)


def _injit_reducescatter(tensor, op: str, axes: tuple):
    if len(axes) != 1:
        raise ValueError("reducescatter supports a single mesh axis")
    if op not in (Average, Sum):
        raise ValueError("reducescatter supports Sum/Average")

    def _rs(t):
        out = lax.psum_scatter(t, axes[0], scatter_dimension=0, tiled=True)
        if op == Average:
            n = _axis_size(axes)
            out = out / jnp.asarray(n, out.dtype)
        return out

    return jax.tree_util.tree_map(_rs, tensor)


# --- eager implementations --------------------------------------------------
#
# The eager data plane: one device per process forms the CROSS mesh; local
# host values are stitched into a global array and a cached compiled program
# performs the reduction with replicated output.  With a single process all
# ops are local identities (sum over one contributor), matching reference
# semantics where size()==1.

_eager_lock = threading.Lock()


@functools.lru_cache(maxsize=1)
def _process_mesh() -> jax.sharding.Mesh:
    devs = {}
    for d in basics.mesh().devices.flat:
        devs.setdefault(d.process_index, d)
    ordered = [devs[p] for p in sorted(devs)]
    return jax.sharding.Mesh(np.array(ordered, dtype=object), axis_names=("proc",))


def _to_global(x: np.ndarray):
    """Stitch per-process host values into one global array with leading
    axis = process, sharded over the process mesh."""
    pm = _process_mesh()
    sharding = jax.sharding.NamedSharding(pm, jax.sharding.PartitionSpec("proc"))
    local_dev = [d for d in pm.devices.flat if d.process_index == jax.process_index()]
    shard = jax.device_put(np.asarray(x)[None], local_dev[0])
    nproc = pm.devices.size
    return jax.make_array_from_single_device_arrays(
        (nproc,) + tuple(np.asarray(x).shape), sharding, [shard]
    )


@functools.lru_cache(maxsize=1)
def _process_local_counts() -> tuple:
    """Chips per process, ordered by process index.

    This is the weight each process's eager contribution carries: the
    API's worker count is CHIPS (``basics.size()``), so with
    ``local_size > 1`` (one process driving several chips) an eager
    submission stands for every local chip — Sum multiplies by the local
    count and Average divides by ``size()``, keeping eager and in-graph
    reductions consistent (the reference has no such seam because a
    process is exactly one GPU; ``common/basics.py:22-211`` contract)."""
    counts: dict = {}
    for d in basics.mesh().devices.flat:
        counts[d.process_index] = counts.get(d.process_index, 0) + 1
    return tuple(counts[p] for p in sorted(counts))


@functools.lru_cache(maxsize=4096)
def _compiled_reduce(op: str, counts: tuple):
    pm = _process_mesh()
    repl = jax.sharding.NamedSharding(pm, jax.sharding.PartitionSpec())
    nchips = int(sum(counts))
    weighted = any(c != 1 for c in counts)

    def fn(a):
        if weighted and op in (Sum, Average, Product):
            w = jnp.asarray(np.asarray(counts), a.dtype).reshape(
                (-1,) + (1,) * (a.ndim - 1))
        if op == Sum:
            return (a * w).sum(axis=0) if weighted else a.sum(axis=0)
        if op == Average:
            # Promote like jnp.mean (ints divide to float).
            s = (a * w).sum(axis=0) if weighted else a.sum(axis=0)
            return s / nchips
        if op == Min:
            return a.min(axis=0)  # duplicates don't change min/max
        if op == Max:
            return a.max(axis=0)
        if op == Product:
            return (a ** w).prod(axis=0) if weighted else a.prod(axis=0)
        raise AssertionError(op)

    return jax.jit(fn, out_shardings=repl)


@functools.lru_cache(maxsize=4096)
def _compiled_identity_replicated():
    pm = _process_mesh()
    repl = jax.sharding.NamedSharding(pm, jax.sharding.PartitionSpec())
    return jax.jit(lambda a: a, out_shardings=repl)


# --- traffic-shaped eager programs -------------------------------------------
#
# Builders are parameterized by (mesh, axis) so tests can compile them over a
# virtual multi-device mesh and assert on the emitted collectives (the
# "bytes proportional to tensor, not P x tensor" contract).  The eager path
# instantiates them over the process mesh via the cached wrappers below.


def _pick_program(mesh, axis: str, src: int):
    """Rooted broadcast: replicate ONE shard of a dim-0-sharded array.

    The owner's block is statically sliced out, so the partitioner moves only
    that tensor (select + all-reduce or collective-broadcast) — never an
    all-gather of every rank's buffer.  Replaces the reference's
    ``MPIBroadcast``/``NCCLBroadcast`` on the eager path."""
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    return jax.jit(
        lambda a: lax.index_in_dim(a, src, axis=0, keepdims=False),
        out_shardings=repl,
    )


def _reducescatter_program(mesh, axis: str, op: str, counts: tuple = None):
    """Eager reduce-scatter as a true ``lax.psum_scatter`` (each process
    receives only its reduced 1/P slice and each link carries (P-1)/P of
    one tensor — half the all-reduce cost; reference
    ``ops/nccl_operations.cc:162-354`` intra-node phase).

    ``counts``: chips per process (see :func:`_process_local_counts`) —
    contributions are chip-weighted so Sum/Average match the in-graph
    (worker-axis) semantics when ``local_size > 1``."""
    from horovod_tpu import spmd

    spec = jax.sharding.PartitionSpec(axis)
    weighted = counts is not None and any(c != 1 for c in counts)
    denom = int(sum(counts)) if counts else None

    def fn(block):  # per-shard: (1, d0, ...)
        t = jnp.squeeze(block, 0)
        if weighted:
            w = jnp.asarray(np.asarray(counts), t.dtype)[
                lax.axis_index(axis)]
            t = t * w
        out = lax.psum_scatter(t, axis, scatter_dimension=0, tiled=True)
        if op == Average:
            n = denom if denom is not None else lax.axis_size(axis)
            out = out / jnp.asarray(n, out.dtype)
        return out[None]

    return jax.jit(spmd.shard(fn, in_specs=spec, out_specs=spec, mesh=mesh))


def _alltoall_program(mesh, axis: str):
    """Eager all-to-all as a true ``lax.all_to_all`` over the process axis
    (traffic: each link carries one peer-slice, not the whole tensor)."""
    from horovod_tpu import spmd

    spec = jax.sharding.PartitionSpec(axis)

    def fn(block):  # per-shard: (1, rows, ...)
        t = jnp.squeeze(block, 0)
        t = lax.all_to_all(t, axis, split_axis=0, concat_axis=0, tiled=True)
        return t[None]

    return jax.jit(spmd.shard(fn, in_specs=spec, out_specs=spec, mesh=mesh))


@functools.lru_cache(maxsize=4096)
def _compiled_pick(src: int):
    return _pick_program(_process_mesh(), "proc", src)


@functools.lru_cache(maxsize=16)
def _compiled_reducescatter(op: str):
    return _reducescatter_program(_process_mesh(), "proc", op,
                                  _process_local_counts())


@functools.lru_cache(maxsize=1)
def _compiled_alltoall():
    return _alltoall_program(_process_mesh(), "proc")


def _replicated_to_host(arr) -> np.ndarray:
    return np.asarray(jax.device_get(arr))


def _local_shard_to_host(arr) -> np.ndarray:
    """Fetch this process's (single) addressable shard of a global array."""
    shards = arr.addressable_shards
    assert len(shards) == 1, len(shards)
    return np.asarray(shards[0].data)


def _eager_allreduce(x, op: str, prescale, postscale) -> np.ndarray:
    xh = np.asarray(x)
    if prescale is not None and prescale != 1.0:
        xh = xh * np.asarray(prescale, xh.dtype)
    if basics.cross_size() == 1:
        # Same chip-weighted semantics as the multi-process path: one
        # process driving N chips submits a value that stands for every
        # local chip, so Sum is N*x (== the in-graph worker-axis psum)
        # and Average is N*x/size() == x.  Min/Max/Adasum(identical
        # contributions) are duplicate-insensitive.
        ls = basics.local_size()
        if ls > 1 and op == Sum:
            out = xh * np.asarray(ls, xh.dtype)
        elif ls > 1 and op == Product:
            out = xh ** ls
        else:
            out = xh.copy()
    elif op == Adasum:
        from horovod_tpu.ops import adasum as _adasum

        out = _adasum.eager_adasum(xh)
    else:
        out = _replicated_to_host(
            _compiled_reduce(op, _process_local_counts())(_to_global(xh))
        )
    if postscale is not None and postscale != 1.0:
        out = out * np.asarray(postscale, out.dtype)
    return out


def _eager_allgather(x) -> np.ndarray:
    xh = np.asarray(x)
    if basics.cross_size() == 1:
        return xh.copy()
    # Variable first-dim support (reference: allgather recvcounts /
    # displacements, ops/collective_operations.cc:120-196): gather sizes,
    # pad to max, gather, slice.
    n0 = np.zeros((), np.int64) + xh.shape[0]
    sizes = _replicated_to_host(
        _compiled_identity_replicated()(_to_global(n0))
    ).astype(int)
    m = int(sizes.max())
    pad = np.zeros((m,) + xh.shape[1:], xh.dtype)
    pad[: xh.shape[0]] = xh
    gathered = _replicated_to_host(_compiled_identity_replicated()(_to_global(pad)))
    return np.concatenate([gathered[i, : sizes[i]] for i in range(len(sizes))], axis=0)


def _eager_broadcast(x, root_rank: int) -> np.ndarray:
    xh = np.asarray(x)
    if basics.cross_size() == 1:
        return xh.copy()
    # root_rank is a worker rank; owning process = root // local_size.
    proc = root_rank // max(basics.local_size(), 1)
    return _replicated_to_host(_compiled_pick(proc)(_to_global(xh)))


def _eager_reducescatter(x, op: str) -> np.ndarray:
    if op not in (Average, Sum):
        raise ValueError("reducescatter supports Sum/Average")
    xh = np.asarray(x)
    P = basics.cross_size()
    if xh.shape[0] % P != 0:
        raise ValueError(
            f"reducescatter requires dim0 ({xh.shape[0]}) divisible by the "
            f"process count ({P}) on the eager path"
        )
    if P == 1:
        # Chip-weighted like _eager_allreduce: Sum over N local chips is
        # N*x; Average is N*x/size() == x.
        ls = basics.local_size()
        if ls > 1 and op == Sum:
            return xh * np.asarray(ls, xh.dtype)
        return xh.copy()
    return _local_shard_to_host(_compiled_reducescatter(op)(_to_global(xh)))[0]


def _eager_alltoall(x, splits) -> np.ndarray:
    xh = np.asarray(x)
    P = basics.cross_size()
    if splits is None and xh.shape[0] % P != 0:
        raise ValueError("alltoall without splits requires dim0 % size == 0")
    if splits is not None:
        splits = np.asarray(splits, np.int64)
        if splits.shape != (P,) or splits.sum() != xh.shape[0]:
            raise ValueError(f"splits must be ({P},) summing to dim0")
    if P == 1:
        return xh.copy()
    if splits is None:
        # Even splits: one true all_to_all — each link carries one
        # tensor/P slice.
        out = _local_shard_to_host(_compiled_alltoall()(_to_global(xh)))
        return out[0]
    # Uneven splits: pad each destination piece to the global max split and
    # run the same all_to_all over (P, max_split) blocks — traffic is
    # P x max_split (~ tensor size), not P x whole-tensor (reference covers
    # uneven recvcounts via MPI_Alltoallv; XLA all_to_all is regular, so
    # padding buys regularity).
    gathered_splits = _replicated_to_host(
        _compiled_identity_replicated()(_to_global(splits))
    ).astype(int)
    m = int(gathered_splits.max())
    send = np.zeros((P, m) + xh.shape[1:], xh.dtype)
    offs = np.concatenate([[0], np.cumsum(splits)])
    for p in range(P):
        send[p, : splits[p]] = xh[offs[p] : offs[p + 1]]
    out = _local_shard_to_host(_compiled_alltoall()(_to_global(send)))[0]
    me = jax.process_index()
    return np.concatenate(
        [out[p, : gathered_splits[p, me]] for p in range(P)], axis=0
    )


# --- native-runtime routing ---------------------------------------------------
#
# When the native control plane (horovod_tpu.native — the C++ re-design of
# the reference's background thread/controller/fusion/cache) is running,
# every eager op is enqueued as a named request and executed only once the
# coordinator declares it globally ready; requests submitted in the same
# cycle fuse into one collective.  Without it (library unavailable or
# HOROVOD_NATIVE=0), ops run directly in program order.


def _native_rt():
    from horovod_tpu import eager_runtime

    return eager_runtime.get()


def _native_kind_and_args(kind: str):
    from horovod_tpu import native

    return {
        "allreduce": native.ALLREDUCE,
        "allgather": native.ALLGATHER,
        "broadcast": native.BROADCAST,
        "alltoall": native.ALLTOALL,
        "reducescatter": native.REDUCESCATTER,
    }[kind]


def _native_submit_tree(rt, kind: str, tree, name, **kw):
    """Submit every leaf as its own named request; returns (treedef,
    [(handle, name)]).  All leaves go in before any wait, so one
    negotiation cycle sees — and fuses — the whole pytree."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    op_type = _native_kind_and_args(kind)
    pairs = []
    for i, leaf in enumerate(leaves):
        lname = rt.auto_name(kind, f"{name}.{i}" if name and len(leaves) > 1
                             else name)
        arr = np.asarray(leaf)
        h = rt.submit(lname, op_type, arr, **kw)
        pairs.append((h, lname))
    return treedef, pairs


def _native_wait_tree(rt, treedef, pairs):
    return jax.tree_util.tree_unflatten(
        treedef, [rt.wait(h, n) for h, n in pairs]
    )


def _native_reduce_op(op: str) -> int:
    from horovod_tpu import eager_runtime

    to_native, _ = eager_runtime._op_maps()
    return to_native[op]


# --- public API --------------------------------------------------------------


def allreduce(
    tensor,
    op: str = Average,
    *,
    axis_name=None,
    compression=None,
    name: Optional[str] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
):
    """Allreduce a tensor (or pytree) across all workers.

    Reference: ``hvd.allreduce`` (``tensorflow/__init__.py:43-118``,
    ``torch/mpi_ops.py:94-180``).  ``op=Average`` divides by worker count in
    the compiled graph (the reference divides in the completion callback,
    ``torch/mpi_ops_v2.cc:69-74``).
    """
    _check_op(op)
    if compression is not None:
        tensor, ctx = compression.compress(tensor)
    if _is_traced(tensor):
        try:
            out = _injit_allreduce(
                tensor, op, _axis_names(axis_name), prescale_factor, postscale_factor
            )
        except NameError as e:
            _reraise_unbound(e)
    else:
        basics._ctx()
        rt = _native_rt()
        if rt is not None:
            treedef, pairs = _native_submit_tree(
                rt, "allreduce", tensor, name,
                reduce_op=_native_reduce_op(op),
                prescale=1.0 if prescale_factor is None else prescale_factor,
                postscale=1.0 if postscale_factor is None else postscale_factor,
            )
            out = _native_wait_tree(rt, treedef, pairs)
        else:
            out = jax.tree_util.tree_map(
                lambda t: _eager_allreduce(
                    t, op, prescale_factor, postscale_factor
                ),
                tensor,
            )
    if compression is not None:
        out = compression.decompress(out, ctx)
    return out


def process_sum(tensor, *, name: Optional[str] = None):
    """Sum one contribution PER PROCESS (eager path).

    The eager ``Sum`` is chip-weighted — each process's submission stands
    for all ``local_size()`` chips it drives (see docs/concepts.md).  Use
    this instead when the payload is process-level data (a shard's row
    count, a per-process aggregate): the pre-division by the local chip
    count makes the chip weighting cancel exactly, also with
    heterogeneous chip counts (Σ ls_p · x_p/ls_p = Σ x_p)."""
    if _is_traced(tensor):
        raise ValueError(
            "process_sum is an eager (host-side) op; in-graph code sums "
            "per chip with allreduce(op=Sum)")
    ls = float(basics.local_size()) if basics.is_initialized() else 1.0
    return allreduce(tensor, Sum, name=name, prescale_factor=1.0 / ls)


def grouped_allreduce(tensors: Sequence, op: str = Average, *, axis_name=None, **kw):
    """Allreduce a list of tensors as one logical fused operation
    (reference: grouped allreduce / the fusion buffer).  In-graph, XLA's
    collective combiner fuses adjacent psums; eagerly we bucket explicitly
    via :mod:`horovod_tpu.ops.fusion`."""
    tensors = list(tensors)
    if _is_traced(tensors):
        return [allreduce(t, op, axis_name=axis_name, **kw) for t in tensors]
    basics._ctx()
    # Parse the kwargs the eager paths support; anything else raises LOUDLY
    # rather than silently returning unscaled results (r4 advisor finding).
    name = kw.pop("name", None)
    prescale = kw.pop("prescale_factor", None)
    postscale = kw.pop("postscale_factor", None)
    if kw:
        raise TypeError(
            f"unsupported kwargs for eager grouped allreduce: {sorted(kw)}")
    rt = _native_rt()
    if rt is not None:
        # Submit the whole group before waiting: one negotiation cycle sees
        # all of it and fuses (routing through the native queue also keeps
        # collective launch order globally consistent with concurrent
        # async ops).
        treedef, pairs = _native_submit_tree(
            rt, "allreduce", tensors, name,
            reduce_op=_native_reduce_op(op),
            prescale=1.0 if prescale is None else prescale,
            postscale=1.0 if postscale is None else postscale,
        )
        return _native_wait_tree(rt, treedef, pairs)
    if prescale is not None:
        tensors = [np.asarray(t) * np.asarray(prescale, np.asarray(t).dtype)
                   for t in tensors]

    def _post(out):
        if postscale is None:
            return out
        return [o * np.asarray(postscale, np.asarray(o).dtype) for o in out]

    if op == Adasum:
        # Concatenating a bucket and running one Adasum would change the
        # math (one global pairwise coefficient instead of one per
        # tensor); the group kernel shares the log2(P) communication
        # rounds while keeping per-tensor coefficients (the reference's
        # FusedAllreduce semantics, adasum.h:194-338).
        from horovod_tpu.ops import adasum as _adasum

        return _post(_adasum.eager_adasum_group(
            [np.asarray(t) for t in tensors]))
    from horovod_tpu.ops import fusion

    return _post(fusion.fused_eager_allreduce(tensors, op))


def allgather(tensor, *, axis_name=None, name: Optional[str] = None):
    """Concatenate tensors from all workers along dim 0
    (``MPI_Allgatherv`` analogue; variable first-dim supported eagerly)."""
    if _is_traced(tensor):
        try:
            return _injit_allgather(tensor, _axis_names(axis_name))
        except NameError as e:
            _reraise_unbound(e)
    basics._ctx()
    rt = _native_rt()
    if rt is not None:
        treedef, pairs = _native_submit_tree(rt, "allgather", tensor, name)
        return _native_wait_tree(rt, treedef, pairs)
    return jax.tree_util.tree_map(_eager_allgather, tensor)


def broadcast(tensor, root_rank: int = 0, *, axis_name=None, name=None):
    """Broadcast from worker ``root_rank`` to all workers."""
    if _is_traced(tensor):
        try:
            return _injit_broadcast(tensor, root_rank, _axis_names(axis_name))
        except NameError as e:
            _reraise_unbound(e)
    basics._ctx()
    rt = _native_rt()
    if rt is not None:
        treedef, pairs = _native_submit_tree(
            rt, "broadcast", tensor, name, root_rank=root_rank
        )
        return _native_wait_tree(rt, treedef, pairs)
    return jax.tree_util.tree_map(lambda t: _eager_broadcast(t, root_rank), tensor)


def alltoall(tensor, splits=None, *, axis_name=None, name=None):
    """Exchange dim-0 slices between all workers (TPU extension over the
    reference's op set — added to Horovod post-0.19; here it rides
    ``lax.all_to_all`` / ICI natively)."""
    if _is_traced(tensor):
        if splits is not None:
            raise ValueError("uneven splits only supported eagerly")
        try:
            return _injit_alltoall(tensor, _axis_names(axis_name))
        except NameError as e:
            _reraise_unbound(e)
    basics._ctx()
    rt = _native_rt()
    if rt is not None:
        if splits is None:
            treedef, pairs = _native_submit_tree(rt, "alltoall", tensor, name)
            return _native_wait_tree(rt, treedef, pairs)
        # Uneven splits can't ride the native queue (the wire Request has no
        # splits field, matching the reference v0.19 op set which predates
        # alltoallv), so they run on the direct path.  Flush with a native
        # BARRIER first: under the SPMD ordering contract every op submitted
        # before this point (on any rank) completes before the barrier does,
        # so no negotiated launch can interleave with the direct collective
        # (protocol invariant #4).  A local pending check would NOT work —
        # ranks can disagree on local pending state and then only some of
        # them would enter the global collective.
        rt.barrier()
    return jax.tree_util.tree_map(lambda t: _eager_alltoall(t, splits), tensor)


def reducescatter(tensor, op: str = Average, *, axis_name=None, name=None):
    """Reduce-scatter along dim 0 (the primitive underlying hierarchical
    allreduce, ``ops/nccl_operations.cc:162-354``).  In-graph it lowers to
    ``lax.psum_scatter``; eagerly each worker receives its reduced 1/P
    slice through the same negotiated runtime as the other ops."""
    if _is_traced(tensor):
        try:
            return _injit_reducescatter(tensor, op, _axis_names(axis_name))
        except NameError as e:
            _reraise_unbound(e)
    _validate_reducescatter(tensor, op)
    basics._ctx()
    rt = _native_rt()
    if rt is not None:
        treedef, pairs = _native_submit_tree(
            rt, "reducescatter", tensor, name, reduce_op=_native_reduce_op(op)
        )
        return _native_wait_tree(rt, treedef, pairs)
    return jax.tree_util.tree_map(lambda t: _eager_reducescatter(t, op), tensor)


def _validate_reducescatter(tensor, op: str) -> None:
    """Fail fast with a local ValueError (identically on every rank, since
    shapes match by contract) instead of letting the background executor
    surface an opaque cross-rank NativeError after a negotiation round."""
    if op not in (Average, Sum):
        raise ValueError("reducescatter supports Sum/Average")
    P = basics.cross_size() if basics.is_initialized() else 1
    for leaf in jax.tree_util.tree_leaves(tensor):
        a = np.asarray(leaf)
        if a.ndim == 0:
            raise ValueError("reducescatter requires tensors with >= 1 dim")
        if a.shape[0] % max(P, 1) != 0:
            raise ValueError(
                f"reducescatter requires dim0 ({a.shape[0]}) divisible by "
                f"the worker count ({P})"
            )


def barrier() -> None:
    """Block until all processes arrive (eager, process-level).  With the
    native runtime this is a true BARRIER request through the coordinator;
    otherwise a zero-byte allreduce."""
    basics._ctx()
    rt = _native_rt()
    if rt is not None:
        rt.barrier()
        return
    _eager_allreduce(np.zeros((), np.float32), Sum, None, None)


# --- handle-based async API --------------------------------------------------
#
# Mirrors torch/mpi_ops.py:72-508 + handle_manager.cc:21-55.  Eager jax
# dispatch is already asynchronous, so a handle wraps the in-flight arrays;
# ``synchronize`` materializes them.


class _HandleManager:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._next = 0
        self._results: dict[int, Any] = {}

    def allocate(self, value) -> int:
        with self._lock:
            h = self._next
            self._next += 1
            self._results[h] = value
            return h

    def take(self, handle: int):
        with self._lock:
            if handle not in self._results:
                raise ValueError(f"Unknown or already-synchronized handle {handle}")
            return self._results.pop(handle)

    def peek(self, handle: int):
        with self._lock:
            return self._results.get(handle)


_handles = _HandleManager()


class _NativeInFlight:
    """An op pending in the native runtime's negotiation queue (the
    reference's handle, ``torch/mpi_ops_v2.cc`` + ``handle_manager.cc:
    21-55``).  Carries the compression context so ``synchronize``
    decompresses, matching the synchronous path."""

    def __init__(self, rt, treedef, pairs, compression=None, ctx=None):
        self.rt = rt
        self.treedef = treedef
        self.pairs = pairs
        self.compression = compression
        self.ctx = ctx

    def done(self) -> bool:
        return all(self.rt.poll(h) for h, _ in self.pairs)

    def resolve(self):
        out = _native_wait_tree(self.rt, self.treedef, self.pairs)
        if self.compression is not None:
            out = self.compression.decompress(out, self.ctx)
        return out


def _async(fn, *args, **kw) -> int:
    return _handles.allocate(fn(*args, **kw))


def allreduce_async(tensor, op: str = Average, name=None, **kw) -> int:
    _check_op(op)
    rt = None if _is_traced(tensor) else _native_rt()
    if rt is not None:
        basics._ctx()
        compression = kw.get("compression")
        ctx = None
        if compression is not None:
            tensor, ctx = compression.compress(tensor)
        pre = kw.get("prescale_factor")
        post = kw.get("postscale_factor")
        treedef, pairs = _native_submit_tree(
            rt, "allreduce", tensor, name,
            reduce_op=_native_reduce_op(op),
            prescale=1.0 if pre is None else pre,
            postscale=1.0 if post is None else post,
        )
        return _handles.allocate(
            _NativeInFlight(rt, treedef, pairs, compression, ctx)
        )
    return _async(allreduce, tensor, op, name=name, **kw)


def allgather_async(tensor, name=None, **kw) -> int:
    rt = None if _is_traced(tensor) else _native_rt()
    if rt is not None:
        basics._ctx()
        treedef, pairs = _native_submit_tree(rt, "allgather", tensor, name)
        return _handles.allocate(_NativeInFlight(rt, treedef, pairs))
    return _async(allgather, tensor, name=name, **kw)


def broadcast_async(tensor, root_rank: int = 0, name=None, **kw) -> int:
    rt = None if _is_traced(tensor) else _native_rt()
    if rt is not None:
        basics._ctx()
        treedef, pairs = _native_submit_tree(
            rt, "broadcast", tensor, name, root_rank=root_rank
        )
        return _handles.allocate(_NativeInFlight(rt, treedef, pairs))
    return _async(broadcast, tensor, root_rank, name=name, **kw)


def reducescatter_async(tensor, op: str = Average, name=None, **kw) -> int:
    rt = None if _is_traced(tensor) else _native_rt()
    if rt is not None:
        _validate_reducescatter(tensor, op)
        basics._ctx()
        treedef, pairs = _native_submit_tree(
            rt, "reducescatter", tensor, name, reduce_op=_native_reduce_op(op)
        )
        return _handles.allocate(_NativeInFlight(rt, treedef, pairs))
    return _async(reducescatter, tensor, op, name=name, **kw)


def alltoall_async(tensor, splits=None, name=None, **kw) -> int:
    rt = None if _is_traced(tensor) else _native_rt()
    if rt is not None and splits is None:
        basics._ctx()
        treedef, pairs = _native_submit_tree(rt, "alltoall", tensor, name)
        return _handles.allocate(_NativeInFlight(rt, treedef, pairs))
    return _async(alltoall, tensor, splits, name=name, **kw)


# In-place variants: JAX arrays are immutable; these are aliases kept for
# API parity with allreduce_async_ / broadcast_async_ (torch/mpi_ops.py).
allreduce_async_ = allreduce_async
broadcast_async_ = broadcast_async


def poll(handle: int) -> bool:
    """True if the op behind ``handle`` has completed
    (``horovod_torch_poll``, ``handle_manager.cc:34-41``)."""
    val = _handles.peek(handle)
    if val is None:
        return True
    if isinstance(val, _NativeInFlight):
        return val.done()
    done = True
    for leaf in jax.tree_util.tree_leaves(val):
        if isinstance(leaf, jax.Array):
            done = done and leaf.is_ready()
    return done


def synchronize(handle: int):
    """Wait for and return the result of an async op
    (``torch/mpi_ops.py`` ``synchronize``)."""
    val = _handles.take(handle)
    if isinstance(val, _NativeInFlight):
        return val.resolve()
    return jax.tree_util.tree_map(
        lambda l: jax.block_until_ready(l) if isinstance(l, jax.Array) else l, val
    )
