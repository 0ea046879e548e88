"""Process/topology core: init, rank/size queries, and the device mesh.

TPU-native re-design of the reference's basics layer
(``horovod/common/basics.py:22-211`` and the C API in
``horovod/common/operations.cc:650-788``).  Differences by design:

* A *worker* is a TPU chip (device), not a process.  One Python process per
  host drives all local chips SPMD-style, so ``size()`` is the total device
  count and ``local_size()`` the per-host device count.  The reference's
  GLOBAL / LOCAL / CROSS communicator triple (``common/common.h:110-114``)
  maps onto a 2-D device mesh with axes ``(cross, local)``: ``local`` rides
  ICI within a host/slice, ``cross`` rides DCN between hosts.
* There is no background thread or negotiation at init: topology is known
  statically from the JAX process environment, and collectives issued inside
  ``jit`` are compiled to XLA collectives whose schedule is identical on all
  processes by SPMD construction (see SURVEY.md §7).
* Multi-process bootstrap replaces MPI_Init (``mpi/mpi_context.cc:103-111``)
  with the JAX distributed runtime: the launcher exports ``HOROVOD_RANK`` /
  ``HOROVOD_SIZE`` / ``HOROVOD_COORDINATOR_ADDR`` and we call
  ``jax.distributed.initialize``.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

logger = logging.getLogger("horovod_tpu")

# HOROVOD_LOG_LEVEL values, matching the reference's leveled logger
# (common/logging.{h,cc}; exported by the launcher's --log-level flag).
_LOG_LEVELS = {
    "trace": logging.DEBUG,
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "fatal": logging.CRITICAL,
}


def _configure_logging() -> None:
    """Apply HOROVOD_LOG_LEVEL to the ``horovod_tpu`` logger.  The native
    runtime reads the same variable itself (native/src/logging.h)."""
    raw = os.environ.get("HOROVOD_LOG_LEVEL", "").lower()
    if not raw:
        return
    if raw not in _LOG_LEVELS:
        logger.warning("HOROVOD_LOG_LEVEL=%r not recognized; using warning", raw)
    logger.setLevel(_LOG_LEVELS.get(raw, logging.WARNING))
    if not logger.handlers and not logging.getLogger().handlers:
        h = logging.StreamHandler()
        h.setFormatter(
            logging.Formatter("[%(asctime)s %(name)s %(levelname)s] %(message)s")
        )
        logger.addHandler(h)

# Default mesh axis name for the flat worker axis (the reference's GLOBAL
# communicator).  All collective ops default to this axis.
AXIS: str = "hvd"
# Hierarchical axis names (reference LOCAL / CROSS communicators).
LOCAL_AXIS: str = "local"
CROSS_AXIS: str = "cross"


class NotInitializedError(RuntimeError):
    """Raised when the API is used before ``init()``.

    Mirrors ``CheckInitialized`` (``common/operations.cc:643``) which raises
    "Horovod has not been initialized; use hvd.init()".
    """

    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; use horovod_tpu.init()."
        )


@dataclass
class _Context:
    """Singleton runtime state (analogue of ``HorovodGlobalState``,
    ``common/global_state.h:42-122`` — minus everything SPMD compilation
    makes unnecessary: tensor queue, fusion buffer, response cache live in
    the eager runtime module instead)."""

    mesh: Mesh
    hierarchical_mesh: Optional[Mesh]
    process_rank: int
    num_processes: int
    local_device_count: int
    axis_name: str = AXIS
    elastic_enabled: bool = False
    timeline: Optional[object] = None  # horovod_tpu.timeline.Timeline
    autotuner: Optional[object] = None
    lock: threading.Lock = field(default_factory=threading.Lock)


_context: Optional[_Context] = None


def _parse_env_int(*names: str) -> Optional[int]:
    for n in names:
        v = os.environ.get(n)
        if v is not None and v != "":
            try:
                return int(v)
            except ValueError:
                raise ValueError(f"Environment variable {n}={v!r} is not an int")
    return None


def _bootstrap_distributed() -> None:
    """Connect this process to the cluster coordination service.

    Replaces the reference's MPI bootstrap + Gloo HTTP rendezvous
    (``gloo/gloo_context.cc:113-160``): the launcher exports
    ``HOROVOD_RANK``/``HOROVOD_SIZE``/``HOROVOD_COORDINATOR_ADDR`` and every
    process dials the JAX coordination service instead of an MPI runtime.
    """
    nproc = _parse_env_int("HOROVOD_NUM_PROC", "HOROVOD_CROSS_SIZE")
    rank = _parse_env_int("HOROVOD_RANK", "HOROVOD_CROSS_RANK")
    addr = os.environ.get("HOROVOD_COORDINATOR_ADDR") or os.environ.get(
        "HOROVOD_GLOO_RENDEZVOUS_ADDR"
    )
    if nproc is None or nproc <= 1:
        return
    # Must not touch the XLA backend before jax.distributed.initialize
    # (jax.process_count() would initialize it); inspect the coordination
    # client state directly.
    from jax._src import distributed as _jd

    if _jd.global_state.client is not None:
        return  # already initialized (e.g. by the TPU runtime itself)
    # The JAX coordination service needs its own port: the launcher's
    # HOROVOD_COORDINATOR_PORT is the rendezvous KV server, so rank 0 binds
    # KV+2 for the gRPC service unless HOROVOD_JAX_PORT says otherwise.
    jax_port = os.environ.get("HOROVOD_JAX_PORT")
    if jax_port is None:
        base = os.environ.get("HOROVOD_COORDINATOR_PORT")
        jax_port = str(int(base) + 2) if base else "9373"
    if addr is None:
        addr = f"127.0.0.1:{jax_port}"
    elif ":" not in addr:
        addr = f"{addr}:{jax_port}"
    # Cross-process CPU collectives (multi-process CPU jobs: the tests,
    # the launcher on a chipless host) go over gloo; set before the
    # backend initializes.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=nproc, process_id=rank
    )


def _build_meshes(devices: Sequence[jax.Device], axis_name: str):
    """Build the flat worker mesh and, when the topology is homogeneous,
    the hierarchical ``(cross, local)`` mesh.

    Device order is (process, local-device) lexicographic so that worker
    rank = process_rank * local_size + local_index, matching the rank layout
    the reference computes in ``MPIController::Initialize``
    (``mpi/mpi_controller.cc:25-81``).
    """
    devs = sorted(devices, key=lambda d: (d.process_index, d.id))
    arr = np.array(devs, dtype=object)
    mesh = Mesh(arr, axis_names=(axis_name,))

    # Homogeneity check (reference: is_homogeneous_,
    # mpi/mpi_controller.cc — all nodes must have equal local_size for
    # hierarchical ops to be enabled).
    per_proc: dict[int, int] = {}
    for d in devs:
        per_proc[d.process_index] = per_proc.get(d.process_index, 0) + 1
    sizes = set(per_proc.values())
    hier = None
    if len(sizes) == 1:
        local = sizes.pop()
        cross = len(per_proc)
        if cross * local == len(devs):
            hier = Mesh(
                arr.reshape(cross, local), axis_names=(CROSS_AXIS, LOCAL_AXIS)
            )
    return mesh, hier


def init(
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = AXIS,
) -> None:
    """Initialize horovod_tpu.  Idempotent.

    Analogue of ``hvd.init()`` → ``horovod_init`` → ``InitializeHorovodOnce``
    (``common/operations.cc:593-639``), except nothing asynchronous happens:
    there is no background thread to spawn because collective scheduling is
    done by XLA at compile time.  What remains is (1) optional multi-process
    bootstrap, (2) mesh construction, (3) auxiliary-subsystem setup
    (timeline, autotune) driven by the same ``HOROVOD_*`` env vars the
    reference parses in ``BackgroundThreadLoop``
    (``common/operations.cc:392-489``).
    """
    global _context
    if _context is not None:
        return
    _configure_logging()
    _bootstrap_distributed()
    if devices is None:
        # No fallback: a configured platform that cannot initialize
        # (JAX_PLATFORMS=tpu with no chip, a chip another process holds)
        # raises here rather than continuing on CPU.
        devices = jax.devices()
    mesh, hier = _build_meshes(devices, axis_name)
    local = [d for d in devices if d.process_index == jax.process_index()]
    _context = _Context(
        mesh=mesh,
        hierarchical_mesh=hier,
        process_rank=jax.process_index(),
        num_processes=jax.process_count(),
        local_device_count=len(local) if local else len(devices),
        axis_name=axis_name,
    )

    # Native control-plane runtime (C++): negotiation/fusion/cache/stall/
    # timeline for the eager path.  Optional — without it eager ops run
    # directly in program order.
    native_rt = None
    try:
        from horovod_tpu import eager_runtime
        from horovod_tpu.timeline import expand_rank_path

        native_rt = eager_runtime.start(
            timeline_path=expand_rank_path(
                os.environ.get("HOROVOD_TIMELINE", ""))
        )
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("native runtime unavailable, using direct path: %s", e)

    # Auxiliary subsystems, env-gated exactly like the reference.  When the
    # native runtime is up it owns the HOROVOD_TIMELINE file (rank 0); the
    # Python Timeline otherwise.
    timeline_path = os.environ.get("HOROVOD_TIMELINE")
    if timeline_path and native_rt is None:
        from horovod_tpu.timeline import Timeline

        elastic = os.environ.get("HOROVOD_ELASTIC", "0") \
            not in ("", "0", "false")
        if "%r" in timeline_path:
            # Explicit per-rank substitution: every rank records its
            # own file (merge with `python -m horovod_tpu.obs.merge`).
            _context.timeline = Timeline(timeline_path)
        elif elastic and _context.num_processes > 1:
            # Elastic multi-process default: rank-suffix the path —
            # N respawning ranks all writing one literal path would
            # silently clobber each other's traces.
            root, ext = os.path.splitext(timeline_path)
            _context.timeline = Timeline(f"{root}.rank%r{ext or '.json'}")
        elif _context.process_rank == 0:  # rank 0 writes, like the reference
            _context.timeline = Timeline(timeline_path)
    if os.environ.get("HOROVOD_AUTOTUNE", "0") not in ("", "0", "false"):
        from horovod_tpu.autotune import Autotuner

        _context.autotuner = Autotuner.from_env()
    _register_process_metrics(_context)
    logger.debug(
        "horovod_tpu initialized: size=%d local_size=%d process=%d/%d",
        mesh.devices.size,
        _context.local_device_count,
        _context.process_rank,
        _context.num_processes,
    )


def _register_process_metrics(ctx: _Context) -> None:
    """Seed the process-wide observability registry at init: topology
    gauges plus the training and elastic metric FAMILIES (so a
    ``/metrics`` scrape always exposes them, zero-valued until used —
    probes should not have to special-case a cold process)."""
    try:
        from horovod_tpu.obs import registry as obs_registry

        r = obs_registry.default_registry()
        r.counter("horovod_inits_total",
                  "horovod_tpu.init() calls (re-inits included)",
                  exist_ok=True).inc()
        r.gauge("horovod_world_size", "Total workers (TPU chips)",
                exist_ok=True).set(ctx.mesh.devices.size)
        r.gauge("horovod_local_size", "Workers on this host",
                exist_ok=True).set(ctx.local_device_count)
        r.gauge("horovod_num_processes", "Processes in the job",
                exist_ok=True).set(ctx.num_processes)
        obs_registry.training_metrics()
        obs_registry.elastic_metrics()
        from horovod_tpu import timeline as _timeline_mod

        _timeline_mod._dropped_events_counter()
    except Exception as e:  # pragma: no cover - metrics never gate init
        logger.warning("observability registry unavailable: %s", e)


def shutdown() -> None:
    """Tear down runtime state (``horovod_shutdown``,
    ``common/operations.cc:652+``)."""
    global _context
    if _context is None:
        return
    try:
        from horovod_tpu import eager_runtime

        eager_runtime.stop()
    except Exception:  # pragma: no cover - defensive
        pass
    if _context.timeline is not None:
        _context.timeline.close()
    _context = None


def reinit(
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_name: str = AXIS,
) -> None:
    """Tear down and re-initialize the runtime (elastic restart path).

    Used by ``elastic.run`` after a membership change when the mesh can be
    rebuilt in-process: stops the eager runtime (closing its control-plane
    sockets), drops the context, and re-runs :func:`init` over the current
    environment/devices.  Multi-process jobs cannot re-rendezvous
    in-process (the JAX coordination service is bound to the dead world's
    membership) — the ElasticDriver respawns those ranks with fresh epoch
    env instead."""
    shutdown()
    init(devices=devices, axis_name=axis_name)
    try:
        from horovod_tpu.obs import tracing as obs_tracing
        from horovod_tpu.obs.registry import elastic_metrics

        elastic_metrics().rendezvous.inc()
        obs_tracing.instant("elastic_rerendezvous", {
            "epoch": os.environ.get("HOROVOD_ELASTIC_EPOCH"),
            "size": size()})
    except Exception:  # pragma: no cover - metrics never gate recovery
        pass


atexit.register(shutdown)


def is_initialized() -> bool:
    """``horovod_is_initialized`` equivalent."""
    return _context is not None


def _ctx() -> _Context:
    if _context is None:
        raise NotInitializedError()
    return _context


def mesh() -> Mesh:
    """The flat worker mesh (1-D, axis ``hvd``): the GLOBAL communicator."""
    return _ctx().mesh


def hierarchical_mesh() -> Optional[Mesh]:
    """The 2-D ``(cross, local)`` mesh, or None if hosts are heterogeneous.

    ``local`` maps to ICI within a host/slice and ``cross`` to DCN across
    hosts — the reference's LOCAL/CROSS communicators
    (``common/common.h:110-114``) realized as mesh axes.
    """
    return _ctx().hierarchical_mesh


def axis_name() -> str:
    return _ctx().axis_name


def size() -> int:
    """Total number of workers (TPU chips).  ``horovod_size``."""
    return int(_ctx().mesh.devices.size)


def local_size() -> int:
    """Workers on this host.  ``horovod_local_size``."""
    return _ctx().local_device_count


def cross_size() -> int:
    """Number of processes/hosts.  ``horovod_cross_size``."""
    return _ctx().num_processes


def rank() -> int:
    """Lowest global worker rank owned by this process.

    With one chip per process this equals the reference's ``horovod_rank``;
    with N local chips the process speaks for workers
    ``[rank(), rank() + local_size())``.  Inside compiled code use
    :func:`worker_index` for the per-chip rank.
    """
    c = _ctx()
    return c.process_rank * c.local_device_count


def local_rank() -> int:
    """Process-level local rank (0 for the first process on a host).

    The reference's ``horovod_local_rank`` identifies which GPU of the host a
    process drives; here a process drives all local chips, so this is 0 and
    the per-chip index lives in-graph (:func:`worker_index` modulo
    ``local_size``)."""
    return 0


def cross_rank() -> int:
    """Process index (host index).  ``horovod_cross_rank``."""
    return _ctx().process_rank


def process_rank() -> int:
    return _ctx().process_rank


def num_processes() -> int:
    return _ctx().num_processes


def is_homogeneous() -> bool:
    """True if all hosts drive the same number of chips
    (``horovod_is_homogeneous``, ``mpi/mpi_controller.cc``)."""
    return _ctx().hierarchical_mesh is not None


def worker_index(axis: Optional[str] = None):
    """Per-chip rank, traced: ``jax.lax.axis_index`` over the worker axis.

    Only valid inside ``shard_map``/``pmap`` where the axis is bound.
    """
    return jax.lax.axis_index(axis or _ctx().axis_name)


# --- build-capability introspection (reference: horovod/common/util.py &
# basics.py mpi_built/gloo_built/nccl_built/...) ------------------------------

def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def xla_built() -> bool:
    """The one true backend: XLA collectives over ICI/DCN."""
    return True


def mpi_threads_supported() -> bool:
    return False


def sharding_for(spec: PartitionSpec, *, hierarchical: bool = False) -> NamedSharding:
    """Convenience: a NamedSharding over the global (or hierarchical) mesh."""
    m = hierarchical_mesh() if hierarchical else mesh()
    if m is None:
        raise ValueError("hierarchical mesh unavailable (heterogeneous hosts)")
    return NamedSharding(m, spec)
