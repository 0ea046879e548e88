"""horovod_tpu: a TPU-native distributed training framework with the
capability set of Horovod v0.19 (reference: nzmora/horovod), re-designed for
JAX/XLA/pjit/Pallas over ICI/DCN device meshes.

Typical use (the Horovod "minimal code change" contract, README.rst:37):

    import horovod_tpu as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    step = hvd.spmd.make_train_step(loss_fn, opt)   # compiled SPMD step
    params = hvd.broadcast_parameters(params, root_rank=0)
"""

from horovod_tpu.basics import (
    AXIS,
    CROSS_AXIS,
    LOCAL_AXIS,
    NotInitializedError,
    axis_name,
    ccl_built,
    cross_rank,
    cross_size,
    ddl_built,
    gloo_built,
    gloo_enabled,
    hierarchical_mesh,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mesh,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    num_processes,
    process_rank,
    rank,
    sharding_for,
    shutdown,
    size,
    worker_index,
    xla_built,
)
from horovod_tpu.ops.collectives import (
    Adasum,
    Average,
    Max,
    Min,
    Product,
    Sum,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    allreduce_async_,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    broadcast_async_,
    grouped_allreduce,
    poll,
    process_sum,
    reducescatter,
    reducescatter_async,
    synchronize,
)
from horovod_tpu.ops.compression import Compression
from horovod_tpu.optim import (
    DistributedAdasumOptimizer,
    DistributedGradientTape,
    DistributedOptimizer,
    distributed_gradients,
)
from horovod_tpu.state import (
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
)
from horovod_tpu.join import join, masked_average
from horovod_tpu import callbacks, data, elastic, obs, spmd, parallel, timeline
from horovod_tpu.compile_cache import place_compile_cache
from horovod_tpu.data import DataLoader
from horovod_tpu.timeline import start_timeline, stop_timeline

__version__ = "0.1.0"

__all__ = [k for k in dir() if not k.startswith("_")]
