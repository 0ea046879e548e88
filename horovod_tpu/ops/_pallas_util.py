"""Shared Pallas TPU plumbing for the fused kernels.

Every Pallas kernel in this package (flash attention in
:mod:`~horovod_tpu.ops.attention`, the fused paged-attention decode
kernel in :mod:`~horovod_tpu.ops.paged_attention`) needs the same
scaffolding, factored here so it cannot drift apart:

* :func:`use_interpret` — compiled on TPU, the Pallas interpreter on
  CPU (so the tier-1 CPU suite exercises the REAL kernel body), an
  error anywhere else;
* :func:`out_sds` — ``ShapeDtypeStruct`` that inherits an operand's
  varying-over-mesh-axes type, so a ``pallas_call`` type-checks inside
  ``shard_map`` (ring attention runs per sequence shard, the paged
  decode kernel per tp head shard);
* :func:`smem_spec` / :func:`scalar_operand` — the cached SMEM
  ``BlockSpec`` for scalar operands and the varying-type-matched (1,)
  int32 wrapper that keeps a traced scalar compatible with sharded
  tensor operands.

``NEG_INF`` is the shared finite mask value: ``exp(NEG_INF - x) == 0``
for any real ``x``, and fully-masked rows report ``NEG_INF`` as their
logsumexp so they vanish in cross-block/cross-source merges.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["NEG_INF", "pl", "pltpu", "use_interpret", "out_sds",
           "scalar_operand", "smem_spec"]

NEG_INF = -1e30  # finite mask value: exp(NEG_INF - anything_real) == 0


def use_interpret() -> bool:
    """False on TPU (the kernel is compiled by Mosaic), True on CPU (the
    Pallas interpreter runs the same kernel body).  Any other backend
    is an error: these are TPU kernels and nothing else may quietly
    interpret them."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"horovod_tpu Pallas kernels run compiled on 'tpu' and "
        f"interpreted on 'cpu'; backend {backend!r} is not supported")


def out_sds(shape, dtype, like):
    """ShapeDtypeStruct that inherits ``like``'s varying-over-mesh-axes
    type, so the pallas_call type-checks inside ``shard_map``."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def scalar_operand(value, like):
    """(1,) int32 SMEM operand for the kernels (0 when ``value`` is
    None), cast to ``like``'s varying-over-axis type."""
    arr = jnp.asarray(0 if value is None else value, jnp.int32).reshape(1)
    need = tuple(jax.typeof(like).vma - jax.typeof(arr).vma)
    if need:  # match the tensor operands' varying-over-axis type
        arr = jax.lax.pcast(arr, need, to="varying")
    return arr


_SMEM_SPEC = None


def smem_spec():
    """The cached whole-array SMEM BlockSpec for scalar operands."""
    global _SMEM_SPEC
    if _SMEM_SPEC is None:
        _SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)
    return _SMEM_SPEC
