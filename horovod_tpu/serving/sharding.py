"""Tensor-parallel serving: one engine's compiled tick under GSPMD
over a ``tp`` mesh (docs/serving.md "Tensor-parallel replicas").

The paper's whole move is that the reference's background
negotiate/fuse/launch machinery becomes collectives COMPILED INTO the
XLA program; this module applies it to the serving tick so ONE engine
serves a model bigger than one chip.  Megatron-style tensor
parallelism, expressed purely as sharding annotations on the same
executables the single-chip engine runs:

* a ``tp`` mesh built from :class:`~horovod_tpu.parallel.meshes.
  MeshSpec` (the innermost/ICI-hungry axis of the training mesh
  convention), over the first ``tp`` local devices;
* params placed per :func:`~horovod_tpu.models.transformer.
  serving_param_specs` — attention heads and the MLP hidden dim split
  over ``tp``, embeddings at the vocab dim, norms replicated;
* the paged KV page pool head-dim sharded per :func:`~horovod_tpu.
  models.transformer.paged_pool_specs` — pages split BY HEAD, never by
  page id, so page tables, grants, refcounts, and COW stay host-side
  and sharding-oblivious (replicated tick data, exactly as before);
* every compiled tick body — ``decode_step_paged``,
  ``prefill_with_prefix``, ``decode_verify_paged``,
  ``sample_token_rows`` — jitted with in/out shardings so XLA inserts
  the head-gather / psum collectives itself.  Sharding is an
  ANNOTATION on the same code, which is why everything downstream
  (chunked prefill, speculative verify, sampling columns,
  journal/resume, SSE failover) composes unchanged and output stays
  token-identical to the tp=1 oracle.

Testable on CPU via forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, the
``tests/test_gspmd_multiprocess.py`` trick); :func:`ensure_devices`
arms that from inside a process when the backend is not yet up.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import transformer as T
from horovod_tpu.parallel.meshes import MeshSpec, make_mesh

__all__ = ["ShardingConfigError", "ServingSharding", "ensure_devices",
           "make_tp_mesh", "validate_tp"]

_FORCE_FLAG = "--xla_force_host_platform_device_count"


class ShardingConfigError(ValueError):
    """A tensor-parallel configuration the mesh/model cannot honor —
    raised TYPED at engine construction, never left to surface as an
    XLA shape crash mid-serving."""


def ensure_devices(n: int) -> None:
    """Best-effort: make at least ``n`` devices visible BEFORE the
    backend initializes (CPU hosts: the forced-host-device XLA flag;
    accelerators already expose their real topology).  The ONE copy of
    the flag-arming every ``--tp`` entry point (replica_main,
    examples/serve.py, benchmarks/serving.py) calls.  An already-set
    flag is respected, whatever its value — the supervisor/operator
    owns it then, and too few devices surface as the typed
    :class:`ShardingConfigError` at engine construction, not a silent
    misconfig.  Importing jax does not initialize the backend, so this
    is safe to call after imports as long as no op has run."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()


def validate_tp(cfg: "T.TransformerConfig", tp: int,
                n_devices: Optional[int] = None) -> None:
    """Typed divisibility/topology checks for a tp serving mesh.

    Heads are the unit tensor parallelism splits (wq/wo at ``n_heads``,
    wk/wv and the KV pool at ``kv_heads``), so both must divide by
    ``tp``; everything else (d_ff, vocab) GSPMD pads without a
    correctness cost.  Raises :class:`ShardingConfigError` — at
    construction, not as an XLA shape crash inside the first tick."""
    if tp < 1:
        raise ShardingConfigError(f"tp must be >= 1, got {tp}")
    if cfg.n_heads % tp:
        raise ShardingConfigError(
            f"n_heads={cfg.n_heads} not divisible by tp={tp}; "
            f"attention heads are the tensor-parallel split unit")
    if cfg.kv_heads % tp:
        raise ShardingConfigError(
            f"kv_heads={cfg.kv_heads} (n_kv_heads={cfg.n_kv_heads}) "
            f"not divisible by tp={tp}; the KV pool shards by kv head")
    if n_devices is not None and tp > n_devices:
        raise ShardingConfigError(
            f"tp={tp} exceeds the {n_devices} visible devices "
            f"(CPU hosts: XLA_FLAGS={_FORCE_FLAG}={tp})")


def make_tp_mesh(tp: int,
                 devices: Optional[Sequence[jax.Device]] = None):
    """A serving mesh with ``tp`` on the innermost axis (the
    :data:`~horovod_tpu.parallel.meshes.AXIS_ORDER` convention: tp maps
    to ICI neighbors), over ``devices`` or the first ``tp`` local
    devices.  Training-only axes exist at size 1, so
    ``serving_param_specs``'s replicate-unknown-axes rule applies
    unchanged."""
    if devices is None:
        devices = jax.devices()
        if tp > len(devices):
            raise ShardingConfigError(
                f"tp={tp} exceeds the {len(devices)} visible devices "
                f"(CPU hosts: XLA_FLAGS={_FORCE_FLAG}={tp})")
        devices = devices[:tp]
    if len(devices) != tp:
        raise ShardingConfigError(
            f"tp={tp} mesh needs exactly tp devices, got {len(devices)}")
    return make_mesh(MeshSpec(tp=tp), devices)


class ServingSharding:
    """One tp serving mesh plus every NamedSharding the engine's
    executables need — built once at engine construction, then handed
    to ``jax.jit`` as in/out shardings (and to ``device_put`` for
    params and the page pool).

    ``draft_cfg`` (speculative model drafts) is validated against the
    SAME mesh: the draft pool is slot-aligned with the target pool, so
    it shards by its own kv heads over the same ``tp`` axis.
    """

    def __init__(self, cfg: "T.TransformerConfig", tp: int, *,
                 devices: Optional[Sequence[jax.Device]] = None,
                 draft_cfg: Optional["T.TransformerConfig"] = None):
        validate_tp(cfg, tp,
                    len(devices) if devices is not None
                    else len(jax.devices()))
        if draft_cfg is not None:
            validate_tp(draft_cfg, tp)
        self.cfg = cfg
        self.tp = tp
        self.mesh = make_tp_mesh(tp, devices)
        #: the replicated sharding every host-data tick input (tokens,
        #: masks, tables, sampling columns) and every host-fetched
        #: output (next tokens, max logits, acceptance) pins to — a
        #: STABLE signature, so committed fed-back outputs and fresh
        #: host uploads hit the same executable (zero decode
        #: recompiles across churn).
        self.replicated = NamedSharding(self.mesh, P())

    # -- sharding trees ----------------------------------------------------

    def param_shardings(self,
                        cfg: Optional["T.TransformerConfig"] = None,
                        params: Optional[Dict] = None):
        # serving_shardings is the ONE spec->NamedSharding mapping
        # (T.shard_params routes through it too); with ``params`` each
        # spec is fitted to that tree's leaf (an engine's projections
        # are stored with heads and head size as one axis).
        param_sh, _ = T.serving_shardings(
            self.mesh, cfg if cfg is not None else self.cfg, params)
        return param_sh

    def shard_params(self, params: Dict,
                     cfg: Optional["T.TransformerConfig"] = None) -> Dict:
        return jax.device_put(params, self.param_shardings(cfg, params))

    def pool_shardings(self, quantized: bool = False) -> Dict:
        return {k: NamedSharding(self.mesh, s)
                for k, s in T.paged_pool_specs(quantized).items()}

    def prefill_cache_shardings(self) -> Dict:
        """Out-shardings for a prefill's ``(logits-companion) cache``
        block — head-sharded K/V, replicated per-row pos — so the
        landing scatter into the sharded pool is local."""
        specs = T.cache_specs()
        return {k: NamedSharding(self.mesh, specs[k])
                for k in ("k", "v", "pos")}

    def prefix_kv_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, T.prefix_kv_specs())

    def paged_kernel_shardings(self, quantized: bool = False):
        """NamedShardings for the fused paged-attention kernel's
        operands/results (:func:`~horovod_tpu.models.transformer.
        paged_kernel_specs` order: ``(q, k_pool, v_pool[, k_scale,
        v_scale], table, limit, layer)`` / ``(o, lse)``, the pools the
        stacked ``(L, P, H_kv, page, Dh)``).  The kernel runs
        per-(slot, kv-head) with no cross-head traffic, so the
        head-dim-sharded pool passes straight through: the tick's
        ``shard_map`` uses the raw specs, and these placements exist so
        callers (tests, benchmarks, ahead-of-time placement) can pin
        kernel operands consistently with the pool they came from."""
        in_specs, out_specs = T.paged_kernel_specs(quantized)
        return ([NamedSharding(self.mesh, s) for s in in_specs],
                [NamedSharding(self.mesh, s) for s in out_specs])

    # -- observability -----------------------------------------------------

    def describe(self) -> str:
        """The ``/stats`` ``mesh`` value: a stable, typed (str)
        one-liner of the mesh layout and device set, e.g.
        ``"tp=2 devices=cpu:0,1"``."""
        devs = list(self.mesh.devices.flat)
        ids = ",".join(str(d.id) for d in devs)
        return f"tp={self.tp} devices={devs[0].platform}:{ids}"
