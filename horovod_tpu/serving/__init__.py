"""Continuous-batching inference serving (docs/serving.md).

The paper's fusion-of-pending-work architecture applied to decoding:
one compiled ``decode_step_paged`` executable hot over a fixed set of
slots whose K/V lives in a page pool, a bounded FCFS scheduler admitting requests (one batched
batch-K prefill per tick) into freed slots with zero recompilation,
and a threaded stdlib-HTTP front — wrapped in a fault-tolerance layer
(supervised tick restarts, a watchdog against hung ticks, typed
failure propagation, cancellation, graceful drain) whose invariant is
that every submitted request resolves in bounded time with tokens or
a typed error.  In-flight requests are DURABLE (docs/serving.md
"Operations"): their decode state is journaled
(:mod:`horovod_tpu.serving.journal`), restarts RESUME them
token-identically instead of failing them, and the front tier
continues a dead replica's partially decoded requests on a survivor
from the journal's resume descriptor.  The decode hot loop is a device/host pipeline
(``EngineConfig.overlap``, default on): device-resident tokens feed
tick N's output straight into tick N+1's dispatch while host
bookkeeping runs one tick behind — token-identical to the synchronous
path (docs/serving.md "Performance").

    from horovod_tpu import serving
    engine = serving.InferenceEngine(params, cfg,
                                     serving.EngineConfig(n_slots=8))
    with serving.ServingServer(engine, port=8000):
        ...
"""

from horovod_tpu.serving.cache import (
    PagedSlotCache,
    init_page_pool,
)
from horovod_tpu.serving.engine import (
    DEGRADED,
    DRAINING,
    FAILED,
    HEALTHY,
    EngineConfig,
    GenerationFuture,
    InferenceEngine,
)
from horovod_tpu.serving.faults import (
    FaultInjector,
    FaultSpec,
    InjectedFaultError,
)
from horovod_tpu.serving.journal import (
    JournalEntry,
    RequestJournal,
)
from horovod_tpu.serving.metrics import (
    Counter,
    Gauge,
    Histogram,
    ServingMetrics,
)
from horovod_tpu.serving.sampling import (
    SamplingParams,
    SlotSampling,
)
from horovod_tpu.ops.paged_attention import UnsupportedPagedLayoutError
from horovod_tpu.serving.sharding import (
    ServingSharding,
    ShardingConfigError,
)
from horovod_tpu.serving.sse import (
    SSEParser,
    event_bytes,
)
from horovod_tpu.serving.scheduler import (
    PRIORITY_CLASSES,
    CacheOutOfPagesError,
    DeadlineExceededError,
    DrainingError,
    EngineFailedError,
    EngineStalledError,
    QueueFullError,
    Request,
    RequestTooLongError,
    Scheduler,
    ServingError,
    priority_rank,
)
from horovod_tpu.serving.server import ServingServer
# The replicated front tier (router subpackage) — imported last: it
# builds ON the engine/server modules above, never the reverse.
from horovod_tpu.serving import router  # noqa: E402  (docs/serving.md "Front tier")

__all__ = [
    "router",
    "PagedSlotCache", "init_page_pool",
    "EngineConfig", "GenerationFuture", "InferenceEngine",
    "HEALTHY", "DEGRADED", "DRAINING", "FAILED",
    "FaultInjector", "FaultSpec", "InjectedFaultError",
    "JournalEntry", "RequestJournal",
    "Counter", "Gauge", "Histogram", "ServingMetrics",
    "SamplingParams", "SlotSampling", "SSEParser", "event_bytes",
    "ServingSharding", "ShardingConfigError",
    "UnsupportedPagedLayoutError",
    "CacheOutOfPagesError", "DeadlineExceededError", "DrainingError",
    "EngineFailedError", "EngineStalledError", "QueueFullError",
    "Request", "RequestTooLongError", "Scheduler", "ServingError",
    "ServingServer",
]
