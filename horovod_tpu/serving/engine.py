"""Continuous-batching inference engine.

The paper's core move — a background controller that fuses pending work
from many independent callers into one efficient device operation —
applied to decoding: ONE compiled ``decode_step_paged`` executable stays
hot over a fixed set of S slots whose K/V lives in a pool of fixed-size
pages (:class:`~horovod_tpu.serving.cache.PagedSlotCache`), and new
requests land in freed slots between ticks via a bucketed prefill
scattered into the slot's pages, with zero recompilation of the decode
step (the live set and the page tables are data — an ``(S,)`` active
mask and an ``(S, max_pages)`` table — not structure).

Tick loop (:meth:`InferenceEngine.step`):

1. **Admit**: drain up to K requests from the scheduler into free slots
   (K = ``max_prefills_per_tick`` bounds the decode stall, so TTFT and
   tok/s are both bounded).  The whole group is admitted by ONE
   bucketed batch-K prefill (prompts right-padded to a shared
   power-of-two bucket, per-row ``true_len``; compile set bounded by
   buckets x K), whose last-real-position logits yield each request's
   FIRST token immediately.
2. **Decode**: one masked ``decode_step_paged`` over all S slots;
   inactive slots compute on zeros (Join-style).  Each active slot's
   next greedy token streams to its future; EOS / max-token / capacity
   retirement frees the slot for the next admission.

With ``EngineConfig.overlap`` (the default) the decode half runs as a
TWO-STAGE PIPELINE — the paper's latency-hiding move (overlap the
expensive device work with the host work that feeds it) applied to the
token loop.  ``tokens``/``active`` live on the device: tick N's output
token vector feeds tick N+1's dispatch directly (JAX async dispatch —
no host round-trip, no re-upload), and the host-side fetch + emission +
retirement bookkeeping for tick N runs while the device is already
computing tick N+1.  Retirement therefore lands with ONE TICK of lag;
a per-dispatch identity snapshot keeps the lag invisible (a slot's
token is emitted only if the slot still holds the request it was
computing for — no token after EOS, no stale row leaking into a
reused slot; see :meth:`_retire_pending`), so greedy output stays
token-identical to the synchronous path (``overlap=False``, the A/B
baseline one flag away) and to per-request ``greedy_decode``.

Greedy decoding is deliberate: it makes the engine's output
TOKEN-IDENTICAL to per-request ``greedy_decode`` (the correctness oracle
in ``tests/test_serving.py``) regardless of which requests share the
batch or when they were admitted.

Fault tolerance (docs/serving.md "Operations"; the runtime analogue of
the training side's typed rank-failure surfacing + ``Join`` + elastic
supervision):

* **Supervised tick loop with DURABLE requests** — any exception out
  of :meth:`step` triggers a supervised restart: fresh
  :class:`PagedSlotCache` (the device cache is suspect after a failure),
  bounded consecutive attempts with exponential backoff,
  ``engine_restarts`` counter.  With ``EngineConfig.resume`` (the
  default) in-flight requests SURVIVE the restart: their decode state
  is journaled (:class:`~horovod_tpu.serving.journal.RequestJournal`
  — original prompt, params, tokens emitted so far), and ``_restart``
  re-admits each by prefilling ``prompt + emitted`` and continuing
  decode with the ORIGINAL future still live — concatenated output
  token-identical to an uninterrupted run, wasted work bounded by one
  tick plus one re-prefill.  ``resume=False`` restores the old
  fail-typed behavior
  (:class:`~horovod_tpu.serving.scheduler.EngineFailedError` on every
  in-flight future).  Queued requests survive either way; only when
  the restart budget is exhausted does the engine go terminally
  ``failed`` and resolve everything typed.
* **Watchdog** — :meth:`start` also runs a watchdog thread against a
  per-tick heartbeat; a tick exceeding ``tick_timeout`` is declared
  *stalled* (hung device call).  With ``resume``, in-flight futures
  are HELD through ``stall_grace`` — a tick that returns inside it
  resumes them token-exact — and only past budget + grace does the
  watchdog resolve everything with
  :class:`~horovod_tpu.serving.scheduler.EngineStalledError` (the
  bounded-resolution backstop).  Without ``resume``, in-flight AND
  queued futures resolve immediately at the stall, as before; either
  way a tick that does return restarts through the supervised path.
* **Lifecycle states** — ``healthy`` / ``degraded`` (just restarted) /
  ``draining`` (shutdown in progress, new submits rejected) /
  ``failed`` (restart budget exhausted or stalled), surfaced through
  :attr:`health`, :meth:`stats`, and the server's ``/healthz``.
* **Cancellation** — :meth:`GenerationFuture.cancel` marks a request;
  the engine reclaims its slot (or purges it from the queue) on the
  next tick and resolves the future with ``finish_reason
  "cancelled"`` and the tokens so far.

The one invariant all of this serves: **every submitted request
resolves, in bounded time, with tokens or a typed error** — proven
under deterministic fault injection
(:class:`~horovod_tpu.serving.faults.FaultInjector`, threaded through
:attr:`EngineConfig.faults`) by ``tests/test_chaos.py``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as T
from horovod_tpu.obs import tracing as obs_tracing
from horovod_tpu.ops import _pallas_util
from horovod_tpu.ops import paged_attention as _pa
from horovod_tpu.serving.cache import (  # noqa: F401
    NULL_PAGE,
    PagedSlotCache,
    resolve_kv_dtype,
)
from horovod_tpu.serving.faults import FaultInjector
from horovod_tpu.serving.journal import RequestJournal
from horovod_tpu.serving.metrics import SLOW_STEP_SECONDS, ServingMetrics
from horovod_tpu.serving.sampling import SlotSampling, seed_key
from horovod_tpu.serving.sampling import validate as validate_sampling
from horovod_tpu.serving.scheduler import (
    CacheOutOfPagesError,
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

__all__ = [
    "EngineConfig", "GenerationFuture", "InferenceEngine",
    "HEALTHY", "DEGRADED", "DRAINING", "FAILED",
]

# Engine lifecycle states (the /healthz vocabulary).  healthy/degraded
# serve traffic (degraded = freshly restarted, not yet proven by a
# clean tick); draining/failed reject new work — load balancers should
# stop routing (non-200 /healthz).
HEALTHY = "healthy"
DEGRADED = "degraded"
DRAINING = "draining"
FAILED = "failed"


class GenerationFuture:
    """Per-request result sink: tokens stream in as the engine emits
    them; :meth:`result` blocks until retirement (or a typed rejection).

    ``on_token(token_id, text_piece)`` fires from the ENGINE thread for
    every emitted token (``text_piece`` is None without a detokenizer) —
    keep it cheap."""

    def __init__(self, on_token: Optional[Callable] = None,
                 detokenize: Optional[Callable[[int], str]] = None):
        self._tokens: List[int] = []
        self._text: List[str] = []
        self._done = threading.Event()
        self._exc: Optional[BaseException] = None
        self._on_token = on_token
        self._detokenize = detokenize
        self._cancel = False
        self._resolve_lock = threading.Lock()
        self.finish_reason: Optional[str] = None
        self.ttft: Optional[float] = None
        # Observability: the request's trace record (stamped by the
        # scheduler/engine as it moves through the stack) and the
        # tracer active at submit time — resolution emits the request
        # span + JSONL line through it, from WHICHEVER thread resolves
        # (engine, watchdog, or HTTP handler).
        self.trace: Optional["obs_tracing.RequestTrace"] = None
        self._tracer: Optional["obs_tracing.Tracer"] = None
        self._spans: Optional["obs_tracing.SpanRecorder"] = None
        # Resolution hook (the engine wires the request's journal
        # purge here): fires exactly once, from whichever thread
        # resolves the future, AFTER the resolution is visible.
        self._on_resolve: Optional[Callable[[], None]] = None

    # engine-side ----------------------------------------------------------
    # Resolution is serialized by _resolve_lock: the watchdog may fail
    # a future from its own thread at the same instant the engine
    # thread finishes it normally — whoever wins the lock resolves the
    # future, the loser is a no-op (a bare done-check would let both
    # pass the guard and leave finish_reason AND an exception set).

    def _add_token(self, tok: int) -> bool:
        """Append one emitted token; returns False if the future was
        already resolved (the caller must not journal a token the
        caller-visible result will never contain)."""
        with self._resolve_lock:
            if self._done.is_set():
                return False
            self._tokens.append(tok)
            piece = None
            if self._detokenize is not None:
                piece = self._detokenize(tok)
                self._text.append(piece)
        if self._on_token is not None:
            self._on_token(tok, piece)
        return True

    def _finish(self, reason: str) -> None:
        with self._resolve_lock:
            if self._done.is_set():
                return
            self.finish_reason = reason
            if self.trace is not None:
                self.trace.finished_at = time.monotonic()
                self.trace.finish = reason
                self.trace.tokens = len(self._tokens)
            self._done.set()
        self._emit_trace()
        self._fire_resolve()

    def set_exception(self, exc: BaseException) -> None:
        with self._resolve_lock:
            if self._done.is_set():
                return
            self._exc = exc
            if self.trace is not None:
                self.trace.finished_at = time.monotonic()
                self.trace.error = type(exc).__name__
                self.trace.tokens = len(self._tokens)
            self._done.set()
        self._emit_trace()
        self._fire_resolve()

    def _fire_resolve(self) -> None:
        # Same once-only guarantee as _emit_trace: only the resolving
        # thread gets past the done-check inside the lock.
        cb = self._on_resolve
        if cb is not None:
            try:
                cb()
            except Exception:  # pragma: no cover - cleanup must not fail work
                pass

    def _emit_trace(self) -> None:
        # Outside _resolve_lock (file/queue IO must not serialize
        # resolution); only the resolving thread reaches here, exactly
        # once — the lock's done-check gates both resolution paths.
        tp, tr = self._tracer, self.trace
        if tp is not None and tr is not None:
            try:
                tp.request_done(tr)
            except Exception:  # pragma: no cover - tracing must not fail work
                pass
        sp = self._spans
        if sp is not None and tr is not None:
            # The span stream gets the finish record + the
            # tail-sampling verdict on the buffered detail spans.
            try:
                sp.request_done(tr)
            except Exception:  # pragma: no cover - spans must not fail work
                pass

    # caller-side ----------------------------------------------------------

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> bool:
        """Request cancellation.  Returns False if the future is
        already resolved, True if cancellation was requested.  The
        engine reclaims the request's slot (or removes it from the
        queue) on its next tick and resolves the future with
        ``finish_reason == "cancelled"`` and the tokens generated so
        far — cancellation resolves, it does not raise."""
        if self._done.is_set():
            return False
        self._cancel = True
        return True

    @property
    def cancel_requested(self) -> bool:
        return self._cancel

    @property
    def cancelled(self) -> bool:
        return self.finish_reason == "cancelled"

    @property
    def trace_id(self) -> Optional[str]:
        return self.trace.trace_id if self.trace is not None else None

    def breakdown(self) -> Optional[Dict]:
        """The request's timing breakdown (queue wait, prefill, decode,
        host-sync lag) — final once the future resolves, measured
        up-to-now while it is still running."""
        return self.trace.breakdown() if self.trace is not None else None

    def tokens_so_far(self) -> List[int]:
        return list(self._tokens)

    @property
    def text(self) -> str:
        return "".join(self._text)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Generated token ids; raises the typed rejection if the request
        never ran, TimeoutError if it is still running at ``timeout``."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in progress")
        if self._exc is not None:
            raise self._exc
        return list(self._tokens)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Continuous-batching knobs (tuning notes: docs/serving.md).

    ``n_slots`` (S) is the decode batch the executable is compiled for;
    ``max_len`` caps prompt + generation per slot (0 = cfg.max_seq);
    ``max_prefills_per_tick`` (K) bounds admissions between decode
    ticks AND sizes the batched prefill that admits them (one batch-K
    prefill per tick, compile set buckets x K); ``max_queue_depth``
    bounds the burst the scheduler absorbs; ``min_prefill_bucket``
    floors the power-of-two prompt buckets so tiny prompts share one
    compile.

    ``overlap`` (default on) runs the decode loop as the two-stage
    device/host pipeline (device-resident tokens, one-tick-lag
    retirement — module docstring); ``overlap=False`` is the
    synchronous A/B baseline: fetch-and-apply in the same step, same
    tokens, ~the device wait slower per tick.

    Paged KV cache (docs/serving.md "Paged KV cache"): K/V live in a
    pool of ``n_pages`` fixed-size pages (``page_size`` tokens each;
    ``n_pages=0`` gives every slot its ``max_len`` worth of pages,
    smaller pools trade worst-case capacity for admission headroom),
    resolved
    through per-slot page tables INSIDE the one compiled tick.  Pages
    are granted on demand at tick boundaries, refcounted for prefix
    sharing (:meth:`InferenceEngine.register_prefix`), and
    copy-on-write: a shared page is copied only when a slot must write
    into it.  ``kv_dtype`` selects page storage: None = the model
    dtype, "bf16" halves f32 cache bytes (exact for bf16 models),
    "int8" quarters them (per-vector scales, dequantize-on-attend —
    lossy).  ``paged`` accepts only ``True`` (the slot-contiguous
    cache went in PR 28) and goes when the benchmark's configuration
    files stop passing it.

    Fault tolerance: ``max_restarts`` bounds CONSECUTIVE supervised
    restarts before the engine goes terminally ``failed`` (a clean tick
    resets the count); ``restart_backoff`` / ``restart_backoff_max``
    shape the exponential backoff between attempts; ``tick_timeout`` is
    the watchdog's per-tick wall-clock budget (0 disables the watchdog;
    the budget must cover the first tick's prefill+decode COMPILATION,
    not just steady-state latency); ``watchdog_interval`` is its poll
    period; ``faults`` threads a deterministic
    :class:`~horovod_tpu.serving.faults.FaultInjector` through the
    engine's failure-prone sites (tests only — leave None in
    production).

    Durability (``resume``, default on — docs/serving.md "Operations"):
    in-flight requests survive supervised restarts.  Every live
    request is journaled (:class:`~horovod_tpu.serving.journal.
    RequestJournal`: original prompt, params, trace id, tokens emitted
    so far); a restart re-admits each one by prefilling ``prompt +
    emitted`` and continuing decode, with the original future staying
    live — concatenated output token-identical to an uninterrupted
    run, wasted work bounded by one tick plus one re-prefill.
    ``resume=False`` restores the PR 3 behavior (in-flight futures
    fail typed on any restart).  ``journal_path`` additionally writes
    the journal as an append-only JSONL file that survives SIGKILL —
    the router reads a dead replica's file to fail partially-decoded
    requests over to a surviving replica (docs/serving.md "Front
    tier").  ``stall_grace`` is how long past ``tick_timeout`` a
    STALLED tick may still return and have its requests resumed;
    beyond it the watchdog hard-fails everything typed, restoring the
    bounded-resolution guarantee (None = one extra ``tick_timeout``;
    ignored when ``resume=False`` — stalls then fail futures
    immediately, as before)."""

    n_slots: int = 4
    max_len: int = 0
    max_prefills_per_tick: int = 2
    overlap: bool = True
    paged: bool = True
    page_size: int = 16
    n_pages: int = 0
    kv_dtype: Optional[str] = None
    # Fused paged-attention decode kernel (docs/serving.md "Paged
    # decode kernel"): route every paged decode/draft/verify tick's
    # attention through the Pallas flash-decoding kernel
    # (horovod_tpu/ops/paged_attention.py) — pages stream through VMEM
    # with int8 dequant fused into the load, nothing materialized at
    # logical shape.  None = auto: on a TPU backend engage iff the pool
    # layout passes the compiler's tiling gate
    # (ops.paged_attention.kernel_supported — e.g. int8 storage needs
    # page_size % 32 == 0), else run the unfused XLA tick; on CPU stay
    # unfused (the interpreter runs the kernel faithfully but slowly).
    # True demands it — a typed UnsupportedPagedLayoutError at
    # construction if the compiler cannot tile the layout; False pins
    # the unfused path.  /stats paged_kernel_engaged says what the
    # ticks were built on.  Greedy output is token-identical either
    # way (tests/test_paged.py), and the flag is a CONSTRUCTOR-level
    # knob: it is baked into the tick executables at trace time, so
    # flipping it means a rebuild — tuning/replay.py explores it
    # offline like kv_dtype/page_size.
    paged_kernel: Optional[bool] = None
    # Tensor parallelism (docs/serving.md "Tensor-parallel replicas"):
    # tp > 1 runs EVERY compiled tick body under GSPMD over a tp mesh
    # built from parallel/meshes.MeshSpec — params sharded per
    # serving_param_specs (heads + MLP hidden over tp, embeddings at
    # the vocab dim, norms replicated), the paged KV pool head-dim
    # sharded, page tables replicated as data — so one engine serves a
    # model bigger than one chip and XLA inserts the head-gather/psum
    # collectives itself.  Sharding is an annotation on the SAME
    # executables: chunked prefill, speculative verify, sampling
    # columns, journal/resume, and SSE failover compose unchanged, and
    # output is token-identical to the tp=1 oracle.  Requires
    # n_heads % tp == 0 and kv_heads % tp == 0 (typed
    # ShardingConfigError at construction), and tp visible devices
    # (CPU: XLA_FLAGS=--xla_force_host_platform_device_count=N).
    tp: int = 1
    # Chunked prefill (docs/serving.md "Scheduling"): cap the prompt
    # tokens one tick may spend on ingestion.  A prompt whose
    # (post-prefix-match) length exceeds the budget is admitted into a
    # slot but INGESTED chunk by chunk, one chunk riding each decode
    # tick: every chunk runs through the same ``prefill_with_prefix``
    # executable the prefix registry uses, attending the
    # already-landed pages gathered back through the slot's page table
    # — chunk boundaries are DATA (page lists + a traced prefix
    # length), so the compile set stays bounded by (page-count
    # buckets) x (chunk buckets) and the decode executable never
    # recompiles.  Decode for every OTHER slot proceeds between
    # chunks, which is the whole point: one long prompt no longer
    # stalls the batch for a full prefill (the Sarathi-Serve move).
    # The final chunk's last-position logits are bit-identical to a
    # whole-prompt prefill's, so greedy AND sampled output is
    # token-identical to the un-chunked oracle.  0 disables (whole
    # prompts, the historical behavior).
    prefill_chunk_tokens: int = 0
    # Speculative decoding (docs/serving.md "Speculative decoding"):
    # draft spec_k tokens per active slot inside the compiled tick,
    # verify them all in ONE batched target forward, emit the agreeing
    # prefix plus the target's correction token — 1..spec_k+1 tokens
    # per slot per tick, byte-identical to plain greedy decode (the
    # emitted tokens are always the target's own argmax picks; draft
    # quality moves only the acceptance rate).
    # spec_draft: "model" (a shallower TransformerConfig sharing the
    # tokenizer, passed as InferenceEngine(draft_params=, draft_cfg=),
    # with its own slot-aligned paged KV pool), "ngram" (prompt-lookup
    # self-speculation over a device-resident token history — no
    # second model), or "auto" (model when draft params are given,
    # ngram otherwise).  draft_n_pages sizes the draft pool (0 =
    # capacity parity, like n_pages).  Off by default until the A/B
    # (benchmarks/serving.py --spec-ab) proves it for the workload.
    # spec_adaptive bounds the LOSING case: per-slot recent acceptance
    # is tracked over windows of spec_window speculative ticks, a slot
    # under spec_min_acceptance has speculation auto-disabled (its
    # mask is data), and a tick where NO slot speculates dispatches
    # the plain one-token executable instead — so an adversarial
    # workload decays to plain-engine throughput minus occasional
    # probes (every spec_probe_period ticks a disabled slot re-enables
    # to re-measure).  Output never depends on any of this.
    speculative: bool = False
    spec_k: int = 4
    spec_draft: str = "auto"
    draft_n_pages: int = 0
    spec_adaptive: bool = True
    spec_min_acceptance: float = 0.25
    spec_window: int = 2
    spec_probe_period: int = 256
    # Paged decode growth: grant this many pages AHEAD of the write
    # position at each tick boundary (0 = exactly the write page, the
    # historical behavior).  Pure page-table data — fewer grant calls
    # per decoded page at the price of earlier page-pressure; its main
    # role is as a compile-free online-tunable knob (tuning/params.py).
    # _ensure_write_range caps the span at the request's last real
    # write, so look-ahead never buys a page nobody keeps.
    page_grant_ahead: int = 0
    # The WINDOW layers' page pool of a configuration with sliding-
    # window layers (0 = every slot's bound, ceil(window / page_size)
    # + 1 pages each: the pool then never runs dry).
    window_n_pages: int = 0
    # Online autotuning (docs/serving.md "Autotuning"): after warmup()
    # the engine installs a tuning.OnlineTuner over the compile-safe
    # knob space derived from its warmed state and perturbs/scores/
    # pins serving knobs from the tick loop.  Never changes emitted
    # tokens, never compiles (the tuning/params.py contract); state in
    # /stats["tuning"] and GET /tuning.
    autotune: bool = False
    max_queue_depth: int = 64
    default_max_new_tokens: int = 64
    min_prefill_bucket: int = 8
    max_restarts: int = 3
    restart_backoff: float = 0.05
    restart_backoff_max: float = 2.0
    tick_timeout: float = 60.0
    watchdog_interval: float = 0.05
    resume: bool = True
    journal_path: Optional[str] = None
    stall_grace: Optional[float] = None
    faults: Optional[FaultInjector] = None
    # Fleet-rollout label (docs/serving.md "Fleet rollouts"): which
    # CONFIG GENERATION this engine was built at.  Purely an identity
    # tag — the RolloutController stamps candidates with
    # incumbent_gen + 1, the registry surfaces it per replica, and the
    # chaos suite proves fleet convergence ("every replica reports the
    # same config_generation") through it.  Never read by the engine.
    config_generation: int = 0
    # Model FLOPs per generated token (e.g.
    # obs.xprof.transformer_flops_per_token(params)): turns the token
    # counters into achieved FLOP/s in /stats — the honest utilization
    # number a router/capacity planner balances on.  None disables.
    model_flops_per_token: Optional[float] = None

    def __post_init__(self):
        if not self.paged:
            raise ValueError(
                "EngineConfig(paged=False): the slot-contiguous KV cache "
                "was removed in PR 28; the engine has one cache, the "
                "page pool (drop the argument)")


@dataclasses.dataclass
class _SlotState:
    request: Request
    last_token: int
    n_generated: int


@dataclasses.dataclass
class _IngestState:
    """One slot mid-way through CHUNKED prompt ingestion
    (``EngineConfig.prefill_chunk_tokens``): the request, and how many
    prompt tokens are already landed in its pages (``landed`` counts
    attached shared-prefix tokens too — the next chunk starts there).
    The slot is excluded from the decode mask until the last chunk
    lands and yields the first token.  ``started`` is where ingestion
    began (the attached-prefix length) — ``landed - started`` is the
    prefill compute a suspension throws away, the honest
    wasted-token count for a preempted mid-ingest victim."""

    request: Request
    landed: int
    started: int = 0


@dataclasses.dataclass
class _PrefixEntry:
    """One registered shared prefix: its tokens, the refcount-pinned
    pages its K/V lives in, and the first greedy continuation token
    (cached so a prompt that IS the prefix admits with zero prefill
    compute).  ``epoch`` stamps which cache lifetime the pages belong
    to — a supervised restart replaces the pool, so stale entries
    lazily re-prefill on next use."""

    tokens: tuple
    pages: Optional[List[int]] = None
    first_token: int = 0
    #: the prefix's last-position LOGITS (device (V,) array), kept so a
    #: SAMPLED prompt-is-the-prefix admission can draw its first token
    #: from them (the greedy first token alone is not enough — each
    #: sampled sharer picks with its own key).
    logits: Optional[object] = None
    epoch: int = -1


class InferenceEngine:
    """Continuous-batching engine over one model's params + config.

    Drive it synchronously with :meth:`step` (tests, benchmarks) or as a
    background thread with :meth:`start`/:meth:`stop` (the HTTP server;
    this also arms the watchdog).  ``detokenize`` optionally maps a
    token id to its text piece for streamed detokenization."""

    def __init__(self, params: Dict, cfg: "T.TransformerConfig",
                 engine_cfg: EngineConfig = EngineConfig(), *,
                 detokenize: Optional[Callable[[int], str]] = None,
                 draft_params: Optional[Dict] = None,
                 draft_cfg: Optional["T.TransformerConfig"] = None):
        # The engine serves from a tree of its OWN: the standard
        # attention block's wq/wk/wv laid out once, here, as the
        # product reads them (T.lay_out_projections: a tick and a chunk
        # then cut a layer out of the stack and multiply, with no copy
        # between); every other leaf is the caller's, whose tree is
        # neither changed nor donated.
        self.params, self._relaid_bytes = T.lay_out_projections(params)
        self.cfg = cfg
        self.engine_cfg = engine_cfg
        self.detokenize = detokenize
        # Speculative decoding: resolve the draft source up front so
        # every cache/executable below is built for the right mode.
        self._spec = engine_cfg.speculative
        self._spec_model = False
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        if self._spec:
            if engine_cfg.spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1, got {engine_cfg.spec_k}")
            mode = engine_cfg.spec_draft
            if mode == "auto":
                mode = "model" if draft_params is not None else "ngram"
            if mode not in ("model", "ngram"):
                raise ValueError(
                    f"unknown spec_draft {engine_cfg.spec_draft!r}; "
                    "expected 'model', 'ngram', or 'auto'")
            if mode == "model":
                if draft_params is None or draft_cfg is None:
                    raise ValueError(
                        "spec_draft='model' needs draft_params and "
                        "draft_cfg (a shallower TransformerConfig "
                        "sharing the tokenizer)")
                if draft_cfg.vocab_size != cfg.vocab_size:
                    raise ValueError(
                        f"draft model must share the tokenizer: vocab "
                        f"{draft_cfg.vocab_size} != {cfg.vocab_size}")
            self._spec_model = mode == "model"
            if self._spec_model:
                self.draft_params, laid = T.lay_out_projections(draft_params)
                self._relaid_bytes += laid
        if engine_cfg.prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 1 (or 0 to disable), "
                f"got {engine_cfg.prefill_chunk_tokens}")
        # Every architecture beyond the uniform block (a per-slot state,
        # rows that narrow KV heads share; latent attention, leading
        # dense layers, a share of the experts; window layers' second
        # kind of pages) is written for the paged single-chip tick and
        # the prefills.  Every other MODE refuses it here, typed and by
        # name: none may run it as another model.
        int8 = resolve_kv_dtype(cfg, engine_cfg.kv_dtype)[1]
        for on, what, int8_too, spec in (
            (cfg.has_state or cfg.kv_pack > 1,
             "linear-attention layers (a float32 matrix state a slot) "
             "and block-sparse layers (a compressed key a page)"
             if cfg.has_linear else
             "hybrid layers (a state-space mixer's per-slot state beside "
             "the pages)" if cfg.has_ssm else
             "conv layers (a per-slot state beside the pages)"
             if cfg.has_conv else
             "KV heads sharing a stored row (kv_lane_dense)", True,
             "speculative=True (a rejected draft would have to roll a "
             "state back)" if cfg.has_state else "speculative=True"),
            (cfg.latent or cfg.n_dense_layers
             or cfg.held_offset is not None,
             "latent attention"
             + (" and sparse selection (an indexer)" if cfg.sparse else "")
             + ", leading dense layers or a share of the experts",
             cfg.latent, "speculative=True"),
            (cfg.has_bsa,
             "block-sparse layers (a compressed key a page beside the "
             "pages, a table a slot and KV head)", True,
             "speculative=True"),
            (cfg.has_window,
             f"window layers (pattern {cfg.layer_pattern})", True,
             "speculative=True")):
            refused = [why for hit, why in (
                (engine_cfg.tp > 1, "tp > 1"), (self._spec, spec),
                (int8 and int8_too, "kv_dtype='int8'")) if hit]
            if on and refused:
                raise T.UnsupportedModelConfigError(
                    f"a configuration with {what} is not served with "
                    + ", ".join(refused))
        # Tensor-parallel mesh (EngineConfig.tp): the engine OWNS the
        # mesh — built once here, params and the page pool placed on
        # it, and every executable below jitted with in/out shardings
        # from it.  All validation is typed and happens NOW, never as
        # an XLA shape crash inside the first tick.
        from horovod_tpu.serving.sharding import (
            ServingSharding, ShardingConfigError)
        self._shard: Optional[ServingSharding] = None
        self.mesh = None
        if engine_cfg.tp < 1:
            raise ShardingConfigError(
                f"EngineConfig.tp must be >= 1, got {engine_cfg.tp}")
        if engine_cfg.tp > 1:
            self._shard = ServingSharding(
                cfg, engine_cfg.tp,
                draft_cfg=draft_cfg if self._spec_model else None)
            self.mesh = self._shard.mesh
            self.params = self._shard.shard_params(self.params)
            if self._spec_model:
                self.draft_params = self._shard.shard_params(
                    self.draft_params, self.draft_cfg)
        if cfg.has_bsa and engine_cfg.page_size != cfg.bsa_stride:
            raise T.UnsupportedModelConfigError(
                f"a block-sparse layer keeps one compressed key a page: "
                f"page_size must be its stride ({cfg.bsa_stride}), not "
                f"{engine_cfg.page_size}")
        self.slots = self._make_slots()
        self.wslots = self._make_window_slots()
        self.metrics = ServingMetrics()
        self.scheduler = Scheduler(
            max_queue_depth=engine_cfg.max_queue_depth,
            max_prefills_per_tick=engine_cfg.max_prefills_per_tick,
            on_reject=lambda req, err: self.metrics.rejected.inc(),
            on_cancel=lambda req: self.metrics.cancelled.inc(),
            # A requeued (preempted/resumed) request whose deadline
            # lapses before re-admission RETIRES with its partial
            # tokens — that is a completion, not shed load.
            on_expire=lambda req: self.metrics.completed.inc())
        self._states: List[Optional[_SlotState]] = \
            [None] * engine_cfg.n_slots
        # Chunked-prefill ingestion state (prefill_chunk_tokens): slot
        # -> _IngestState for every slot whose prompt is still landing
        # chunk by chunk; such slots are allocated (pages, occupancy)
        # but excluded from the decode mask until the last chunk's
        # logits yield their first token.  _tick_prefill_spent is the
        # per-tick ingestion-token ledger the admission admit_fn and
        # _advance_ingest share.
        self._ingest: Dict[int, _IngestState] = {}
        self._tick_prefill_spent = 0
        self._tick_ingested: set = set()  # slots advanced this tick
        # Requests popped from the queue but not yet landed in a slot —
        # a tick failing mid-admission must fail these futures too.
        self._taken: List[Request] = []
        self._lock = threading.Lock()  # engine-loop state (step is serial)
        self._thread: Optional[threading.Thread] = None
        self._watchdog: Optional[threading.Thread] = None
        self._stop = threading.Event()

        # Fault-tolerance state.  _hb_lock guards the tick heartbeat,
        # epoch, and stall flag — the ONLY state the watchdog touches
        # while the engine thread may be hung inside _lock (taking
        # _lock from the watchdog would deadlock recovery).
        self._hb_lock = threading.Lock()
        self._tick_started: Optional[float] = None
        self._last_tick_done: Optional[float] = None  # /healthz heartbeat age
        self._epoch = 0          # bumped on every restart
        self._stalled = False    # set by the watchdog, cleared on recovery
        self._stall_hard_failed = False  # grace spent: futures resolved typed
        self._health = HEALTHY
        self._health_lock = threading.Lock()
        self._transitions: List[str] = [HEALTHY]
        self._consec_failures = 0
        # Sticky lifecycle facts that the health STATE alone cannot
        # carry: a watchdog stall overwrites DRAINING with FAILED, and
        # a later stall-recovery must restore DRAINING (never reopen a
        # draining engine as DEGRADED); _terminal marks a failure no
        # restart may undo (budget exhausted / terminate()).
        self._draining = False
        self._terminal = False
        # Requests suspended for resume mid-_recover: in neither the
        # queue nor a slot until the requeue lands, but their futures
        # are live — drain() must not read that window as "idle".
        self._resuming = 0

        # Durability: the journal records every live request's original
        # prompt, params, and emitted-so-far tokens — what a restart
        # re-admits (resume) and what the router reads post-mortem from
        # a SIGKILL'd replica's journal file (journal_path).  Created
        # whenever either consumer exists.
        self.journal: Optional[RequestJournal] = None
        if engine_cfg.resume or engine_cfg.journal_path:
            self.journal = RequestJournal(engine_cfg.journal_path)

        # Compile-count hook: the traced-function body runs ONLY when jax
        # (re)traces, so this counter IS the number of decode
        # compilations — the acceptance criterion asserts it stays at 1
        # after warmup.
        self._decode_traces = 0

        # Online autotuner (tuning/tuner.py): installed at the END of
        # warmup() when engine_cfg.autotune — the knob space must be
        # derived from (and applied to) a fully WARMED engine, and a
        # tuner live DURING warmup could shrink the admission batch
        # mid-sweep and leave (bucket, k) shapes uncompiled.
        self._tuner = None
        self._warmed = False

        # Fused paged-attention kernel engagement (paged_kernel knob):
        # decided HERE, once, to a Python bool the tick bodies below
        # close over at trace time — so engagement can never cause a
        # steady-state recompile, and /stats reports what the ticks
        # were actually built on.  Where the kernel would be COMPILED
        # (a TPU backend) the real pool layouts must pass the
        # compiler's tiling gate (ops.paged_attention.kernel_supported):
        # auto (None) then engages iff they do; an explicit True on a
        # layout the gate rejects is a typed error, never a quiet
        # reference path.  Under the CPU interpreter any layout runs,
        # and auto stays on the unfused XLA tick (the interpreter is
        # faithful but slow) while tests opt in with paged_kernel=True.
        layouts = [(self.slots._storage_dtype, engine_cfg.page_size,
                    cfg.latent_row, cfg.kv_lora_rank) if cfg.latent else
                   (self.slots._storage_dtype, engine_cfg.page_size,
                    cfg.head_dim * cfg.kv_pack)]
        if self._spec_model:
            layouts.append((draft_cfg.dtype, engine_cfg.page_size,
                            draft_cfg.head_dim))
        compiled = not _pallas_util.use_interpret()
        rejected = [lay for lay in layouts
                    if not _pa.kernel_supported(*lay)] if compiled else []
        want = engine_cfg.paged_kernel
        if want and rejected:
            dt, ps, dh = rejected[0][:3]
            raise _pa.UnsupportedPagedLayoutError(
                f"paged_kernel=True, but the TPU compiler cannot tile "
                f"a {jnp.dtype(dt).name} pool with page_size={ps}, "
                f"head_dim={dh} (needs head_dim % 128 == 0 and "
                f"page_size % 8/16/32 == 0 for f32/bf16/int8 "
                f"storage); use paged_kernel=None for the unfused "
                f"tick or change page_size")
        self._paged_kernel = (compiled and not rejected
                              if want is None else bool(want))
        _pk = self._paged_kernel
        _pk_mesh = self.mesh if (_pk and engine_cfg.tp > 1) else None

        # Tensor-parallel in/out shardings for every executable below
        # (all None on a single-device engine).  The placement rule:
        # params and the page pool carry their head-sharded placements;
        # EVERYTHING the host uploads or fetches (tokens, masks,
        # tables, sampling columns, logits, acceptance) is pinned
        # REPLICATED.  Explicit shardings keep executable signatures
        # stable — a fed-back committed output and a fresh host upload
        # hit the same compiled program — so the zero-decode-recompile
        # guard holds under tp unchanged.
        shd = self._shard
        self._sh_R = _R = shd.replicated if shd else None
        self._sh_params = _psh = (
            shd.param_shardings(params=self.params) if shd else None)
        self._sh_draft_params = _dpsh = (
            shd.param_shardings(draft_cfg, self.draft_params)
            if shd and self._spec_model else None)
        _poolsh = shd.pool_shardings(self.slots.quantized) if shd else None
        _dpoolsh = (shd.pool_shardings(False)
                    if shd and self._spec_model else None)
        self._sh_prefill = _kvsh = (shd.prefill_cache_shardings()
                                    if shd else None)
        self._sh_prefix = _presh = (shd.prefix_kv_sharding()
                                    if shd else None)

        # The PLAIN one-token tick, built once for every engine.  An
        # expert model's tick also hands back its experts' load (three
        # numbers, fetched with the tokens); a model with window layers
        # takes the two kinds' tables as a pair.
        _moe = cfg.n_experts > 1

        def _tick(params, tokens, active, table, pool, s_t, s_k,
                  s_p, s_key):
            self._decode_traces += 1
            # Runs once per (re)trace: this IS a compile event — count
            # it and mark it on the active trace/timeline.
            obs_tracing.record_compile("serving_decode")
            pos = pool["pos"]
            table, wtable = table if cfg.has_window else (table, None)
            logits, pool, *load = T.decode_step_paged(
                params, tokens, pool, table, self.cfg, active,
                kernel=_pk, mesh=_pk_mesh, wtable=wtable,
                return_moe_load=_moe)
            # The pick — per-slot temperature/top-k/top-p COLUMNS and
            # PRNG key ROWS, all data (greedy rows are temperature 0):
            # no parameter mix ever retraces this body.
            nxt, mx = self._pick(logits, pos, active, s_t, s_k, s_p,
                                 s_key, replicated=_R)
            return (nxt, mx, pool, *load)

        # Donate the pool: without it XLA keeps input AND output pools
        # alive across the tick (2x the KV HBM — half the servable
        # slots) and copies the whole pool every token.  (The page
        # TABLE is not donated — it is host-owned tick data, like the
        # active mask.)
        self._tick_fn = self._jit(
            _tick, donate=(4,),
            in_s=shd and (_psh, _R, _R, _R, _poolsh, _R, _R, _R, _R),
            out_s=shd and ((_R, _R, _poolsh) + ((_R,) if _moe else ())))

        # A speculative engine's DRAFT/VERIFY tick rides beside it: a
        # tick where no slot speculates (every request opted out, or
        # spec_adaptive disabled them all) dispatches the plain tick
        # above instead — the losing case pays plain-engine cost, not
        # a W-wide verify for nothing.  Both executables are warmed by
        # warmup(); per-slot acceptance and the mask are data, so the
        # compile count stays constant at two.
        self._spec_tick_fn = None
        if self._spec:
            # draft -> one batched W-position verify -> accepted-prefix
            # select, all device-resident.  Shapes are static in S and
            # W = spec_k + 1; the per-slot accepted length is DATA, so
            # varying acceptance never recompiles.  The device-side
            # next-token is the bonus/correction token t[s, acc[s]] —
            # the overlap pipeline's tick N+1 input, no host round-trip.
            K = engine_cfg.spec_k
            if self._spec_model:
                dcfg = draft_cfg

                def _tick(params, dparams, tokens, active, spec_on,
                          table, dtable, pool, dpool, s_t, s_k, s_p,
                          s_key):
                    self._decode_traces += 1
                    obs_tracing.record_compile("serving_decode")
                    # Draft pos follows the TARGET pos at tick entry
                    # too (not just exit): a probe-time rebuild from
                    # host state can lag the device by an in-flight
                    # tick, and drafting from a skewed position would
                    # misplace the window's K/V for the whole tenancy.
                    dpool = {**dpool, "pos": pool["pos"]}
                    drafts, dpool = T.draft_propose_paged(
                        dparams, tokens, dpool, dtable, dcfg, active, K,
                        kernel=_pk, mesh=_pk_mesh)
                    window = jnp.concatenate([tokens[:, None], drafts],
                                             axis=1)
                    t, mx, acc, pool = T.decode_verify_paged(
                        params, window, pool, table, self.cfg, active,
                        spec_on, sample=(s_t, s_k, s_p, s_key),
                        kernel=_pk, mesh=_pk_mesh, replicated=_R)
                    # Draft rollback on rejection = reset pos to the
                    # committed depth; the rejected tail's stale draft
                    # K/V is overwritten before it is ever attended
                    # (write-before-attend, per draft page).
                    dpool = {**dpool, "pos": pool["pos"]}
                    nxt = t[jnp.arange(t.shape[0]), acc]
                    return (jnp.where(active, nxt, 0), t, mx, acc,
                            pool, dpool)

                self._spec_tick_fn = self._jit(
                    _tick, donate=(7, 8),
                    in_s=shd and (_psh, _dpsh, _R, _R, _R, _R, _R,
                                  _poolsh, _dpoolsh, _R, _R, _R, _R),
                    out_s=shd and (_R, _R, _R, _R, _poolsh, _dpoolsh))
            else:
                def _tick(params, tokens, active, spec_on, table, pool,
                          hist, s_t, s_k, s_p, s_key):
                    self._decode_traces += 1
                    obs_tracing.record_compile("serving_decode")
                    pos = pool["pos"]
                    Th = hist.shape[1]
                    rows = jnp.arange(hist.shape[0])
                    # The last committed token joins the history first
                    # (it IS committed); mode="drop" discards inactive
                    # rows and out-of-range positions.
                    hidx = jnp.where(active & (pos < Th), pos, Th)
                    hist = hist.at[rows, hidx].set(tokens, mode="drop")
                    drafts = T.ngram_propose(hist, pos, K)
                    window = jnp.concatenate([tokens[:, None], drafts],
                                             axis=1)
                    t, mx, acc, pool = T.decode_verify_paged(
                        params, window, pool, table, self.cfg, active,
                        spec_on, sample=(s_t, s_k, s_p, s_key),
                        kernel=_pk, mesh=_pk_mesh, replicated=_R)
                    # Accepted drafts are now committed history too.
                    j = jnp.arange(1, K + 1, dtype=jnp.int32)[None, :]
                    wp = pos[:, None] + j
                    ok = (active[:, None] & (j <= acc[:, None])
                          & (wp < Th))
                    hist = hist.at[rows[:, None],
                                   jnp.where(ok, wp, Th)].set(
                        drafts, mode="drop")
                    nxt = t[rows, acc]
                    return (jnp.where(active, nxt, 0), t, mx, acc,
                            pool, hist)

                self._spec_tick_fn = self._jit(
                    _tick, donate=(5, 6),
                    in_s=shd and (_psh, _R, _R, _R, _R, _poolsh, _R,
                                  _R, _R, _R, _R),
                    out_s=shd and (_R, _R, _R, _R, _poolsh, _R))
        self._prefill_fns: Dict[tuple, Callable] = {}
        self._prefill_traces = 0
        # layers with a state-space mixer (0: none): what the two
        # ssm_* counters count rows and tokens by
        self._ssm_layers = cfg.kind_count("hybrid")
        # ... and the lin_* and bsa_* counters theirs
        self._lin_layers = cfg.kind_count("linear")
        self._bsa_layers = cfg.kind_count("block_sparse")
        self._prefill_calls = 0  # prefill FORWARD PASSES (sharing hook)

        # Paged-cache host state: _page_pos mirrors each slot's device
        # write position AT DISPATCH TIME (admission sets it to the
        # prompt length; every dispatched tick advances active rows by
        # one, exactly like the device-side pos) — page grants and COW
        # happen against this mirror at tick boundaries, BEFORE the
        # write that needs them.  _dev_table caches the device upload
        # of the page table, refreshed only when table_version moves.
        self._page_pos = np.zeros(engine_cfg.n_slots, np.int64)
        self._dev_table = None
        self._table_uploaded = -1
        # What else the CURRENT step ran beside its tick, its kind, phases
        # and retired tick; the collector's seconds; a walk step's tokens.
        self._step_tick = self._step_prefill = self._step_chunk = False
        self._step_kind = self._retired = None
        self._step_spans, self._gc_s = [], 0.0
        self._walk_block_tokens = self.slots.page_size * (
            _pa.block_pages(
                self.slots.page_size, 1, cfg.latent_row,
                self.slots._storage_dtype, self.slots.max_pages, True)
            if cfg.latent else _pa.block_pages(
                self.slots.page_size,
                cfg.kv_heads // cfg.kv_pack // engine_cfg.tp,
                cfg.head_dim * cfg.kv_pack, self.slots._storage_dtype,
                self.slots.max_pages))
        # ... and one step of a sparse model's index walk
        self._index_block_tokens = self.slots.page_size * (
            _pa.index_block_pages(
                self.slots.page_size, cfg.index_head_dim,
                self.slots._storage_dtype, self.slots.max_pages)
            if cfg.sparse else 0)
        # Registered shared prefixes (token tuple -> entry); epoch
        # stamps which cache lifetime the pinned pages belong to.
        self._prefixes: Dict[tuple, _PrefixEntry] = {}
        self._prefix_version = 0  # bumps on (un)register: match cache
        self._cache_epoch = 0

        def _suffix_prefill(params, padded, lens, prefix, p0, win_start=0):
            self._prefill_traces += 1
            obs_tracing.record_compile("serving_prefill")
            return T.prefill_with_prefix(
                params, padded, prefix, p0, self.cfg, true_len=lens,
                win_start=win_start)

        # jax.jit caches per (n_prefix_pages, bucket, k) shape; the
        # prefix length p0 is a traced scalar, so prefixes of any
        # length share the page-granular compile set.
        self._suffix_prefill = self._jit(
            _suffix_prefill,
            in_s=shd and (_psh, _R, _R,
                          dict.fromkeys(cfg.kind("full").block, _presh), _R),
            out_s=shd and (_R, _kvsh))
        self._update_page_gauges()

        # Speculative host state: the per-slot enablement mask (the
        # per-request opt-out, uploaded as DATA like the active mask),
        # the draft model's PAIRED paged pool (slot-aligned with the
        # target pool; same refcount/COW machinery) or the n-gram
        # draft's device-resident token history, and the draft model's
        # own prefill compile cache.
        self._spec_host = np.ones(engine_cfg.n_slots, bool)
        # Runtime speculation gate (tuning/params.py "spec_enabled"):
        # pure admission-mask data — False routes NEW admissions down
        # the plain greedy path (both tick executables are warmed, so
        # the toggle never compiles and never changes emitted tokens).
        self._spec_runtime_enabled = True
        self._dev_spec = None
        self._dev_spec_host: Optional[np.ndarray] = None
        # Adaptive speculation state (spec_adaptive): _spec_live is the
        # auto-disable mask (False = acceptance fell below the floor),
        # _spec_win accumulates (drafted, accepted) per slot over the
        # evaluation window, _spec_idle counts ticks since disable (a
        # probe re-enables at spec_probe_period), and _spec_stale marks
        # slots whose draft state (n-gram history / draft-pool K/V)
        # missed plain ticks and must be rebuilt before re-enabling.
        self._spec_live = np.ones(engine_cfg.n_slots, bool)
        self._spec_win = np.zeros((engine_cfg.n_slots, 2), np.int64)
        self._spec_idle = np.zeros(engine_cfg.n_slots, np.int64)
        self._spec_stale = np.zeros(engine_cfg.n_slots, bool)
        self.draft_slots = self._make_draft_slots()
        self._dev_dtable = None
        self._dtable_uploaded = -1
        self._dev_history = None
        self._draft_prefill_fns: Dict[tuple, Callable] = {}
        if self._spec and not self._spec_model:
            # One scatter lands an admission group's prompt rows in the
            # history (jit caches per (k, bucket) shape).  Replicated
            # in/out under tp: the history is committed tick data, and
            # pinning it keeps its placement on the mesh device set the
            # spec tick expects.
            self._hist_land = self._jit(
                lambda hist, slots, padded: hist.at[
                    slots[:, None],
                    jnp.arange(padded.shape[1])[None, :]].set(padded),
                donate=(0,),
                in_s=shd and (_R, _R, _R), out_s=shd and _R)

        # Overlapped-pipeline state (engine_cfg.overlap).  _pending is
        # the ONE in-flight decode tick: its un-fetched device outputs
        # plus a host snapshot of which request each slot was computing
        # for at dispatch (the identity check that makes one-tick-lag
        # retirement safe).  _dev_tokens is the device-resident token
        # vector — tick N's output feeds tick N+1's dispatch without a
        # host round-trip — and _dev_active caches the device copy of
        # the active mask, re-uploaded only when the host mask changes.
        self._pending: Optional[Dict] = None
        self._dev_tokens = None
        self._dev_active = None
        self._dev_active_host: Optional[np.ndarray] = None
        # where(mask, vals, toks): lands freshly admitted slots' first
        # tokens in the device token vector (one tiny async op).
        # Replicated in/out under tp — its output IS the next tick's
        # token input, so the placement must match the tick's.
        self._merge_tokens = self._jit(
            lambda toks, vals, mask: jnp.where(mask, vals, toks),
            in_s=shd and (_R, _R, _R), out_s=shd and _R)

        # Per-slot sampling columns (serving/sampling.py): temperature /
        # top_k / top_p / PRNG key rows ride the tick as DATA — one
        # executable for every parameter mix, greedy = temperature-0
        # rows.  _first_sample picks an admission group's FIRST tokens
        # from the prefill logits with the same kernel (jit caches per
        # (k, vocab) shape — warmed by warmup(), counted separately
        # from the prefill compile set).
        self._samp = SlotSampling(engine_cfg.n_slots)
        self._sample_traces = 0

        def _first_sample(logits, s_t, s_k, s_p, s_key, positions):
            self._sample_traces += 1
            obs_tracing.record_compile("serving_sample")
            return T.sample_token_rows(
                logits, s_t, s_k, s_p, s_key, positions,
                jnp.zeros_like(positions), replicated=_R)

        self._first_sample = self._jit(
            _first_sample,
            in_s=shd and (_R, _R, _R, _R, _R, _R), out_s=shd and _R)

        # Token-rate window for achieved FLOP/s: (monotonic, tokens)
        # samples taken at each stats() call, pruned to ~60s — the
        # scrape cadence defines the window, no hot-path cost.
        # Own lock (not self._lock): stats() is served from concurrent
        # HTTP handler threads and must not contend with the tick loop.
        self._rate_samples: List = []
        self._rate_lock = threading.Lock()
        self._rate_metrics = self.metrics
        if engine_cfg.model_flops_per_token:
            self.metrics.model_flops_per_token.set(
                engine_cfg.model_flops_per_token)
        self.metrics.tp_degree.set(engine_cfg.tp)

    # -- lifecycle / health ------------------------------------------------

    @property
    def health(self) -> str:
        """Current lifecycle state: healthy | degraded | draining |
        failed."""
        return self._health

    @property
    def state_transitions(self) -> List[str]:
        """The state-machine trail (capped), oldest first."""
        return list(self._transitions)

    @property
    def terminal(self) -> bool:
        """True once the engine can never serve again (restart budget
        exhausted or :meth:`terminate`) — a transient watchdog
        ``failed`` that a supervised restart may still recover from
        reads False.  Replica processes key their exit code on this
        (router/replica_main.py)."""
        return self._terminal

    @property
    def heartbeat_age(self) -> Optional[float]:
        """Seconds since the last COMPLETED tick (None before the
        first) — the liveness number ``/healthz`` reports so probes
        can tell a quiet engine from a wedged one without parsing
        ``/stats``."""
        with self._hb_lock:
            t = self._last_tick_done
        return time.monotonic() - t if t is not None else None

    def _set_health(self, state: str) -> None:
        with self._health_lock:
            if self._health == state:
                return
            self._health = state
            self._transitions.append(state)
            del self._transitions[:-50]  # bounded trail

    def begin_drain(self) -> None:
        """Enter ``draining``: new :meth:`submit` calls raise
        :class:`DrainingError`; admitted and queued requests keep
        running.  Draining is sticky — even a stall-recovery restart
        stays draining.  A terminally failed engine stays ``failed``
        (check-and-set under ONE lock hold: a concurrent watchdog
        FAILED must never be overwritten, or drain() would burn its
        whole budget on a dead engine)."""
        self._draining = True
        with self._health_lock:
            if self._health in (FAILED, DRAINING):
                return
            self._health = DRAINING
            self._transitions.append(DRAINING)
            del self._transitions[:-50]

    # -- submission --------------------------------------------------------

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None,
               eos_id: Optional[int] = None,
               deadline: Optional[float] = None,
               on_token: Optional[Callable] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None,
               sampled: bool = False,
               speculative: Optional[bool] = None,
               temperature: float = 0.0,
               top_k: int = 0,
               top_p: float = 0.0,
               seed: Optional[int] = None,
               priority: str = "interactive") -> GenerationFuture:
        """Queue a generation request; returns its future.

        ``priority`` selects the request's SLO class
        (:data:`~horovod_tpu.serving.scheduler.PRIORITY_CLASSES`;
        validated here — unknown classes are a typed
        :class:`ServingError`, HTTP 400).  The scheduler serves
        classes strictly in order (``interactive`` before ``batch``)
        with EDF inside each class, and under slot/page pressure the
        engine may SUSPEND a strictly-worse-class victim (journal
        frontier kept, re-admitted later, output byte-identical) to
        bound the better class's wait — docs/serving.md
        "Scheduling".

        ``temperature`` / ``top_k`` / ``top_p`` / ``seed`` select
        per-request SAMPLING (serving/sampling.py; validated here,
        :class:`ServingError` on bad values).  ``temperature=0`` (the
        default) is greedy; a sampled request's token stream is
        token-identical to ``sample_decode`` at the same seed/params —
        including across restart-resume and router failover, because
        the PRNG key schedule depends only on (seed, token position).
        All of it rides the ONE compiled tick as per-slot data; no
        parameter mix recompiles anything.  On a speculative engine a
        sampled request decodes one token per tick through the same
        executable (drafts are verified by argmax agreement, which a
        sampled stream never satisfies).

        ``speculative`` is the per-request opt-out on a speculative
        engine (None = engine default): ``False`` pins the request to
        one-token-per-tick greedy decode AS DATA — identical output,
        predictable per-tick pacing, no recompile.  Ignored on a
        non-speculative engine.

        ``trace_id`` propagates a caller-supplied id (the server passes
        the ``X-Trace-Id`` header) into the request's
        :class:`~horovod_tpu.obs.tracing.RequestTrace`; a fresh id is
        minted when absent, so :attr:`GenerationFuture.trace_id` and
        :meth:`GenerationFuture.breakdown` are always available.
        ``parent_span`` nests this request's span under an upstream
        caller's span (the router's proxy-attempt span, via
        ``X-Parent-Span``), and ``sampled`` forces full-detail span
        retention past tail sampling (``X-Trace-Sampled``) — both
        no-ops unless a :func:`~horovod_tpu.obs.tracing.spans`
        recorder is active.

        Typed rejections: :class:`RequestTooLongError` (prompt +
        max_new_tokens cannot fit a cache slot — raised immediately),
        :class:`QueueFullError` (bounded queue at capacity),
        :class:`DrainingError` / :class:`EngineFailedError` (engine
        draining or terminally failed — nothing is ever enqueued on a
        dead engine), and :class:`DeadlineExceededError` (set on the
        FUTURE if ``deadline`` — an absolute ``time.monotonic()``
        instant — passes while queued).  A deadline that lapses AFTER
        admission retires the slot early instead: the future completes
        with the partial result and ``finish_reason == "deadline"``, so
        abandoned requests don't pin slots."""
        if self._draining:
            raise DrainingError("engine is draining; not accepting work")
        if self._health == FAILED:
            if self._terminal:
                raise EngineFailedError(
                    "engine has failed permanently "
                    "(restart budget exhausted or terminated)")
            raise EngineFailedError(
                "engine is recovering from a stalled tick; retry shortly")
        prompt = [int(t) for t in prompt]
        n_new = (max_new_tokens if max_new_tokens is not None
                 else self.engine_cfg.default_max_new_tokens)
        temperature, top_k, top_p, seed = validate_sampling(
            temperature, top_k, top_p, seed)
        priority_rank(priority)  # typed ServingError on unknown class
        if not prompt:
            raise ServingError("empty prompt")
        if n_new < 1:
            raise ServingError(f"max_new_tokens must be >= 1, got {n_new}")
        cap = self.slots.max_len
        # First token comes from prefill logits, so a slot needs room for
        # the prompt plus the n_new - 1 decode-step writes.
        if len(prompt) + n_new - 1 > cap:
            self.metrics.rejected.inc()
            raise RequestTooLongError(
                f"prompt ({len(prompt)}) + max_new_tokens ({n_new}) "
                f"exceeds slot capacity ({cap})")
        if (self.slots.pages_for(len(prompt) + n_new - 1)
                > self.slots.n_pages):
            # Could NEVER run, even with the whole pool to itself — a
            # typed rejection now, not an admission stall forever.
            self.metrics.rejected.inc()
            raise CacheOutOfPagesError(
                f"prompt ({len(prompt)}) + max_new_tokens ({n_new}) "
                f"needs {self.slots.pages_for(len(prompt) + n_new - 1)} "
                f"pages; the pool holds {self.slots.n_pages}")
        fut = GenerationFuture(on_token=on_token,
                               detokenize=self.detokenize)
        fut.trace = obs_tracing.RequestTrace(trace_id,
                                             parent_span_id=parent_span)
        fut.trace.sampled = bool(sampled)
        fut._tracer = obs_tracing.get()
        fut._spans = obs_tracing.spans()
        req = Request(prompt=prompt, max_new_tokens=n_new, future=fut,
                      eos_id=eos_id, deadline=deadline, trace=fut.trace,
                      speculative=speculative, temperature=temperature,
                      top_k=top_k, top_p=top_p, seed=seed,
                      priority=priority)
        if self.journal is not None:
            # Journal BEFORE the enqueue, purge-on-resolve wired first:
            # every resolution path (retire, typed error, cancel,
            # terminate, the post-enqueue race checks below) funnels
            # through the future, so an entry can never outlive its
            # request — no ghost re-admission after a later restart.
            journal, rid = self.journal, req.id
            fut._on_resolve = lambda: journal.end(rid)
            journal.begin(req)
        try:
            self.scheduler.submit(req)  # QueueFullError counts, on_reject
        except QueueFullError:
            if self.journal is not None:
                self.journal.end(req.id)  # never enqueued: nothing to resume
            raise
        if fut._spans is not None:
            # Span START is written (and flushed) the moment the
            # request is live: a SIGKILL after this instant leaves the
            # start record + every typed event in the stream — the
            # durable half of the autopsy.  (Submit-time rejections
            # above never ran; they need no span.)
            try:
                fut._spans.request_begin(fut.trace, attrs={
                    "prompt_tokens": len(prompt),
                    "max_new_tokens": n_new,
                    "request_id": req.id})
            except Exception:  # pragma: no cover - spans must not fail work
                pass
        # Post-enqueue re-checks close the submit-vs-shutdown races:
        # the pre-checks above can pass just before a terminal failure
        # drains the queue, or just before begin_drain() + drain()
        # sample an (at that instant) empty queue and stop the engine —
        # either way THIS request must not be left enqueued unresolved.
        if self._health == FAILED:
            # Resolve ONLY this request: the terminal path already
            # drained the queue, and failing it wholesale here could
            # collateral-kill requests legitimately enqueued by other
            # threads after a stall-recovery restart.  take() drops
            # already-done requests if the engine ever ticks again.
            exc = EngineFailedError("engine failed during submit")
            fut.set_exception(exc)
            raise exc
        if self._draining:
            exc = DrainingError("engine began draining during submit")
            fut.set_exception(exc)  # take() drops already-done requests
            raise exc
        self.metrics.queue_depth.set(self.scheduler.depth)
        return fut

    # -- paged cache plumbing ----------------------------------------------

    def _jit(self, fn, *, donate=(), in_s=None, out_s=None):
        """``jax.jit`` with the tp mesh's in/out shardings when the
        engine is sharded (plain jit on a single-device engine —
        ``in_s``/``out_s`` are None there by construction, and an
        EXPLICIT ``in_shardings=None`` would mean replicate-everything,
        which is not the same as unspecified)."""
        if self._shard is None or in_s is None:
            return jax.jit(fn, donate_argnums=donate)
        return jax.jit(fn, donate_argnums=donate,
                       in_shardings=in_s, out_shardings=out_s)

    def _make_slots(self) -> PagedSlotCache:
        ec = self.engine_cfg
        return PagedSlotCache(
            self.cfg, ec.n_slots, ec.max_len, page_size=ec.page_size,
            n_pages=ec.n_pages, kv_dtype=ec.kv_dtype, mesh=self.mesh,
            n_layers=self.cfg.layers_with("k"))

    def _make_window_slots(self) -> Optional[PagedSlotCache]:
        """The WINDOW layers' page pool of a configuration that has
        them: the same allocator class as the full layers', slot-
        aligned with it (same slot ids, same retirement, like the
        draft pool), told the window — a slot gives a page back once
        it lies wholly behind the next query's window."""
        if not self.cfg.has_window:
            return None
        ec = self.engine_cfg
        return PagedSlotCache(
            self.cfg, ec.n_slots, ec.max_len, page_size=ec.page_size,
            n_pages=ec.window_n_pages, kv_dtype=ec.kv_dtype,
            n_layers=self.cfg.kind_count("sliding"),
            window=self.cfg.window)

    def _make_draft_slots(self) -> Optional[PagedSlotCache]:
        """The draft model's page pool: slot-aligned with the target
        pool (same slot ids, same max_len) so retirement and admission
        pair one-to-one.  Model dtype storage — draft quality only
        moves the acceptance rate, but there is no reason to quantize a
        pool this shallow."""
        if not (self._spec and self._spec_model):
            return None
        ec = self.engine_cfg
        return PagedSlotCache(self.draft_cfg, ec.n_slots,
                              self.slots.max_len,
                              page_size=ec.page_size,
                              n_pages=ec.draft_n_pages,
                              mesh=self.mesh)

    def _release_slot(self, slot: int) -> None:
        """Free a slot in the target pool AND its speculative
        companions: the draft pool's paired slot (its pages return to
        the draft free heap) and the opt-out mask (reset to the engine
        default for the next tenant)."""
        self.slots.free(slot)
        if self.wslots is not None and self.wslots._active[slot]:
            self.wslots.free(slot)
        self._samp.clear(slot)  # greedy/zero row for the next tenant
        self._spec_host[slot] = True
        # The adaptive live/idle state deliberately SURVIVES the
        # tenancy: acceptance is a property of the workload, and on
        # homogeneous hostile traffic a slot that just proved drafts
        # useless should not re-pay the evaluation window for every
        # new request — probes still re-enable it periodically.
        self._spec_win[slot] = 0
        if (self.draft_slots is not None
                and self.draft_slots._active[slot]):
            self.draft_slots.free(slot)

    def register_prefix(self, tokens: Sequence[int]) -> None:
        """Register a SHARED PREFIX (e.g. the system prompt): its K/V
        is prefilled ONCE into refcount-pinned pages, and every future
        request whose prompt starts with it attaches those pages and
        prefills only its suffix — N concurrent requests, one prefix
        prefill.  A request whose prompt IS the prefix admits with no
        prefill at all (the first greedy token is cached here).  Pages
        stay pinned across slot churn; a supervised restart invalidates
        the entry, which lazily re-prefills on next use."""
        for on, why in (
            (self.wslots is not None, "window layers' pages (a sharer's "
             "window would release a page its peers read)"),
            (self.cfg.sparse, "sparse attention (an indexer's keys beside "
             "the latent rows)"),
            (self.cfg.has_bsa, "block-sparse layers (a sharer's compressed "
             "keys would span the prefix's last page and its own first)"),
            (self.cfg.has_state,
             ("linear-attention" if self.cfg.has_linear else
              "hybrid" if self.cfg.has_ssm else "conv") + " layers (a "
             "sharer would need the state as it stood at the prefix's "
             "end: a snapshot a page boundary)")):
            if on:
                raise T.UnsupportedModelConfigError(
                    "prefix sharing is not written for " + why)
        tokens = tuple(int(t) for t in tokens)
        if not tokens:
            raise ServingError("empty prefix")
        if len(tokens) > self.slots.max_len:
            raise RequestTooLongError(
                f"prefix ({len(tokens)}) exceeds slot capacity "
                f"({self.slots.max_len})")
        with self._lock:
            fresh = tokens not in self._prefixes
            entry = self._prefixes.setdefault(tokens,
                                              _PrefixEntry(tokens=tokens))
            try:
                self._ensure_prefix(entry)
            except BaseException:
                if fresh:
                    # A failed registration must leave NOTHING behind:
                    # a phantom entry would lazily re-pin pages later
                    # for a prefix the caller was told never registered
                    # (and so will never unregister).
                    self._prefixes.pop(tokens, None)
                raise
            if fresh:
                self._prefix_version += 1

    def unregister_prefix(self, tokens: Sequence[int]) -> None:
        """Drop a registered prefix's pin; its pages return to the free
        heap once the last attached slot retires."""
        with self._lock:
            entry = self._prefixes.pop(tuple(int(t) for t in tokens), None)
            if entry is not None:
                self._prefix_version += 1
            if (entry is not None and entry.pages
                    and entry.epoch == self._cache_epoch):
                self.slots.release_raw(entry.pages)

    def _matched_prefix(self, req: Request) -> Optional[_PrefixEntry]:
        """:meth:`_match_prefix`, once per request: the match is
        needed by ``_group_key`` (scheduler take), ``_plan_pages``
        (page budget), and ``_admit_paged`` — an O(prefixes x
        prefix_len) prompt scan each, every tick the request waits
        under back-pressure.  Cached on the request, invalidated when
        the registration set changes."""
        cached = getattr(req, "_prefix_match", None)
        if cached is not None and cached[0] == self._prefix_version:
            return cached[1]
        entry = self._match_prefix(req.prompt)
        req._prefix_match = (self._prefix_version, entry)
        return entry

    def _match_prefix(self, prompt) -> Optional[_PrefixEntry]:
        """Longest registered prefix the prompt starts with."""
        best = None
        for entry in self._prefixes.values():
            n = len(entry.tokens)
            if n <= len(prompt) and tuple(prompt[:n]) == entry.tokens:
                if best is None or n > len(best.tokens):
                    best = entry
        return best

    def _ensure_prefix(self, entry: _PrefixEntry) -> None:
        """(Re-)prefill a prefix entry into pinned pages — the ONE
        prefix forward pass its sharers amortize.  Raises
        :class:`CacheOutOfPagesError` if the pool cannot pin it."""
        if entry.pages is not None and entry.epoch == self._cache_epoch:
            return
        p0 = len(entry.tokens)
        pages = self.slots.grant_raw(self.slots.pages_for(p0))
        try:
            bucket = self._bucket(p0)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :p0] = entry.tokens
            logits, pre = self._prefill_fn(bucket, 1)(
                self.params, jnp.asarray(padded),
                jnp.asarray([p0], np.int32))
            self._prefill_calls += 1
            self.slots.land_raw(pages, pre, p0)
            self.metrics.host_syncs.inc()  # the argmax fetch blocks
            entry.first_token = int(jnp.argmax(logits[0]))  # cold sync
            # Kept on device for sampled prompt-is-the-prefix sharers:
            # each draws its own first token from these logits.
            entry.logits = logits[0]
        except BaseException:
            # Unpin on ANY failure (compile OOM, device fault at the
            # blocking sync): without this the pages leak at refcount 1
            # and every retry drains the pool a little further.
            self.slots.release_raw(pages)
            raise
        entry.pages = pages
        entry.epoch = self._cache_epoch

    def _prefix_landed(self, req: Request) -> int:
        """Tokens a matched, CURRENT-epoch prefix would pre-land for
        this request (0 without one) — what chunking and page planning
        subtract from the prompt."""
        entry = self._matched_prefix(req)
        if (entry is not None and entry.pages is not None
                and entry.epoch == self._cache_epoch):
            return len(entry.tokens)
        return 0

    def _chunked(self, req: Request) -> bool:
        """Does this request's prompt ingest CHUNK BY CHUNK?  Yes when
        chunking is on and the prompt tokens that actually need
        prefill (past any matched shared prefix) exceed the per-tick
        budget."""
        chunk = self.engine_cfg.prefill_chunk_tokens
        return bool(chunk) and (len(req.prompt)
                                - self._prefix_landed(req)) > chunk

    def _prefill_cost(self, req: Request) -> int:
        """Prompt tokens admitting this request costs THIS tick: the
        un-prefixed suffix, capped at one chunk for a chunked
        ingestion (later chunks ride later ticks)."""
        suf = len(req.prompt) - self._prefix_landed(req)
        chunk = self.engine_cfg.prefill_chunk_tokens
        return min(suf, chunk) if chunk else suf

    def _plan_pages(self, req: Request) -> int:
        """Pages an admission would consume (private grants + one COW/
        growth margin page) — the scheduler back-pressure budget.
        Shared prefix pages cost nothing: attaching is a refcount.  A
        CHUNKED admission plans only its first chunk's span (later
        chunks grant on demand at their tick, preempting or waiting
        like decode growth does)."""
        ps = self.slots.page_size
        p0 = self._prefix_landed(req)
        upto = len(req.prompt)
        if self._chunked(req):
            upto = p0 + self.engine_cfg.prefill_chunk_tokens
        n_idx = (upto - 1) // ps + 1
        if p0 > 0:
            if len(req.prompt) == p0:
                return 1  # attach-only; margin covers the first COW/grant
            return n_idx - p0 // ps + 1
        return n_idx + 1

    def _group_key(self, req: Request):
        """Admission-group key for :meth:`Scheduler.take`: groups must
        share one prefill executable, so the key is the prompt bucket —
        and the matched prefix (one shared-prefix gather + suffix
        prefill serves the whole group) with the SUFFIX bucket.
        A CHUNKED request is taken ALONE (singleton key): its
        ingestion spans many ticks and shares no prefill shape with
        anyone."""
        if self._chunked(req):
            return ("chunk", req.id)
        entry = self._matched_prefix(req)
        if entry is None:
            return ("full", self._bucket(len(req.prompt)))
        suf = len(req.prompt) - len(entry.tokens)
        if suf == 0:
            return ("attach", entry.tokens)
        return ("suffix", entry.tokens, self._bucket(suf))

    def _occupants(self) -> List:
        """Every occupied slot as ``(priority rank, request id, slot,
        request)`` — decoding slots and mid-ingestion slots alike (an
        ingesting slot holds pages too)."""
        occ = [(st.request.priority_rank, st.request.id, s, st.request)
               for s, st in enumerate(self._states) if st is not None]
        occ += [(ing.request.priority_rank, ing.request.id, s,
                 ing.request)
                for s, ing in self._ingest.items()]
        return occ

    def _build_resume(self, req: Request) -> Optional[Request]:
        """A RESUME request for ``req`` from its journal frontier —
        prompt + emitted tokens as the new prompt, the remaining
        decode budget, and the ORIGINAL id/deadline/trace/class/
        sampling/future — or None when no trustworthy frontier exists
        (no journal entry, resume off, or nothing left to decode).
        Shared by the restart-resume path (:meth:`_resume_or_fail`)
        and preemption (:meth:`_preempt`): both re-admissions are the
        same re-prefill-and-continue operation, so their output is
        byte-identical to an uninterrupted run by the same argument."""
        if not self.engine_cfg.resume or self.journal is None:
            return None
        entry = self.journal.get(req.id)
        if entry is None or entry.remaining < 1:
            return None
        new = Request(prompt=list(entry.prompt) + list(entry.emitted),
                      max_new_tokens=entry.remaining, future=req.future,
                      eos_id=entry.eos_id, deadline=req.deadline,
                      trace=req.trace, speculative=req.speculative,
                      # Sampling params survive verbatim: the key
                      # schedule is position-based, so the re-prefill
                      # of prompt + emitted continues the exact stream.
                      temperature=entry.temperature, top_k=entry.top_k,
                      top_p=entry.top_p, seed=entry.seed,
                      priority=req.priority)
        # The ORIGINAL id is kept: it is the journal key, and it
        # preserves the request's age in the scheduling order
        # (preemption picks victims by id — surviving a crash or a
        # preemption must not mark old work as young).
        new.id = req.id
        new.submitted_at = req.submitted_at
        # Wasted work = tokens RE-prefilled that were already computed
        # once.  A request that never landed a prefill (no emitted
        # tokens) re-queues for free.
        new._resume_wasted = len(new.prompt) if entry.emitted else 0
        return new

    def _preempt(self, slot: int, reason: str) -> bool:
        """SUSPEND the request occupying ``slot`` — journal frontier
        kept, pages and slot freed, request requeued for ordinary
        re-admission with its future still live (output byte-identical
        to an uninterrupted run: the re-prefill of prompt + emitted
        continues the exact token stream, greedy or sampled).  Falls
        back to the legacy typed :class:`CacheOutOfPagesError` when no
        resume frontier exists (``resume=False``).  Returns True if
        the slot was vacated."""
        st = self._states[slot]
        ing = self._ingest.get(slot)
        if st is None and ing is None:
            return False
        req = st.request if st is not None else ing.request
        fut = req.future
        # The SUBMIT-TIME recorder handle (not the global): begin and
        # finish went through fut._spans, so events must too — a
        # recorder swapped mid-request (the A/B seam) must not orphan
        # an event onto a stream that never saw the span start.
        srec = fut._spans
        if srec is not None and req.trace is not None:
            try:
                srec.request_event(req.trace, "eviction",
                                   {"slot": slot, "reason": reason})
            except Exception:  # pragma: no cover - spans must not fail
                pass
        self._states[slot] = None
        self._ingest.pop(slot, None)
        self._release_slot(slot)
        if fut.done():
            return True
        if fut.cancel_requested:
            fut._finish("cancelled")
            self.metrics.cancelled.inc()
            return True
        new = self._build_resume(req)
        if new is None:
            fut.set_exception(CacheOutOfPagesError(
                f"preempted ({reason}); no resume frontier — retry "
                f"with backoff"))
            self.metrics.rejected.inc()
            return True
        if ing is not None:
            # A mid-ingestion victim emitted nothing, but its landed
            # chunks were real prefill compute the re-ingestion
            # repeats — count them (the journal alone cannot see
            # them).
            new._resume_wasted = max(getattr(new, "_resume_wasted", 0),
                                     ing.landed - ing.started)
        self.metrics.preemptions.inc()
        wasted = getattr(new, "_resume_wasted", 0)
        if wasted:
            self.metrics.resume_wasted_tokens.inc(wasted)
        self.journal.note_resume(req.id)
        # Back into the queue (depth-exempt — the caller is still
        # waiting on a live future); the scheduling order places it by
        # class/EDF/id, and the paged admit_fn keeps it waiting until
        # the pressure that evicted it clears.
        self.scheduler.requeue_front([new])
        self.metrics.queue_depth.set(self.scheduler.depth)
        return True

    def _evict_for_pages(self) -> bool:
        """Preempt one victim to reclaim pages: the WORST class first,
        youngest within it (highest request id — oldest work keeps
        its progress; a batch-class slot always pays before an
        interactive one).  The victim SUSPENDS through the resume path
        (see :meth:`_preempt`) rather than failing, so its output
        stays byte-identical.  False when nothing is left to evict."""
        occ = self._occupants()
        if not occ:
            return False
        _, _, s, _ = max(occ)
        return self._preempt(s, "out_of_pages")

    def _preempt_for_slots(self) -> bool:
        """SLOT-pressure preemption: when every slot is busy and a
        STRICTLY better-class request waits, suspend the worst
        occupant (worst class, youngest within it) so the winner
        admits this tick — bounded wait for the winner, suspended (not
        lost) work for the victim.  Never fires within a class (equal
        peers wait FCFS, as ever) and never without a resume frontier
        to suspend onto."""
        if not (self.engine_cfg.resume and self.journal is not None):
            return False
        if self.slots.free_count > 0 or self.scheduler.depth == 0:
            return False
        best = self.scheduler.peek_best_rank()
        if best is None:
            return False
        occ = self._occupants()
        if not occ:
            return False
        worst = max(occ)
        if worst[0] <= best:
            return False  # nothing strictly better is waiting
        return self._preempt(worst[2], "slot_pressure")

    def _ensure_write_page(self, s: int) -> bool:
        """Grant (or copy-on-write) slot ``s``'s write page for the
        next dispatch — the one-token point case of
        :meth:`_ensure_write_range` (which, like chunk ingestion,
        routes through the ONE :meth:`_claim_page` grant/COW/evict
        protocol).  ``page_grant_ahead`` widens the span by that many
        pages past the write position (capped by the range method at
        the request's last real write — look-ahead never buys a page
        nobody keeps).  Returns False if ``s`` itself was evicted
        paying for its page."""
        wp = int(self._page_pos[s])
        ahead = self.engine_cfg.page_grant_ahead
        hi = wp + ahead * self.slots.page_size if ahead > 0 else wp
        return self._ensure_write_range(s, wp, hi)

    def _prepare_paged_tick(self) -> None:
        """Tick-boundary page maintenance: every active slot gets a
        PRIVATE page under its write position (grant on demand, COW on
        sharing, preemption on exhaustion), then the page table is
        re-uploaded iff it changed — table updates are host bookkeeping
        plus one async upload, never a device sync."""
        for s in range(self.engine_cfg.n_slots):
            if self._states[s] is not None:
                self._ensure_write_page(s)
        version = (self.slots.table_version,
                   self.wslots and self.wslots.table_version)
        if self._dev_table is None or self._table_uploaded != version:
            self._dev_table = jnp.asarray(self.slots.table)
            if self.wslots is not None:
                # (a COPY: a window's entries go back to NULL while the
                # tick in flight still reads them, and on a CPU backend
                # jnp.asarray may alias the host array)
                self._dev_table = (self._dev_table,
                                   jnp.asarray(self.wslots.table.copy()))
            self._table_uploaded = version

    def _ensure_write_range(self, s: int, lo: int, hi: int) -> bool:
        """Grant/COW PRIVATE pages under every write position in
        ``[lo, hi]`` — the speculative tick writes a WINDOW, not a
        point.  Positions past the request's last real write (or the
        table's capacity) are left unmapped: the kernel routes those
        writes to the NULL page, so no page is ever bought for a token
        nobody keeps.  Evicts youngest-first on exhaustion; returns
        False if slot ``s`` itself was the victim."""
        st = self._states[s]
        if st is None:
            return False
        last_real = (len(st.request.prompt)
                     + st.request.max_new_tokens - 2)
        hi = min(hi, last_real, self.slots.max_len - 1)
        if hi < lo:
            return True
        ps = self.slots.page_size
        mine = lambda: self._states[s] is not None  # noqa: E731
        for idx in range(max(lo, 0) // ps, hi // ps + 1):
            if not self._claim_page(s, idx, mine):
                return False  # s itself was the victim — it paid
        # (look-ahead is the full pool's knob: the window's bound holds)
        return self._ensure_window_pages(s, max(lo, 0), max(lo, 0), mine)

    def _ensure_window_pages(self, slot: int, nxt: int, hi: int,
                             still_mine) -> bool:
        """The window layers' side of a page plan (nothing without
        them): ``nxt`` is the next position the slot queries from, so
        pages wholly behind ITS window go back first, then every page
        from the window's first up to position ``hi`` is claimed —
        in that order, so a slot never holds more than the window's
        bound.  Returns False if ``slot`` itself was evicted."""
        w = self.wslots
        if w is None:
            return True
        w.release_behind(slot, nxt)
        for idx in range(w.first_live(nxt), hi // w.page_size + 1):
            if (w.table[slot, idx] == NULL_PAGE
                    and not self._claim_page(slot, idx, still_mine, w)):
                return False
        return True

    def _claim_page(self, slot: int, idx: int, still_mine,
                    cache: Optional[PagedSlotCache] = None) -> bool:
        """THE grant/COW/evict protocol, in one copy (decode growth,
        speculative windows, and chunk ingestion all route here):
        ensure ``slot`` owns a PRIVATE page at table index ``idx`` —
        grant when unmapped, copy-on-write when present-but-shared
        (no-op when already private) — preempting victims on
        exhaustion.  ``still_mine()`` is the caller's occupancy check;
        returns False when the caller itself was evicted paying for
        its page.  ``cache``: the pool to claim in (the full layers'
        by default; the window layers' from
        :meth:`_ensure_window_pages`)."""
        cache = cache or self.slots
        while True:
            try:
                if cache.table[slot, idx] == NULL_PAGE:
                    cache.grant(slot, idx)
                else:
                    cache.cow(slot, idx)
                return True
            except CacheOutOfPagesError:
                self._evict_for_pages()
                if not still_mine():
                    return False

    def _ensure_draft_range(self, s: int, lo: int, hi: int) -> None:
        """Draft-pool companion of :meth:`_ensure_write_range`.  Draft
        pages never evict anyone: on exhaustion the slot's speculation
        is simply DISABLED (acceptance forced to 0 as data — the plain
        greedy path through the same executable) and its draft pages
        return to the heap; correctness never depends on the draft."""
        draft = self.draft_slots
        st = self._states[s]
        if (st is None or not self._spec_host[s]
                or not self._spec_live[s] or not draft._active[s]):
            return
        last_real = (len(st.request.prompt)
                     + st.request.max_new_tokens - 2)
        hi = min(hi, last_real, draft.max_len - 1)
        if hi < lo:
            return
        ps = draft.page_size
        try:
            for idx in range(max(lo, 0) // ps, hi // ps + 1):
                if draft.table[s, idx] == NULL_PAGE:
                    draft.grant(s, idx)
        except CacheOutOfPagesError:
            draft.free(s)
            self._spec_host[s] = False

    def _prepare_spec_tick(self) -> None:
        """Tick-boundary maintenance for the SPECULATIVE tick.  The
        window writes positions ``[pos, pos + K]``; with the overlap
        pipeline, one dispatched-but-unfetched tick may have advanced
        the device pos by up to ``K + 1`` already — the host learns the
        accepted length one tick late — so grants cover the worst case
        (``_page_pos`` is the FETCH-time mirror here, unlike the
        non-speculative dispatch-time advance).  Over-granted pages are
        not waste: pos only grows, so they are used within a few ticks
        or freed at retirement."""
        W = self.engine_cfg.spec_k + 1
        pend = self._pending
        for s in range(self.engine_cfg.n_slots):
            st = self._states[s]
            if st is None:
                continue
            base = int(self._page_pos[s])
            inflight = (pend is not None and bool(pend["active"][s])
                        and pend["reqs"][s] is st.request)
            hi = base + (2 if inflight else 1) * W - 1
            if (self._ensure_write_range(s, base, hi)
                    and self._spec_model):
                self._ensure_draft_range(s, base, hi)
        if (self._dev_table is None
                or self._table_uploaded != self.slots.table_version):
            self._dev_table = jnp.asarray(self.slots.table)
            self._table_uploaded = self.slots.table_version
        if self._spec_model:
            d = self.draft_slots
            if (self._dev_dtable is None
                    or self._dtable_uploaded != d.table_version):
                self._dev_dtable = jnp.asarray(d.table)
                self._dtable_uploaded = d.table_version
        spec = (self._spec_host & self._spec_live
                & self._decode_mask())
        if (self._dev_spec_host is None
                or not np.array_equal(spec, self._dev_spec_host)):
            self._dev_spec = jnp.asarray(spec)
            self._dev_spec_host = spec

    def _draft_prefill_fn(self, bucket: int, k: int) -> Callable:
        fn = self._draft_prefill_fns.get((bucket, k))
        if fn is None:
            dcfg = self.draft_cfg

            def _prefill(params, padded, true_lens):
                self._prefill_traces += 1
                obs_tracing.record_compile("serving_draft_prefill")
                cache = T.init_cache(dcfg, k, bucket)
                return T.prefill(params, padded, cache, dcfg,
                                 true_len=true_lens, mesh=self.mesh)

            fn = self._jit(
                _prefill,
                in_s=self._shard and (self._sh_draft_params, self._sh_R,
                                      self._sh_R),
                out_s=self._shard and (self._sh_R, self._sh_prefill))
            self._draft_prefill_fns[(bucket, k)] = fn
        return fn

    def _spec_admit(self, slots: List[int], reqs: List[Request]) -> None:
        """Per-admission speculative bookkeeping.  The per-request
        opt-out lands in the slot mask; the n-gram draft gets the
        prompt row scattered into the device history; the model draft
        prefills its own paged pool with the FULL prompt (the draft
        has no prefix registry — one extra shallow forward per
        admission group, never fetched, so no host sync).  A draft
        pool that cannot hold the prompt disables speculation for the
        slot, never the request."""
        if not self._spec:
            return
        for slot, req in zip(slots, reqs):
            # A SAMPLED request never speculates: drafts are verified
            # by argmax agreement, which a sampled stream would reject
            # every tick — the kernel also forces its acceptance to 0
            # as defense in depth, this just skips paying for drafts.
            self._spec_host[slot] = (req.speculative is not False
                                     and req.temperature <= 0.0
                                     and self._spec_runtime_enabled)
        if not self._spec_model:
            # FULL-WIDTH rows: zero the whole row, not just the prompt
            # bucket — a previous tenant's committed tokens beyond the
            # bucket would otherwise survive in the history and could
            # be gathered into this request's drafts once its pos
            # grows past them (wasted verify width, and no request's
            # tokens should transit another's draft path).  Compile
            # set: one (k, max_len) shape per admission size k.
            k = len(slots)
            padded = np.zeros((k, self.slots.max_len), np.int32)
            for i, r in enumerate(reqs):
                padded[i, :len(r.prompt)] = r.prompt
            self._dev_history = self._hist_land(
                self._history(), np.asarray(slots, np.int32), padded)
            for slot in slots:
                self._spec_stale[slot] = False
            return
        draft = self.draft_slots
        for slot, req in zip(slots, reqs):
            if not self._spec_host[slot]:
                continue
            if not self._spec_live[slot]:
                # Adaptively disabled: skip the draft prefill now; a
                # probe rebuilds from prompt + emitted if it re-enables.
                self._spec_stale[slot] = True
                continue
            draft.acquire(slot)
            try:
                for idx in range(
                        (len(req.prompt) - 1) // draft.page_size + 1):
                    draft.grant(slot, idx)
            except CacheOutOfPagesError:
                draft.free(slot)
                self._spec_host[slot] = False
        live = [(s, r) for s, r in zip(slots, reqs)
                if self._spec_host[s] and draft._active[s]]
        if not live:
            return
        k = len(live)
        bucket = self._bucket(max(len(r.prompt) for _, r in live))
        padded = np.zeros((k, bucket), np.int32)
        lens = np.zeros((k,), np.int32)
        for i, (_, r) in enumerate(live):
            padded[i, :len(r.prompt)] = r.prompt
            lens[i] = len(r.prompt)
        _, pre = self._draft_prefill_fn(bucket, k)(
            self.draft_params, jnp.asarray(padded), jnp.asarray(lens))
        self._prefill_calls += 1
        draft.land([s for s, _ in live], pre, lens, start=0)
        for s, _ in live:
            self._spec_stale[s] = False

    def _reset_spec_state(self) -> None:
        """Reset ALL per-slot speculative state (opt-out mask, adaptive
        live/idle/window, staleness) — the ONE copy the restart,
        terminal, and post-warmup paths share."""
        self._spec_host[:] = True
        self._spec_live[:] = True
        self._spec_win[:] = 0
        self._spec_idle[:] = 0
        self._spec_stale[:] = False

    def _history(self):
        """The n-gram draft's device-resident committed-token buffer,
        created on first use (ONE definition of its shape)."""
        if self._dev_history is None:
            self._dev_history = jnp.zeros(
                (self.engine_cfg.n_slots, self.slots.max_len), jnp.int32)
        return self._dev_history

    def _spec_adapt(self, s: int, accepted: int) -> None:
        """Window the slot's acceptance; auto-disable speculation when
        it falls under the floor (spec_adaptive).  Disabling is pure
        data — output is identical either way — it just stops paying
        draft+verify for a stream the draft cannot predict."""
        if not self.engine_cfg.spec_adaptive:
            return
        self._spec_win[s, 0] += self.engine_cfg.spec_k
        self._spec_win[s, 1] += accepted
        if (self._spec_win[s, 0]
                >= self.engine_cfg.spec_window * self.engine_cfg.spec_k):
            rate = self._spec_win[s, 1] / self._spec_win[s, 0]
            if rate < self.engine_cfg.spec_min_acceptance:
                self._spec_live[s] = False
                self._spec_idle[s] = 0
                st = self._states[s]
                # submit-time handle, same reason as _evict_for_pages
                srec = st.request.future._spans if st is not None \
                    else None
                if (srec is not None and st is not None
                        and st.request.trace is not None):
                    try:
                        srec.request_event(
                            st.request.trace, "spec_fallback",
                            {"slot": s, "acceptance": round(rate, 4)})
                    except Exception:  # pragma: no cover
                        pass
                if self._spec_model:
                    # A disabled slot's draft POOL decays even during
                    # spec ticks (no pages are granted for it, so its
                    # writes route to the NULL page) — the probe must
                    # rebuild it or re-enabling would draft against a
                    # garbage gap and re-disable forever.  The n-gram
                    # HISTORY stays current through spec ticks (the
                    # kernel commits every active row's tokens), so it
                    # only goes stale on all-plain fallback ticks.
                    self._spec_stale[s] = True
            self._spec_win[s] = 0

    def _spec_probe_clock(self, s: int) -> None:
        """Tick the disabled slot's probe clock; at spec_probe_period
        re-enable speculation for one evaluation window (rebuilding
        any draft state plain ticks staled) so a stream that BECOMES
        predictable gets speculation back."""
        if not self._spec_live[s] and self._spec_host[s]:
            self._spec_idle[s] += 1
            if self._spec_idle[s] >= self.engine_cfg.spec_probe_period:
                if self._spec_stale[s] and not self._respec_slot(s):
                    self._spec_idle[s] = 0  # rebuild failed: try later
                    return
                self._spec_stale[s] = False
                self._spec_live[s] = True
                self._spec_idle[s] = 0
                self._spec_win[s] = 0

    def _respec_slot(self, s: int) -> bool:
        """Rebuild slot ``s``'s draft state after plain ticks staled it
        — the committed stream is ``prompt + tokens emitted this
        tenancy``: re-land the n-gram history row, or re-prefill the
        draft pool up to (but excluding) the pending input token, just
        like admission does."""
        st = self._states[s]
        if st is None:
            return False
        fut = st.request.future
        toks = fut.tokens_so_far()
        gen = toks[len(toks) - st.n_generated:] if st.n_generated else []
        committed = list(st.request.prompt) + [int(t) for t in gen]
        if not self._spec_model:
            # FULL-WIDTH row (not the prompt's bucket): committed
            # length grows with every probe, and a bucketed landing
            # here would JIT-compile a new shape mid-serving for each
            # new length class — one (1, max_len) shape serves every
            # probe forever.
            padded = np.zeros((1, self.slots.max_len), np.int32)
            padded[0, :len(committed)] = committed
            self._dev_history = self._hist_land(
                self._history(), np.asarray([s], np.int32), padded)
            return True
        draft = self.draft_slots
        # The probe fires at FETCH time, after _page_pos advanced for
        # the tick being retired but before its token is emitted — at
        # that instant the cache-committed set is exactly prompt + all
        # tokens emitted so far (the incoming token, this tick's, is
        # the next pending input and is NOT in `committed` yet).  So
        # the FULL list re-prefills, landing draft pos = len(committed)
        # = the device pos; the in-kernel entry sync covers any
        # overlap-pipeline skew beyond that.
        body = committed
        if not body:
            return False
        if not draft._active[s]:
            draft.acquire(s)
        try:
            for idx in range((len(body) - 1) // draft.page_size + 1):
                if draft.table[s, idx] == NULL_PAGE:
                    draft.grant(s, idx)
        except CacheOutOfPagesError:
            draft.free(s)
            return False
        # FIXED full-width prefill shape (max_len, 1), like the n-gram
        # branch: the committed length grows past every warmed prompt
        # bucket, and a bucketed call here would JIT-compile inside a
        # serving step (and inside the watchdog budget) at probe time.
        # warmup() pre-compiles this one shape.
        width = self.slots.max_len
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(body)] = body
        lens = np.asarray([len(body)], np.int32)
        _, pre = self._draft_prefill_fn(width, 1)(
            self.draft_params, jnp.asarray(padded), jnp.asarray(lens))
        self._prefill_calls += 1
        draft.land([s], pre, lens, start=0)
        return True

    @staticmethod
    def _pick(logits, pos, active, s_t, s_k, s_p, s_key, replicated=None):
        """The ONE in-tick next-token pick, shared by every tick body:
        the token being chosen sits at logical position ``pos + 1``
        (``pos`` = the pool position at tick ENTRY — the input token's
        slot), so its PRNG key is ``fold_in(fold_in(key, pos + 1), 0)``
        — exactly the per-request ``sample_decode`` oracle's schedule
        for row 0 (tests/test_sampling.py).  A batch of greedy rows
        runs the argmax alone: each further stage (draw, top-k sort,
        nucleus sort) runs only in a tick where some row asks for it,
        by ``lax.cond`` inside the kernel (``replicated``: under tp, the
        sharding that holds the logits whole on every device, so that
        no branch holds a collective).  Returns ``(next tokens, zeroed
        for inactive rows; per-slot max logit)`` — the max rides along for
        the host-side finiteness check: NaN/Inf logits (bad params,
        flaky hardware) must become a typed engine failure, not
        silently-greedy garbage tokens."""
        with jax.named_scope("sample"):
            nxt = T.sample_token_rows(logits, s_t, s_k, s_p, s_key,
                                      pos + 1, jnp.zeros_like(pos),
                                      replicated=replicated)
            return jnp.where(active, nxt, 0), jnp.max(logits, axis=-1)

    def _run_tick(self, tokens_dev, active_dev):
        """Dispatch ONE compiled decode tick.  Returns ``(next-token
        device vector, pending extras)`` — the extras are what
        :meth:`_retire_pending` fetches: plain ticks carry ``nxt``
        ``(S,)`` / ``mx`` ``(S,)``; speculative ticks carry the full
        target-token window ``nxt`` ``(S, W)``, ``mx`` ``(S, W)``, the
        per-slot accepted length ``acc`` ``(S,)``, and the dispatch-
        time speculation mask."""
        s_t, s_k, s_p, s_key = self._samp.device()
        # Which stages of the pick this tick's columns open: the device
        # decides from the same columns (T.sample_gates), the host only
        # counts.
        draws, any_k, any_p = self._samp.gates()
        if not draws:
            self.metrics.sample_ticks_drawfree.inc()
        if not (draws and (any_k or any_p)):
            self.metrics.sample_ticks_sortfree.inc()
        if self._spec and self._dev_spec_host.any():
            if self._spec_model:
                nxt, t, mx, acc, pool, dpool = self._spec_tick_fn(
                    self.params, self.draft_params, tokens_dev,
                    active_dev, self._dev_spec, self._dev_table,
                    self._dev_dtable, self.slots.cache,
                    self.draft_slots.cache, s_t, s_k, s_p, s_key)
                self.draft_slots.cache = dpool
            else:
                nxt, t, mx, acc, pool, hist = self._spec_tick_fn(
                    self.params, tokens_dev, active_dev, self._dev_spec,
                    self._dev_table, self.slots.cache,
                    self._history(), s_t, s_k, s_p, s_key)
                self._dev_history = hist
            self.slots.cache = pool
            return nxt, {"nxt": t, "mx": mx, "acc": acc,
                         "spec": self._dev_spec_host.copy()}
        if self._spec:
            # Nobody speculating this tick: the plain one-token
            # executable earns the same greedy token at plain cost.
            # Draft state (history / draft cache) goes stale for the
            # slots it skips — marked for rebuild at re-probe.
            self._spec_stale |= self.slots.active_mask()
        pool = self.slots.cache
        if self.wslots is not None:   # the window layers' arrays beside
            pool = {**pool, **{w: self.wslots.cache[n]
                               for w, n in T.WINDOW_ARRAYS.items()}}
        nxt, mx, cache, *load = self._tick_fn(
            self.params, tokens_dev, active_dev, self._dev_table,
            pool, s_t, s_k, s_p, s_key)
        if self.wslots is not None:
            self.wslots.cache = {**self.wslots.cache, **{
                n: cache.pop(w) for w, n in T.WINDOW_ARRAYS.items()}}
        self.slots.cache = cache
        return nxt, {"nxt": nxt, "mx": mx,
                     **({"moe": load[0]} if load else {})}

    def _update_page_gauges(self) -> None:
        # Statics re-asserted too: benchmarks swap in a fresh
        # ServingMetrics after warmup, which would otherwise zero them.
        self.metrics.kv_pages_total.set(self.slots.n_pages)
        self.metrics.kv_bytes_per_token.set(self.slots.bytes_per_token)
        self.metrics.kv_pages_free.set(self.slots.free_pages)
        self.metrics.kv_pages_shared.set(self.slots.pages_shared)
        if self.wslots is not None:
            self.metrics.kv_window_pages_total.set(self.wslots.n_pages)
            self.metrics.kv_window_pages_free.set(self.wslots.free_pages)
            self.metrics.kv_window_pages_per_slot_max.set(
                self.wslots.slot_pages_max)

    # -- the tick ----------------------------------------------------------

    def step(self) -> bool:
        """One SUPERVISED engine tick: admit up to K requests into free
        slots, then one masked decode over all S slots.  Returns True
        if any work was done (False = idle; callers may sleep).

        An exception anywhere in the tick does not propagate: every
        in-flight future is resolved with a typed
        :class:`EngineFailedError` and the engine restarts (fresh slot
        cache, bounded attempts, exponential backoff) — or goes
        terminally ``failed`` when the budget is exhausted."""
        self._step_spans.clear()  # the phases of THIS step (start's loop)
        if self._health == FAILED:
            return False
        t_step = time.monotonic()
        with self._hb_lock:
            self._tick_started = t_step
        try:
            faults = self.engine_cfg.faults
            if faults is not None:
                faults.probe("watchdog")  # a "hang" here stalls the tick
            # The phases below (and those inside the calls) PARTITION
            # the step: one after another, none inside another.
            with self._phase("lock_wait"):  # the acquisition, no more
                self._lock.acquire()
            try:
                with self._phase("reclaim"):
                    worked = self._reclaim_cancelled()
                worked = self._admit_pending() or worked
                if self.engine_cfg.overlap:
                    worked = self._decode_tick_overlapped() or worked
                else:
                    worked = self._decode_tick() or worked
                with self._phase("bookkeeping"):
                    # Freeing a device array gives up the interpreter
                    # lock: after a tick's emissions woke a handler
                    # thread a stream, the engine thread stands HERE
                    # until each has had its turn (PERF.md section 5).
                    self._retired = None
                    self.metrics.queue_depth.set(self.scheduler.depth)
                    self.metrics.slot_occupancy.set(self.slots.occupancy)
                    self._update_page_gauges()
            finally:
                self._lock.release()
            self._observe_step_kind(t_step)
        except Exception as exc:  # supervised: ANY tick failure recovers
            self._observe_step_kind(t_step)
            with self._hb_lock:
                self._tick_started = None
                stalled = self._stalled
            # A stalled tick that ends by RAISING is still one incident:
            # the watchdog already counted it when it declared the stall.
            self._recover(exc, counted=stalled)
            return True
        with self._hb_lock:
            self._tick_started = None
            self._last_tick_done = time.monotonic()
            stalled = self._stalled
        if stalled:
            # The watchdog declared us dead mid-tick but the tick DID
            # return: futures are already resolved; restart the engine
            # through the same supervised path (no double-counting —
            # the watchdog already counted the failure).
            self._recover(EngineStalledError(
                f"tick exceeded the {self.engine_cfg.tick_timeout}s "
                f"watchdog budget"), counted=True)
            return True
        # Clean tick: recover health, reset the consecutive-failure
        # budget the supervised restarts draw from.
        if self._consec_failures or self._health == DEGRADED:
            self._consec_failures = 0
            if self._health == DEGRADED:
                self._set_health(HEALTHY)
        # Autotuner hook, OUTSIDE the step lock: a knob apply
        # re-acquires it, which makes every swap a clean tick-boundary
        # transaction (tuning/tuner.py).  Clean ticks only — a
        # recovering tick's window would score restart noise.
        if self._tuner is not None:
            try:
                with self._phase("bookkeeping"):
                    self._tuner.on_tick(self, worked)
            except Exception:  # tuning must never take serving down
                self._tuner = None
        return worked

    def _phase(self, name: str, **attrs) -> obs_tracing.phase:
        """One phase of the engine loop: an ``hvd:<name>`` span on a
        profiler trace, its two histograms (wall and CPU seconds) in
        the CURRENT metrics object (benchmarks swap in a fresh one
        after warm-up), the active tracer's tick row, and — kept until
        the next step begins — the loop's record of a slow step."""
        metrics = self.metrics
        ph = obs_tracing.phase(name, metrics.phases[name],
                               metrics.phases_cpu[name], **attrs)
        self._step_spans.append(ph)
        return ph

    def _observe_step_kind(self, t_step: float) -> None:
        """Close a step that dispatched a decode tick: its wall time goes
        to ``engine_step{kind=}`` by what else the same step ran — an
        admission prefill (``prefill``, also when a chunk rode along),
        an ingest chunk (``chunk``), or neither (``plain``)."""
        self._step_kind = None
        if self._step_tick:
            self._step_kind = kind = (
                "prefill" if self._step_prefill
                else "chunk" if self._step_chunk else "plain")
            self.metrics.engine_step[kind].observe(
                time.monotonic() - t_step)
        self._step_tick = self._step_prefill = self._step_chunk = False

    def _count_prefill(self, tokens: int, rows: int, bucket: int,
                       chunk: bool = False) -> None:
        """One prefill forward pass of the target model: ``tokens`` real
        prompt tokens in ``rows`` rows padded to ``bucket``.  It marks
        the step as one that carried an admission prefill (or an ingest
        ``chunk``) here, where an executable ran: an admission that only
        attached shared pages, or whose group emptied, leaves it plain."""
        if chunk:
            self._step_chunk = True
        else:
            self._step_prefill = True
        self._prefill_calls += 1
        self.metrics.prefill_tokens.inc(tokens)
        self.metrics.prefill_padded_tokens.inc(rows * bucket)
        self.metrics.ssm_scanned_tokens.inc(tokens * self._ssm_layers)
        self.metrics.lin_scanned_tokens.inc(tokens * self._lin_layers)

    def _count_paged_walk(self, active: np.ndarray) -> None:
        """One dispatched paged tick: the positions its active slots may
        attend (everything up to and including the token being written —
        read BEFORE the dispatch-time advance of ``_page_pos``) beside
        the positions the kernel's walk covers for those limits (the
        kernel's own trip count, ``ops.paged_attention.walk``)."""
        limit = self._page_pos[active] + 1
        self.metrics.ssm_updated_slots.inc(len(limit) * self._ssm_layers)
        self.metrics.lin_updated_slots.inc(len(limit) * self._lin_layers)
        if self.cfg.has_bsa:
            # a block-sparse tick scores the compressed row of every
            # whole window a KV head and attends the chosen blocks
            # alone (every block of a context within bsa_dense_len):
            # the whole pool's walk does not run
            cfg = self.cfg
            rows = np.maximum(limit // cfg.bsa_stride - 1, 0)
            chosen = (cfg.bsa_init_blocks + cfg.bsa_topk
                      + cfg.bsa_window // cfg.bsa_block - 1) * cfg.bsa_block \
                + (limit - 1) % cfg.bsa_block + 1
            attended = np.where(limit <= cfg.bsa_dense_len, limit,
                                np.minimum(limit, chosen))
            self.metrics.bsa_scored_rows.inc(
                int(rows.sum()) * cfg.kv_heads * self._bsa_layers)
            self.metrics.bsa_attended_tokens.inc(
                int(attended.sum()) * self._bsa_layers)
            self.metrics.bsa_live_tokens.inc(
                int(limit.sum()) * self._bsa_layers)
            return
        if self.cfg.sparse:
            # a sparse model's tick walks the INDEX keys of every live
            # token and reads at most index_topk latent rows a slot:
            # the latent pool's own walk does not run
            k = self.cfg.index_topk
            _, walked = _pa.walk(limit, self._index_block_tokens)
            self.metrics.dsa_scored_tokens.inc(int(limit.sum()))
            self.metrics.dsa_walked_tokens.inc(int(walked.sum()))
            self.metrics.dsa_selected_tokens.inc(
                int(np.minimum(limit, k).sum()))
            self.metrics.dsa_full_rows.inc(int((limit <= k).sum()))
            return
        _, walked = _pa.walk(limit, self._walk_block_tokens)
        self.metrics.paged_live_tokens.inc(int(limit.sum()))
        self.metrics.paged_walked_tokens.inc(int(walked.sum()))
        if self.wslots is not None:
            # a window layer's walk: the same statement, told the bound
            lower = np.maximum(limit - self.cfg.window, 0)
            _, walked = _pa.walk(limit, self._walk_block_tokens, lower)
            self.metrics.window_live_tokens.inc(int((limit - lower).sum()))
            self.metrics.window_walked_tokens.inc(int(walked.sum()))

    def _reclaim_cancelled(self) -> bool:
        """Free slots whose requests were cancelled caller-side — their
        futures resolve with the tokens so far (reason "cancelled") —
        or whose futures were already resolved externally (a submit
        that raced a drain); either way the slot must not leak."""
        worked = False
        for s, st in enumerate(self._states):
            if st is None:
                continue
            fut = st.request.future
            if fut.done():
                self._states[s] = None
                self._release_slot(s)
                worked = True
                continue
            if fut.cancel_requested:
                fut._finish("cancelled")
                self.metrics.cancelled.inc()
                self._states[s] = None
                self._release_slot(s)
                worked = True
        for s in list(self._ingest):
            worked = self._reap_ingest(s) or worked
        return worked

    def _reap_ingest(self, slot: int) -> bool:
        """Release an ingesting slot whose request can no longer run
        — future already resolved (raced a drain) or cancellation
        pending — in ONE copy (shared by the per-tick reclaim sweep
        and the chunk step's entry check).  Returns True if the slot
        was reaped."""
        ing = self._ingest.get(slot)
        if ing is None:
            return False
        fut = ing.request.future
        if not (fut.done() or fut.cancel_requested):
            return False
        if not fut.done():
            fut._finish("cancelled")
            self.metrics.cancelled.inc()
        self._ingest.pop(slot, None)
        self._release_slot(slot)
        return True

    def _admit_pending(self) -> bool:
        with self._phase("admit"):
            # Tick-boundary deadline sweep: resolve EVERY dead queued
            # request (lapsed deadline, cancel, raced drain) wherever it
            # sits — a doomed request's 504 must not wait behind a long
            # admission stall for take() to reach it.
            swept = self.scheduler.sweep()
            self._tick_prefill_spent = 0
            self._tick_ingested = set()
            # Slot-pressure preemption BEFORE the take: a strictly
            # better-class arrival claims a slot from the worst occupant
            # (suspended, never lost) instead of waiting out its decode.
            preempted = self._preempt_for_slots()
            # Page back-pressure: the take stops (requests WAIT,
            # scheduling order intact) when the next admission's
            # private pages would overdraw the free heap — typed
            # starvation-free admission control instead of silent
            # over-allocation.
            budget = self.slots.free_pages
            # Clamp the plan to the deepest the free heap can ever get
            # (pool minus registry-pinned prefix pages): the plan's
            # growth-margin page is a heuristic, and an unclamped
            # demand above that depth would park a request the
            # submit-time fit check accepted at the FCFS head FOREVER
            # — admit it when the pool is as free as it gets and let
            # on-demand grant/preemption resolve the tail instead.
            pinned = sum(
                len(e.pages) for e in self._prefixes.values()
                if e.pages is not None and e.epoch == self._cache_epoch)
            attainable = max(self.slots.n_pages - pinned, 1)
            reserved = 0

            # Per-tick prefill TOKEN budget (chunked prefill): admissions
            # past the first stop once the tick's ingestion budget is
            # spent — they wait one tick, bounding how long the decode
            # batch stalls on prompt ingestion.  The FIRST admission is
            # always allowed (liveness: a chunked one costs <= one chunk
            # by construction, and a short over-budget prompt must not
            # park forever).
            tok_budget = self.engine_cfg.prefill_chunk_tokens
            n_admit = 0

            def admit_fn(req):
                nonlocal n_admit, reserved
                need = min(self._plan_pages(req), attainable)
                if reserved + need > budget:
                    return False
                reserved += need
                if tok_budget:
                    cost = self._prefill_cost(req)
                    if n_admit and self._tick_prefill_spent + cost \
                            > tok_budget:
                        return False
                    if not self._chunked(req):
                        # A chunked admission's spend is counted by its
                        # _ingest_step — counting it here too would
                        # double-charge the tick.
                        self._tick_prefill_spent += cost
                n_admit += 1
                return True

            reqs = self.scheduler.take(
                self.slots.free_count, bucket_fn=self._group_key,
                admit_fn=admit_fn)
            if not reqs and self.scheduler.depth \
                    and self.engine_cfg.resume and self.journal is not None:
                # PAGE-pressure preemption: an empty take with a non-empty
                # queue means the scheduling-order head was blocked — by
                # the page budget (slot pressure already ran pre-take; the
                # token budget and bucket truncation never block the FIRST
                # candidate).  If the head outranks the worst occupant,
                # suspend that occupant so its pages free the head next
                # tick; within a class the head keeps waiting, as ever.
                best = self.scheduler.peek_best_rank()
                occ = self._occupants()
                if best is not None and occ:
                    worst = max(occ)
                    if worst[0] > best:
                        self._preempt(worst[2], "page_pressure")
            self._taken = list(reqs)
            live: List[Request] = []
            for req in reqs:
                if req.future.done():  # resolved while taken (raced drain)
                    self._taken.remove(req)
                    continue
                if req.future.cancel_requested:
                    req.future._finish("cancelled")
                    self.metrics.cancelled.inc()
                    self._taken.remove(req)
                    continue
                live.append(req)
        if live:
            self._admit_batch(live)
        self._taken = []
        advanced = self._advance_ingest()
        return bool(reqs) or advanced or bool(swept) or preempted

    def _prefill_fn(self, bucket: int, k: int) -> Callable:
        fn = self._prefill_fns.get((bucket, k))
        if fn is None:
            def _prefill(params, padded, true_lens):
                self._prefill_traces += 1
                obs_tracing.record_compile("serving_prefill")
                cache = T.init_cache(self.cfg, k, bucket)
                return T.prefill(params, padded, cache, self.cfg,
                                 true_len=true_lens, mesh=self.mesh)

            fn = self._jit(
                _prefill,
                in_s=self._shard and (self._sh_params, self._sh_R,
                                      self._sh_R),
                out_s=self._shard and (self._sh_R, self._sh_prefill))
            self._prefill_fns[(bucket, k)] = fn
        return fn

    def _bucket(self, n: int) -> int:
        b = max(self.engine_cfg.min_prefill_bucket, 1)
        while b < n:
            b *= 2
        return min(b, self.slots.max_len)

    def _first_tokens(self, reqs: List[Request], logits) -> np.ndarray:
        """An admission group's FIRST tokens from its prefill logits —
        the prefill IS the first decode step.  All-greedy groups keep
        the plain argmax fetch; any sampled member routes the whole
        group through the shared sampling kernel (greedy rows still
        argmax inside it), each row drawing with its own seed at key
        index ``len(prompt)`` — for a RESUMED request the prompt
        already includes the emitted tokens, so the index continues
        the stream exactly where the last life stopped."""
        if all(r.temperature <= 0.0 for r in reqs):
            return np.asarray(jnp.argmax(logits, axis=-1))
        k = len(reqs)
        temp = np.array([r.temperature for r in reqs], np.float32)
        tk = np.array([r.top_k for r in reqs], np.int32)
        tp = np.array([r.top_p for r in reqs], np.float32)
        keys = np.stack([seed_key(r.seed) for r in reqs])
        pos = np.array([len(r.prompt) for r in reqs], np.int32)
        return np.asarray(self._first_sample(
            logits, jnp.asarray(temp), jnp.asarray(tk), jnp.asarray(tp),
            jnp.asarray(keys), jnp.asarray(pos)))

    def _admit_batch(self, reqs: List[Request]) -> None:
        """ONE bucketed batch-K prefill admits the whole group (the
        burst-TTFT lever: K prompts cost one forward pass, not K) ->
        one insert scatter lands all K in their slots -> one host
        fetch yields the K first tokens (prefill logits ARE the first
        greedy step).  The scheduler's bucket-uniform take keeps the
        group on one bucket, so the compile set is buckets x K."""
        if len(reqs) == 1 and self._chunked(reqs[0]):
            # Long prompt: chunked ingestion (singleton group by
            # construction of _group_key) — it rides the tick, it
            # does not stall it.
            self._admit_chunked(reqs[0])
            return
        costs = [self._prefill_cost(r) for r in reqs]
        with self._phase(
                "prefill", k=len(reqs), bucket=self._bucket(max(costs)),
                tokens=sum(costs),
                trace_ids=",".join(r.trace.trace_id for r in reqs
                                   if r.trace is not None)):
            self._prefill_group(reqs)

    def _prefill_group(self, reqs: List[Request]) -> None:
        """The body of a whole-prompt admission (the ``prefill`` phase):
        dispatch, landing, and the first-token fetch."""
        faults = self.engine_cfg.faults
        if faults is not None:
            faults.probe("prefill")
        t_adm = time.monotonic()
        for req in reqs:
            if req.trace is not None and req.trace.admitted_at is None:
                # queue-wait ends here; a RESUMED re-admission keeps
                # its first life's stamps (prefill_s would otherwise
                # go negative against the original first_token_at)
                req.trace.admitted_at = t_adm
                self.metrics.observe_queue_wait(
                    req.priority, t_adm - req.submitted_at)
        slots, reqs, firsts, synced = self._admit_paged(reqs)
        if not reqs:
            return
        if synced:
            # Attach-only admission (prompt == prefix) fetches
            # nothing — the counter tracks real blocking syncs only.
            self.metrics.host_syncs.inc()
        now = time.monotonic()
        for slot, req, first in zip(slots, reqs, firsts):
            if req.future.ttft is None:
                # A RESUMED request already served its first token in a
                # previous life — its TTFT was honest then and must not
                # be rewritten by the re-admission.
                ttft = now - req.submitted_at
                req.future.ttft = ttft
                self.metrics.observe_ttft(req.priority, ttft)
            if req.trace is not None:
                req.trace.slot = slot
                if req.trace.first_token_at is None:
                    req.trace.first_token_at = now
            self.metrics.admitted.inc()
            # The slot's sampling columns land BEFORE the next decode
            # dispatch (step() admits first) — an async re-upload of
            # four (S,)-rows, no sync.  Greedy requests write zeros,
            # which IS the greedy row.
            self._samp.set(slot, temperature=req.temperature,
                           top_k=req.top_k, top_p=req.top_p,
                           seed=req.seed)
            self._states[slot] = _SlotState(request=req,
                                            last_token=int(first),
                                            n_generated=0)
            self._emit(slot, int(first))
            self._taken.remove(req)  # landed: _states[slot] owns it now
        if self._dev_tokens is not None:
            # Land the first tokens in the device-resident token vector
            # (a slot retired by its own first token — EOS at admission
            # — is inactive in the mask; its value is a don't-care).
            vals = np.zeros(self.engine_cfg.n_slots, np.int32)
            mask = np.zeros(self.engine_cfg.n_slots, bool)
            for slot, first in zip(slots, firsts):
                vals[slot] = int(first)
                mask[slot] = True
            self._dev_tokens = self._merge_tokens(
                self._dev_tokens, jnp.asarray(vals), jnp.asarray(mask))

    def _alloc_slot(self) -> int:
        """A free slot (``take()`` is bounded by ``free_count``), taken
        in the window layers' paired pool too."""
        slot = self.slots.alloc()
        assert slot is not None
        if self.wslots is not None:
            self.wslots.acquire(slot)
        return slot

    def _land(self, slots, pre: Dict, lens, start: int) -> None:
        """Land a prefilled block in the slots' pages — each kind of
        layer's K/V in its own pool."""
        self.slots.land(slots, pre, lens, start=start)
        if self.wslots is not None:
            self.wslots.land(slots, {"pos": pre["pos"], **{
                n: pre[w] for w, n in T.WINDOW_ARRAYS.items()}},
                lens, start=start)

    def _map_pages(self, slot: int, req: Request,
                   entry: Optional[_PrefixEntry]) -> None:
        """Build one slot's page table for admission: attach the shared
        prefix pages (refcount, no copy), COW the partially-filled
        prefix page if the suffix must write into it, grant fresh
        private pages for the rest of the prompt."""
        ps = self.slots.page_size
        n_idx = (len(req.prompt) - 1) // ps + 1
        if entry is None:
            for idx in range(n_idx):
                self.slots.grant(slot, idx)
            if self.wslots is not None:
                # only what the first decoded token's window reaches:
                # the landing routes the rest to the trash page
                for idx in range(self.wslots.first_live(len(req.prompt)),
                                 n_idx):
                    self.wslots.grant(slot, idx)
            return
        p0 = len(entry.tokens)
        self.slots.attach(slot, entry.pages)
        if len(req.prompt) == p0:
            return  # attach-only; decode growth grants/COWs at dispatch
        first_new = p0 // ps
        if p0 % ps:
            # The last prefix page is partial and the suffix lands
            # inside it: copy-on-write BEFORE any write targets it.
            self.slots.cow(slot, first_new)
            first_new += 1
        for idx in range(first_new, n_idx):
            self.slots.grant(slot, idx)

    def _admit_paged(self, reqs: List[Request]):
        """Paged admission.  The group key guarantees every request
        here shares one prefill shape AND one matched prefix, so the
        whole group costs: zero prefill (prompt == prefix: attach pages
        + cached first token), or ONE suffix prefill attending the
        shared prefix pages, or ONE full prefill — then one landing
        scatter into granted pages.  A request whose page plumbing
        overdraws the pool (the admission budget is a heuristic, not a
        reservation) is resolved with the typed
        :class:`CacheOutOfPagesError` and the rest of the group
        proceeds."""
        entry = self._matched_prefix(reqs[0])
        if entry is not None:
            try:
                self._ensure_prefix(entry)
            except CacheOutOfPagesError:
                entry = None  # degrade: full prefill, no sharing
        p0 = len(entry.tokens) if entry is not None else 0
        slots: List[int] = []
        live: List[Request] = []
        for req in reqs:
            slot = self._alloc_slot()
            try:
                self._map_pages(slot, req, entry)
            except CacheOutOfPagesError as e:
                self._release_slot(slot)  # releases whatever got mapped
                req.future.set_exception(e)
                self.metrics.rejected.inc()
                self._taken.remove(req)
                continue
            slots.append(slot)
            live.append(req)
        if not live:
            return [], [], [], False
        k = len(live)
        synced = True  # a prefill's argmax fetch — except attach-only
        if entry is not None:
            suf_lens = np.asarray([len(r.prompt) - p0 for r in live],
                                  np.int32)
            if int(suf_lens.max()) == 0:
                # The prompt IS the prefix: its K/V already exists —
                # admission is pure bookkeeping, and GREEDY sharers
                # reuse the cached first token.  SAMPLED sharers each
                # draw their own first token from the prefix's cached
                # last-position logits (one kernel call, same (k, V)
                # executable as a regular sampled admission).
                self.slots.set_pos(slots, [p0] * k)
                if any(r.temperature > 0.0 for r in live):
                    firsts = self._first_tokens(live, jnp.broadcast_to(
                        entry.logits, (k, entry.logits.shape[-1])))
                else:
                    firsts = np.asarray([entry.first_token] * k)
                    synced = False
            else:
                bucket = self._bucket(int(suf_lens.max()))
                padded = np.zeros((k, bucket), np.int32)
                for i, r in enumerate(live):
                    padded[i, :len(r.prompt) - p0] = r.prompt[p0:]
                logits, suf = self._suffix_prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray(suf_lens),
                    self.slots.gather_prefix(entry.pages), jnp.int32(p0))
                self._count_prefill(int(suf_lens.sum()), k, bucket)
                self.slots.land(slots, suf, suf_lens, start=p0)
                firsts = self._first_tokens(live, logits)
        else:
            bucket = max(self._bucket(len(r.prompt)) for r in live)
            padded = np.zeros((k, bucket), np.int32)
            lens = np.zeros((k,), np.int32)
            for i, r in enumerate(live):
                padded[i, :len(r.prompt)] = r.prompt
                lens[i] = len(r.prompt)
            logits, pre = self._prefill_fn(bucket, k)(
                self.params, jnp.asarray(padded), jnp.asarray(lens))
            self._count_prefill(int(lens.sum()), k, bucket)
            self._land(slots, pre, lens, start=0)
            firsts = self._first_tokens(live, logits)
        for slot, req in zip(slots, live):
            self._page_pos[slot] = len(req.prompt)
        self._spec_admit(slots, live)
        return slots, live, firsts, synced

    # -- chunked prefill (EngineConfig.prefill_chunk_tokens) ---------------

    def _decode_mask(self) -> np.ndarray:
        """Active mask for the DECODE tick: allocated slots minus
        those still ingesting their prompt chunk by chunk — an
        ingesting slot holds pages and occupancy but has no token
        stream to decode yet."""
        active = self.slots.active_mask()
        if self._ingest:
            active = active.copy()
            for s in self._ingest:
                active[s] = False
        return active

    def _admit_chunked(self, req: Request) -> None:
        """Admit ONE long-prompt request into a slot for CHUNKED
        ingestion: attach any matched shared prefix (refcount, no
        compute), open the ingest state, and land the first chunk on
        this tick's budget.  The slot decodes nothing until the last
        chunk's logits yield the first token
        (:meth:`_finish_ingest`)."""
        with self._phase("admit"):
            t_adm = time.monotonic()
            if req.trace is not None and req.trace.admitted_at is None:
                req.trace.admitted_at = t_adm
                self.metrics.observe_queue_wait(
                    req.priority, t_adm - req.submitted_at)
            entry = self._matched_prefix(req)
            if entry is not None:
                try:
                    self._ensure_prefix(entry)
                except CacheOutOfPagesError:
                    entry = None  # degrade: chunk the whole prompt
            slot = self._alloc_slot()
            p0 = 0
            if entry is not None:
                self.slots.attach(slot, entry.pages)
                p0 = len(entry.tokens)
            self._ingest[slot] = _IngestState(request=req, landed=p0,
                                              started=p0)
            self._page_pos[slot] = p0
            self.metrics.admitted.inc()
            self._taken.remove(req)  # the ingest state owns it now
        self._ingest_step(slot)

    def _ensure_ingest_pages(self, slot: int, lo: int, hi: int) -> bool:
        """Grant/COW the pages a chunk landing on ``[lo, hi]`` will
        write — the ingestion face of the ONE :meth:`_claim_page`
        protocol (COW covers the partially-filled last page of an
        attached prefix; grants cover the fresh chunk span).  Evicts
        through the preemption policy on exhaustion; returns False if
        ``slot`` itself was the victim."""
        ps = self.slots.page_size
        for idx in range(max(lo, 0) // ps, hi // ps + 1):
            if not self._claim_page(
                    slot, idx, lambda: slot in self._ingest):
                return False  # we were the youngest — we paid
        return True

    def _gather_landed(self, slot: int, lo: int):
        """What the slot's layers keep for its first ``lo`` positions,
        as ``T.prefill_with_prefix`` takes it for the next chunk: ONE
        dict under the pool's names, and where a window layer's block
        starts (none: ``()``).  The pages are the first
        ``pages_for(lo)`` of the table, padded to a power-of-two page
        count with NULL pages (their junk is masked out by the traced
        prefix length ``lo``), so the gather + suffix-prefill compile
        set is bounded by page-count buckets — chunk boundaries stay
        pure data."""
        n_pg = self.slots.pages_for(lo)

        def gather(cache, first):
            pages = [int(cache.table[slot, i]) for i in range(first, n_pg)]
            padded = 1
            while padded < len(pages):
                padded *= 2
            return cache.gather_prefix(
                pages + [NULL_PAGE] * (padded - len(pages)))

        prefix = gather(self.slots, 0)
        # ... and the per-slot state as the last chunk left it (the
        # first array by slot_state's default name: the benchmark's
        # controls patch that method in its one-argument form)
        first, *rest = self.slots.state_arrays or (None,)
        if first:
            prefix[first] = self.slots.slot_state(slot)
        prefix.update((n, self.slots.slot_state(slot, n)) for n in rest)
        if self.wslots is None:
            return prefix, ()
        # the window layers' block starts at the first page the
        # chunk's first query (position lo) still sees
        first = self.wslots.first_live(lo)
        landed = gather(self.wslots, first)
        prefix.update((w, landed[n]) for w, n in T.WINDOW_ARRAYS.items())
        return prefix, (jnp.int32(first * self.wslots.page_size),)

    def _ingest_step(self, slot: int) -> bool:
        """Land ONE chunk of ``slot``'s prompt: grant/COW the chunk's
        pages, run the chunk through ``prefill_with_prefix`` attending
        the already-landed pages (position-wise bit-identical to a
        whole-prompt prefill), and scatter the chunk K/V into the
        slot's pages.  The final chunk's logits ARE the whole-prompt
        logits — :meth:`_finish_ingest` turns them into the first
        token.  Returns True if any work was done."""
        ing = self._ingest.get(slot)
        if ing is None:
            return False
        lo = ing.landed
        hi = lo + min(len(ing.request.prompt) - lo,
                      self.engine_cfg.prefill_chunk_tokens)
        with self._phase("ingest_chunk", slot=slot, lo=lo, hi=hi,
                         trace_id=ing.request.trace.trace_id
                         if ing.request.trace is not None else ""):
            return self._ingest_chunk(slot, ing)

    def _ingest_chunk(self, slot: int, ing: _IngestState) -> bool:
        """The body of :meth:`_ingest_step` (the ``ingest_chunk``
        phase)."""
        if self._reap_ingest(slot):
            return True
        req = ing.request
        fut = req.future
        if req.deadline is not None and time.monotonic() > req.deadline:
            # The caller is gone (504/timeout): retire with whatever a
            # previous life emitted instead of finishing an ingestion
            # nobody reads.
            fut._finish("deadline")
            self.metrics.completed.inc()
            self._ingest.pop(slot, None)
            self._release_slot(slot)
            return True
        faults = self.engine_cfg.faults
        if faults is not None:
            faults.probe("prefill_chunk")
        lo = ing.landed
        n = min(len(req.prompt) - lo,
                self.engine_cfg.prefill_chunk_tokens)
        if not self._ensure_ingest_pages(slot, lo, lo + n - 1):
            return True  # preempted paying for its own chunk
        # The landed prefix is read through the tables as they stand
        # (its gather is dispatched now); only then do the window
        # layers give back what the NEXT chunk's window no longer
        # reaches and claim the pages this chunk's tail lands in.
        prefix, win_start = self._gather_landed(slot, lo) if lo else ({}, ())
        if not self._ensure_window_pages(
                slot, lo + n, lo + n - 1, lambda: slot in self._ingest):
            return True
        # ONE bucket for every chunk — the full chunk width, with the
        # tail chunk right-padded and its real length as data
        # (true_len): a partial last chunk must not mint its own
        # compile shape mid-serving.
        bucket = self._bucket(self.engine_cfg.prefill_chunk_tokens)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = req.prompt[lo:lo + n]
        lens = jnp.asarray([n], jnp.int32)
        if lo == 0:
            logits, pre = self._prefill_fn(bucket, 1)(
                self.params, jnp.asarray(padded), lens)
            self._count_prefill(n, 1, bucket, chunk=True)
            self._land([slot], pre, np.asarray([n]), start=0)
        else:
            logits, suf = self._suffix_prefill(
                self.params, jnp.asarray(padded), lens, prefix,
                jnp.int32(lo), *win_start)
            self._count_prefill(n, 1, bucket, chunk=True)
            self._land([slot], suf, np.asarray([n]), start=lo)
        self._tick_prefill_spent += n
        self._tick_ingested.add(slot)
        ing.landed = lo + n
        self._page_pos[slot] = ing.landed
        if ing.landed >= len(req.prompt):
            # Non-final chunks never fetch their logits (no host
            # sync); only this last one pays the first-token fetch.
            self._finish_ingest(slot, ing, logits)
        return True

    def _finish_ingest(self, slot: int, ing: _IngestState,
                       logits) -> None:
        """The last chunk landed: the chunk logits are the
        whole-prompt last-position logits, so the first token (greedy
        argmax or the sampled draw at key index ``len(prompt)``) is
        token-identical to an un-chunked admission's — from here the
        slot joins the decode mask like any other."""
        req = ing.request
        self._ingest.pop(slot, None)
        firsts = self._first_tokens([req], logits)
        self.metrics.host_syncs.inc()  # the first-token fetch blocks
        now = time.monotonic()
        first = int(firsts[0])
        if req.future.ttft is None:
            # A RESUMED request already served its first token in a
            # previous life — its TTFT was honest then.
            ttft = now - req.submitted_at
            req.future.ttft = ttft
            self.metrics.observe_ttft(req.priority, ttft)
        if req.trace is not None:
            req.trace.slot = slot
            if req.trace.first_token_at is None:
                req.trace.first_token_at = now
        self._samp.set(slot, temperature=req.temperature,
                       top_k=req.top_k, top_p=req.top_p, seed=req.seed)
        self._states[slot] = _SlotState(request=req, last_token=first,
                                        n_generated=0)
        self._page_pos[slot] = len(req.prompt)
        # Speculative bookkeeping BEFORE the emit — the same order as
        # the batch path (_spec_admit inside _admit_paged precedes
        # _emit): the first token may retire the request (max_new 1,
        # EOS) and free the slot, and acquiring a draft slot AFTER
        # that would re-activate a freed slot with no owner.
        if self._spec and self._spec_model:
            # A MODEL draft would prefill the entire long prompt in
            # one tick (and mint a draft compile shape per long-prompt
            # bucket) — exactly the stall chunking removes.  Degrade
            # the SLOT to plain greedy instead (output identical; the
            # n-gram draft keeps speculating — its history landing is
            # one cheap full-width scatter).
            self._spec_host[slot] = False
        else:
            self._spec_admit([slot], [req])
        self._emit(slot, first)
        if self._dev_tokens is not None:
            # Land the first token in the device-resident token vector
            # (a slot retired by its own first token is inactive in
            # the mask; its value is a don't-care).
            vals = np.zeros(self.engine_cfg.n_slots, np.int32)
            mask = np.zeros(self.engine_cfg.n_slots, bool)
            vals[slot] = first
            mask[slot] = True
            self._dev_tokens = self._merge_tokens(
                self._dev_tokens, jnp.asarray(vals), jnp.asarray(mask))

    def _advance_ingest(self) -> bool:
        """Advance in-progress chunked ingestions with this tick's
        remaining prefill-token budget, oldest request first.  The
        oldest ingestion gets a STARVATION GUARD: it advances one
        chunk even on a tick whose budget admissions already spent —
        unless a strictly better class is waiting for next tick's
        budget (per-tick prefill work then stays <= 2x the budget in
        the worst case, and ingestion can never be starved by
        equal-or-worse-class arrivals)."""
        if not self._ingest:
            return False
        chunk = self.engine_cfg.prefill_chunk_tokens
        worked = False
        oldest = True
        for slot in sorted(self._ingest,
                           key=lambda s: self._ingest[s].request.id):
            ing = self._ingest.get(slot)
            if ing is None:
                continue  # evicted by an earlier step's grant
            if self._tick_prefill_spent >= chunk:
                if not oldest or slot in self._tick_ingested:
                    break  # one chunk per slot per tick, budget spent
                best = self.scheduler.peek_best_rank()
                if (best is not None
                        and best < ing.request.priority_rank):
                    break  # yield the next tick's budget to the winner
            worked = self._ingest_step(slot) or worked
            oldest = False
        return worked

    def _emit(self, slot: int, tok: int) -> None:
        """Stream one token to the slot's future; retire on EOS,
        max-token, or cache-capacity exhaustion."""
        st = self._states[slot]
        if st is None:
            return
        if st.request.future.done():
            # Resolved externally: by the watchdog (stall declared while
            # the tick was in flight — recovery rebuilds slot state
            # anyway) or by a submit that raced a drain.  Reclaim the
            # slot here so it cannot leak and pin drain() forever.
            self._states[slot] = None
            self._release_slot(slot)
            return
        if st.request.future._add_token(tok) and self.journal is not None:
            # The journal mirrors the future EXACTLY: a token is
            # recorded iff the caller will see it, so a resume's
            # re-prefill (prompt + emitted) reproduces precisely the
            # oracle's state — never a token from a stale or
            # already-resolved row.
            self.journal.append(st.request.id, tok)
        st.last_token = tok
        st.n_generated += 1
        self.metrics.tokens_generated.inc()
        reason = None
        if st.request.eos_id is not None and tok == st.request.eos_id:
            reason = "eos"
        elif st.n_generated >= st.request.max_new_tokens:
            reason = "length"
        # Next decode tick would write at prompt + n_generated - 1 (the
        # first token came from prefill, no write) — retire at capacity.
        elif (len(st.request.prompt) + st.n_generated - 1
              >= self.slots.max_len):
            reason = "capacity"  # submit() sizing makes this unreachable
        # Deadline AFTER admission: the caller is gone (504/timeout) —
        # retire with the partial result instead of pinning the slot
        # until max_new_tokens on output nobody reads.  (A deadline that
        # lapses while QUEUED is a typed rejection — Scheduler.take.)
        elif (st.request.deadline is not None
              and time.monotonic() > st.request.deadline):
            reason = "deadline"
        if reason is not None:
            st.request.future._finish(reason)
            self.metrics.completed.inc()
            self._states[slot] = None
            self._release_slot(slot)

    def _decode_tick(self) -> bool:
        """The SYNCHRONOUS decode tick (``overlap=False``, the A/B
        baseline): upload tokens + mask, dispatch, fetch, and apply the
        bookkeeping all in the same step — the device idles through the
        host half, which is exactly what the pipeline hides."""
        self._prepare_tick_pages()  # grants/COWs; may preempt
        active = self._decode_mask()
        if not active.any():
            return False
        faults = self.engine_cfg.faults
        kind = faults.probe("decode_tick") if faults is not None else None
        tokens = np.zeros(self.engine_cfg.n_slots, np.int32)
        for s, st in enumerate(self._states):
            if st is not None:
                tokens[s] = st.last_token
        with self._phase("tick_dispatch") as dispatch:
            nxt, extra = self._dispatch_tick(
                jnp.asarray(tokens), jnp.asarray(active), active)
        # Same fetch-and-apply tail as the pipeline, just not deferred.
        self._retire_pending({
            **extra, "active": active,
            "reqs": [st.request if st is not None else None
                     for st in self._states],
            "kind": kind, "dispatched_at": dispatch.start,
        })
        return True

    def _prepare_tick_pages(self) -> None:
        """Tick-boundary page maintenance of an engine with live
        slots (the ``page_prep`` phase): grants, COWs and table uploads
        — host bookkeeping plus async uploads, nothing blocks on the
        device; a preemption here is never dispatched."""
        if self.slots.active_count:
            with self._phase("page_prep"):
                if self._spec:
                    self._prepare_spec_tick()   # window grants
                else:
                    self._prepare_paged_tick()

    def _dispatch_tick(self, tokens_dev, active_dev, active: np.ndarray):
        """What BOTH loops do inside ``tick_dispatch``: count the walk,
        dispatch, advance the dispatch-time position mirror."""
        self._count_paged_walk(active)
        nxt, extra = self._run_tick(tokens_dev, active_dev)
        if not self._spec:
            # Speculative ticks advance the mirror at FETCH (the
            # accepted length is data the host learns there).
            self._page_pos += active
        self.metrics.decode_ticks.inc()
        self._step_tick = True
        return nxt, extra

    def _decode_tick_overlapped(self) -> bool:
        """One PIPELINED decode step (``overlap=True``): dispatch tick
        N+1 FIRST — its token input is tick N's device-resident output,
        so no host value gates the dispatch — then fetch and apply tick
        N's results while the device is already computing N+1.  Host
        bookkeeping runs one tick behind the device; the identity
        snapshot in ``_pending`` keeps the lag safe
        (:meth:`_retire_pending`)."""
        worked = False
        faults = self.engine_cfg.faults
        # Page maintenance BEFORE the mask snapshot: a preemption here
        # must not be dispatched.
        self._prepare_tick_pages()
        active = self._decode_mask()
        new_pending: Optional[Dict] = None
        if active.any():
            kind = (faults.probe("decode_tick")
                    if faults is not None else None)
            with self._phase("tick_dispatch") as dispatch:
                nxt, extra = self._dispatch_overlapped(active)
            new_pending = {
                **extra, "active": active,
                "reqs": [st.request if st is not None else None
                         for st in self._states],
                "kind": kind, "dispatched_at": dispatch.start,
            }
            worked = True
        prev, self._pending = self._pending, new_pending
        if prev is not None:
            self._retire_pending(prev)
            worked = True
        return worked

    def _dispatch_overlapped(self, active: np.ndarray):
        """The pipelined loop's ``tick_dispatch`` body: (re)seed the
        device-resident inputs, dispatch, keep the output as the next
        tick's input."""
        if self._dev_tokens is None:
            # Pipeline (re)start: seed the device token vector from
            # host slot state.  After this the ONLY recurring
            # upload is the active mask, and only when it changes.
            tokens = np.zeros(self.engine_cfg.n_slots, np.int32)
            for s, st in enumerate(self._states):
                if st is not None:
                    tokens[s] = st.last_token
            self._dev_tokens = jnp.asarray(tokens)
        if (self._dev_active_host is None
                or not np.array_equal(active, self._dev_active_host)):
            self._dev_active = jnp.asarray(active)
            self._dev_active_host = active
        nxt, extra = self._dispatch_tick(
            self._dev_tokens, self._dev_active, active)
        self._dev_tokens = nxt  # tick N+2's input — never fetched
        return nxt, extra

    def _retire_pending(self, p: Dict) -> None:
        """Fetch a dispatched tick's results — THE one host sync of a
        steady-state step — and apply its bookkeeping.  The ONE copy of
        the nonfinite check and the emission rules, shared by the
        synchronous tick (applied immediately) and the overlapped
        pipeline (applied one tick late), so the two paths cannot
        diverge.

        Why the pipeline's lag preserves the greedy oracle: a slot's
        token is emitted only if the slot still holds the request it
        was computing for at dispatch time (the ``reqs`` identity
        snapshot).  A slot retired by EOS/length/deadline, cancelled,
        or re-admitted between dispatch and fetch fails that check and
        its stale row is DROPPED — so no token is ever emitted after
        EOS, and a freed slot can never leak a token into its next
        tenant.  The stale row's device write is harmless by the same
        write-before-attend argument as bucketed prefill padding
        (``decode_step_paged``).  (In the synchronous path the snapshot
        always matches — nothing can retire a slot between dispatch and
        this call within one locked step.)"""
        faults = self.engine_cfg.faults
        if faults is not None:
            faults.probe("decode_fetch")
        with self._phase("tick_device_wait") as wait:
            nxt = np.asarray(p["nxt"])           # (S,) — or (S, W) spec
            mx = np.asarray(p["mx"])
            acc = np.asarray(p["acc"]) if "acc" in p else None
            if "moe" in p:   # the experts' load rides the same fetch
                rows, touched, load_max = np.asarray(p["moe"]).tolist()
                self.metrics.moe_rows.inc(rows)
                self.metrics.moe_experts_touched.inc(touched)
                self.metrics.moe_load_max_rows.inc(load_max)
                self.metrics.moe_load_mean_rows.inc(
                    rows / self.cfg.experts_held)
                # a chip's share: the picks whose expert lies elsewhere
                # (every active slot picks k in every expert layer)
                self.metrics.moe_rows_routed_away.inc(
                    int(p["active"].sum()) * self.cfg.n_experts_per_tok
                    * (self.cfg.n_layers - self.cfg.n_dense_layers) - rows)
            self.metrics.host_syncs.inc()
        with self._phase("tick_host"):
            self._apply_tick(p, nxt, mx, acc, wait.start + wait.dur)
        self._retired = p  # its device arrays go in `bookkeeping` (step)

    def _apply_tick(self, p: Dict, nxt, mx, acc, t1: float) -> None:
        """The host half of :meth:`_retire_pending` (the ``tick_host``
        phase): the nonfinite check and the emission rules over results
        fetched at ``t1``."""
        active = p["active"]
        if p["kind"] == "nonfinite":  # injected: NaN logits
            mx = np.where(active if mx.ndim == 1 else active[:, None],
                          np.nan, mx)
        if not np.isfinite(mx[active]).all():
            raise EngineFailedError(
                "non-finite logits from decode tick (bad params or "
                "device fault)")
        lat = t1 - p["dispatched_at"]
        spec_k = self.engine_cfg.spec_k
        for s in np.nonzero(active)[0]:
            s = int(s)
            st = self._states[s]
            if st is None or st.request is not p["reqs"][s]:
                continue  # retired / re-admitted since dispatch: stale
            self.metrics.token_latency.observe(lat)
            tr = st.request.trace
            # Per-request tick DETAIL is buffered only when the
            # request's SUBMIT-TIME recorder is live (same handle its
            # begin/finish go through — one attribute read per slot);
            # whether the tuples ever leave the process is the
            # tail-sampling verdict at resolution.
            srec = st.request.future._spans
            if tr is not None:
                tr.decode_ticks += 1
                # dispatch-to-fetch latency of the tick that produced
                # this token: with the overlapped pipeline this is the
                # one-tick lag made visible in the breakdown.
                tr.host_sync_lag = lat
            if acc is None:
                self.metrics.tokens_per_tick.observe(1)
                if srec is not None and tr is not None:
                    if len(tr.ticks) < tr.MAX_TICKS:
                        tr.ticks.append((p["dispatched_at"], t1, 1))
                    else:
                        tr.ticks_overflow += 1
                if self._spec:
                    # A plain tick dispatched by the speculative
                    # engine (nobody speculating): pos advanced by
                    # exactly one — mirror it, and let the slot's
                    # probe clock run toward re-enabling.
                    self._page_pos[s] += 1
                    self._spec_probe_clock(s)
                self._emit(s, int(nxt[s]))
                continue
            # Speculative: the device committed acc+1 positions for
            # this slot whatever the host emits below (EOS/length may
            # truncate the run) — mirror the advance before emission
            # can retire the slot.
            n = int(acc[s]) + 1
            self._page_pos[s] += n
            if p["spec"][s]:
                self.metrics.spec_drafted.inc(spec_k)
                self.metrics.spec_accepted.inc(int(acc[s]))
                self.metrics.spec_wasted.inc(spec_k - int(acc[s]))
                self.metrics.spec_acceptance.observe(
                    int(acc[s]) / spec_k)
                self._spec_adapt(s, int(acc[s]))
            elif self._spec_host[s] and not self._spec_live[s]:
                # Speculating for OTHERS this tick while this slot sat
                # disabled: the n-gram history stays current (the
                # kernel commits every active row's tokens) and the
                # model draft was already marked stale at disable —
                # only the probe clock moves here.
                self._spec_probe_clock(s)
            # The tick-detail entry is appended BEFORE the emit loop —
            # the final _emit may retire the request and synchronously
            # run request_done, which writes tr.ticks — as a MUTABLE
            # list whose count is bumped per emission, so it records
            # the EMITTED count (EOS inside the accepted run truncates
            # what the caller sees; the autopsy's tick detail must sum
            # to the response, not to the device-committed acc+1).
            tick_entry = None
            if srec is not None and tr is not None:
                if len(tr.ticks) < tr.MAX_TICKS:
                    tick_entry = [p["dispatched_at"], t1, 0]
                    tr.ticks.append(tick_entry)
                else:
                    tr.ticks_overflow += 1
            emitted = 0
            for jt in range(n):
                if self._states[s] is not st:
                    # EOS / length / deadline retired the slot inside
                    # the accepted run: the greedy oracle would never
                    # emit the tail — drop it.
                    break
                if tick_entry is not None:
                    tick_entry[2] += 1
                self._emit(s, int(nxt[s, jt]))
                emitted += 1
            self.metrics.tokens_per_tick.observe(emitted)

    # -- failure recovery --------------------------------------------------

    def _fail_inflight(self, exc: BaseException) -> None:
        """Resolve every in-flight future (slots + taken-but-unlanded)
        with ``exc`` and reset slot bookkeeping — the TERMINAL path
        (and :meth:`terminate`): nothing will resume, so every future
        fails typed (which also purges its journal entry).  Idempotent
        per future (set_exception no-ops once done)."""
        for st in self._states:
            if st is not None:
                st.request.future.set_exception(exc)
        for req in self._taken:
            req.future.set_exception(exc)
        for ing in self._ingest.values():
            ing.request.future.set_exception(exc)
        self._clear_inflight_state()

    def _suspend_inflight(self, exc: BaseException) -> List[Request]:
        """The NON-terminal restart path: collect every in-flight
        request (slots + taken-but-unlanded) as a RESUME request —
        original prompt + journaled emitted tokens as the new prompt,
        the remaining decode budget, the original deadline, trace, and
        (crucially) the original live future — then reset slot
        bookkeeping exactly like :meth:`_fail_inflight`.  Requests
        that cannot resume (future already resolved, cancellation
        pending, no journal entry, or ``resume=False``) are resolved
        in place.  Returned in original FCFS order (by request id),
        ready for :meth:`Scheduler.requeue_front`."""
        resumed: List[Request] = []
        pending = [st.request for st in self._states if st is not None]
        pending += list(self._taken)
        # Mid-ingestion requests suspend too: no tokens were emitted
        # yet, so their journal frontier is the original prompt — the
        # resume re-ingests from scratch, oracle-exact (the chunk
        # boundary a crash interrupted is not observable in the
        # output).  Their landed chunks were real prefill compute the
        # re-ingestion repeats — record the honest wasted count
        # before the ingest map is cleared.
        pending += [ing.request for ing in self._ingest.values()]
        ingest_wasted = {ing.request.id: ing.landed - ing.started
                         for ing in self._ingest.values()}
        for req in pending:
            # The typed engine_restart edge on every interrupted
            # request's span, BEFORE its resolution/suspension is
            # decided — this is the restart path specifically, so
            # terminate()/drain force-resolves (plain _fail_inflight)
            # never mislabel themselves as restarts.
            srec = req.future._spans
            if srec is not None and req.trace is not None:
                try:
                    srec.request_event(req.trace, "engine_restart",
                                       {"epoch": self._epoch})
                except Exception:  # pragma: no cover
                    pass
            r = self._resume_or_fail(req, exc)
            if r is not None:
                if r.id in ingest_wasted:
                    r._resume_wasted = max(
                        getattr(r, "_resume_wasted", 0),
                        ingest_wasted[r.id])
                resumed.append(r)
        self._clear_inflight_state()
        resumed.sort(key=lambda r: r.id)
        self._resuming = len(resumed)
        return resumed

    def _resume_or_fail(self, req: Request,
                        exc: BaseException) -> Optional[Request]:
        fut = req.future
        if fut.done():
            return None  # resolved elsewhere (drain race, hard fail)
        if fut.cancel_requested:
            fut._finish("cancelled")
            self.metrics.cancelled.inc()
            return None
        entry = self.journal.get(req.id) if self.journal is not None \
            else None
        if entry is not None and self.engine_cfg.resume \
                and entry.remaining < 1:
            # Fully emitted: only the retirement bookkeeping was lost
            # — finish now.
            fut._finish("length")
            self.metrics.completed.inc()
            return None
        # Decode — greedy AND sampled (the PRNG key schedule is a pure
        # function of seed + token position) — is a pure function of
        # the token sequence, so prefilling prompt + emitted and
        # continuing yields output token-identical to an uninterrupted
        # run (_build_resume, shared with preemption).
        new = self._build_resume(req)
        if new is None:
            fut.set_exception(exc)
        return new

    def _clear_inflight_state(self) -> None:
        """Reset slot bookkeeping after a failure — including the slot
        allocator, so terminal states (no _restart to rebuild it) don't
        report phantom occupancy forever."""
        self._taken = []
        self._states = [None] * self.engine_cfg.n_slots
        self._ingest = {}
        self.slots.release_all()
        for paired in (self.wslots, self.draft_slots):
            if paired is not None:
                paired.release_all()
        self._reset_spec_state()
        # release_all zeroed every page refcount, including the prefix
        # registry's pins: bump the epoch HERE (not just in _restart)
        # so stale entries can neither attach freed pages to a new
        # admission in the failing/terminal window nor underflow a
        # refcount on unregister — they lazily re-prefill instead.
        self._cache_epoch += 1
        self._reset_pipeline()

    def _reset_pipeline(self) -> None:
        """Drop the in-flight tick and the device-resident token state
        (restart/terminal paths — the old device arrays belong to a
        suspect cache lineage); the next dispatch reseeds from host
        slot state."""
        self._pending = self._retired = None
        self._dev_tokens = None
        self._dev_active = None
        self._dev_active_host = None
        self._dev_table = None
        self._table_uploaded = -1
        self._page_pos[:] = 0
        self._dev_spec = None
        self._dev_spec_host = None
        self._dev_dtable = None
        self._dtable_uploaded = -1
        self._dev_history = None
        # Sampling columns: zero the host rows and drop the device
        # copy (it belonged to the dead lineage); re-admissions — the
        # resume path included — repopulate before the next dispatch.
        self._samp.reset()

    def _fail_queue(self, exc: BaseException) -> None:
        for req in self.scheduler.drain_pending():
            req.future.set_exception(exc)

    def _recover(self, exc: BaseException, *, counted: bool = False) -> None:
        """The supervised-restart path.  With ``resume`` (default),
        in-flight requests are SUSPENDED — journaled state, live
        futures — and re-admitted at the queue head after the restart,
        so a crash costs one tick plus one re-prefill instead of the
        request; without it (or at a terminal failure) they fail with
        the typed error, as before.  Either way the engine restarts
        (fresh PagedSlotCache, exponential backoff) or goes terminally
        ``failed`` when ``max_restarts`` consecutive attempts are
        spent."""
        if not isinstance(exc, EngineFailedError):
            wrapped = EngineFailedError(f"engine tick failed: {exc!r}")
            wrapped.__cause__ = exc
            exc = wrapped
        with self._hb_lock:
            self._stalled = False
        if not counted:
            self.metrics.engine_failures.inc()
        with self._lock:
            self._consec_failures += 1
            attempt = self._consec_failures
            if (self._terminal
                    or attempt > self.engine_cfg.max_restarts):
                self._terminal = True
                self._fail_inflight(exc)
                self._set_health(FAILED)
                obs_tracing.instant("engine_failed", {
                    "consecutive_failures": attempt,
                    "max_restarts": self.engine_cfg.max_restarts})
                self._fail_queue(exc)
                self.metrics.queue_depth.set(0)
                self.metrics.slot_occupancy.set(0.0)
                return
            resume_ok = True
            faults = self.engine_cfg.faults
            if faults is not None:
                try:
                    faults.probe("restart_resume")
                except Exception:
                    # The resume machinery itself failed (chaos site:
                    # unreadable journal, corrupted state): degrade to
                    # the legacy fail-typed restart — never replay
                    # from state the engine cannot trust.
                    resume_ok = False
            if resume_ok:
                resumed = self._suspend_inflight(exc)
            else:
                resumed = []
                self._fail_inflight(exc)
        backoff = min(
            self.engine_cfg.restart_backoff * (2.0 ** (attempt - 1)),
            self.engine_cfg.restart_backoff_max)
        time.sleep(backoff)
        with self._lock:
            # terminate() may have landed during the backoff sleep — a
            # terminal declaration is never undone by a restart, and
            # the suspended requests must not dangle on it.
            if self._terminal:
                for req in resumed:
                    req.future.set_exception(exc)
                self._resuming = 0
                self._set_health(FAILED)
                self._fail_queue(exc)
                return
            self._restart()
            self._resuming = 0
            # The tuner's scoring window must not straddle the
            # restart: its baseline predates the crash, so the first
            # post-restart window would score the dead time + the
            # resume re-prefills against the knob setting — garbage
            # that can trip a spurious SLO rollback (and GET /tuning
            # would serve it).  Drop the baseline; the next worked
            # tick opens a fresh window.
            reset = getattr(self._tuner, "reset_window", None)
            if reset is not None:
                try:
                    reset()
                except Exception:  # pragma: no cover - tuner never
                    pass           # gates recovery
            if resumed:
                # Back to the HEAD of the queue in original FCFS order:
                # the next tick re-prefills prompt + emitted through the
                # ordinary bucketed batch admission (pages re-granted,
                # prefix sharing re-applied) and decode continues where
                # it left off.
                self.scheduler.requeue_front(resumed)
                for req in resumed:
                    self.metrics.resumed.inc()
                    wasted = getattr(req, "_resume_wasted",
                                     len(req.prompt))
                    if wasted:
                        self.metrics.resume_wasted_tokens.inc(wasted)
                    if self.journal is not None:
                        self.journal.note_resume(req.id)
                    # submit-time handle (begin/finish used it too)
                    srec = req.future._spans
                    if srec is not None and req.trace is not None:
                        # The typed resume edge on the request's own
                        # span: a postmortem sees WHICH requests the
                        # restart interrupted and what the re-prefill
                        # cost, not just the engine-wide instant.
                        try:
                            srec.request_event(
                                req.trace, "resume",
                                {"epoch": self._epoch,
                                 "wasted_tokens": wasted})
                        except Exception:  # pragma: no cover
                            pass
                obs_tracing.instant("requests_resumed", {
                    "count": len(resumed), "epoch": self._epoch})
                self.metrics.queue_depth.set(self.scheduler.depth)

    def _restart(self) -> None:
        """Fresh PagedSlotCache + slot bookkeeping (the old device cache is
        suspect after a failure); queued requests survive and are
        admitted by the next tick.  Caller holds ``_lock``.

        A stall overwrites the health state with FAILED, so the
        restart target comes from the sticky ``_draining`` flag, not
        from the state it is replacing — a draining engine restarts
        DRAINING (still rejecting new work), everything else restarts
        DEGRADED."""
        self.slots = self._make_slots()
        self.wslots = self._make_window_slots()
        self.draft_slots = self._make_draft_slots()
        self._reset_spec_state()
        self._states = [None] * self.engine_cfg.n_slots
        self._reset_pipeline()
        # The page pool is fresh: registered prefixes' pinned pages
        # died with the old cache — bump the epoch so entries lazily
        # re-prefill (once) on their next use.
        self._cache_epoch += 1
        self._update_page_gauges()
        with self._hb_lock:
            self._epoch += 1
            self._stalled = False
            self._stall_hard_failed = False
        self.metrics.engine_restarts.inc()
        obs_tracing.instant("engine_restart", {
            "epoch": self._epoch,
            "restarts": self.metrics.engine_restarts.value})
        self._set_health(DRAINING if self._draining else DEGRADED)

    # -- watchdog ----------------------------------------------------------

    def _stall_grace_s(self) -> float:
        g = self.engine_cfg.stall_grace
        return g if g is not None else self.engine_cfg.tick_timeout

    def _watchdog_loop(self) -> None:
        budget = self.engine_cfg.tick_timeout
        while not self._stop.is_set():
            time.sleep(self.engine_cfg.watchdog_interval)
            with self._hb_lock:
                started = self._tick_started
                epoch = self._epoch
                stalled = self._stalled
                hard = self._stall_hard_failed
            if started is None:
                continue
            age = time.monotonic() - started
            if not stalled:
                if age > budget:
                    self._declare_stalled(epoch, started)
            elif (self.engine_cfg.resume and not hard
                    and age > budget + self._stall_grace_s()):
                # The stall outlived its resume grace: presume the tick
                # never returns and restore the bounded-resolution
                # guarantee.
                self._stall_hard_fail(epoch, started)

    def _declare_stalled(self, epoch: int, started: float) -> None:
        """The tick has been running past its budget — a hung device
        call.  Runs on the WATCHDOG thread, which must never take
        ``_lock`` (the hung engine thread holds it): it only resolves
        futures (thread-safe, idempotent) and flips flags.  Slot
        bookkeeping is rebuilt by the engine thread if/when the hung
        tick returns; if it never returns, the engine stays ``failed``
        and nothing is left waiting on it.

        With ``resume`` the in-flight futures are NOT resolved here:
        their decode state is journaled, and a tick that returns
        within ``stall_grace`` resumes them token-exact through the
        supervised restart.  Only past budget + grace does
        :meth:`_stall_hard_fail` resolve everything typed."""
        with self._hb_lock:
            if (self._stalled or self._epoch != epoch
                    or self._tick_started != started):
                return  # the tick finished or recovery already ran
            self._stalled = True
        self.metrics.engine_failures.inc()
        obs_tracing.instant("watchdog_stall", {
            "epoch": epoch,
            "budget_s": self.engine_cfg.tick_timeout,
            "tick_age_s": round(time.monotonic() - started, 3)})
        self._set_health(FAILED)
        if self.engine_cfg.resume:
            return  # futures held for resume; hard fail at budget+grace
        exc = EngineStalledError(
            f"engine stalled: tick exceeded the "
            f"{self.engine_cfg.tick_timeout}s watchdog budget")
        # The engine thread is hung inside _lock, so _states is frozen —
        # snapshot-read it without the lock and resolve every future a
        # hung tick would otherwise strand (in-flight AND queued).
        for st in list(self._states):
            if st is not None:
                st.request.future.set_exception(exc)
        for req in list(self._taken):
            req.future.set_exception(exc)
        for ing in list(self._ingest.values()):
            ing.request.future.set_exception(exc)
        self._fail_queue(exc)

    def _stall_hard_fail(self, epoch: int, started: float) -> None:
        """Resume-mode backstop, still on the watchdog thread: the
        stalled tick spent its grace too.  Resolve every future typed
        — resolution purges each journal entry, so a zombie tick that
        returns even later finds nothing to resume and the restart
        comes up empty rather than replaying ghosts."""
        with self._hb_lock:
            if (self._stall_hard_failed or not self._stalled
                    or self._epoch != epoch
                    or self._tick_started != started):
                return
            self._stall_hard_failed = True
        exc = EngineStalledError(
            f"engine stalled: tick exceeded the "
            f"{self.engine_cfg.tick_timeout}s watchdog budget plus the "
            f"{self._stall_grace_s()}s resume grace")
        obs_tracing.instant("stall_hard_fail", {
            "epoch": epoch, "grace_s": self._stall_grace_s()})
        for st in list(self._states):
            if st is not None:
                st.request.future.set_exception(exc)
        for req in list(self._taken):
            req.future.set_exception(exc)
        for ing in list(self._ingest.values()):
            ing.request.future.set_exception(exc)
        self._fail_queue(exc)

    # -- background loop ---------------------------------------------------

    def start(self, idle_sleep: float = 0.001) -> None:
        """Run the tick loop in a daemon thread until :meth:`stop`; arm
        the watchdog when ``tick_timeout > 0``."""
        if self._thread is not None:
            return

        def loop():
            # engine_loop observes every iteration end to end on both
            # clocks, so the phases' sums over it are the share of this
            # thread's time, and of its work, that lies inside a phase.
            t_start = t_prev = time.monotonic()
            c_prev = time.thread_time()
            gc_prev = self._gc_s
            while not self._stop.is_set():
                compiles = self._decode_traces + self._prefill_traces
                if not self.step():
                    with self._phase("idle"):
                        time.sleep(idle_sleep)
                now, cpu = time.monotonic(), time.thread_time()
                gc_s = self._gc_s
                metrics = self.metrics
                if gc_s != gc_prev:
                    metrics.fold_gc()
                metrics.engine_loop.observe(now - t_prev)
                metrics.engine_loop_cpu.observe(cpu - c_prev)
                if now - t_prev > SLOW_STEP_SECONDS:
                    metrics.slow_step(self._slow_step_record(
                        t_prev - t_start, now - t_prev, cpu - c_prev,
                        gc_s - gc_prev, self._decode_traces
                        + self._prefill_traces - compiles))
                t_prev, c_prev, gc_prev = now, cpu, gc_s

        obs_tracing.gc_watch.add(self._on_gc)
        self._stop.clear()
        self._thread = threading.Thread(target=loop,
                                        name="serving-engine", daemon=True)
        self._thread.start()
        if self.engine_cfg.tick_timeout > 0:
            self._watchdog = threading.Thread(
                target=self._watchdog_loop, name="serving-watchdog",
                daemon=True)
            self._watchdog.start()

    def stop(self, timeout: float = 10.0) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._thread.join(timeout)
        self._thread = None
        obs_tracing.gc_watch.remove(self._on_gc)
        if self._watchdog is not None:
            self._watchdog.join(timeout)
            self._watchdog = None

    def _on_gc(self, generation: int, seconds: float) -> None:
        """``obs.tracing.gc_watch``'s sink while the loop runs: one
        collection of the interpreter, on whichever thread and under
        whatever lock that thread holds, so it takes none
        (``ServingMetrics.fold_gc`` observes the histogram later)."""
        self.metrics.gc_pending[generation].append(seconds)
        self._gc_s += seconds

    def _slow_step_record(self, at_s: float, wall_s: float, cpu_s: float,
                          gc_s: float, compiles: int) -> Dict:
        """Where an iteration of the loop that took ``wall_s`` stood:
        its phases' seconds on both clocks (what lies under none is
        ``wall_s`` less their sum), the collector's seconds and the
        executables compiled inside it.  ``at_s``: when it began, on
        ``time.monotonic()`` since :meth:`start`."""
        phases: Dict[str, List[float]] = {}
        for ph in self._step_spans:
            both = phases.setdefault(ph.name, [0.0, 0.0])
            both[0] += ph.dur
            both[1] += ph.cpu
        return {
            "at_s": round(at_s, 3), "wall_s": round(wall_s, 6),
            "cpu_s": round(cpu_s, 6),
            "phases": {name: [round(w, 6), round(c, 6)]
                       for name, (w, c) in phases.items()},
            "gc_s": round(gc_s, 6), "compiles": compiles,
            "active_slots": self.slots.active_count,
            "kind": self._step_kind}

    def warmup(self, prompt_lens: Sequence[int] = (1,)) -> None:
        """Drive the engine SYNCHRONOUSLY until every compile the given
        prompt lengths can demand exists: one prefill + cache-insert
        executable per (bucket, admission-batch-k) shape for k up to
        ``max_prefills_per_tick``, plus the decode tick (and, with
        ``overlap``, the token-merge op).  Call before :meth:`start` so
        first-request latency — and a tight watchdog ``tick_timeout`` —
        never pays XLA compilation (docs/serving.md "Watchdog tuning").
        The ONE definition of the warm sweep, shared by the chaos
        suite and ``benchmarks/serving.py``, so warm coverage tracks
        the engine's compile-set shape."""
        kmax = min(self.engine_cfg.max_prefills_per_tick,
                   self.engine_cfg.n_slots)
        # The warm sweep's synthetic prompts are not traffic: keep
        # them out of the journal so a journaled trace replays real
        # requests only (tuning/replay.py), then restore it.
        journal, self.journal = self.journal, None
        try:
            self._warm_sweep(prompt_lens, kmax)
        finally:
            self.journal = journal
        self._warmed = True
        if self.engine_cfg.autotune and self._tuner is None:
            # Install AFTER the warm sweep: the knob space's compile-
            # safe bounds are derived from what warmup just compiled,
            # and a tuner live during warmup could shrink the
            # admission batch mid-sweep and leave shapes uncompiled.
            from horovod_tpu.tuning.tuner import OnlineTuner

            OnlineTuner.install(self)

    def _warm_sweep(self, prompt_lens: Sequence[int], kmax: int) -> None:
        prompts = [[0] * max(int(n), 1) for n in prompt_lens]
        # Registered prefixes compile their own executables (suffix
        # prefill per (prefix pages, suffix bucket, k), prefix-page
        # gather): warm those too, with prompt_lens as the SUFFIX
        # lengths — otherwise the first shared-prefix admission after
        # start() pays XLA compilation inside the watchdog's budget.
        for entry in list(self._prefixes.values()):
            prompts += [list(entry.tokens) + [0] * max(int(n), 1)
                        for n in prompt_lens
                        if len(entry.tokens) + int(n) + 2
                        <= self.slots.max_len]
        for prompt in prompts:
            for k in range(1, kmax + 1):
                # max_new_tokens=2: the second token exercises the
                # decode tick (the first comes from prefill logits).
                futs = [self.submit(prompt, max_new_tokens=2)
                        for _ in range(k)]
                while not all(f.done() for f in futs):
                    self.step()
        # Sampled admissions compile the (k, vocab) first-token sampler
        # (the tick executables already contain the sampling kernel —
        # parameters are data — so only this admission-side shape set
        # needs warming; one sampled group per k covers it).
        for k in range(1, kmax + 1):
            futs = [self.submit(prompts[0], max_new_tokens=2,
                                temperature=1.0, seed=i)
                    for i in range(k)]
            while not all(f.done() for f in futs):
                self.step()
        if self._spec:
            # The speculative engine owns TWO decode executables — the
            # draft/verify tick and the plain one-token tick it falls
            # back to when no slot speculates (opt-outs, adaptive
            # disable).  Warm the plain one too: an adaptive disable
            # mid-serving must not pay XLA compilation inside the
            # watchdog budget.
            futs = [self.submit(prompts[0], max_new_tokens=2,
                                speculative=False)]
            while not all(f.done() for f in futs):
                self.step()
            # Warm the probe-path executables (both shape-stable at
            # (1, max_len) by construction): history re-landing for
            # the n-gram draft, the full-width draft re-prefill for
            # the model draft.
            if not self._spec_model:
                self._dev_history = self._hist_land(
                    self._history(), np.zeros((1,), np.int32),
                    np.zeros((1, self.slots.max_len), np.int32))
            else:
                width = self.slots.max_len
                self._draft_prefill_fn(width, 1)(
                    self.draft_params,
                    jnp.zeros((1, width), jnp.int32),
                    jnp.ones((1,), jnp.int32))
            # Warmup's synthetic zero-token prompts can legitimately
            # measure poor acceptance — that must not carry a
            # persistent adaptive disable into real traffic.
            self._reset_spec_state()

    def drain(self, timeout: float = 60.0, poll: float = 0.002) -> bool:
        """Block until queue and slots are empty (True) or timeout.
        Synchronous callers (no background thread) should loop
        :meth:`step` instead."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._health == FAILED:
                with self._hb_lock:
                    hard = self._stall_hard_failed
                if (self._terminal or hard
                        or not self.engine_cfg.resume):
                    return True  # recovery already resolved everything
                # (a non-terminal FAILED with resume on is a stall
                # window: journaled requests may still resume — keep
                # waiting; the caller's terminate() bounds the worst
                # case.  After a hard fail everything IS resolved, so
                # waiting out the hung tick would be pure delay.)
            # Sample under the step lock: between scheduler.take() and
            # slots.alloc() a request is in neither counter, and an
            # unlocked read could report "drained" mid-admission.  A
            # TIMED acquire, not a blocking one — a hung tick holds
            # _lock indefinitely, and drain must keep re-checking its
            # own deadline (and the FAILED the watchdog sets) instead
            # of inheriting the hang.
            if self._lock.acquire(timeout=poll):
                try:
                    idle = (self.scheduler.depth == 0
                            and self.slots.active_count == 0
                            and not self._taken
                            # suspended-for-resume requests are in
                            # neither counter until the requeue lands
                            and self._resuming == 0)
                finally:
                    self._lock.release()
                if idle:
                    return True
            if self._thread is None:
                self.step()
            else:
                time.sleep(poll)
        return False

    def terminate(self, reason: str = "engine terminated") -> None:
        """Force-resolve EVERYTHING (slots, taken, queue) with a typed
        :class:`EngineFailedError` and go terminally ``failed`` — the
        drain-timeout escape hatch: teardown must finish in bounded
        time even if requests cannot.  If the step lock cannot be
        acquired (a hung tick holds it — possibly with the watchdog
        disabled), futures are resolved WITHOUT it: the hung engine
        thread is not mutating slot state, and ``_terminal`` guarantees
        a late-returning tick can only land in the terminal branch of
        ``_recover``, never a restart."""
        self._terminal = True
        exc = EngineFailedError(reason)
        locked = self._lock.acquire(timeout=1.0)
        try:
            self._fail_inflight(exc)
            self._fail_queue(exc)
        finally:
            if locked:
                self._lock.release()
        self._set_health(FAILED)

    # -- observability -----------------------------------------------------

    @property
    def decode_compilations(self) -> int:
        """How many times the decode tick was traced/compiled — the
        zero-recompilation acceptance hook (stays 1 after warmup)."""
        return self._decode_traces

    def _update_achieved_flops(self) -> None:
        """Refresh ``serving_achieved_flops_per_sec`` from the token
        rate between stats() samples (window capped at ~60s so the
        number tracks current load, not job-lifetime average)."""
        fpt = self.engine_cfg.model_flops_per_token
        if not fpt:
            return
        # Re-assert the configured gauge: benchmarks swap in a fresh
        # ServingMetrics after warmup, which would otherwise leave it 0.
        metrics = self.metrics
        metrics.model_flops_per_token.set(fpt)
        now = time.monotonic()
        with self._rate_lock:
            if metrics is not self._rate_metrics:
                # A fresh ServingMetrics restarts the token counter at
                # 0; a window base from the old counter would make the
                # next rate negative.
                self._rate_samples.clear()
                self._rate_metrics = metrics
            self._rate_samples.append((now, metrics.tokens_generated.value))
            while (len(self._rate_samples) > 2
                   and now - self._rate_samples[0][0] > 60.0):
                self._rate_samples.pop(0)
            t0, n0 = self._rate_samples[0]
            n1 = self._rate_samples[-1][1]
        if now <= t0:
            return
        metrics.achieved_flops.set((n1 - n0) / (now - t0) * fpt)

    def refresh_windowed_gauges(self) -> None:
        """Refresh rate-windowed gauges (achieved FLOP/s) and fold the
        collector's pending pauses into their histograms without
        building a /stats snapshot — the cheap hook a /metrics scrape
        wants."""
        self._update_achieved_flops()
        self.metrics.fold_gc()

    def stats(self) -> Dict:
        age = self.heartbeat_age
        self._update_achieved_flops()
        # Re-assert on the CURRENT metrics object: benchmarks swap in a
        # fresh ServingMetrics after warmup, which would zero the gauge.
        self.metrics.tp_degree.set(self.engine_cfg.tp)
        return {
            **self.metrics.snapshot(),
            "state": self._health,
            # The ROUTING CONTRACT (docs/serving.md "HTTP API"): these
            # four keys are always present and typed — the front tier
            # balances and evicts on them, so their absence or a None
            # must never be a reachable state.  heartbeat_age_s is
            # -1.0 until the first tick completes (a warming engine,
            # not a wedged one).
            "queue_depth": int(self.scheduler.depth),
            "occupancy": float(self.slots.occupancy),
            "engine_state": str(self._health),
            "heartbeat_age_s": round(age, 3) if age is not None else -1.0,
            # Routing-contract additions (docs/serving.md
            # "Tensor-parallel replicas"): always present, always
            # typed — tp is the replica's tensor-parallel degree
            # (int >= 1), mesh its axis/device layout (str; "" on an
            # unsharded engine) — so the registry and the router's
            # per-replica fleet view surface serving topology.
            "tp": int(self.engine_cfg.tp),
            "mesh": self._shard.describe() if self._shard is not None
            else "",
            # Fleet-rollout contract addition (docs/serving.md "Fleet
            # rollouts"): the config generation this engine was built
            # at — always present, always int, so the registry and the
            # rollout controller can tell incumbent from candidate
            # replicas without parsing knobs.
            "config_generation": int(self.engine_cfg.config_generation),
            "state_transitions": self.state_transitions,
            "n_slots": self.engine_cfg.n_slots,
            "slots_active": self.slots.active_count,
            "max_len": self.slots.max_len,
            "overlap": self.engine_cfg.overlap,
            "resume": self.engine_cfg.resume,
            "journal_inflight":
                len(self.journal) if self.journal is not None else 0,
            "decode_compilations": self._decode_traces,
            "prefill_compilations": self._prefill_traces,
            "prefill_calls": self._prefill_calls,
            # The admission-side first-token sampler's compile count
            # ((k, vocab) shapes, warmed by warmup()) — the decode
            # guard stays on decode_compilations: sampling parameters
            # are data and never retrace the tick.
            "sample_compilations": self._sample_traces,
            # (bucket, batch) shape pairs the prefill has compiled for
            # — bounded by buckets x max_prefills_per_tick.
            "prefill_buckets": sorted(self._prefill_fns),
            "paged": True,  # the only cache since PR 28; readers exist
            # SLO scheduling (docs/serving.md "Scheduling"): the chunk
            # budget (0 = whole-prompt prefill) and how many slots are
            # mid-ingestion right now; per-class TTFT/queue-wait and
            # the preemption counter ride the metrics snapshot above.
            "prefill_chunk_tokens": self.engine_cfg.prefill_chunk_tokens,
            "slots_ingesting": len(self._ingest),
            "speculative": self._spec,
            # Online autotuning (docs/serving.md "Autotuning"):
            # enabled flag always present; full tuner state (phase,
            # current/best knobs, trajectory) rides along — and is
            # served standalone at GET /tuning — once a tuner exists.
            "autotune": self._tuner is not None,
            **({"tuning": self._tuner.snapshot()}
               if self._tuner is not None else {}),
            **({
                "spec_k": self.engine_cfg.spec_k,
                "spec_draft": "model" if self._spec_model else "ngram",
            } if self._spec else {}),
            "page_size": self.slots.page_size,
            "kv_dtype": str(jnp.dtype(self.slots._storage_dtype).name),
            # what a token leaves in a LATENT pool, every layer's row
            # (0: the pool holds every head's K and V, kv_bytes_per_token)
            "kv_latent_bytes_per_token": self.slots.latent_bytes_per_token,
            # ... and in a sparse model's index-key array beside it
            "kv_index_bytes_per_token": self.slots.index_bytes_per_token,
            # the bytes of the projection leaves this engine laid out
            # at load (T.lay_out_projections; 0: a latent model has none)
            "params_relaid_bytes": self._relaid_bytes,
            # a conv model's second kind of per-request state: bytes a
            # slot holds beside its pages (fixed, whatever its context),
            # and the slots that hold a request's now
            "conv_state_bytes_per_slot":
                self.slots.conv_state_bytes_per_slot,
            "conv_state_slots_live": self.slots.active_count
                if self.cfg.has_state else 0,
            # ... and a third: a state-space mixer's matrix state, its
            # bytes a slot, the slots holding one now, and what the two
            # bodies that touch it have done (rows x layers a tick
            # updated in place; true tokens x layers a prompt's or a
            # chunk's scan carried it over: ssm_*_total, with the
            # metrics' counters)
            "ssm_state_bytes_per_slot":
                self.slots.ssm_state_bytes_per_slot,
            "ssm_state_slots_live": self.slots.active_count
                if self.cfg.has_ssm else 0,
            # ... a fourth: a linear-attention layer's float32 matrix
            # state (lin_*_total as ssm_*_total); and what a page of a
            # block-sparse model holds beside its tokens' rows
            "lin_state_bytes_per_slot":
                self.slots.lin_state_bytes_per_slot,
            "kv_compressed_bytes_per_page":
                self.slots.compressed_bytes_per_page,
            "kv_pages_high_water": self.slots.pages_high_water,
            "kv_window_pages_per_slot_bound":
                self.wslots.window_pages_bound
                if self.wslots is not None else 0,
            "prefixes_registered": len(self._prefixes),
            # Whether the decode/draft/verify ticks were built on the
            # fused Pallas paged-attention kernel — what RAN, not the
            # flag: resolved at construction from
            # EngineConfig.paged_kernel AND the pool layout (see
            # docs/serving.md "Paged decode kernel").
            "paged_kernel_engaged": self._paged_kernel,
        }
