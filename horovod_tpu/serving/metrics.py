"""Serving observability: the engine's instrument panel, now a thin
view over :mod:`horovod_tpu.obs.registry`.

Historically this module owned its own Counter/Gauge/Histogram classes;
those now live in the process-wide registry layer (same semantics,
thread-safe, constant-memory histograms) and are re-exported here for
backward compatibility.  :class:`ServingMetrics` registers every
instrument under a ``serving_*`` Prometheus family name in a PRIVATE
:class:`~horovod_tpu.obs.registry.MetricsRegistry` (one per engine
lifetime — tests and benchmarks create many engines per process, and
their series must not collide), keeps the original attribute API the
engine updates (``metrics.admitted.inc()`` …), and keeps the original
``snapshot()`` dict the ``/stats`` endpoint serves.  The server's
``GET /metrics`` renders this registry PLUS the default registry
(training/elastic/timeline families) as Prometheus text exposition.
"""

from __future__ import annotations

import collections
import logging
from typing import Dict, Optional

from horovod_tpu.obs import tracing as obs_tracing
from horovod_tpu.obs.registry import (  # noqa: F401  (back-compat re-export)
    DEFAULT_LATENCY_BUCKETS,
    TICK_PHASE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "ServingMetrics",
    "DEFAULT_LATENCY_BUCKETS", "TICK_PHASE_BUCKETS", "PHASES",
    "SLOW_STEP_SECONDS", "SLOW_STEP_RING", "phase_key",
]

#: The engine loop's partition, in the order a step passes through them.
PHASES = ("lock_wait", "reclaim", "admit", "prefill", "ingest_chunk",
          "page_prep", "tick_dispatch", "tick_device_wait", "tick_host",
          "bookkeeping", "idle")


def phase_key(name: str, cpu: bool = False) -> str:
    """The ``/stats`` key of a phase's histogram on either clock (the
    three ``tick_*`` phases keep the keys they had before the others)."""
    return (("" if name.startswith("tick_") else "phase_") + name
            + ("_cpu_seconds" if cpu else "_seconds"))


#: An iteration of the engine loop longer than this leaves a record
#: (:meth:`ServingMetrics.slow_step`).  The longest SOUND step of any
#: benchmarked cell is a chunk step of 101-118 ms (PERF.md section 5).
SLOW_STEP_SECONDS = 0.25

#: ... and ``/stats`` keeps this many of the newest.
SLOW_STEP_RING = 16

_log = logging.getLogger(__name__)


class ServingMetrics:
    """The engine's instrument panel, surfaced verbatim through /stats
    and as Prometheus families through /metrics.

    * ``ttft`` — submit-to-first-token latency (prefill + queueing),
      a ``{class=}``-labeled family (one child histogram per SLO
      priority class) so the per-class tail the scheduler orders on
      is observable per class; ``/stats`` serves both the merged
      population (``ttft_seconds``, the historical key) and the
      per-class split (``ttft_seconds_by_class``).
    * ``queue_wait`` — submit-to-admission latency, same ``{class=}``
      labeling: the share of TTFT the SLO scheduler can actually move
      (prefill cost is the model's).
    * ``preemptions`` — admitted requests suspended under slot/page
      pressure (journal frontier kept, re-admitted later, output
      byte-identical); the victim count the preemption policy pays
      for bounded winner wait.
    * ``token_latency`` — per-token decode-tick latency.
    * ``queue_depth`` / ``slot_occupancy`` — gauges sampled every tick.
    * ``admitted`` / ``rejected`` / ``completed`` / ``cancelled`` —
      request counters (rejected covers queue-full, deadline, and
      too-long — BOTH the submit-time and the take-time paths;
      cancelled covers caller-side :meth:`GenerationFuture.cancel`,
      including the server's 504 slot reclamation).
    * ``engine_failures`` / ``engine_restarts`` — fault-tolerance
      counters: every tick failure or watchdog stall, and every
      successful supervised restart (fresh page pool).
    * ``resumed`` / ``resume_wasted_tokens`` — durability counters
      (docs/serving.md "Operations"): in-flight requests re-admitted
      across a supervised restart with their futures still live, and
      the tokens those re-admissions re-prefilled (original prompt +
      previously emitted) — the bounded price of not re-executing
      from scratch.  ``resume_wasted_tokens / tokens_generated`` is
      the wasted-token ratio ``benchmarks/serving.py --chaos``
      reports.
    * ``tick_dispatch`` / ``tick_device_wait`` / ``tick_host`` — the
      pipeline phase timers: time to BUILD AND DISPATCH a decode tick
      (async — returns before the device finishes), time BLOCKED
      fetching a tick's results (the host-visible device wait; with the
      overlapped loop this is the residual the pipeline could not
      hide), and time in host bookkeeping (emit / retire / admission
      accounting).  ``device_wait / (dispatch + device_wait + host)``
      is the overlap-efficiency number ``benchmarks/serving.py``
      reports — 1.0 means every host cycle was hidden behind device
      compute.
    * ``phases`` — the engine loop's partition
      (docs/observability.md "Engine phases"): every moment of the
      engine thread lies in at most one of ``lock_wait``, ``reclaim``,
      ``admit``, ``prefill``, ``ingest_chunk``, ``page_prep``,
      ``tick_dispatch``, ``tick_device_wait``, ``tick_host``,
      ``bookkeeping``, ``idle`` (:data:`PHASES`); ``engine_loop``
      observes the loop's own wall time per iteration, so
      ``sum(phases) / engine_loop`` is the share of the loop the phases
      cover.  The three ``tick_*`` keep their own families; the other
      eight are the ``{phase=}`` children of one family.
      ``phases_cpu`` / ``engine_loop_cpu`` are their twins on the engine
      thread's CPU clock (``time.thread_time()``): a phase's ``wall -
      cpu`` is the time the thread did not run — the wait, in a phase
      that blocks (``tick_device_wait``, ``idle``, ``lock_wait``, a
      prefill's first-token fetch); time it was runnable and the
      interpreter lock or the OS kept from it, in a host-only one.
    * ``gc_pause`` — the collector's pauses by generation
      (``obs.tracing.gc_watch``), on whichever thread they ran: every
      thread stands still for one.  The collector's callback only
      appends to ``gc_pending`` (it may run under any lock its thread
      holds, a histogram's own included); :meth:`fold_gc` observes them
      from outside the collector.
    * ``slow_step`` — an iteration of the loop longer than
      :data:`SLOW_STEP_SECONDS` leaves a record of where it stood
      (``/stats`` ``slow_steps``, the newest :data:`SLOW_STEP_RING`),
      counted in ``slow_steps`` / ``slow_step_seconds``.
    * ``engine_step`` — wall time of the ``step()`` calls that
      dispatched a decode tick, by what else the same step ran:
      ``{kind="prefill"}`` an admission prefill, ``{kind="chunk"}`` an
      ingest chunk (and no admission), ``{kind="plain"}`` neither.  The
      counts are ``decode_ticks_plain`` / ``_prefill`` / ``_chunk`` in
      ``/stats`` and sum to ``decode_ticks``.
    * ``prefill_tokens`` / ``prefill_padded_tokens`` — prompt tokens
      the prefill executables were asked to ingest, and the
      bucket-/chunk-padded tokens they actually ran.
    * ``ssm_updated_slots`` / ``ssm_scanned_tokens`` — what the two
      bodies of a state-space mixer were asked for: active rows x
      layers a dispatched tick (each a matrix state read and written
      once), true prompt tokens x layers a prefill executable (0 for a
      model with none).
    * ``lin_updated_slots`` / ``lin_scanned_tokens`` — the same two for
      linear-attention layers (a float32 matrix state a head): active
      rows x layers a dispatched tick, true prompt tokens x layers a
      prefill executable.
    * ``bsa_scored_rows`` / ``bsa_attended_tokens`` / ``bsa_live_tokens``
      — a block-sparse model's dispatched ticks, summed over its
      block-sparse layers: the compressed rows a (slot, KV head) scores
      (the whole windows of its context), the tokens a slot's KV head
      attends (the chosen blocks', or every one of a context no longer
      than ``bsa_dense_len``) and the tokens it holds.
    * ``paged_live_tokens`` / ``paged_walked_tokens`` — per dispatched
      paged tick, the positions the active slots may attend, and the
      positions the paged kernel's walk covers for them: each slot's
      limit rounded up to a block (``ops.paged_attention.walk``).
      With window layers these are the FULL layers'; ``window_live_
      tokens`` / ``window_walked_tokens`` are the window layers' (live:
      the window's span; walked: whole blocks from the one holding the
      window's first position).
    * ``dsa_scored_tokens`` / ``dsa_walked_tokens`` /
      ``dsa_selected_tokens`` / ``dsa_full_rows`` — a sparse-attention
      model's dispatched ticks (a layer counted once): the live tokens
      the index walk scores, the tokens its blocks fetch for them
      (``ops.paged_attention.index_block_pages``), the rows the selected
      attend reads (``min(index_topk, context)`` a slot), and the
      slot-ticks whose context is no longer than ``index_topk`` (the
      selection then keeps everything).
    * ``sample_ticks_drawfree`` / ``sample_ticks_sortfree`` — dispatched
      decode ticks whose next-token pick skipped the draw (every row
      greedy) / ran no sort (that, or no row with a top-k or a nucleus):
      the host's reading of the gates ``sample_token_rows`` takes on the
      device (``SlotSampling.gates``).
    * ``moe_rows`` / ``moe_experts_touched`` / ``moe_load_max_rows`` /
      ``moe_load_mean_rows`` — an expert model's decode ticks, summed
      over layers: expert rows computed (active slots x experts a
      token), experts handed at least one row, the largest expert's
      rows, and rows / experts (``max / mean`` is the imbalance).  They
      ride out with the tick's tokens: no extra host sync.  Of ONE
      CHIP'S SHARE of the experts these count what is routed HERE, and
      ``moe_rows_routed_away`` the picks whose expert another chip
      holds (0 where every expert is held).
    * ``kv_pages_total`` / ``kv_pages_free`` / ``kv_pages_shared`` /
      ``kv_bytes_per_token`` — page-pool pressure gauges for the paged
      KV cache (docs/serving.md "Paged KV cache"): pool size, free
      heap depth (admission headroom), pages referenced by >1 owner
      (prefix sharing in effect), and the per-token cache cost the
      ``kv_dtype`` lever moves.
      ``kv_window_pages_total`` / ``_free`` / ``_per_slot_max`` are
      the window layers' pool (0 without window layers): size, free
      heap, and the most pages one slot ever held at once.
    * ``decode_ticks`` / ``host_syncs`` — dispatched decode ticks and
      host sync points (value fetches that block on device work) on
      the decode hot path.  Steady-state overlapped decode performs
      exactly ONE sync per tick (the deferred fetch of the previous
      tick); ``host_syncs_per_tick`` in the snapshot is the regression
      guard against an accidental ``np.asarray`` /
      ``block_until_ready`` creeping back onto the hot path.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        r = registry if registry is not None else MetricsRegistry()
        self.registry = r
        self.ttft = r.histogram(
            "serving_ttft_seconds",
            "Submit-to-first-token latency (queueing + prefill), "
            "labeled by SLO priority class",
            labels=("class",))
        self.queue_wait = r.histogram(
            "serving_queue_wait_seconds",
            "Submit-to-admission latency, labeled by SLO priority "
            "class — the share of TTFT scheduling policy can move",
            labels=("class",))
        self.preemptions = r.counter(
            "serving_preemptions_total",
            "Admitted requests suspended under slot/page pressure "
            "(requeued with their journal frontier; output stays "
            "byte-identical)")
        self.token_latency = r.histogram(
            "serving_token_latency_seconds",
            "Per-token decode-tick latency (dispatch to host fetch)")
        self.queue_depth = r.gauge(
            "serving_queue_depth", "Requests queued awaiting admission")
        self.slot_occupancy = r.gauge(
            "serving_slot_occupancy", "Active slots / total slots")
        self.admitted = r.counter(
            "serving_requests_admitted_total", "Requests admitted to slots")
        self.rejected = r.counter(
            "serving_requests_rejected_total",
            "Typed rejections (queue-full, deadline, too-long)")
        self.completed = r.counter(
            "serving_requests_completed_total",
            "Requests retired with tokens (eos/length/capacity/deadline)")
        self.cancelled = r.counter(
            "serving_requests_cancelled_total",
            "Requests cancelled caller-side (incl. 504 slot reclamation)")
        self.tokens_generated = r.counter(
            "serving_tokens_generated_total", "Tokens emitted to futures")
        self.resumed = r.counter(
            "serving_requests_resumed_total",
            "In-flight requests re-admitted after an engine restart "
            "(journaled decode state; the original future stays live)")
        self.resume_wasted_tokens = r.counter(
            "serving_resume_wasted_tokens",
            "Tokens re-prefilled by resume admissions (prompt + "
            "previously emitted) — the bounded re-work durability costs")
        self.engine_failures = r.counter(
            "serving_engine_failures_total",
            "Tick failures and watchdog stalls")
        self.engine_restarts = r.counter(
            "serving_engine_restarts_total",
            "Successful supervised restarts (fresh page pool)")
        self.tick_dispatch = r.histogram(
            "serving_tick_dispatch_seconds",
            "Time to build and dispatch one decode tick (async)",
            buckets=TICK_PHASE_BUCKETS)
        self.tick_device_wait = r.histogram(
            "serving_tick_device_wait_seconds",
            "Host-visible wait fetching a tick's results",
            buckets=TICK_PHASE_BUCKETS)
        self.tick_host = r.histogram(
            "serving_tick_host_seconds",
            "Host bookkeeping per tick (emit/retire/admission)",
            buckets=TICK_PHASE_BUCKETS)
        phase_family = r.histogram(
            "serving_engine_phase_seconds",
            "Time in one phase of the engine loop (lock_wait, reclaim, "
            "admit, prefill, ingest_chunk, page_prep, bookkeeping, "
            "idle); with the three serving_tick_* families the phases "
            "partition the engine thread's time",
            buckets=TICK_PHASE_BUCKETS, labels=("phase",))
        ticks = {"tick_dispatch": self.tick_dispatch,
                 "tick_device_wait": self.tick_device_wait,
                 "tick_host": self.tick_host}
        self.phases: Dict[str, Histogram] = {
            name: ticks.get(name) or phase_family.labels(phase=name)
            for name in PHASES}
        cpu_family = r.histogram(
            "serving_phase_cpu_seconds",
            "CPU time of the engine thread (time.thread_time) in one "
            "phase of the engine loop, all eleven: wall less cpu is "
            "the time the thread did not run",
            buckets=TICK_PHASE_BUCKETS, labels=("phase",))
        self.phases_cpu: Dict[str, Histogram] = {
            name: cpu_family.labels(phase=name) for name in PHASES}
        self.engine_loop = r.histogram(
            "serving_engine_loop_seconds",
            "Wall time of one iteration of the engine thread's loop "
            "(a step and its idle sleep): the denominator of the "
            "phases' coverage",
            buckets=TICK_PHASE_BUCKETS)
        self.engine_loop_cpu = r.histogram(
            "serving_engine_loop_cpu_seconds",
            "CPU time of the engine thread over one iteration of its "
            "loop, from the same two reads as "
            "serving_engine_loop_seconds",
            buckets=TICK_PHASE_BUCKETS)
        self._gc_family = r.histogram(
            "serving_gc_pause_seconds",
            "Garbage collections while the engine ran, by generation "
            "(wall time from the collector's start to its stop, on "
            "whichever thread)",
            buckets=TICK_PHASE_BUCKETS, labels=("generation",))
        self.gc_pause: Dict[int, Histogram] = {
            g: self._gc_family.labels(generation=str(g)) for g in (0, 1, 2)}
        self.gc_pending = tuple(collections.deque() for _ in self.gc_pause)
        self.slow_steps = r.counter(
            "serving_slow_steps_total",
            "Iterations of the engine loop longer than "
            "SLOW_STEP_SECONDS (each left a record in /stats "
            "slow_steps)")
        self.slow_step_seconds = r.counter(
            "serving_slow_step_seconds_total",
            "Wall seconds of the iterations counted by "
            "serving_slow_steps_total")
        self._slow_ring: collections.deque = collections.deque(
            maxlen=SLOW_STEP_RING)
        step_family = r.histogram(
            "serving_engine_step_seconds",
            "Wall time of a step() that dispatched a decode tick, by "
            "what else it ran: kind=prefill (an admission prefill), "
            "chunk (an ingest chunk), plain (neither)",
            buckets=TICK_PHASE_BUCKETS, labels=("kind",))
        self.engine_step: Dict[str, Histogram] = {
            kind: step_family.labels(kind=kind)
            for kind in ("plain", "prefill", "chunk")}
        self.prefill_tokens = r.counter(
            "serving_prefill_tokens_total",
            "Prompt tokens run through a prefill executable "
            "(admission groups and ingest chunks)")
        self.prefill_padded_tokens = r.counter(
            "serving_prefill_padded_tokens_total",
            "Tokens the prefill executables ran, bucket and chunk "
            "padding included (rows x bucket per call)")
        self.paged_live_tokens = r.counter(
            "serving_paged_live_tokens_total",
            "Per dispatched paged tick, the positions its active "
            "slots may attend")
        self.paged_walked_tokens = r.counter(
            "serving_paged_walked_tokens_total",
            "Per dispatched paged tick, the positions the paged "
            "attention's walk covers (each active slot's limit rounded "
            "up to a block of pages)")
        self.ssm_updated_slots = r.counter(
            "serving_ssm_updated_slots_total",
            "Per dispatched paged tick of a model with state-space "
            "mixers, active rows x layers: the matrix states its "
            "update read and wrote in place")
        self.ssm_scanned_tokens = r.counter(
            "serving_ssm_scanned_tokens_total",
            "Prompt tokens x layers a state-space mixer's chunked scan "
            "carried a state over (admission groups and ingest chunks)")
        self.lin_updated_slots = r.counter(
            "serving_lin_updated_slots_total",
            "Per dispatched paged tick of a model with linear-attention "
            "layers, active rows x layers: the float32 matrix states "
            "its update read and wrote in place")
        self.lin_scanned_tokens = r.counter(
            "serving_lin_scanned_tokens_total",
            "Prompt tokens x layers a linear-attention layer's chunked "
            "scan carried a state over (admission groups and ingest "
            "chunks)")
        self.bsa_scored_rows = r.counter(
            "serving_bsa_scored_rows_total",
            "Per dispatched paged tick of a block-sparse model, the "
            "compressed rows scored: whole windows x KV heads x layers")
        self.bsa_attended_tokens = r.counter(
            "serving_bsa_attended_tokens_total",
            "Per dispatched paged tick, the tokens a slot's KV head "
            "attends in a block-sparse layer (the chosen blocks'), "
            "x layers")
        self.bsa_live_tokens = r.counter(
            "serving_bsa_live_tokens_total",
            "Per dispatched paged tick, the tokens the active slots "
            "hold, x block-sparse layers")
        self.window_live_tokens = r.counter(
            "serving_window_live_tokens_total",
            "Per dispatched paged tick, the positions its active slots "
            "may attend in a window layer (the window's span)")
        self.window_walked_tokens = r.counter(
            "serving_window_walked_tokens_total",
            "Per dispatched paged tick, the positions a window layer's "
            "walk covers (whole blocks from the window's first)")
        self.dsa_scored_tokens = r.counter(
            "serving_dsa_scored_tokens_total",
            "Per dispatched paged tick of a sparse-attention model, the "
            "live tokens its index walk scores (a layer counted once)")
        self.dsa_walked_tokens = r.counter(
            "serving_dsa_walked_tokens_total",
            "Per dispatched paged tick, the tokens the index walk's "
            "blocks fetch (each slot's limit rounded up to a block)")
        self.dsa_selected_tokens = r.counter(
            "serving_dsa_selected_tokens_total",
            "Per dispatched paged tick, the cache rows the selected "
            "attend reads: min(index_topk, context) a slot")
        self.dsa_full_rows = r.counter(
            "serving_dsa_full_rows_total",
            "Slot-ticks whose context is at most index_topk: the "
            "selection keeps every position")
        self.sample_ticks_drawfree = r.counter(
            "serving_sample_ticks_drawfree_total",
            "Dispatched decode ticks whose batch held no sampled row: "
            "the next-token pick ran the argmax alone")
        self.sample_ticks_sortfree = r.counter(
            "serving_sample_ticks_sortfree_total",
            "Dispatched decode ticks whose pick ran no full-vocabulary "
            "sort: no sampled row, or none with a top-k or a nucleus")
        self.moe_rows = r.counter(
            "serving_moe_rows_total",
            "Expert rows computed by decode ticks, summed over layers "
            "(active slots x experts a token)")
        self.moe_rows_routed_away = r.counter(
            "serving_moe_rows_routed_away_total",
            "Decode ticks' expert picks whose expert another chip holds "
            "(a chip's share of an expert-parallel layer; 0 where every "
            "expert is held): rows / (rows + routed away) is the share "
            "that lands here")
        self.moe_experts_touched = r.counter(
            "serving_moe_experts_touched_total",
            "Experts handed at least one row by a decode tick, summed "
            "over layers")
        self.moe_load_max_rows = r.counter(
            "serving_moe_load_max_rows_total",
            "The largest expert's rows in a decode tick, summed over "
            "layers")
        self.moe_load_mean_rows = r.counter(
            "serving_moe_load_mean_rows_total",
            "Rows over experts in a decode tick, summed over layers")
        self.decode_ticks = r.counter(
            "serving_decode_ticks_total", "Decode ticks dispatched")
        self.host_syncs = r.counter(
            "serving_host_syncs_total",
            "Host sync points (blocking value fetches) on the decode path")
        self.kv_pages_total = r.gauge(
            "serving_kv_pages_total",
            "KV page pool size")
        self.kv_pages_free = r.gauge(
            "serving_kv_pages_free",
            "KV pages on the free heap (admission headroom)")
        self.kv_pages_shared = r.gauge(
            "serving_kv_pages_shared",
            "KV pages referenced by more than one owner "
            "(prefix sharing in effect)")
        self.kv_window_pages_total = r.gauge(
            "serving_kv_window_pages_total",
            "Window layers' KV page pool size (0 = no window layers)")
        self.kv_window_pages_free = r.gauge(
            "serving_kv_window_pages_free",
            "Window layers' KV pages on the free heap")
        self.kv_window_pages_per_slot_max = r.gauge(
            "serving_kv_window_pages_per_slot_max",
            "Most window-layer pages one slot ever held at once")
        self.kv_bytes_per_token = r.gauge(
            "serving_kv_bytes_per_token",
            "KV cache bytes per stored token (k+v across layers, "
            "incl. int8 scales) — the kv_dtype lever made legible")
        # Speculative decoding (docs/serving.md "Speculative decoding"):
        # tokens_per_tick is the multiplier made visible — every active
        # slot observes how many tokens one tick emitted for it (always
        # 1 on a non-speculative engine, 1..K+1 under speculation), so
        # the speculative A/B and the overlap pipeline report on the
        # same per-tick axis.  Acceptance is drafted-vs-accepted:
        # wasted = drafted - accepted is the draft compute speculation
        # burned on disagreement.
        self.tokens_per_tick = r.histogram(
            "serving_tokens_per_tick",
            "Tokens emitted per slot per decode tick (1 without "
            "speculation; 1..K+1 with it)",
            buckets=tuple(float(b) for b in range(1, 18)))
        self.spec_drafted = r.counter(
            "serving_spec_drafted_tokens_total",
            "Draft tokens proposed to the verify kernel")
        self.spec_accepted = r.counter(
            "serving_spec_accepted_tokens_total",
            "Draft tokens the target's greedy verify accepted")
        self.spec_wasted = r.counter(
            "serving_spec_wasted_tokens_total",
            "Draft tokens rejected by the verify (drafted - accepted)")
        self.spec_acceptance = r.histogram(
            "serving_spec_acceptance_ratio",
            "Accepted/drafted ratio per slot per speculative tick",
            buckets=(0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875,
                     1.0))
        # Streaming transport (docs/serving.md "HTTP API"): per-token
        # SSE delivery, cancel-on-disconnect, and the user-facing
        # latency number streaming exists to improve — time to the
        # FIRST STREAMED TOKEN EVENT on the wire (vs ttft, which stops
        # at the engine emitting it).
        self.streamed_tokens = r.counter(
            "serving_streamed_tokens_total",
            "Tokens delivered as SSE token events (stream=true)")
        self.disconnects = r.counter(
            "serving_disconnects_total",
            "Streaming clients that vanished mid-stream (request "
            "cancelled, slot/pages reclaimed within one tick)")
        self.streamed_ttfb = r.histogram(
            "serving_streamed_ttfb_seconds",
            "Request arrival to first streamed token event on the "
            "wire (the honest user-facing TTFT for stream=true)")
        # Tensor-parallel serving (docs/serving.md "Tensor-parallel
        # replicas"): the replica's tp degree as a gauge so a fleet
        # dashboard can tell a tp=4 replica's tok/s from four tp=1
        # replicas' at a glance (cataloged in docs/observability.md).
        self.tp_degree = r.gauge(
            "serving_tp_degree",
            "Tensor-parallel degree of this engine's serving mesh "
            "(1 = unsharded single-device serving)")
        self.model_flops_per_token = r.gauge(
            "serving_model_flops_per_token",
            "Configured model FLOPs per generated token "
            "(EngineConfig.model_flops_per_token; 0 = not configured)")
        self.achieved_flops = r.gauge(
            "serving_achieved_flops_per_sec",
            "Achieved model FLOP/s over the recent token-rate window "
            "(tokens/sec x model_flops_per_token; 0 until configured "
            "and two samples apart)")
        # Online autotuning (docs/serving.md "Autotuning"): one sample
        # = one scored knob setting over one window of worked ticks.
        # Registered unconditionally (cheap) so the families are
        # documented and lint-checked whether or not a tuner runs.
        self.tuning_samples = r.counter(
            "tuning_samples_total",
            "Knob settings scored by the online autotuner "
            "(one per scoring window, warmup/settling discarded)")
        self.tuning_rollbacks = r.counter(
            "tuning_rollbacks_total",
            "Tuning samples rolled back for violating a per-class "
            "SLO constraint beyond the guard band")
        self.tuning_objective = r.gauge(
            "tuning_objective",
            "Weighted objective of the most recent scored window")
        self.tuning_best_objective = r.gauge(
            "tuning_best_objective",
            "Best constraint-satisfying objective seen this trajectory")

    # -- the collector's pauses --------------------------------------------

    def fold_gc(self) -> None:
        """Observe the pauses the collector's callback left in
        ``gc_pending``: every reader does so first (:meth:`snapshot`,
        a ``/metrics`` scrape), and the engine loop after an iteration
        that saw a collection.  Never from inside a collection: one may
        start on a thread that holds a histogram's lock."""
        for generation, pending in enumerate(self.gc_pending):
            while pending:
                try:
                    seconds = pending.popleft()
                except IndexError:  # another thread folded it
                    break
                self.gc_pause[generation].observe(seconds)

    # -- a slow step's record ----------------------------------------------

    def slow_step(self, record: Dict) -> None:
        """One iteration of the engine loop that stood still: into the
        ring ``/stats`` serves, the two counters, one warning line (it
        reaches the process's stderr, so a stall names its phase in
        any run) and an instant on whatever trace is recording."""
        self._slow_ring.append(record)
        self.slow_steps.inc()
        self.slow_step_seconds.inc(record["wall_s"])
        _log.warning("slow engine step: %s", record)
        obs_tracing.instant("slow_step", record)

    # -- per-class observation hooks ---------------------------------------

    def observe_ttft(self, priority: str, v: float) -> None:
        self.ttft.labels(**{"class": priority}).observe(v)

    def observe_queue_wait(self, priority: str, v: float) -> None:
        self.queue_wait.labels(**{"class": priority}).observe(v)

    @staticmethod
    def _merged(family) -> Dict:
        """Label-merged histogram snapshot — the historical /stats
        shape (count/sum/mean/p50/p99/buckets over the WHOLE
        population), rebuilt bucket-wise from the labeled children
        (they all share their family's bucket edges)."""
        children = [child for _, child in family.children()]
        h = Histogram(children[0].buckets) if children else Histogram()
        for child in children:
            st = child.state()
            h._counts = [a + b for a, b in zip(h._counts, st["counts"])]
            h._sum += st["sum"]
            h._count += st["count"]
        return h.snapshot()

    @staticmethod
    def _by_class(family) -> Dict:
        return {key[0]: child.snapshot()
                for key, child in family.children()}

    def snapshot(self) -> Dict:
        self.fold_gc()
        ticks = self.decode_ticks.value
        return {
            "ttft_seconds": self._merged(self.ttft),
            "ttft_seconds_by_class": self._by_class(self.ttft),
            "queue_wait_seconds_by_class": self._by_class(self.queue_wait),
            "preemptions": self.preemptions.value,
            "token_latency_seconds": self.token_latency.snapshot(),
            "queue_depth": self.queue_depth.value,
            "slot_occupancy": self.slot_occupancy.value,
            "requests_admitted": self.admitted.value,
            "requests_rejected": self.rejected.value,
            "requests_completed": self.completed.value,
            "requests_cancelled": self.cancelled.value,
            "requests_resumed": self.resumed.value,
            "resume_wasted_tokens": self.resume_wasted_tokens.value,
            "tokens_generated": self.tokens_generated.value,
            "engine_failures": self.engine_failures.value,
            "engine_restarts": self.engine_restarts.value,
            "tick_dispatch_seconds": self.tick_dispatch.snapshot(),
            "tick_device_wait_seconds": self.tick_device_wait.snapshot(),
            "tick_host_seconds": self.tick_host.snapshot(),
            "decode_ticks": ticks,
            **{phase_key(name): h.snapshot()
               for name, h in self.phases.items()
               if not name.startswith("tick_")},
            **{phase_key(name, cpu=True): h.snapshot()
               for name, h in self.phases_cpu.items()},
            "engine_loop_seconds": self.engine_loop.snapshot(),
            "engine_loop_cpu_seconds": self.engine_loop_cpu.snapshot(),
            "gc_pause_seconds": self._merged(self._gc_family),
            "gc_pause_seconds_gen2": self.gc_pause[2].snapshot(),
            "slow_steps": list(self._slow_ring),
            "slow_steps_total": self.slow_steps.value,
            "slow_step_seconds_total":
                round(self.slow_step_seconds.value, 6),
            **{f"engine_step_seconds_{kind}": h.snapshot()
               for kind, h in self.engine_step.items()},
            **{f"decode_ticks_{kind}": h.count
               for kind, h in self.engine_step.items()},
            "prefill_tokens_total": self.prefill_tokens.value,
            "prefill_padded_tokens_total":
                self.prefill_padded_tokens.value,
            "paged_live_tokens_total": self.paged_live_tokens.value,
            "paged_walked_tokens_total": self.paged_walked_tokens.value,
            "ssm_updated_slots_total": self.ssm_updated_slots.value,
            "ssm_scanned_tokens_total": self.ssm_scanned_tokens.value,
            "lin_updated_slots_total": self.lin_updated_slots.value,
            "lin_scanned_tokens_total": self.lin_scanned_tokens.value,
            "bsa_scored_rows_total": self.bsa_scored_rows.value,
            "bsa_attended_tokens_total": self.bsa_attended_tokens.value,
            "bsa_live_tokens_total": self.bsa_live_tokens.value,
            "window_live_tokens_total": self.window_live_tokens.value,
            "window_walked_tokens_total": self.window_walked_tokens.value,
            "dsa_scored_tokens_total": self.dsa_scored_tokens.value,
            "dsa_walked_tokens_total": self.dsa_walked_tokens.value,
            "dsa_selected_tokens_total": self.dsa_selected_tokens.value,
            "dsa_full_rows_total": self.dsa_full_rows.value,
            "sample_ticks_drawfree_total":
                self.sample_ticks_drawfree.value,
            "sample_ticks_sortfree_total":
                self.sample_ticks_sortfree.value,
            "moe_rows_total": self.moe_rows.value,
            "moe_rows_routed_away_total": self.moe_rows_routed_away.value,
            "moe_experts_touched_total": self.moe_experts_touched.value,
            "moe_load_max_rows_total": self.moe_load_max_rows.value,
            "moe_load_mean_rows_total": self.moe_load_mean_rows.value,
            "kv_pages_total": self.kv_pages_total.value,
            "kv_pages_free": self.kv_pages_free.value,
            "kv_pages_in_use":
                self.kv_pages_total.value - self.kv_pages_free.value,
            "kv_window_pages_total": self.kv_window_pages_total.value,
            "kv_window_pages_free": self.kv_window_pages_free.value,
            "kv_window_pages_in_use":
                self.kv_window_pages_total.value
                - self.kv_window_pages_free.value,
            "kv_window_pages_per_slot_max":
                self.kv_window_pages_per_slot_max.value,
            "kv_pages_shared": self.kv_pages_shared.value,
            "kv_bytes_per_token": self.kv_bytes_per_token.value,
            "tokens_per_tick": self.tokens_per_tick.snapshot(),
            "spec_drafted_tokens": self.spec_drafted.value,
            "spec_accepted_tokens": self.spec_accepted.value,
            "spec_wasted_tokens": self.spec_wasted.value,
            "spec_acceptance_ratio":
                round(self.spec_accepted.value / self.spec_drafted.value,
                      4) if self.spec_drafted.value else None,
            "streamed_tokens": self.streamed_tokens.value,
            "disconnects": self.disconnects.value,
            "streamed_ttfb_seconds": self.streamed_ttfb.snapshot(),
            "host_syncs": self.host_syncs.value,
            "host_syncs_per_tick":
                round(self.host_syncs.value / ticks, 4) if ticks else None,
            "model_flops_per_token":
                self.model_flops_per_token.value or None,
            "achieved_flops_per_sec": self.achieved_flops.value or None,
        }
