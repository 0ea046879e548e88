"""The program's own names on the profiler's trace (ISSUE 24).

* ``obs.tracing.phase`` — the one span primitive: an ``hvd:<name>``
  ``TraceAnnotation`` on the profiler's clock, a histogram observation
  on each of two clocks (wall and the thread's CPU), and the active
  tracer's tick row.
* The engine loop's eleven phases partition its time on both clocks
  (their sums and the time under none add up to ``engine_loop_seconds``
  / ``engine_loop_cpu_seconds``; none nests in another), and the
  tick-kind, prefill-padding and paged-walk counters count where the
  work happens.
* The waits have a place (ISSUE 36): the step lock's wait is a phase,
  the collector's pauses a histogram and an ``hvd:gc`` span, and an
  iteration that stood still leaves a record that names where.
* ``jax.named_scope`` cuts every compiled body into one flat vocabulary
  (``T.DEVICE_SCOPES``) and every Pallas call carries a ``name=`` — as
  trace-time metadata only: the optimised HLO is unchanged.
"""

import contextlib
import gc
import glob
import json
import logging
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu import obs, serving, spmd
from horovod_tpu.models import transformer as T
from horovod_tpu.obs import tracing as TR
from horovod_tpu.obs.registry import Histogram
from horovod_tpu.ops import attention as A
from horovod_tpu.ops import paged_attention as PA
from horovod_tpu.serving import cache as C
from horovod_tpu.serving import metrics as M
from horovod_tpu.serving.faults import FaultInjector, FaultSpec

pytestmark = pytest.mark.serving

PHASES = ("lock_wait", "reclaim", "admit", "prefill", "ingest_chunk",
          "page_prep", "tick_dispatch", "tick_device_wait", "tick_host",
          "bookkeeping", "idle")
KERNEL_NAMES = ("hvd_paged_attend", "hvd_flash_fwd", "hvd_flash_bwd_dq",
                "hvd_flash_bwd_dkv")


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=64, dtype=jnp.float32, attention_impl="reference",
                n_kv_heads=2)
    base.update(kw)
    return T.TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(model, **kw):
    params, cfg = model
    defaults = dict(n_slots=3, max_len=56, min_prefill_bucket=4,
                    page_size=4, prefill_chunk_tokens=8,
                    restart_backoff=0.01, restart_backoff_max=0.05)
    defaults.update(kw)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**defaults))


def _stat_key(name, cpu=False):
    """The ``/stats`` key of a phase's histogram on either clock."""
    return (("" if name.startswith("tick_") else "phase_") + name
            + ("_cpu_seconds" if cpu else "_seconds"))


def _host_events(trace_dir, prefix):
    """``[(name, {stat: value})]`` of the host-plane events of a
    profiler trace whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    return [(e.name, dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name.startswith(prefix)]


class _Recorder:
    """Stub tracer: keeps every ``(name, start, dur)`` handed to the tick
    row; any other tracer call is a no-op."""

    def __init__(self):
        self.spans = []

    def tick_phase(self, name, start, dur):
        self.spans.append((name, start, dur))

    def __getattr__(self, name):
        return lambda *a, **k: None


@contextlib.contextmanager
def _recording():
    rec = _Recorder()
    prev = TR.activate(rec)
    try:
        yield rec
    finally:
        TR.activate(prev)


# -- (1) the primitive ---------------------------------------------------------


class TestPhasePrimitive:
    def test_span_lands_on_the_profilers_trace_and_in_the_histogram(
            self, tmp_path):
        hist = Histogram()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with TR.phase("demo", hist, k=3, bucket=16) as ph:
                time.sleep(0.01)
        finally:
            jax.profiler.stop_trace()
        assert _host_events(str(tmp_path), "hvd:") == [
            ("hvd:demo", {"k": 3, "bucket": 16})]
        assert hist.count == 1
        assert hist.sum == ph.dur >= 0.01
        assert ph.start > 0

    def test_without_a_trace_or_tracer_it_only_times(self):
        assert TR.get() is None
        hist = Histogram()
        with TR.phase("quiet", hist):
            pass
        with TR.phase("no_histogram"):
            pass
        assert hist.count == 1

    def test_a_sleeping_body_has_wall_time_and_next_to_no_cpu(self):
        wall, cpu = Histogram(), Histogram()
        with TR.phase("asleep", wall, cpu) as ph:
            time.sleep(0.05)
        assert (wall.count, cpu.count) == (1, 1)
        assert (wall.sum, cpu.sum) == (ph.dur, ph.cpu)
        assert ph.dur >= 0.05
        assert 0 <= ph.cpu <= ph.dur
        assert ph.cpu < 0.01

    def test_a_busy_body_has_as_much_cpu_as_wall_time(self):
        wall, cpu = Histogram(), Histogram()
        c0 = time.thread_time()
        with TR.phase("busy", wall, cpu) as ph:
            end = time.thread_time() + 0.05      # 50 ms of WORK,
            while time.thread_time() < end:      # however loaded the host
                pass
        worked = time.thread_time() - c0
        assert (wall.sum, cpu.sum) == (ph.dur, ph.cpu)
        # the thread's own clock, read inside the wall clock's reads
        assert 0.05 <= ph.cpu <= worked
        assert ph.cpu <= ph.dur + 1e-4
        assert worked - ph.cpu < 0.005

    def test_exception_closes_the_span_and_propagates(self):
        hist = Histogram()
        with _recording() as rec, pytest.raises(KeyError):
            with TR.phase("boom", hist):
                raise KeyError("x")
        assert hist.count == 1
        assert [n for n, _, _ in rec.spans] == ["boom"]

    def test_tracer_row_still_gets_the_three_tick_phases(self, model,
                                                         tmp_path):
        path = str(tmp_path / "trace.json")
        TR.start(path)
        try:
            engine = _engine(model)
            fut = engine.submit([2, 3, 4], max_new_tokens=6)
            for _ in range(40):
                if fut.done():
                    break
                engine.step()
            assert fut.done()
        finally:
            TR.stop()
        events = json.load(open(path))
        row = {e["name"] for e in events if e.get("cat") == "serving.tick"}
        assert {"tick_dispatch", "tick_device_wait", "tick_host"} <= row
        assert row <= set(PHASES)
        assert {"admit", "prefill"} <= row

    def test_training_step_is_a_step_annotation(self, tmp_path):
        jax.profiler.start_trace(str(tmp_path))
        try:
            with obs.training_step():
                pass
        finally:
            jax.profiler.stop_trace()
        ((name, stats),) = _host_events(str(tmp_path), "hvd:")
        assert name == "hvd:train_step"
        assert "step_num" in stats


# -- (2) the phases partition the loop ----------------------------------------


class TestPhasesPartitionTheLoop:
    def test_sums_add_up_to_the_loop_and_nothing_nests(self):
        # ticks of tens of ms and idle sleeps of 5 ms, so that the bound
        # tests the partition and not the interpreter's overhead
        cfg = _cfg(d_model=1024, d_ff=4096, n_layers=4, vocab_size=2048)
        model = (T.init_params(jax.random.PRNGKey(1), cfg), cfg)
        engine = _engine(model, n_slots=4)
        engine.warmup((3, 20))
        engine.metrics = serving.ServingMetrics()
        with _recording() as rec:
            engine.start(idle_sleep=0.005)
            idle = engine.metrics.phases["idle"]

            def idle_steps(n):
                seen, end = idle.count, time.monotonic() + 30
                while idle.count < seen + n and time.monotonic() < end:
                    time.sleep(0.005)

            try:
                idle_steps(5)
                futs = [engine.submit(list(range(2, 5)), max_new_tokens=12),
                        engine.submit(list(range(1, 21)), max_new_tokens=6),
                        engine.submit(list(range(3, 8)), max_new_tokens=9)]
                for f in futs:
                    f.result(timeout=60)
                idle_steps(5)
            finally:
                engine.stop()
        stats = engine.stats()
        sums = {n: stats[_stat_key(n)]["sum"] for n in PHASES}
        cpus = {n: stats[_stat_key(n, cpu=True)]["sum"] for n in PHASES}
        assert all(v > 0 for v in sums.values()), sums
        loop = stats["engine_loop_seconds"]["sum"]
        loop_cpu = stats["engine_loop_cpu_seconds"]["sum"]
        assert loop > 0
        assert sum(sums.values()) <= loop
        assert sum(sums.values()) >= 0.98 * loop, (sums, loop)
        # no phase inside another: in the order they began, each ends
        # before the next begins
        spans = sorted(rec.spans, key=lambda s: s[1])
        assert {n for n, _, _ in spans} == set(PHASES)
        for (n0, s0, d0), (n1, s1, _) in zip(spans, spans[1:]):
            assert s0 + d0 <= s1 + 1e-9, (n0, n1)
        # ... and the time under NO phase, read off the spans themselves
        # (the gaps between one's end and the next one's start), is what
        # the loop's own clock has beyond the phases' sums
        gaps = sum(s1 - (s0 + d0)
                   for (_, s0, d0), (_, s1, _) in zip(spans, spans[1:]))
        assert sum(sums.values()) + gaps == pytest.approx(loop, rel=0.01)
        # the CPU clock: a phase cannot have worked longer than it
        # lasted, nor the phases together longer than the loop; in
        # `idle` the thread sleeps
        for n in PHASES:
            assert 0 <= cpus[n] <= sums[n] + 1e-3, (n, cpus[n], sums[n])
        assert 0 < sum(cpus.values()) <= loop_cpu <= loop
        uncovered_cpu = loop_cpu - sum(cpus.values())
        assert uncovered_cpu <= loop - sum(sums.values()) + 1e-3
        assert cpus["idle"] < 0.5 * sums["idle"]

    def test_prefill_span_carries_its_attributes(self, model, tmp_path):
        engine = _engine(model)
        engine.warmup((3, 20))
        jax.profiler.start_trace(str(tmp_path))
        try:
            # named: a drawn id of sixteen hex digits now and then reads
            # as a number ("262e047069604807" came back from the trace as
            # inf, once in a whole run of PR 41)
            a = engine.submit([2, 3, 4], max_new_tokens=2,
                              trace_id="request-a")
            b = engine.submit(list(range(1, 21)), max_new_tokens=2,
                              trace_id="request-b")
            for _ in range(40):
                if a.done() and b.done():
                    break
                engine.step()
        finally:
            jax.profiler.stop_trace()
        events = _host_events(str(tmp_path), "hvd:")
        (pre,) = [st for n, st in events if n == "hvd:prefill"]
        assert (pre["k"], pre["bucket"], pre["tokens"]) == (1, 4, 3)
        assert pre["trace_ids"] == a.trace_id
        chunks = [st for n, st in events if n == "hvd:ingest_chunk"]
        assert [(c["lo"], c["hi"]) for c in chunks] == [
            (0, 8), (8, 16), (16, 20)]
        assert {c["trace_id"] for c in chunks} == {b.trace_id}
        assert len({c["slot"] for c in chunks}) == 1


# -- (2b) the waits, the collector and a slow step -----------------------------


@contextlib.contextmanager
def _running(engine, idle_sleep=0.002):
    engine.start(idle_sleep=idle_sleep)
    try:
        yield engine
    finally:
        engine.stop()


def _wait_for(pred, timeout=30.0):
    end = time.monotonic() + timeout
    while not pred() and time.monotonic() < end:
        time.sleep(0.002)
    assert pred()


class TestWaitsCollectionsAndSlowSteps:
    def test_stats_has_the_new_keys_and_not_the_two_removed(self, model):
        engine = _engine(model, speculative=True, spec_k=3)
        stats = engine.stats()
        for name in PHASES:
            assert stats[_stat_key(name)]["count"] == 0
            assert stats[_stat_key(name, cpu=True)]["count"] == 0
        for key in ("engine_loop_cpu_seconds", "gc_pause_seconds",
                    "gc_pause_seconds_gen2"):
            assert stats[key]["count"] == 0
        assert stats["slow_steps"] == []
        assert stats["slow_steps_total"] == 0
        assert stats["slow_step_seconds_total"] == 0
        assert stats["spec_k"] == 3 and stats["spec_draft"] == "ngram"
        assert "spec_slots_live" not in stats
        assert "draft_pages_free" not in stats
        text = engine.metrics.registry.to_prometheus()
        assert 'serving_phase_cpu_seconds_count{phase="lock_wait"}' in text
        assert 'serving_engine_phase_seconds_count{phase="lock_wait"}' in text
        assert 'serving_gc_pause_seconds_count{generation="2"}' in text
        assert "serving_engine_loop_cpu_seconds_count" in text
        assert "serving_slow_steps_total" in text

    def test_a_held_step_lock_is_lock_wait_and_costs_no_cpu(self, model):
        engine = _engine(model)
        with _running(engine):
            idle = engine.metrics.phases["idle"]
            _wait_for(lambda: idle.count >= 3)
            before = engine.stats()
            with engine._lock:
                time.sleep(0.05)
            seen = idle.count
            _wait_for(lambda: idle.count >= seen + 2)
        after = engine.stats()
        wall = (after["phase_lock_wait_seconds"]["sum"]
                - before["phase_lock_wait_seconds"]["sum"])
        cpu = (after["phase_lock_wait_cpu_seconds"]["sum"]
               - before["phase_lock_wait_cpu_seconds"]["sum"])
        assert wall >= 0.04
        assert cpu < 0.005
        assert after["slow_steps_total"] == 0

    def test_a_full_collection_is_counted_and_is_one_span(self, model,
                                                          tmp_path):
        engine = _engine(model)
        gc.collect()
        gc.disable()        # no collection but the one forced below
        try:
            with _running(engine):
                jax.profiler.start_trace(str(tmp_path))
                try:
                    gc.collect()
                finally:
                    jax.profiler.stop_trace()
        finally:
            gc.enable()
        stats = engine.stats()
        assert stats["gc_pause_seconds_gen2"]["count"] == 1
        assert stats["gc_pause_seconds"]["count"] == 1
        assert (stats["gc_pause_seconds"]["sum"]
                == stats["gc_pause_seconds_gen2"]["sum"] > 0)
        assert [n for n, _ in _host_events(str(tmp_path), "hvd:gc")] == [
            "hvd:gc"]

    def test_one_hook_however_many_engines_and_stop_removes_it(self, model):
        before = list(gc.callbacks)
        a, b = _engine(model), _engine(model)
        a.start()
        b.start()
        try:
            assert len(gc.callbacks) == len(before) + 1
            gc.collect()
            a.stop()
            assert len(gc.callbacks) == len(before) + 1
            gc.collect()
        finally:
            a.stop()
            b.stop()
        assert gc.callbacks == before
        assert a.stats()["gc_pause_seconds_gen2"]["count"] == 1
        assert b.stats()["gc_pause_seconds_gen2"]["count"] == 2
        gc.collect()        # nobody listens any more
        assert b.stats()["gc_pause_seconds_gen2"]["count"] == 2

    def test_a_collection_under_a_histograms_lock_does_not_deadlock(
            self, model):
        # A collection starts on whichever thread allocates, so also on
        # one that reads /stats or /metrics and holds a histogram's
        # lock: the collector's sink takes none.  With a threshold of 1
        # every read below starts collections of every generation.
        # (CPython puts the OLDEST generation off while few objects are
        # new beside the process's long-lived ones — millions in a test
        # worker that has imported TensorFlow: they are set aside for
        # the length of this test, so "every generation" holds whatever
        # ran in the worker before.)
        gc.freeze()
        gc.collect()
        engine = _engine(model)
        engine.warmup((3,))
        done = threading.Event()

        def poll():
            for _ in range(150):
                engine.stats()
                engine.metrics.registry.to_prometheus()
            done.set()

        threshold = gc.get_threshold()
        gc.set_threshold(1, 1, 1)
        try:
            with _running(engine):
                poller = threading.Thread(target=poll, daemon=True)
                poller.start()
                engine.submit([2, 3, 4], max_new_tokens=4).result(timeout=60)
                assert done.wait(30), "a /stats reader hangs in the collector"
        finally:
            gc.set_threshold(*threshold)
            gc.unfreeze()
        stats = engine.stats()
        assert stats["gc_pause_seconds"]["count"] > 150
        assert stats["gc_pause_seconds_gen2"]["count"] > 0
        assert not any(engine.metrics.gc_pending)
        engine.metrics.gc_pending[2].append(0.5)
        engine.refresh_windowed_gauges()    # what a /metrics scrape calls
        assert not any(engine.metrics.gc_pending)

    def test_the_collectors_sink_takes_no_lock(self, model):
        engine = _engine(model)
        hist = engine.metrics.gc_pause[2]
        with hist._lock:        # where a collection may find its thread
            for pause in ((2, 0.25), (0, 0.001)):
                sink = threading.Thread(target=engine._on_gc, args=pause,
                                        daemon=True)
                sink.start()
                sink.join(10)
                assert not sink.is_alive()
        assert hist.count == 0
        stats = engine.stats()
        assert stats["gc_pause_seconds"]["count"] == 2
        assert stats["gc_pause_seconds_gen2"]["sum"] == 0.25
        engine.metrics.fold_gc()        # folded once
        assert engine.stats()["gc_pause_seconds"]["count"] == 2

    def test_a_step_that_slept_leaves_one_record_naming_the_phase(
            self, model, caplog):
        faults = FaultInjector()
        engine = _engine(model, faults=faults)
        engine.warmup((3,))
        engine.metrics = serving.ServingMetrics()
        with _running(engine), caplog.at_level(logging.WARNING):
            engine.submit([2, 3, 4], max_new_tokens=4).result(timeout=60)
            assert engine.stats()["slow_steps_total"] == 0    # a clean run
            faults.add(FaultSpec(site="prefill", kind="hang", delay=0.3,
                                 skip=faults.visits("prefill")))
            engine.submit([2, 3, 4], max_new_tokens=4).result(timeout=60)
        stats = engine.stats()
        assert stats["slow_steps_total"] == 1
        (rec,) = stats["slow_steps"]
        assert stats["slow_step_seconds_total"] == rec["wall_s"] >= 0.3
        assert rec["wall_s"] > M.SLOW_STEP_SECONDS
        wall, cpu = rec["phases"]["prefill"]
        assert wall >= 0.3 and cpu < 0.1
        assert max(rec["phases"], key=lambda n: rec["phases"][n][0]) \
            == "prefill"
        assert set(rec["phases"]) <= set(PHASES)
        assert sum(w for w, _ in rec["phases"].values()) <= rec["wall_s"]
        assert rec["cpu_s"] < 0.1 and rec["at_s"] >= 0
        assert (rec["kind"], rec["compiles"], rec["active_slots"]) == (
            "prefill", 0, 1)
        assert rec["gc_s"] >= 0
        (line,) = [r.getMessage() for r in caplog.records
                   if "slow engine step" in r.getMessage()]
        assert "'prefill'" in line
        json.dumps(stats["slow_steps"])     # /stats serves it as it is

    def test_the_ring_keeps_the_newest_sixteen(self):
        metrics = serving.ServingMetrics()
        for i in range(M.SLOW_STEP_RING + 4):
            metrics.slow_step({"at_s": float(i), "wall_s": 0.5})
        snap = metrics.snapshot()
        assert M.SLOW_STEP_RING == 16
        assert [r["at_s"] for r in snap["slow_steps"]] == [
            float(i) for i in range(4, 20)]
        assert snap["slow_steps_total"] == 20
        assert snap["slow_step_seconds_total"] == 10.0


# -- (3) tick kinds, (4) padding and walk counters ----------------------------


class TestCountersWhereTheWorkHappens:
    def test_tick_kinds_classify_a_scripted_sequence(self, model):
        engine = _engine(model, overlap=False)
        kinds = ("plain", "prefill", "chunk")

        def step_kind():
            before = engine.stats()
            engine.step()
            after = engine.stats()
            grown = [k for k in kinds if after[f"decode_ticks_{k}"]
                     > before[f"decode_ticks_{k}"]]
            assert len(grown) <= 1
            assert (after["decode_ticks"] - before["decode_ticks"]
                    == len(grown))
            return grown[0] if grown else None

        assert step_kind() is None                      # idle: no tick
        a = engine.submit([2, 3, 4], max_new_tokens=40)
        assert step_kind() == "prefill"                 # admission + tick
        assert step_kind() == "plain"
        b = engine.submit(list(range(1, 21)), max_new_tokens=4)
        assert [step_kind() for _ in range(3)] == ["chunk"] * 3
        assert step_kind() == "plain"
        c = engine.submit(list(range(1, 21)), max_new_tokens=4)
        d = engine.submit([5, 6], max_new_tokens=4)
        # the long prompt is taken alone (its own group), the short one
        # a step later beside the second chunk: both -> "prefill"
        assert [step_kind() for _ in range(4)] == [
            "chunk", "prefill", "chunk", "plain"]
        stats = engine.stats()
        assert (sum(stats[f"decode_ticks_{k}"] for k in kinds)
                == stats["decode_ticks"])
        for k in kinds:
            assert (stats[f"engine_step_seconds_{k}"]["count"]
                    == stats[f"decode_ticks_{k}"])
            assert stats[f"engine_step_seconds_{k}"]["sum"] > 0
        for f in (a, b, c, d):
            f.cancel()

    def test_attach_only_admission_is_a_plain_step(self, model):
        """A prompt that IS a registered prefix is admitted by attaching
        its pages: no prefill executable runs, so the step that admits
        it counts as plain (the flag is set where the work happens)."""
        engine = _engine(model, overlap=False)
        prefix = [2, 3, 4, 5, 6, 7, 8, 9]
        engine.register_prefix(prefix)
        before = engine.stats()
        fut = engine.submit(prefix, max_new_tokens=6)
        engine.step()
        after = engine.stats()
        assert after["prefill_calls"] == before["prefill_calls"]
        assert after["decode_ticks"] == before["decode_ticks"] + 1
        assert after["decode_ticks_plain"] == before["decode_ticks_plain"] + 1
        assert after["decode_ticks_prefill"] == before["decode_ticks_prefill"]
        fut.cancel()

    def test_prefill_tokens_real_and_padded(self, model):
        engine = _engine(model)
        futs = [engine.submit([2, 3, 4, 5, 6], max_new_tokens=2),
                engine.submit(list(range(1, 21)), max_new_tokens=2)]
        for _ in range(40):
            if all(f.done() for f in futs):
                break
            engine.step()
        stats = engine.stats()
        # 5 tokens in a bucket of 8; 20 tokens in three chunks of 8
        assert stats["prefill_tokens_total"] == 5 + 20
        assert stats["prefill_padded_tokens_total"] == 8 + 3 * 8
        assert stats["prefill_calls"] == 4

    def test_paged_live_and_walked_tokens(self, model, monkeypatch):
        _, cfg = model
        ps, pages = 4, 2        # a walk of 2 pages = 8 tokens a step
        monkeypatch.setattr(
            PA, "_BLOCK_BYTES", pages * cfg.kv_heads * ps * cfg.head_dim * 4)
        engine = _engine(model, n_slots=3, prefill_chunk_tokens=0)
        assert engine.slots.page_size == ps
        assert engine.slots.max_pages == 56 // ps
        la, lb = 5, 7           # one bucket: admitted by one prefill
        futs = [engine.submit(list(range(1, 1 + la)), max_new_tokens=30),
                engine.submit(list(range(1, 1 + lb)), max_new_tokens=30)]
        n = 6
        for _ in range(n):
            engine.step()
        stats = engine.stats()
        assert stats["decode_ticks"] == n
        assert not any(f.done() for f in futs)
        # tick i (from 0) attends positions <= len(prompt) + i per slot
        assert stats["paged_live_tokens_total"] == sum(
            (la + i + 1) + (lb + i + 1) for i in range(n)) == 114
        # ... and walks each slot's limit rounded up to 8: limits 6..11
        # and 8..13; the third slot is idle and walks nothing
        assert stats["paged_walked_tokens_total"] == (
            3 * 8 + 3 * 16) + (8 + 5 * 16)
        for f in futs:
            f.cancel()

    def test_walk_is_the_kernels_bound(self, monkeypatch):
        """One Pallas call, found the way the benchmark finds it (its
        name; the ``(S, max_pages)`` int32 table its first operand),
        one grid step a slot, and a trip count from ``PA.walk`` — the
        function the engine's counter calls."""
        S, Hkv, R, Dh, ps, max_pages, n_pages = 2, 2, 2, 8, 4, 6, 13
        monkeypatch.setattr(PA, "_BLOCK_BYTES", 2 * Hkv * ps * Dh * 4)
        assert PA.block_pages(ps, Hkv, Dh, jnp.float32, max_pages) == 2
        asked = []
        walk = PA.walk
        monkeypatch.setattr(
            PA, "walk", lambda limit, bt: asked.append(bt) or walk(limit, bt))
        pool = jnp.zeros((n_pages, Hkv, ps, Dh))
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, t, lim: PA.paged_attend(q, k, v, None, None,
                                                    t, lim))(
            jnp.zeros((S, Hkv, R, Dh)), pool, pool,
            jnp.zeros((S, max_pages), jnp.int32), jnp.zeros((S,), jnp.int32))
        (call,) = _pallas_calls(jaxpr.jaxpr)
        assert call.params["name"] == PA.KERNEL_NAME == "hvd_paged_attend"
        assert call.params["grid_mapping"].grid == (S,)
        table = call.invars[0].aval
        assert (table.shape, table.dtype) == ((S, max_pages), jnp.int32)
        assert asked == [2 * ps]
        # without the cap of the budget a step takes the whole table
        monkeypatch.undo()
        assert PA.block_pages(ps, Hkv, Dh, jnp.float32, max_pages) == max_pages
        blocks, tokens = PA.walk(np.array([0, 1, 8, 9, 24]), 8)
        assert blocks.tolist() == [0, 1, 1, 2, 3]
        assert tokens.tolist() == [0, 8, 8, 16, 24]


def _pallas_calls(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                out.extend(_pallas_calls(inner))
    return out


# -- (5) scopes and kernel names in the lowered text --------------------------


def _scopes_in(lowered) -> set:
    """The components of every operation's ``op_name`` in a lowering."""
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]+)"', text)
    return {part for n in names for part in re.split(r"[/()]", n) if part}


def _decode_tick_lowering(model, kernel=True):
    params, cfg = model
    S, ps, max_pages, n_pages = 2, 4, 3, 7
    pool = C.init_page_pool(cfg, S, n_pages, ps)
    table = jnp.zeros((S, max_pages), jnp.int32)
    active = jnp.ones((S,), bool)
    samp = (jnp.zeros((S,)), jnp.zeros((S,), jnp.int32), jnp.zeros((S,)),
            jnp.zeros((S, 2), jnp.uint32))

    def tick(params, tokens, pool):
        pos = pool["pos"]
        logits, pool = T.decode_step_paged(params, tokens, pool, table, cfg,
                                           active, kernel=kernel)
        return serving.InferenceEngine._pick(logits, pos, active, *samp), pool

    return jax.jit(tick).lower(params, jnp.zeros((S,), jnp.int32), pool)


def _chunk_lowering(model):
    params, cfg = model
    ps, n_pages = 4, 7
    pool = C.init_page_pool(cfg, 2, n_pages, ps)

    def chunk(params, pool, suffix, pages, land, first):
        logits, suf = T.prefill_with_prefix(
            params, suffix, C.gather_prefix_pages(pool, pages), jnp.int32(6),
            cfg, true_len=jnp.asarray([8]))
        return logits, C.paged_insert(
            pool, jnp.asarray([0]), suf["pos"], land, first,
            jnp.asarray([8]), suf)

    return jax.jit(chunk).lower(
        params, pool, jnp.zeros((1, 8), jnp.int32),
        jnp.zeros((2,), jnp.int32),
        jnp.zeros((1, C.landing_pages(8, ps)), jnp.int32), jnp.int32(2))


def _train_step_lowering(hvd, cfg, params, batch):
    opt = hvd.DistributedOptimizer(optax.sgd(0.1))
    state = opt.init(params)

    def step(params, state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg))(params)
        updates, state = opt.update(grads, state, params)
        return (optax.apply_updates(params, updates), state,
                jax.lax.pmean(loss, hvd.AXIS))

    fn = jax.jit(spmd.shard(step, in_specs=(P(), P(), P(hvd.AXIS)),
                            out_specs=(P(), P(), P())))
    return fn.lower(params, state, batch)


class TestDeviceScopes:
    def test_every_scope_and_kernel_name_is_in_a_lowering(self, model, hvd):
        params, cfg = model
        seen = _scopes_in(_decode_tick_lowering(model))
        assert {"embed", "attn_qkv", "kv_write", "paged_attend",
                "hvd_paged_attend", "attn_out", "mlp", "head",
                "sample"} <= seen
        chunk = _scopes_in(_chunk_lowering(model))
        assert {"embed", "attn_qkv", "landed_gather", "chunk_attn",
                "attn_out", "mlp", "head", "kv_land"} <= chunk
        fcfg = _cfg(attention_impl="flash", max_seq=128)
        fparams = T.init_params(jax.random.PRNGKey(2), fcfg)
        whole = _scopes_in(jax.jit(
            lambda p, x: T.prefill(p, x, T.init_cache(fcfg, 1, 128), fcfg)
        ).lower(fparams, jnp.zeros((1, 128), jnp.int32)))
        assert {"attn", "hvd_flash_fwd", "kv_land"} <= whole
        n = hvd.size()
        batch = {"tokens": jnp.zeros((n, 128), jnp.int32),
                 "targets": jnp.zeros((n, 128), jnp.int32)}
        train = _scopes_in(_train_step_lowering(hvd, fcfg, fparams, batch))
        assert {"embed", "attn_qkv", "attn", "attn_out", "mlp", "head",
                "loss", "grad_allreduce", "opt_update", "hvd_flash_fwd",
                "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"} <= train
        assert "transpose" in train and "jvp" in train   # the backward
        everything = seen | chunk | whole | train
        assert set(T.DEVICE_SCOPES) <= everything
        assert set(KERNEL_NAMES) <= everything

    def test_every_pallas_call_in_ops_is_named(self):
        import inspect

        for mod in (A, PA):
            src = inspect.getsource(mod)
            calls = len(re.findall(r"= pl\.pallas_call\(", src))
            assert calls and len(re.findall(r'\bname=("hvd_\w+"|\w*KERNEL_NAME)',
                                            src)) == calls, mod.__name__


# -- (6) scopes are metadata only ---------------------------------------------


def _instruction_count(lowered) -> int:
    text = lowered.compile().as_text()
    return sum(1 for ln in text.splitlines() if re.match(r"\s+(ROOT )?%?\S+ = ",
                                                         ln))


def _scoped_probe(x):
    with jax.named_scope("mlp"):
        return jnp.tanh(x @ x)


class TestScopesAreMetadataOnly:
    def test_optimised_hlo_has_the_same_instructions_without_them(
            self, model, hvd, monkeypatch):
        params, cfg = model
        n = hvd.size()
        batch = {"tokens": jnp.zeros((n, 16), jnp.int32),
                 "targets": jnp.zeros((n, 16), jnp.int32)}

        def counts():
            return (_instruction_count(_decode_tick_lowering(model, False)),
                    _instruction_count(_chunk_lowering(model)),
                    _instruction_count(
                        _train_step_lowering(hvd, cfg, params, batch)))

        with_scopes = counts()
        scoped = _scopes_in(_decode_tick_lowering(model, False))
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        assert not scoped & _scopes_in(_decode_tick_lowering(model, False)) \
            & set(T.DEVICE_SCOPES)
        assert counts() == with_scopes
        assert min(with_scopes) > 20

    def test_cached_executables_are_keyed_by_their_metadata(self, model):
        """Because scopes change no instruction, JAX's persistent cache
        (which by default leaves metadata out of its key) would hand a
        scoped program the executable of an unscoped one, and a trace
        would show the old names (seen on the chip, PERF.md PR 24):
        ``place_compile_cache`` (conftest calls it, as every entry point
        does) puts the metadata in the key — the scopes and the one line
        that emits each operation, relative to the checkout, so that
        neither the checkout's place nor an edit to a caller moves a
        key."""
        import horovod_tpu as hvd_pkg
        from horovod_tpu import compile_cache

        hvd_pkg.place_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        text = _decode_tick_lowering(model, False).as_text(debug_info=True)
        assert '"horovod_tpu/models/transformer.py"' in text
        assert compile_cache.CHECKOUT not in text
        # no frame of a caller (this test lowers the tick)
        assert "test_phases.py" not in text
        # ... and what the COMPILER keeps as op_name still holds the
        # scope path (with full tracebacks off it is the primitive alone)
        hlo = jax.jit(_scoped_probe).lower(jnp.ones((4, 4))).compile()
        assert 'op_name="jit(_scoped_probe)/mlp/' in hlo.as_text()

    def test_engine_compile_counts_unchanged_by_phases(self, model):
        engine = _engine(model)
        engine.warmup((3, 20))
        base = engine.stats()
        futs = [engine.submit([2, 3, 4], max_new_tokens=5),
                engine.submit(list(range(1, 21)), max_new_tokens=5)]
        with _recording():
            for _ in range(60):
                if all(f.done() for f in futs):
                    break
                engine.step()
        after = engine.stats()
        assert all(f.done() for f in futs)
        assert after["decode_compilations"] == base["decode_compilations"] == 1
        assert after["prefill_compilations"] == base["prefill_compilations"]
