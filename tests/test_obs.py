"""Unified observability layer (horovod_tpu/obs/): metrics registry +
Prometheus exposition, request tracing, timeline dropped-event
accounting, and the /metrics endpoint.

The registry is the ONE place instruments live (duplicate registration
raises — the CI self-check); the tracer threads a Dapper-style trace
id submit -> prefill -> decode -> retirement and renders request spans,
tick-phase spans, and lifecycle instants through the existing timeline
writer so one Perfetto file carries training and serving on one time
axis.  The perf-marked tests bound the span work of a steady decode
tick (eight ``tracing.phase`` entries on two clocks: <= 55us with no
tracer, <= 90us with one — 0.5 % and 0.8 % of the 11 ms tick of the
fastest cell on the chip, 2.4 and 3 times what this host measures).  They
take the FASTEST of many short repetitions: the work is deterministic, and
a repetition of a few ms that no other test worker interrupted reads the
same beside five workers as alone."""

import json
import queue
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu import timeline as TL
from horovod_tpu.models import transformer as T
from horovod_tpu.obs import registry as R
from horovod_tpu.obs import tracing as TR
from horovod_tpu.obs import training_step

from conftest import http_post_json as _post  # noqa: E402
from conftest import parse_prometheus_text  # noqa: E402

pytestmark = pytest.mark.serving


def _cfg():
    return T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _engine(model, **kw):
    params, cfg = model
    defaults = dict(n_slots=2, max_len=40, min_prefill_bucket=4,
                    restart_backoff=0.01, restart_backoff_max=0.05)
    defaults.update(kw)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**defaults))


#: The phases one steady-state step of the paged, overlapped engine
#: passes through, in order (docs/observability.md "Engine phases").
STEADY_TICK_PHASES = ("lock_wait", "reclaim", "admit", "page_prep",
                      "tick_dispatch", "tick_device_wait", "tick_host",
                      "bookkeeping")


def _run_until_done(engine, futs, max_ticks=300):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


@pytest.fixture()
def tracer(tmp_path):
    """A started tracer writing to tmp files, torn down afterwards so
    the module-global never leaks into other tests."""
    path = str(tmp_path / "trace.json")
    t = TR.start(path, jsonl_path=path + ".jsonl")
    yield t, path
    if TR.get() is None and t is not None:
        TR.activate(t)  # stop() needs it active
    TR.stop()


class TestRegistry:
    def test_duplicate_registration_raises(self):
        """CI self-check: a name registers once; a second registration
        — same kind or different — raises typed, it never silently
        shares or shadows."""
        r = R.MetricsRegistry()
        r.counter("x_total")
        with pytest.raises(R.DuplicateMetricError):
            r.counter("x_total")
        with pytest.raises(R.DuplicateMetricError):
            r.gauge("x_total")
        with pytest.raises(R.DuplicateMetricError):
            r.histogram("x_total")
        # exist_ok is the explicit create-or-fetch — and still
        # type-checks
        assert r.counter("x_total", exist_ok=True) is r.get("x_total")
        with pytest.raises(R.DuplicateMetricError):
            r.gauge("x_total", exist_ok=True)
        with pytest.raises(R.DuplicateMetricError):
            r.counter("x_total", labels=("a",), exist_ok=True)

    def test_name_and_label_validation(self):
        r = R.MetricsRegistry()
        with pytest.raises(ValueError):
            r.counter("bad name")
        with pytest.raises(ValueError):
            r.counter("1leading_digit")
        with pytest.raises(ValueError):
            r.counter("ok_total", labels=("bad-label",))

    def test_counter_monotonic(self):
        c = R.Counter()
        c.inc()
        c.inc(5)
        assert c.value == 6
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labeled_family_children_independent(self):
        r = R.MetricsRegistry()
        fam = r.counter("hits_total", labels=("site",))
        fam.labels(site="a").inc(2)
        fam.labels(site="b").inc()
        assert fam.labels(site="a").value == 2
        assert fam.labels(site="b").value == 1
        with pytest.raises(ValueError):
            fam.labels(wrong="x")
        snap = r.snapshot()
        assert snap["hits_total"] == {'site="a"': 2, 'site="b"': 1}

    def test_prometheus_exposition_parses(self):
        r = R.MetricsRegistry()
        r.counter("req_total", "requests").inc(3)
        r.gauge("depth", "queue depth").set(2.5)
        h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 9.0):
            h.observe(v)
        fam = r.counter("by_site_total", "per site", labels=("site",))
        fam.labels(site='we"ird\\').inc()
        text = r.to_prometheus()
        fams = parse_prometheus_text(text)
        assert fams["req_total"]["type"] == "counter"
        assert fams["req_total"]["samples"] == [("req_total", {}, 3.0)]
        assert fams["depth"]["samples"] == [("depth", {}, 2.5)]
        # histogram: cumulative buckets + sum/count validated by the
        # parser; spot-check the numbers
        hs = {(n, l.get("le")): v
              for n, l, v in fams["lat_seconds"]["samples"]}
        assert hs[("lat_seconds_bucket", "0.1")] == 1
        assert hs[("lat_seconds_bucket", "1")] == 2
        assert hs[("lat_seconds_bucket", "+Inf")] == 3
        assert hs[("lat_seconds_count", None)] == 3
        assert abs(hs[("lat_seconds_sum", None)] - 9.55) < 1e-9
        # escaped label values survive the round trip
        (_, labels, v), = fams["by_site_total"]["samples"]
        assert v == 1.0 and "site" in labels

    def test_histogram_api_unchanged(self):
        """The serving suite's Histogram contract (percentiles,
        snapshot dict) is served by the registry implementation."""
        h = serving.Histogram(buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 20.0):
            h.observe(v)
        assert h.snapshot()["buckets"] == {"0.1": 2, "1": 1, "10": 0,
                                           "+Inf": 1}
        assert h.percentile(0.5) == 0.1

    def test_serving_metrics_is_registry_view(self):
        """ServingMetrics keeps its attribute + snapshot API but every
        instrument is registered under a serving_* family in a PRIVATE
        registry — two engines never collide."""
        m1, m2 = serving.ServingMetrics(), serving.ServingMetrics()
        m1.admitted.inc(3)
        assert m2.admitted.value == 0
        snap = m1.snapshot()
        assert snap["requests_admitted"] == 3  # /stats keys unchanged
        fams = parse_prometheus_text(m1.registry.to_prometheus())
        assert fams["serving_requests_admitted_total"]["samples"][0][2] == 3
        assert "serving_ttft_seconds" in fams
        assert fams["serving_ttft_seconds"]["type"] == "histogram"

    def test_default_registry_families_seeded_at_init(self, hvd):
        """basics.init() registers the process gauges and the training
        + elastic families, so a /metrics scrape on a cold process
        already exposes them."""
        fams = parse_prometheus_text(R.default_registry().to_prometheus())
        for name in ("horovod_world_size", "horovod_inits_total",
                     "training_step_seconds", "training_steps_total",
                     "elastic_restarts_total", "elastic_commits_total",
                     "timeline_dropped_events_total"):
            assert name in fams, name
        assert fams["horovod_world_size"]["samples"][0][2] == hvd.size()

    def test_training_step_context(self, hvd):
        m = R.training_metrics()
        steps0, count0 = m.steps.value, m.step_time.count
        with training_step():
            time.sleep(0.002)
        assert m.steps.value == steps0 + 1
        assert m.step_time.count == count0 + 1


class TestTimelineDroppedEvents:
    def test_drops_counted_and_flushed_on_close(self, tmp_path):
        """The _emit queue.Full path is no longer silent: drops are
        counted (instance + registry) and the count is flushed as a
        trailing event on close(), so a sparse trace discloses its own
        gaps."""
        reg_counter = R.default_registry().get(
            "timeline_dropped_events_total")
        reg0 = reg_counter.value
        path = str(tmp_path / "tl.json")
        tl = TL.Timeline(path, queue_size=4)
        # Deterministic full-queue: make put_nowait refuse, as it would
        # under a wedged/slow writer, without racing the real thread.
        orig = tl._q.put_nowait
        tl._q.put_nowait = lambda ev: (_ for _ in ()).throw(queue.Full())
        for _ in range(5):
            tl.instant("lost")
        assert tl.dropped_events == 5
        assert reg_counter.value == reg0 + 5
        tl._q.put_nowait = orig
        tl.instant("kept")
        tl.close()
        events = json.load(open(path))
        assert [e["name"] for e in events].count("lost") == 0
        assert any(e["name"] == "kept" for e in events)
        trailing = events[-1]
        assert trailing["name"] == "TIMELINE_DROPPED_EVENTS"
        assert trailing["args"]["dropped_events"] == 5

    def test_no_trailer_without_drops(self, tmp_path):
        path = str(tmp_path / "tl2.json")
        tl = TL.Timeline(path)
        tl.instant("only")
        tl.close()
        events = json.load(open(path))
        assert [e["name"] for e in events] == ["only"]


class TestTracing:
    def test_mint_and_validate(self):
        a, b = TR.mint_trace_id(), TR.mint_trace_id()
        assert a != b and TR.valid_trace_id(a)
        assert TR.valid_trace_id("req-1.retry_2")
        assert not TR.valid_trace_id("")
        assert not TR.valid_trace_id(None)
        assert not TR.valid_trace_id("x" * 65)
        assert not TR.valid_trace_id('bad"quote')
        assert not TR.valid_trace_id("sp ace")

    def test_breakdown_math(self):
        tr = TR.RequestTrace("tid1")
        tr.submitted_at = 100.0
        tr.admitted_at = 100.5
        tr.first_token_at = 101.0
        tr.finished_at = 103.0
        tr.decode_ticks = 7
        tr.tokens = 8
        tr.host_sync_lag = 0.002
        tr.finish = "length"
        b = tr.breakdown()
        assert b == {
            "trace_id": "tid1", "span_id": tr.span_id,
            "queue_wait_s": 0.5, "prefill_s": 0.5,
            "decode_s": 2.0, "decode_ticks": 7, "tokens": 8,
            "host_sync_lag_s": 0.002, "total_s": 3.0, "finish": "length",
        }
        # unfinished / never-admitted requests measure what they can
        tr2 = TR.RequestTrace("tid2")
        tr2.submitted_at = 100.0
        b2 = tr2.breakdown(now=101.0)
        assert b2["queue_wait_s"] == 1.0 and b2["total_s"] == 1.0
        assert b2["prefill_s"] is None and b2["finish"] is None

    def test_engine_trace_propagation_and_spans(self, model, tracer):
        """A traced request: caller-supplied id survives to the future,
        the breakdown is coherent, and the trace file carries the
        request span (with nested phases), tick-phase spans, and an
        xla_compile instant — all through the ONE timeline writer."""
        t, path = tracer
        engine = _engine(model)
        fut = engine.submit([3, 4, 5], max_new_tokens=5,
                            trace_id="golden-req-1")
        _run_until_done(engine, [fut])
        toks = fut.result(timeout=0)
        assert fut.trace_id == "golden-req-1"
        b = fut.breakdown()
        assert b["finish"] == "length" and b["tokens"] == len(toks) == 5
        assert b["queue_wait_s"] >= 0 and b["prefill_s"] >= 0
        assert b["decode_s"] >= 0 and b["decode_ticks"] == 4
        assert b["host_sync_lag_s"] > 0
        assert abs(b["total_s"]
                   - (b["queue_wait_s"] + b["prefill_s"] + b["decode_s"])
                   ) < 1e-3
        TR.stop()
        TR.activate(t)  # fixture stops again; keep its handle valid
        events = json.load(open(path))
        names = [e["name"] for e in events]
        assert "request golden-req-1" in names
        for n in ("queue", "prefill", "decode", "tick_dispatch",
                  "tick_device_wait", "tick_host", "xla_compile"):
            assert n in names, n
        span = next(e for e in events
                    if e["name"] == "request golden-req-1")
        assert span["ph"] == "X"
        assert span["args"]["trace_id"] == "golden-req-1"
        # JSONL structured log carries the same breakdown
        lines = [json.loads(l) for l in
                 open(path + ".jsonl").read().splitlines()]
        rec = next(l for l in lines if l["trace_id"] == "golden-req-1")
        assert rec["event"] == "request" and rec["tokens"] == 5

    def test_minted_id_when_absent(self, model):
        engine = _engine(model)
        fut = engine.submit([1, 2], max_new_tokens=2)
        _run_until_done(engine, [fut])
        assert TR.valid_trace_id(fut.trace_id)

    def test_start_requires_path_or_timeline(self):
        with pytest.raises(ValueError, match="trace path"):
            TR.start()

    def test_double_start_raises(self, tracer):
        with pytest.raises(ValueError, match="already started"):
            TR.start("/tmp/never.json")


class TestServerObservability:
    @pytest.fixture()
    def served(self, model):
        engine = _engine(model)
        with serving.ServingServer(engine, port=0) as srv:
            host, port = srv.address
            yield engine, f"http://{host}:{port}"

    def test_metrics_endpoint_prometheus_golden(self, served, hvd):
        """GOLDEN: /metrics parses as valid Prometheus text exposition
        and covers the serving, training, AND elastic families in one
        scrape."""
        engine, base = served
        code, _ = _post(base + "/generate",
                        {"tokens": [3, 4], "max_new_tokens": 3})
        assert code == 200
        req = urllib.request.Request(base + "/metrics")
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.status == 200
            ctype = r.headers["Content-Type"]
            text = r.read().decode()
        assert ctype.startswith("text/plain")
        assert "version=0.0.4" in ctype
        fams = parse_prometheus_text(text)
        # serving family reflects the request we just made
        assert fams["serving_requests_admitted_total"]["samples"][0][2] >= 1
        assert fams["serving_ttft_seconds"]["type"] == "histogram"
        # training + elastic + process families ride the same scrape
        for name in ("training_step_seconds", "training_steps_total",
                     "elastic_restarts_total", "elastic_rendezvous_total",
                     "horovod_world_size", "xla_compiles_total"):
            assert name in fams, name

    def test_healthz_heartbeat_age_and_restarts(self, served):
        """Liveness probes read heartbeat age + restart count straight
        off /healthz — no /stats parsing."""
        engine, base = served
        code, _ = _post(base + "/generate",
                        {"tokens": [5, 6], "max_new_tokens": 2})
        assert code == 200
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            h = json.loads(r.read())
        assert h["status"] == "healthy"
        assert isinstance(h["heartbeat_age_s"], float)
        assert 0 <= h["heartbeat_age_s"] < 60
        assert h["engine_restarts"] == 0

    def test_trace_header_roundtrip(self, served):
        """X-Trace-Id in -> same id in the response body, response
        header, and per-request breakdown; absent/invalid headers get
        a minted id."""
        engine, base = served
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": [3, 4, 5],
                             "max_new_tokens": 4}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "edge-abc.1"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
            hdr = r.headers["X-Trace-Id"]
        assert out["trace_id"] == hdr == "edge-abc.1"
        assert out["breakdown"]["trace_id"] == "edge-abc.1"
        assert out["breakdown"]["finish"] == "length"
        assert out["breakdown"]["tokens"] == 4
        # invalid header -> minted, never echoed
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": [1], "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "bad header!{}"})
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert out["trace_id"] != "bad header!{}"
        assert TR.valid_trace_id(out["trace_id"])

    def test_submit_rejection_carries_trace_id(self, served):
        engine, base = served
        code, out = _post(base + "/generate",
                          {"tokens": list(range(60)),
                           "max_new_tokens": 8})
        assert (code, out["type"]) == (413, "too_long")
        assert TR.valid_trace_id(out["trace_id"])


@pytest.mark.perf
class TestTracingOverhead:
    def test_enabled_per_tick_work_bounded(self, tmp_path):
        """PERF GUARD (enabled): the span work one steady-state decode
        tick performs — one ``phase`` per engine phase of a steady
        tick (an inactive TraceAnnotation, two reads of each of the two
        clocks, two histogram observations, a buffered tick_phase
        record) plus the amortized batch flush through the live writer
        thread — must cost <= 90us per tick: 0.8 % of the shortest
        tick any cell has on the chip (11 ms), and three times the
        29-31us it measures here, alone or beside seven busy processes
        (17 before the CPU clock, PR 36; the old bound was 2.9 times
        its reading).  The fastest of 120 repetitions of ~4 ms, not a
        quantile: other test workers interrupt most repetitions and
        some they do not.  A deterministic micro-bound instead of an
        engine wall-clock A/B: this sandbox's host noise swings
        per-tick times tens of percent (the same reason _ab_decode
        compares p25s and only the BENCHMARK reports the measured
        ratio — see tracing_overhead_ratio in
        benchmarks/serving.py)."""
        path = str(tmp_path / "perf_trace.json")
        TR.start(path)
        metrics = serving.ServingMetrics()
        hists, cpu_hists = metrics.phases, metrics.phases_cpu
        try:
            n, reps = 100, 120
            samples = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(n):
                    # exactly what the engine enters per steady tick
                    for name in STEADY_TICK_PHASES:
                        with TR.phase(name, hists[name], cpu_hists[name]):
                            pass
                samples.append((time.perf_counter() - t0) / n)
            per_tick = min(samples)
            assert per_tick <= 90e-6, f"{per_tick * 1e6:.1f}us per tick"
        finally:
            TR.stop()

    def test_enabled_tick_emissions_bounded(self, model, tmp_path):
        """Structural half of the enabled bound: a steady-state decode
        tick of the paged, overlapped engine makes EXACTLY one tracer
        call per engine phase it passes through — lock_wait, reclaim,
        admit, page_prep, tick_dispatch, tick_device_wait, tick_host,
        bookkeeping — and no per-token, per-slot, or per-future
        emission creeps onto the hot path.  Counted with a stub tracer
        so the assertion is exact."""
        calls = {"tick_phase": 0, "other": 0}
        names = []

        class StubTracer:
            def tick_phase(self, name, *a, **k):
                calls["tick_phase"] += 1
                names.append(name)

            def __getattr__(self, name):
                def record(*a, **k):
                    calls["other"] += 1
                return record

        engine = _engine(model, n_slots=2)
        fut = engine.submit([2, 3, 4], max_new_tokens=36)
        for _ in range(6):  # admission + pipeline fill
            engine.step()
        assert not fut.done()
        prev = TR.activate(StubTracer())
        try:
            n = 10
            for _ in range(n):
                engine.step()
        finally:
            TR.activate(prev)
        assert not fut.done()  # still steady-state
        assert calls["tick_phase"] == len(STEADY_TICK_PHASES) * n, calls
        assert names == list(STEADY_TICK_PHASES) * n
        assert calls["other"] == 0, calls
        _run_until_done(engine, [fut])

    def test_disabled_per_tick_work_bounded(self):
        """PERF GUARD (disabled): with no tracer attached and no
        profiler session, the span work of a steady tick is eight
        ``phase`` entries — an inactive TraceAnnotation (~0.4us), two
        reads of ``time.monotonic()`` and two of ``time.thread_time()``
        (a system call each, ~0.45us here), two histogram observations
        and one module-global read each.  Bound it at 55us per tick —
        0.5 % of an 11 ms tick on the chip, 2.4 times the 22-23us it
        measures here alone and 22-42 beside seven busy processes (~10
        for seven phases on one clock before PR 36, under a bound of
        20); the fastest repetition, as above."""
        assert TR.get() is None
        metrics = serving.ServingMetrics()
        hists, cpu_hists = metrics.phases, metrics.phases_cpu
        n, reps = 200, 150
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                for name in STEADY_TICK_PHASES:
                    with TR.phase(name, hists[name], cpu_hists[name]):
                        pass
            samples.append((time.perf_counter() - t0) / n)
        per_tick = min(samples)
        assert per_tick <= 55e-6, f"{per_tick * 1e6:.2f}us per tick"

    def test_disabled_tracing_adds_no_host_syncs(self, model):
        """Structural half of the <=2%-disabled bound: with no tracer,
        the steady-state tick performs the same single host sync — the
        hooks never touch the device path."""
        engine = _engine(model, n_slots=2)
        assert TR.get() is None
        fut = engine.submit([2, 3, 4], max_new_tokens=30)
        for _ in range(6):
            engine.step()
        syncs0 = engine.metrics.host_syncs.value
        ticks0 = engine.metrics.decode_ticks.value
        for _ in range(10):
            engine.step()
        assert (engine.metrics.host_syncs.value - syncs0
                <= engine.metrics.decode_ticks.value - ticks0)
        _run_until_done(engine, [fut])
