"""Continuous-batching inference engine (horovod_tpu/serving/).

The gold check is TOKEN-IDENTITY: whatever mix of requests shares the
slot pool, whenever they were admitted, each one's greedy output must
equal per-request ``greedy_decode`` — plus ZERO recompilations of the
decode executable after warmup (the engine's compile-count hook).
Everything runs on JAX_PLATFORMS=cpu with a tiny TransformerConfig and
S <= 4 slots so the suite stays tier-1-fast; the HTTP soak test is
marked ``slow``.
"""

import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T

pytestmark = pytest.mark.serving


def _cfg(**kw):
    import dataclasses

    base = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)
    return dataclasses.replace(base, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _ref_greedy(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _run_until_done(engine, futs, max_ticks=200):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


class TestSlots:
    """The slot surface of the one KV cache (``PagedSlotCache``)."""

    def test_alloc_free_fcfs_lowest(self, model):
        _, cfg = model
        slots = serving.PagedSlotCache(cfg, 3, max_len=16, page_size=8)
        assert [slots.alloc() for _ in range(3)] == [0, 1, 2]
        assert slots.alloc() is None and slots.free_count == 0
        slots.free(1)
        slots.free(0)
        assert slots.alloc() == 0  # lowest index first, not LIFO
        assert slots.occupancy == pytest.approx(2 / 3)
        with pytest.raises(ValueError):
            slots.free(2), slots.free(2)

    def test_landed_prefill_reads_back_through_the_table(self, model):
        """A prefilled block lands in the slot's granted pages — handed
        out of order — and reads back through its table row as the
        prefill's own K/V; the slot adopts the prompt's length and no
        other slot's pages are touched."""
        params, cfg = model
        prompt = jnp.asarray([[5, 9, 2, 7, 1, 3, 8, 4, 6, 2, 9]], jnp.int32)
        n = prompt.shape[1]
        _, pre = T.prefill(params, prompt, T.init_cache(cfg, 1, 16), cfg)
        slots = serving.PagedSlotCache(cfg, 3, max_len=16, page_size=8,
                                       n_pages=6)
        slots.alloc(), slots.alloc()
        held = slots.grant_raw(1)           # so slot 1's pages are 2, 3
        slots.grant(1, 1), slots.grant(1, 0)
        assert slots.table[1].tolist() == [3, 2]
        slots.land([1], pre, [n])
        k, v = (slots.gather_prefix(slots.table[1])[n] for n in "kv")
        np.testing.assert_array_equal(
            np.asarray(k[:, :, :n]), np.asarray(pre["k"][:, 0, :, :n]))
        np.testing.assert_array_equal(
            np.asarray(v[:, :, :n]), np.asarray(pre["v"][:, 0, :, :n]))
        assert slots.positions().tolist() == [0, n, 0]
        # pages no slot was granted stay zero
        assert not np.asarray(slots.cache["k"][:, held + [4, 5, 6]]).any()

    def test_land_requires_allocated_slot(self, model):
        params, cfg = model
        _, pre = T.prefill(params, jnp.asarray([[1]], jnp.int32),
                           T.init_cache(cfg, 1, 8), cfg)
        slots = serving.PagedSlotCache(cfg, 2, max_len=16, page_size=8)
        with pytest.raises(ValueError, match="not allocated"):
            slots.land([0], pre, [1])


class TestOneCache:
    def test_paged_false_is_refused_typed(self):
        """The slot-contiguous cache went in PR 28: the field stays only
        because the benchmark's configuration files pass ``paged=True``."""
        with pytest.raises(ValueError, match="PR 28"):
            serving.EngineConfig(paged=False)
        assert not hasattr(serving, "SlotCache")
        assert not hasattr(T, "decode_step_slots")


class TestEngineCorrectness:
    @pytest.mark.slow
    def test_token_identity_staggered_admission(self, model):
        """ACCEPTANCE: >= 3 concurrently admitted requests with unequal
        prompt lengths, admitted at different ticks, each token-identical
        to sequential greedy_decode — with zero decode recompilations
        after warmup."""
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=4, max_len=40, max_prefills_per_tick=1,
                min_prefill_bucket=4))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (3, 9, 5, 12)]
        steps = 11

        futs = [engine.submit(prompts[0], max_new_tokens=steps)]
        engine.step()          # admit r0 + warmup decode tick
        warm = engine.decode_compilations
        assert warm == 1
        futs.append(engine.submit(prompts[1], max_new_tokens=steps))
        engine.step()          # r1 admitted while r0 mid-decode
        futs.append(engine.submit(prompts[2], max_new_tokens=steps))
        futs.append(engine.submit(prompts[3], max_new_tokens=steps))
        _run_until_done(engine, futs)

        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, steps)
            assert f.finish_reason == "length"
        # the acceptance hook: the decode executable never recompiled
        assert engine.decode_compilations == warm == 1
        assert engine.stats()["requests_completed"] == 4

    @pytest.mark.slow
    def test_slot_reuse_no_contamination(self, model):
        """More requests than slots: retirement frees slots that later
        requests reuse; every output must still match per-request
        greedy_decode (stale K/V never attended)."""
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=2, max_len=40, max_prefills_per_tick=2,
                min_prefill_bucket=4, max_queue_depth=8))
        rng = np.random.default_rng(11)
        cases = [(rng.integers(0, cfg.vocab_size, n).tolist(), s)
                 for n, s in ((4, 6), (8, 3), (2, 9), (6, 5), (3, 7))]
        futs = [engine.submit(p, max_new_tokens=s) for p, s in cases]
        _run_until_done(engine, futs)
        for (p, s), f in zip(cases, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, s)
        assert engine.decode_compilations == 1
        assert engine.stats()["requests_completed"] == 5

    def test_eos_retirement(self, model):
        params, cfg = model
        prompt = [3, 4, 5]
        ref = _ref_greedy(params, cfg, prompt, 12)
        eos = ref[4]  # stop mid-stream at a token greedy really emits
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        fut = engine.submit(prompt, max_new_tokens=12, eos_id=eos)
        _run_until_done(engine, [fut])
        out = fut.result(timeout=0)
        assert fut.finish_reason == "eos"
        assert out == ref[:ref.index(eos) + 1]
        assert engine.slots.active_count == 0  # slot freed on retirement

    def test_first_token_eos_retires_at_admission(self, model):
        params, cfg = model
        prompt = [3, 4, 5]
        ref = _ref_greedy(params, cfg, prompt, 1)
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        fut = engine.submit(prompt, max_new_tokens=8, eos_id=ref[0])
        engine.step()
        assert fut.result(timeout=0) == ref
        assert fut.finish_reason == "eos"
        assert engine.slots.active_count == 0

    def test_streaming_callback_and_detokenize(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4),
            detokenize=lambda t: f"<{t}>")
        seen = []
        fut = engine.submit([3, 4], max_new_tokens=4,
                            on_token=lambda tok, piece: seen.append(
                                (tok, piece)))
        _run_until_done(engine, [fut])
        toks = fut.result(timeout=0)
        assert [t for t, _ in seen] == toks
        assert fut.text == "".join(f"<{t}>" for t in toks)


class TestAdmissionControl:
    def test_queue_full_typed_rejection(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              max_queue_depth=2,
                                              min_prefill_bucket=4))
        engine.submit([1], max_new_tokens=2)
        engine.submit([2], max_new_tokens=2)
        with pytest.raises(serving.QueueFullError):
            engine.submit([3], max_new_tokens=2)
        assert engine.stats()["requests_rejected"] == 1

    def test_deadline_exceeded_typed_rejection(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        fut = engine.submit([1, 2], max_new_tokens=4,
                            deadline=time.monotonic() - 0.01)
        engine.step()
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=1.0)
        assert engine.stats()["requests_rejected"] == 1
        assert engine.stats()["requests_admitted"] == 0

    def test_deadline_after_admission_retires_slot(self, model):
        """A deadline lapsing AFTER admission frees the slot with a
        partial result (finish_reason 'deadline') instead of decoding
        to max_new_tokens for a caller that already timed out."""
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        fut = engine.submit([1, 2], max_new_tokens=16,
                            deadline=time.monotonic() + 60)
        engine.step()  # admit: first token emitted, slot occupied
        assert engine.slots.active_count == 1
        engine._states[0].request.deadline = time.monotonic() - 1
        engine.step()
        assert fut.done() and fut.finish_reason == "deadline"
        assert 1 <= len(fut.result(timeout=0)) < 16
        assert engine.slots.active_count == 0

    def test_rejected_counts_both_paths(self, model):
        """metrics.rejected sees BOTH rejection paths: submit-time
        QueueFullError (via the scheduler's constructor on_reject) and
        take-time DeadlineExceededError — /stats never under-reports
        shed load."""
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              max_queue_depth=1,
                                              min_prefill_bucket=4))
        # take-time path: queued past its deadline
        fut = engine.submit([1, 2], max_new_tokens=2,
                            deadline=time.monotonic() - 0.01)
        # submit-time path: queue (depth 1) already full
        with pytest.raises(serving.QueueFullError):
            engine.submit([3, 4], max_new_tokens=2)
        assert engine.stats()["requests_rejected"] == 1  # submit-time
        engine.step()
        with pytest.raises(serving.DeadlineExceededError):
            fut.result(timeout=1.0)
        assert engine.stats()["requests_rejected"] == 2  # + take-time

    def test_requeue_front_restores_fcfs_and_ignores_depth_bound(self):
        """The resume path's re-admission hook: requeued requests keep
        their ORIGINAL (older) ids — the real resume/preemption paths
        preserve them — so the scheduling order places them ahead of
        everything younger in their class, and they are exempt from
        max_queue_depth (their callers already hold live futures)."""
        class _F:
            def done(self):
                return False
            cancel_requested = False

        sched = serving.Scheduler(max_queue_depth=2)
        # Resumed requests were submitted (and got their ids) BEFORE
        # the still-queued one, exactly like a real crash window.
        r1 = serving.Request(prompt=[1], max_new_tokens=1, future=_F())
        r2 = serving.Request(prompt=[2], max_new_tokens=1, future=_F())
        r3 = serving.Request(prompt=[3], max_new_tokens=1, future=_F())
        queued = serving.Request(prompt=[9], max_new_tokens=1, future=_F())
        sched.submit(queued)
        sched.requeue_front([r1, r2, r3])  # depth 4 > bound 2: allowed
        assert sched.depth == 4
        out = sched.take(free_slots=4)
        # resumed requests first, in id (original FCFS) order
        assert [r.prompt for r in out[:2]] == [[1], [2]]
        out += sched.take(free_slots=4)
        assert [r.prompt for r in out] == [[1], [2], [3], [9]]

    def test_request_too_long_typed_rejection(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=16,
                                              min_prefill_bucket=4))
        with pytest.raises(serving.RequestTooLongError):
            engine.submit(list(range(10)), max_new_tokens=8)
        # boundary: prompt + max_new - 1 == capacity is admissible
        fut = engine.submit(list(range(9)), max_new_tokens=8)
        _run_until_done(engine, [fut])
        assert len(fut.result(timeout=0)) == 8

    def test_metrics_populated(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        futs = [engine.submit([1, 2, 3], max_new_tokens=3)
                for _ in range(2)]
        _run_until_done(engine, futs)
        s = engine.stats()
        assert s["requests_admitted"] == 2
        assert s["requests_completed"] == 2
        assert s["tokens_generated"] == 6
        assert s["ttft_seconds"]["count"] == 2
        assert s["ttft_seconds"]["p50"] is not None
        # 2 requests x 2 decode-step tokens each (first came from prefill)
        assert s["token_latency_seconds"]["count"] == 4
        assert s["decode_compilations"] == 1


class TestHistogram:
    def test_percentiles_and_snapshot(self):
        h = serving.Histogram(buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.05, 0.5, 20.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 4
        assert snap["buckets"] == {"0.1": 2, "1": 1, "10": 0, "+Inf": 1}
        assert h.percentile(0.5) == 0.1
        assert h.percentile(0.99) == 10.0  # +Inf reports largest edge
        assert serving.Histogram().percentile(0.5) is None


from conftest import http_post_json as _post  # noqa: E402


class TestServer:
    @pytest.fixture()
    def served(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(n_slots=2, max_len=40,
                                              min_prefill_bucket=4))
        with serving.ServingServer(engine, port=0) as srv:
            host, port = srv.address
            yield engine, f"http://{host}:{port}"

    def test_generate_healthz_stats(self, served, model):
        params, cfg = model
        engine, base = served
        with urllib.request.urlopen(base + "/healthz", timeout=10) as r:
            assert json.loads(r.read())["status"] == "healthy"
        code, out = _post(base + "/generate",
                          {"tokens": [3, 4, 5], "max_new_tokens": 5})
        assert code == 200
        assert out["tokens"] == _ref_greedy(params, cfg, [3, 4, 5], 5)
        assert out["finish_reason"] == "length"
        assert out["ttft_ms"] > 0
        with urllib.request.urlopen(base + "/stats", timeout=10) as r:
            stats = json.loads(r.read())
        assert stats["requests_completed"] == 1
        assert stats["decode_compilations"] == 1

    def test_http_typed_rejections(self, served):
        _, base = served
        code, out = _post(base + "/generate",
                          {"tokens": list(range(60)),
                           "max_new_tokens": 8})
        assert (code, out["type"]) == (413, "too_long")
        code, out = _post(base + "/generate", {"tokens": []})
        assert code == 400
        code, out = _post(base + "/generate",
                          {"text": "no encoder configured"})
        assert code == 400

    @pytest.mark.slow
    def test_soak_concurrent_clients(self, model):
        """Soak: many concurrent HTTP clients with mixed lengths; every
        response token-identical to sequential greedy_decode and no
        decode recompilation under the whole load."""
        params, cfg = model
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=4, max_len=40, max_queue_depth=64,
                min_prefill_bucket=4))
        rng = np.random.default_rng(3)
        cases = [(rng.integers(0, cfg.vocab_size, int(n)).tolist(), int(s))
                 for n, s in zip(rng.integers(2, 12, 24),
                                 rng.integers(2, 10, 24))]
        results = [None] * len(cases)

        with serving.ServingServer(engine, port=0) as srv:
            host, port = srv.address
            base = f"http://{host}:{port}"

            def client(i):
                p, s = cases[i]
                results[i] = _post(base + "/generate",
                                   {"tokens": p, "max_new_tokens": s})

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(cases))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
        for (p, s), r in zip(cases, results):
            assert r is not None and r[0] == 200
            assert r[1]["tokens"] == _ref_greedy(params, cfg, p, s)
        assert engine.decode_compilations == 1
