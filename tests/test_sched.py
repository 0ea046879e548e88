"""SLO-aware scheduling (docs/serving.md "Scheduling"): chunked
prefill interleaved with decode, priority classes + EDF ordering, and
preemption under slot/page pressure.

The gold checks:

* CHUNKED prefill is invisible in the output: greedy AND sampled
  engine output with ``prefill_chunk_tokens`` set is token-identical
  to the whole-prompt oracle (``greedy_decode`` / ``sample_decode``),
  with the decode executable still compiled exactly once — chunk
  boundaries are data, never structure.
* Decode RIDES THROUGH ingestion: a short request admitted behind a
  long prompt finishes before the long prompt's first token — the
  prefill/decode interference chunking exists to kill.
* PREEMPTION is a suspension, not a loss: the victim's future stays
  live, it re-admits from its journal frontier, and its final output
  is byte-identical to an uninterrupted run — composed with COW
  prefix sharing (refcounts balance) and SSE streaming (the stream
  continues gapless).
* A lapsed-deadline request resolves at the NEXT TICK BOUNDARY
  (``Scheduler.sweep``), not whenever admission happens to reach it.
"""

import dataclasses
import http.client
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.serving import sse
from horovod_tpu.serving.faults import FaultInjector, FaultSpec
from horovod_tpu.serving.journal import RequestJournal
from horovod_tpu.serving.scheduler import (
    DeadlineExceededError,
    Request,
    Scheduler,
    ServingError,
    priority_rank,
)
from horovod_tpu.serving.server import ServingServer

pytestmark = [pytest.mark.serving, pytest.mark.sched]


def _cfg(**kw):
    base = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=96, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)
    return dataclasses.replace(base, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _ref_greedy(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _ref_sampled(params, cfg, prompt, steps, *, temperature, top_k=0,
                 top_p=0.0, seed=0):
    return np.asarray(T.sample_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg,
        rng=jax.random.PRNGKey(seed), temperature=temperature,
        top_k=top_k, top_p=top_p))[0].tolist()


def _run_until_done(engine, futs, max_ticks=800):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


def _engine(params, cfg, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_len", 96)
    kw.setdefault("min_prefill_bucket", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("tick_timeout", 0)  # stepped engines: no watchdog
    return serving.InferenceEngine(params, cfg,
                                   serving.EngineConfig(**kw))


class _F:
    """Minimal future stub for scheduler-only tests."""

    cancel_requested = False

    def __init__(self):
        self.exc = None
        self.reason = None
        self._d = False

    def done(self):
        return self._d

    def set_exception(self, e):
        self.exc, self._d = e, True

    def _finish(self, reason):
        self.reason, self._d = reason, True


def _req(**kw):
    kw.setdefault("prompt", [1])
    kw.setdefault("max_new_tokens", 1)
    kw.setdefault("future", _F())
    return Request(**kw)


# ---------------------------------------------------------------------------
# scheduler ordering (pure unit)
# ---------------------------------------------------------------------------


class TestSchedulerOrdering:
    def test_priority_class_before_submission_order(self):
        s = Scheduler(max_prefills_per_tick=8)
        batch = _req(prompt=[1], priority="batch")
        inter = _req(prompt=[2], priority="interactive")
        s.submit(batch)
        s.submit(inter)  # submitted later, served first
        out = s.take(free_slots=4)
        assert [r.prompt for r in out] == [[2], [1]]

    def test_edf_within_class(self):
        clock = [0.0]
        s = Scheduler(clock=lambda: clock[0], max_prefills_per_tick=8)
        late = _req(prompt=[1], deadline=100.0)
        soon = _req(prompt=[2], deadline=5.0)
        none = _req(prompt=[3])  # no deadline: after every deadline
        for r in (none, late, soon):
            s.submit(r)
        out = s.take(free_slots=4)
        assert [r.prompt for r in out] == [[2], [1], [3]]

    def test_edf_never_crosses_class(self):
        clock = [0.0]
        s = Scheduler(clock=lambda: clock[0], max_prefills_per_tick=8)
        urgent_batch = _req(prompt=[1], priority="batch", deadline=1.0)
        lazy_inter = _req(prompt=[2], deadline=1000.0)
        s.submit(urgent_batch)
        s.submit(lazy_inter)
        out = s.take(free_slots=4)
        assert [r.prompt for r in out] == [[2], [1]]

    def test_fcfs_tiebreak_within_class(self):
        s = Scheduler(max_prefills_per_tick=8)
        a, b = _req(prompt=[1]), _req(prompt=[2])
        s.submit(a)
        s.submit(b)
        assert [r.prompt for r in s.take(4)] == [[1], [2]]

    def test_bucket_uniform_truncates_in_order(self):
        s = Scheduler(max_prefills_per_tick=4)
        a = _req(prompt=[1] * 4)
        b = _req(prompt=[2] * 16)
        c = _req(prompt=[3] * 4)
        for r in (a, b, c):
            s.submit(r)
        out = s.take(4, bucket_fn=lambda r: len(r.prompt))
        # the head's bucket wins; the first mismatch stops the take —
        # c is NOT pulled around b (order truncated, never violated)
        assert [r.prompt[0] for r in out] == [1]

    def test_peek_best_rank_skips_dead(self):
        clock = [0.0]
        s = Scheduler(clock=lambda: clock[0])
        doomed = _req(prompt=[1], deadline=1.0)  # interactive but dead
        alive = _req(prompt=[2], priority="batch")
        s.submit(doomed)
        s.submit(alive)
        clock[0] = 2.0
        assert s.peek_best_rank() == priority_rank("batch")

    def test_sweep_resolves_lapsed_behind_live_head(self):
        """SATELLITE regression: a lapsed request BEHIND the order
        head (a worse class — within a class EDF puts lapsed
        deadlines first) resolves promptly wherever it sits: sweep()
        scans the WHOLE queue, and a zero-budget take() routes
        through the same sweep instead of stopping at the live
        head."""
        clock = [0.0]
        rejected = []
        s = Scheduler(clock=lambda: clock[0],
                      on_reject=lambda r, e: rejected.append(r))
        live = _req(prompt=[1])  # interactive: the order head
        doomed = _req(prompt=[2], priority="batch", deadline=1.0)
        s.submit(live)
        s.submit(doomed)
        clock[0] = 2.0
        # a zero-budget take is a cheap no-op: dead resolution is the
        # sweep's job (the engine runs it at every tick boundary)
        assert s.take(free_slots=0) == []
        assert not doomed.future.done()
        assert s.sweep() == 1              # resolved behind the head
        assert isinstance(doomed.future.exc, DeadlineExceededError)
        assert rejected == [doomed]        # metrics hook fired
        assert s.depth == 1                # the live head stays

    def test_requeued_victim_deadline_finishes_partial(self):
        """REGRESSION (review): a preempted victim waiting to
        re-admit already served tokens — a deadline lapsing in the
        queue must FINISH it with the partial result (the
        deadline-after-admission contract), never 504 away paid-for
        output."""
        clock = [0.0]
        expired = []
        s = Scheduler(clock=lambda: clock[0],
                      on_expire=lambda r: expired.append(r))
        fut = _F()
        fut.ttft = 0.01  # admitted once: a previous life emitted
        victim = _req(prompt=[1, 2, 7], future=fut, deadline=1.0)
        s.requeue_front([victim])
        clock[0] = 2.0
        assert s.sweep() == 1
        assert fut.exc is None and fut.reason == "deadline"
        assert expired == [victim]
        # ... and a victim preempted MID-INGESTION (admitted, no token
        # yet, so no ttft — only trace.admitted_at) gets the same
        # finish: its uninterrupted twin would have lapsed in-slot
        fut2 = _F()
        victim2 = _req(prompt=[3, 4], future=fut2, deadline=1.5)
        victim2.trace = type("Tr", (), {"admitted_at": 0.5})()
        s.requeue_front([victim2])
        assert s.sweep() == 1
        assert fut2.exc is None and fut2.reason == "deadline"
        assert expired == [victim, victim2]

    def test_requeued_no_deadline_victim_not_starved_by_edf(self):
        """REGRESSION (review): a preempted victim WITHOUT a deadline
        must not sort behind every deadlined same-class arrival
        forever — the requeue boost puts it ahead of everything
        non-requeued in its class."""
        s = Scheduler(max_prefills_per_tick=8, clock=lambda: 0.0)
        victim = _req(prompt=[1])          # no deadline
        s.requeue_front([victim])
        rival = _req(prompt=[2], deadline=5.0)  # EDF-favored arrival
        s.submit(rival)
        out = s.take(free_slots=4)
        assert [r.prompt for r in out] == [[1], [2]]

    def test_unknown_priority_rejected(self):
        with pytest.raises(ServingError):
            priority_rank("platinum")


# ---------------------------------------------------------------------------
# tick-boundary deadline sweep (engine level)
# ---------------------------------------------------------------------------


class TestDeadlineSweep:
    def test_doomed_request_resolves_during_admission_stall(self, model):
        """A queued request whose deadline lapses while every slot is
        busy (and a live request is queued AHEAD of it) gets its 504
        within a tick — it does not wait for the stall to clear."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=1)
        busy = engine.submit([1, 2, 3], max_new_tokens=60)
        for _ in range(4):
            engine.step()
        ahead = engine.submit([4, 5], max_new_tokens=2)
        doomed = engine.submit(
            [6, 7], max_new_tokens=2, priority="batch",
            deadline=time.monotonic() + 0.03)
        time.sleep(0.05)
        engine.step()  # one tick boundary: the sweep runs
        assert doomed.done() and not ahead.done() and not busy.done()
        with pytest.raises(serving.DeadlineExceededError):
            doomed.result(timeout=0)
        _run_until_done(engine, [busy, ahead])


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------


class TestChunkedPrefill:
    def test_chunked_greedy_oracle_overlap(self, model):
        """Mixed long/short greedy traffic, chunked: token-identical
        to the whole-prompt oracle; ONE decode compile (chunk
        boundaries are data)."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8)
        rng = np.random.default_rng(7)
        prompts = [[int(t) for t in rng.integers(1, 64, n)]
                   for n in (41, 3, 27, 5)]
        futs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        _run_until_done(engine, futs)
        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, 8)
        assert engine.decode_compilations == 1
        assert engine.stats()["slots_ingesting"] == 0

    def test_chunked_greedy_oracle_sync(self, model):
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8,
                         overlap=False)
        rng = np.random.default_rng(9)
        p = [int(t) for t in rng.integers(1, 64, 37)]
        fut = engine.submit(p, max_new_tokens=6)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg, p, 6)
        assert engine.decode_compilations == 1

    def test_chunked_sampled_oracle(self, model):
        """A SAMPLED long prompt: the final chunk's logits feed the
        first draw at key index len(prompt), so the stream matches
        sample_decode exactly — chunking never touches the PRNG
        schedule."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8)
        rng = np.random.default_rng(11)
        p = [int(t) for t in rng.integers(1, 64, 33)]
        fut = engine.submit(p, max_new_tokens=8, temperature=0.8,
                            top_k=12, seed=13)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_sampled(
            params, cfg, p, 8, temperature=0.8, top_k=12, seed=13)

    def test_chunked_attends_shared_prefix(self, model):
        """Chunked ingestion composes with COW prefix sharing: the
        prefix pages attach (no compute), the chunks land only the
        suffix, output matches the oracle, and every page recycles
        after retirement (the pin stays)."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8)
        prefix = [9, 8, 7, 6, 5, 4, 3, 2]
        engine.register_prefix(prefix)
        pinned = len(engine._prefixes[tuple(prefix)].pages)
        rng = np.random.default_rng(13)
        suffix = [int(t) for t in rng.integers(1, 64, 30)]
        p = prefix + suffix
        fut = engine.submit(p, max_new_tokens=6)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg, p, 6)
        assert engine.slots.free_pages == engine.slots.n_pages - pinned
        assert engine.slots.pages_shared == 0  # nothing left attached

    def test_decode_rides_through_ingestion(self, model):
        """THE Sarathi property: a short request admitted behind a
        long prompt decodes to completion while the long prompt is
        still ingesting — whole-prompt prefill would have stalled it
        for the full prompt."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8)
        rng = np.random.default_rng(17)
        long_p = [int(t) for t in rng.integers(1, 64, 64)]
        long_fut = engine.submit(long_p, max_new_tokens=4)
        engine.step()  # first chunk lands; ingestion is under way
        short_fut = engine.submit([5, 9], max_new_tokens=3)
        for _ in range(400):
            engine.step()
            if short_fut.done():
                break
        assert short_fut.done()
        # the long prompt is still ingesting: no first token yet
        assert not long_fut.done()
        assert long_fut.tokens_so_far() == []
        assert short_fut.result(timeout=0) == _ref_greedy(
            params, cfg, [5, 9], 3)
        _run_until_done(engine, [long_fut])
        assert long_fut.result(timeout=0) == _ref_greedy(
            params, cfg, long_p, 4)

    @pytest.mark.perf
    def test_chunk_compile_set_is_bounded(self, model):
        """Chunk boundaries are DATA: a second long prompt of the same
        length re-uses every chunk executable (no new prefill traces),
        and decode never recompiles."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=8)
        rng = np.random.default_rng(19)
        p1 = [int(t) for t in rng.integers(1, 64, 43)]
        fut = engine.submit(p1, max_new_tokens=4)
        _run_until_done(engine, [fut])
        traces = engine._prefill_traces
        decode = engine.decode_compilations
        p2 = [int(t) for t in rng.integers(1, 64, 43)]
        fut2 = engine.submit(p2, max_new_tokens=4)
        _run_until_done(engine, [fut2])
        assert engine._prefill_traces == traces
        assert engine.decode_compilations == decode == 1
        assert fut2.result(timeout=0) == _ref_greedy(params, cfg, p2, 4)


# ---------------------------------------------------------------------------
# preemption
# ---------------------------------------------------------------------------


class TestPreemption:
    def test_slot_pressure_suspends_batch_for_interactive(self, model):
        """Every slot busy with batch work + an interactive arrival:
        the youngest batch occupant SUSPENDS (live future, journal
        frontier), the interactive request admits promptly, and the
        victim's final output is byte-identical to an uninterrupted
        run."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2)
        b1 = engine.submit([1, 2, 3], max_new_tokens=24,
                           priority="batch")
        b2 = engine.submit([4, 5, 6], max_new_tokens=24,
                           priority="batch")
        for _ in range(6):
            engine.step()
        assert not b1.done() and not b2.done()
        inter = engine.submit([7, 8, 9], max_new_tokens=3)
        for _ in range(40):
            engine.step()
            if inter.done():
                break
        assert inter.done()          # admitted well before a batch slot
        assert not (b1.done() and b2.done())  # one was suspended
        assert engine.stats()["preemptions"] >= 1
        _run_until_done(engine, [b1, b2])
        assert b1.result(timeout=0) == _ref_greedy(
            params, cfg, [1, 2, 3], 24)
        assert b2.result(timeout=0) == _ref_greedy(
            params, cfg, [4, 5, 6], 24)

    def test_no_preemption_within_class(self, model):
        """Equal classes wait FCFS: an interactive arrival never
        suspends an interactive occupant."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=1)
        first = engine.submit([1, 2, 3], max_new_tokens=12)
        for _ in range(4):
            engine.step()
        second = engine.submit([4, 5], max_new_tokens=2)
        _run_until_done(engine, [first, second])
        assert engine.stats()["preemptions"] == 0
        assert first.result(timeout=0) == _ref_greedy(
            params, cfg, [1, 2, 3], 12)
        assert second.result(timeout=0) == _ref_greedy(
            params, cfg, [4, 5], 2)

    @pytest.mark.slow
    def test_preemption_cow_refcounts_balance(self, model):
        """COMPOSITION: preempting a victim that shares COW prefix
        pages decrefs exactly its references — after everything
        retires the pool is back to the pin, and the prefix stays
        servable.  Slow (PR 17 budget pass): ~8 s; test_paged's
        resume/COW refcount-balance tests keep the refcount invariant
        tier-1."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2)
        prefix = [9, 8, 7, 6, 5, 4, 3, 2]
        engine.register_prefix(prefix)
        pinned = len(engine._prefixes[tuple(prefix)].pages)
        b1 = engine.submit(prefix + [1], max_new_tokens=20,
                           priority="batch")
        b2 = engine.submit(prefix + [2], max_new_tokens=20,
                           priority="batch")
        for _ in range(6):
            engine.step()
        inter = engine.submit(prefix + [3], max_new_tokens=3)
        _run_until_done(engine, [inter, b1, b2])
        assert engine.stats()["preemptions"] >= 1
        assert inter.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + [3], 3)
        assert b1.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + [1], 20)
        assert b2.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + [2], 20)
        assert engine.slots.free_pages == engine.slots.n_pages - pinned
        assert engine.slots.pages_shared == 0

    @pytest.mark.slow
    def test_preempted_streaming_client_sees_gapless_stream(self, model):
        """Slow (PR 17 budget pass): HTTP server + live SSE stream is
        ~6 s; the non-streamed preemption tests here and
        test_streaming's in-process mid-stream continuation keep both
        halves of the composition tier-1.

        COMPOSITION: a STREAMED batch request that gets preempted
        resumes on the same engine with the same live future — the
        client's SSE stream pauses, then continues with gapless
        indices and finishes byte-identical to the oracle."""
        params, cfg = model
        engine = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=1, max_len=96, min_prefill_bucket=4, page_size=8))
        srv = ServingServer(engine, port=0)
        srv.start()
        try:
            host, port = srv.address
            c = http.client.HTTPConnection(host, port, timeout=60)
            c.request("POST", "/generate", body=json.dumps({
                "tokens": [1, 2, 3], "max_new_tokens": 16,
                "priority": "batch", "stream": True}).encode())
            resp = c.getresponse()
            assert resp.status == 200
            # wait until the stream is live, then put it under slot
            # pressure from an interactive request
            deadline = time.monotonic() + 20
            while engine.metrics.streamed_tokens.value == 0:
                assert time.monotonic() < deadline
                time.sleep(0.005)
            c2 = http.client.HTTPConnection(host, port, timeout=60)
            c2.request("POST", "/generate", body=json.dumps({
                "tokens": [7, 8], "max_new_tokens": 2}).encode())
            r2 = c2.getresponse()
            assert r2.status == 200
            out2 = json.loads(r2.read())
            assert out2["tokens"] == _ref_greedy(params, cfg, [7, 8], 2)
            events = sse.read_stream(resp)
            toks = [p["token"] for k, p in events if k == "token"]
            idxs = [p["i"] for k, p in events if k == "token"]
            done = [p for k, p in events if k == "done"]
            assert len(done) == 1
            assert idxs == list(range(len(toks)))  # gapless
            assert toks == done[0]["tokens"] == _ref_greedy(
                params, cfg, [1, 2, 3], 16)
            assert engine.stats()["preemptions"] >= 1
        finally:
            srv.stop(drain_timeout=10)

    def test_chunked_ingestion_preempted_resumes_exact(self, model):
        """COMPOSITION: the victim is MID-INGESTION (no tokens emitted
        yet) — suspension frees its chunk pages and the re-admission
        re-ingests from the original prompt, oracle-exact."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=1,
                         prefill_chunk_tokens=8)
        rng = np.random.default_rng(23)
        long_p = [int(t) for t in rng.integers(1, 64, 48)]
        victim = engine.submit(long_p, max_new_tokens=4,
                               priority="batch")
        engine.step()  # a chunk or two land
        engine.step()
        assert engine.stats()["slots_ingesting"] == 1
        inter = engine.submit([5, 6], max_new_tokens=2)
        _run_until_done(engine, [inter, victim])
        assert engine.stats()["preemptions"] >= 1
        # the landed-but-discarded chunks count as wasted re-prefill
        # work (the journal alone cannot see them)
        assert engine.stats()["resume_wasted_tokens"] >= 8
        assert inter.result(timeout=0) == _ref_greedy(
            params, cfg, [5, 6], 2)
        assert victim.result(timeout=0) == _ref_greedy(
            params, cfg, long_p, 4)

    @pytest.mark.slow
    def test_chunked_first_token_retire_on_model_draft_engine(self,
                                                              model):
        """Slow (PR 17 budget pass): builds a second (model-draft
        speculative) engine, ~11 s; the plain-engine preemption and
        chunked-retire tests above keep the slot-lifecycle invariants
        tier-1.

        REGRESSION (review): a chunked request whose FIRST token
        retires it (max_new_tokens=1) on a model-draft speculative
        engine — the draft-slot acquire must happen before the emit
        can free the slot, or the freed slot is re-activated with no
        owner and the next tenant crashes the tick."""
        params, cfg = model
        dcfg = dataclasses.replace(cfg, n_layers=1)
        dparams = T.init_params(jax.random.PRNGKey(1), dcfg)
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=2, max_len=96, min_prefill_bucket=4,
                page_size=8, tick_timeout=0, prefill_chunk_tokens=8,
                speculative=True, spec_k=2, spec_draft="model"),
            draft_params=dparams, draft_cfg=dcfg)
        rng = np.random.default_rng(41)
        p1 = [int(t) for t in rng.integers(1, 64, 30)]
        f1 = engine.submit(p1, max_new_tokens=1)
        _run_until_done(engine, [f1])
        assert f1.result(timeout=0) == _ref_greedy(params, cfg, p1, 1)
        # the same slot must be reusable by the next chunked tenant
        p2 = [int(t) for t in rng.integers(1, 64, 30)]
        f2 = engine.submit(p2, max_new_tokens=4)
        _run_until_done(engine, [f2])
        assert f2.result(timeout=0) == _ref_greedy(params, cfg, p2, 4)
        # ... and a chunked admission never pays a one-tick
        # whole-prompt DRAFT prefill (the slot degrades to plain
        # greedy instead): no draft-prefill compile shapes exist
        assert engine._draft_prefill_fns == {}


# ---------------------------------------------------------------------------
# chunked prefill x restart-resume (crash mid-chunk)
# ---------------------------------------------------------------------------


class TestChunkedResume:
    @pytest.mark.slow
    def test_crash_mid_chunk_resumes_oracle_exact(self, model):
        """Slow (PR 17 budget pass): restart + re-ingest is ~9 s;
        test_chunked_ingestion_preempted_resumes_exact keeps the
        suspend-mid-ingestion/re-ingest-exact path tier-1, and
        tests/test_chaos.py runs this same fault site under the full
        chaos invariant.

        A tick failure at a CHUNK boundary suspends the ingesting
        request through the ordinary resume path; the restart
        re-ingests from scratch and the output is token-identical to
        an uninterrupted run (tests/test_chaos.py runs the same site
        under the full chaos invariant)."""
        params, cfg = model
        inj = FaultInjector([FaultSpec(site="prefill_chunk",
                                       kind="raise", skip=2)])
        engine = _engine(params, cfg, prefill_chunk_tokens=8,
                         faults=inj, restart_backoff=0.01)
        rng = np.random.default_rng(29)
        long_p = [int(t) for t in rng.integers(1, 64, 40)]
        short = engine.submit([3, 4], max_new_tokens=3)
        victim = engine.submit(long_p, max_new_tokens=5,
                               priority="batch")
        _run_until_done(engine, [short, victim])
        assert inj.fired and inj.fired[0][0] == "prefill_chunk"
        assert engine.stats()["engine_restarts"] == 1
        assert victim.result(timeout=0) == _ref_greedy(
            params, cfg, long_p, 5)
        assert short.result(timeout=0) == _ref_greedy(
            params, cfg, [3, 4], 3)


# ---------------------------------------------------------------------------
# plumbing: per-class metrics, HTTP priority, journal round-trip
# ---------------------------------------------------------------------------


class TestPriorityPlumbing:
    def test_per_class_metrics_and_stats(self, model):
        params, cfg = model
        engine = _engine(params, cfg)
        fi = engine.submit([1, 2], max_new_tokens=2)
        fb = engine.submit([3, 4], max_new_tokens=2, priority="batch")
        _run_until_done(engine, [fi, fb])
        s = engine.stats()
        assert s["ttft_seconds_by_class"]["interactive"]["count"] == 1
        assert s["ttft_seconds_by_class"]["batch"]["count"] == 1
        assert s["ttft_seconds"]["count"] == 2  # merged, historical key
        assert s["queue_wait_seconds_by_class"]["batch"]["count"] == 1
        assert s["preemptions"] == 0
        text = engine.metrics.registry.to_prometheus()
        assert 'serving_ttft_seconds_count{class="batch"}' in text
        assert 'serving_queue_wait_seconds_count{class="interactive"}' \
            in text
        assert "serving_preemptions_total" in text

    def test_unknown_priority_is_typed_rejection(self, model):
        params, cfg = model
        engine = _engine(params, cfg)
        with pytest.raises(ServingError):
            engine.submit([1], max_new_tokens=1, priority="platinum")

    def test_http_priority_roundtrip_and_400(self, model):
        params, cfg = model
        engine = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=2, max_len=96, min_prefill_bucket=4))
        srv = ServingServer(engine, port=0)
        srv.start()
        try:
            host, port = srv.address
            c = http.client.HTTPConnection(host, port, timeout=30)
            c.request("POST", "/generate", body=json.dumps({
                "tokens": [1, 2], "max_new_tokens": 2,
                "priority": "batch"}).encode())
            r = c.getresponse()
            assert r.status == 200
            assert json.loads(r.read())["tokens"] == _ref_greedy(
                params, cfg, [1, 2], 2)
            assert engine.stats()[
                "ttft_seconds_by_class"]["batch"]["count"] == 1
            c.request("POST", "/generate", body=json.dumps({
                "tokens": [1, 2], "max_new_tokens": 2,
                "priority": "platinum"}).encode())
            r = c.getresponse()
            assert r.status == 400
            r.read()
        finally:
            srv.stop(drain_timeout=10)

    def test_journal_roundtrips_priority(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = RequestJournal(path)
        req = _req(prompt=[1, 2], max_new_tokens=4, priority="batch")
        req.trace = type("Tr", (), {"trace_id": "t" * 32,
                                    "span_id": None})()
        j.begin(req)
        j.append(req.id, 7)
        live = RequestJournal.read_live(path)
        assert live["t" * 32]["priority"] == "batch"
        assert live["t" * 32]["emitted_tokens"] == [7]
        # default class stays off the wire (pre-priority readers)
        req2 = _req(prompt=[3], max_new_tokens=1)
        req2.trace = type("Tr", (), {"trace_id": "u" * 32,
                                     "span_id": None})()
        j.begin(req2)
        with open(path) as f:
            lines = [json.loads(l) for l in f if l.strip()]
        assert "pri" not in lines[-1]
        assert RequestJournal.read_live(path)[
            "u" * 32]["priority"] == "interactive"

    def test_priority_survives_restart_resume(self, model):
        """A batch-class request interrupted by an engine crash
        resumes as batch (journal + _build_resume carry the class)."""
        params, cfg = model
        inj = FaultInjector([FaultSpec(site="decode_tick",
                                       kind="raise", skip=6)])
        engine = _engine(params, cfg, faults=inj,
                         restart_backoff=0.01)
        fut = engine.submit([1, 2, 3], max_new_tokens=10,
                            priority="batch")
        _run_until_done(engine, [fut])
        assert engine.stats()["engine_restarts"] == 1
        assert engine.stats()["requests_resumed"] == 1
        assert fut.result(timeout=0) == _ref_greedy(
            params, cfg, [1, 2, 3], 10)
        # per-class TTFT was observed once, in the batch class
        assert engine.stats()[
            "ttft_seconds_by_class"]["batch"]["count"] == 1
