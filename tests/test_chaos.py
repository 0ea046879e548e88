"""Chaos suite for the serving fault-tolerance layer.

THE invariant (docs/serving.md "Operations"): **no submitted request
ever hangs** — under injected device exceptions, non-finite logits,
hung ticks, and mid-stream cancellations, every
:class:`GenerationFuture` resolves with tokens or a typed error within
a bounded wall-clock, the engine recovers through supervised restarts,
and post-recovery greedy output is still token-identical to
per-request ``greedy_decode`` (the same oracle as
``tests/test_serving.py``).

Faults come from :class:`horovod_tpu.serving.FaultInjector` — seeded,
site-addressed, visit-counted — so every test here is deterministic:
same spec, same call sequence, same faults.  Engines are WARMED before
the watchdog is armed (first-tick XLA compilation would otherwise
read as a stall on CPU).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T

pytestmark = [pytest.mark.serving, pytest.mark.chaos]


def _cfg():
    return T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _ref_greedy(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _engine(model, *, faults=None, **kw):
    params, cfg = model
    defaults = dict(n_slots=2, max_len=40, min_prefill_bucket=4,
                    restart_backoff=0.01, restart_backoff_max=0.05,
                    faults=faults)
    defaults.update(kw)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**defaults))


def _run_until_done(engine, futs, max_ticks=300):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


def _warm(engine, prompt_lens=(3,)):
    """Compile every (prefill bucket, admission batch size) shape +
    the decode tick BEFORE arming the watchdog: XLA compilation takes
    seconds on CPU and must not read as a stall.  The sweep itself is
    the engine's own :meth:`warmup` — one definition, so warm coverage
    tracks the engine's compile-set shape."""
    engine.warmup(prompt_lens)


def _wait_for(pred, timeout=15.0, poll=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(poll)
    return False


from conftest import http_post_json as _post  # noqa: E402
from conftest import parse_prometheus_text  # noqa: E402


class TestFaultInjector:
    def test_deterministic_and_site_addressed(self):
        def run():
            inj = serving.FaultInjector([
                serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=1, max_fires=2, p=0.5),
            ], seed=42)
            fired = []
            for _ in range(20):
                try:
                    inj.probe("decode_tick")
                except serving.InjectedFaultError:
                    fired.append(inj.fired[-1])
                inj.probe("prefill")  # other sites never fire this spec
            return fired, inj

        fired_a, inj_a = run()
        fired_b, _ = run()
        assert fired_a == fired_b            # same seed, same faults
        assert len(fired_a) == 2             # max_fires honored
        assert all(site == "decode_tick" for site, _, _ in fired_a)
        assert all(visit >= 1 for _, _, visit in fired_a)  # skip honored
        assert inj_a.exhausted

    def test_validation(self):
        with pytest.raises(ValueError, match="site"):
            serving.FaultInjector([serving.FaultSpec(site="nope")])
        with pytest.raises(ValueError, match="kind"):
            serving.FaultInjector(
                [serving.FaultSpec(site="prefill", kind="nope")])

    def test_hang_sleeps(self):
        inj = serving.FaultInjector([
            serving.FaultSpec(site="watchdog", kind="hang", delay=0.05)])
        t0 = time.monotonic()
        assert inj.probe("watchdog") == "hang"
        assert time.monotonic() - t0 >= 0.05
        assert inj.probe("watchdog") is None  # max_fires=1 default


class TestSupervisedRestart:
    """The PRE-RESUME contract (``resume=False``): a restart fails
    in-flight futures typed.  Kept as the explicit legacy mode — the
    default engine now RESUMES them instead (TestRestartResume)."""

    def test_decode_raise_fails_inflight_and_restarts(self, model):
        """A device exception mid-decode resolves every in-flight
        future with a typed EngineFailedError, restarts the engine
        (fresh PagedSlotCache), and post-restart output is oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise", skip=1)])
        engine = _engine(model, faults=inj, resume=False)
        futs = [engine.submit([3, 4, 5], max_new_tokens=8),
                engine.submit([7, 8], max_new_tokens=8)]
        _run_until_done(engine, futs)
        for f in futs:
            with pytest.raises(serving.EngineFailedError):
                f.result(timeout=0)
        s = engine.stats()
        assert s["engine_failures"] == 1
        assert s["engine_restarts"] == 1
        assert "degraded" in s["state_transitions"]
        # recovery: the engine serves oracle-identical output
        fut = engine.submit([3, 4, 5], max_new_tokens=8)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [3, 4, 5], 8)
        assert engine.health == "healthy"

    def test_prefill_fault_fails_admitting_request(self, model):
        """A fault during admission (mid-prefill) must fail the request
        being admitted — it is in neither the queue nor a slot at that
        instant."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="prefill", kind="raise")])
        engine = _engine(model, faults=inj, resume=False)
        fut = engine.submit([5, 6, 7], max_new_tokens=6)
        _run_until_done(engine, [fut])
        with pytest.raises(serving.EngineFailedError):
            fut.result(timeout=0)
        fut = engine.submit([5, 6, 7], max_new_tokens=6)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [5, 6, 7], 6)
        assert engine.stats()["engine_restarts"] == 1

    def test_nonfinite_logits_typed_failure(self, model):
        """NaN logits out of the decode tick become a typed engine
        failure (never silently-greedy garbage tokens), then recovery."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="nonfinite")])
        engine = _engine(model, faults=inj, resume=False)
        fut = engine.submit([9, 10], max_new_tokens=5)
        _run_until_done(engine, [fut])
        with pytest.raises(serving.EngineFailedError, match="non-finite"):
            fut.result(timeout=0)
        fut = engine.submit([9, 10], max_new_tokens=5)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [9, 10], 5)

    def test_restart_budget_exhausted_goes_terminal(self, model):
        """Consecutive failures past max_restarts: the engine goes
        terminally failed, resolves the queue, and rejects new submits
        with a typed error — nothing ever hangs on a dead engine."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise",
                              max_fires=None)])
        engine = _engine(model, faults=inj, max_restarts=1, resume=False)
        f1 = engine.submit([1, 2], max_new_tokens=4)
        engine.step()  # admit + decode -> failure #1 -> restart
        assert engine.health == "degraded"
        with pytest.raises(serving.EngineFailedError):
            f1.result(timeout=0)
        f2 = engine.submit([3, 4], max_new_tokens=4)
        f3 = engine.submit([5, 6], max_new_tokens=4)
        engine.step()  # failure #2 > budget -> terminal
        assert engine.health == "failed"
        for f in (f2, f3):  # in-flight AND still-queued both resolved
            with pytest.raises(serving.EngineFailedError):
                f.result(timeout=0)
        with pytest.raises(serving.EngineFailedError):
            engine.submit([7], max_new_tokens=2)
        assert engine.step() is False  # dead engines don't tick
        s = engine.stats()
        assert s["state"] == "failed"
        assert s["engine_restarts"] == 1
        assert s["state_transitions"][-1] == "failed"
        # no phantom in-flight work on a dead engine
        assert s["slots_active"] == 0
        assert engine.slots.free_count == engine.engine_cfg.n_slots


class TestWatchdog:
    @pytest.mark.slow
    def test_stall_resolves_futures_then_recovers(self, model):
        """A hung tick: the watchdog fails in-flight + queued futures
        with EngineStalledError within the budget (the tick may never
        return); when it does return, the supervised restart brings the
        engine back to oracle-exact output."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, n_slots=2, resume=False,
                         tick_timeout=0.3, watchdog_interval=0.02)
        _warm(engine)
        # Scheduled RELATIVE to the post-warm visit count: the warm
        # phase must stay fault-free, and the overlapped pipeline's
        # tick count through warmup differs from the sync loop's.
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=1.2,
            skip=inj.visits("decode_tick") + 2))
        engine.start()
        try:
            t0 = time.monotonic()
            f_run = engine.submit([11, 12, 13], max_new_tokens=30)
            f_queued = engine.submit([14, 15], max_new_tokens=30)
            f_queued2 = engine.submit([16], max_new_tokens=30)
            # n_slots=2: f_run/f_queued admitted, f_queued2 waits.  The
            # 4th decode tick hangs 1.2s; the watchdog declares a stall
            # at ~0.3s and resolves ALL of them typed.
            for f in (f_run, f_queued, f_queued2):
                with pytest.raises(serving.EngineStalledError):
                    f.result(timeout=10.0)
            resolved_in = time.monotonic() - t0
            assert resolved_in < 1.2  # resolved BEFORE the hung tick ends
            assert "failed" in engine.state_transitions
            # the hung tick returns -> supervised restart -> healthy
            assert _wait_for(lambda: engine.health == "healthy")
            fut = engine.submit([11, 12, 13], max_new_tokens=6)
            assert fut.result(timeout=10.0) == _ref_greedy(
                params, cfg, [11, 12, 13], 6)
            s = engine.stats()
            assert s["engine_restarts"] >= 1
            assert "degraded" in s["state_transitions"]
        finally:
            engine.stop()

    def test_terminate_bounded_with_hung_tick_no_watchdog(self, model):
        """Watchdog disabled + hung tick: drain() must not inherit the
        hang (its lock acquire is timed), and terminate() still
        force-resolves every future in bounded time — teardown is
        bounded even when nothing else is."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, tick_timeout=0)
        _warm(engine)
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=1.5,
            skip=inj.visits("decode_tick") + 1))
        engine.start()
        try:
            fut = engine.submit([1, 2], max_new_tokens=10)
            assert _wait_for(lambda: engine.slots.active_count == 1,
                             timeout=5.0)
            time.sleep(0.1)  # now inside the 1.5s hang, _lock held
            t0 = time.monotonic()
            assert engine.drain(timeout=0.3) is False
            assert time.monotonic() - t0 < 1.0  # bounded, not hung
            engine.terminate("operator shutdown")
            assert time.monotonic() - t0 < 2.0
            with pytest.raises(serving.EngineFailedError):
                fut.result(timeout=1.0)
            assert engine.health == "failed"
            # the late-returning tick may only land terminal, never a
            # restart that reopens the engine
            time.sleep(1.6)
            assert engine.health == "failed"
            with pytest.raises(serving.EngineFailedError):
                engine.submit([3], max_new_tokens=2)
        finally:
            engine.stop()

    def test_draining_sticky_across_stall_recovery(self, model):
        """A stall overwrites DRAINING with FAILED; the recovery
        restart must restore DRAINING — never reopen a draining engine
        as DEGRADED behind a still-open listener."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, tick_timeout=0.2,
                         resume=False, watchdog_interval=0.02)
        _warm(engine)
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=0.8,
            skip=inj.visits("decode_tick") + 1))
        engine.start()
        try:
            fut = engine.submit([1, 2], max_new_tokens=20)
            engine.begin_drain()
            with pytest.raises(serving.EngineStalledError):
                fut.result(timeout=10.0)
            assert _wait_for(
                lambda: engine.metrics.engine_restarts.value >= 1)
            assert engine.health == "draining"
            with pytest.raises(serving.DrainingError):
                engine.submit([3], max_new_tokens=2)
        finally:
            engine.stop()

    def test_hang_before_admission_fails_queued(self, model):
        """A stall while requests are still QUEUED (hang at the
        watchdog probe site, before admission) resolves them too — the
        queue is never left behind a hung engine."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="watchdog", kind="hang", delay=0.9,
                              skip=0)])
        engine = _engine(model, faults=inj, tick_timeout=0.2,
                         resume=False, watchdog_interval=0.02)
        # Submit BEFORE start: the very first step hangs ahead of
        # admission, so both requests are queued when the stall lands.
        f1 = engine.submit([1, 2], max_new_tokens=4)
        f2 = engine.submit([3, 4], max_new_tokens=4)
        engine.start()
        try:
            for f in (f1, f2):
                with pytest.raises(serving.EngineStalledError):
                    f.result(timeout=10.0)
            assert _wait_for(lambda: engine.health == "healthy")
        finally:
            engine.stop()


class TestDecodeFetchFaults:
    """Faults at the overlapped pipeline's deferred-fetch boundary —
    the one host sync per steady-state tick, where an async device
    failure from the PREVIOUS tick actually surfaces.  The invariant
    is unchanged: every submitted request resolves with tokens or a
    typed error, and the engine recovers to oracle-exact output with
    zero decode recompiles."""

    def test_fetch_raise_fails_inflight_and_restarts(self, model):
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_fetch", kind="raise",
                              skip=2)])
        engine = _engine(model, faults=inj, resume=False)
        assert engine.engine_cfg.overlap  # the deferred-fetch path
        futs = [engine.submit([3, 4, 5], max_new_tokens=8),
                engine.submit([7, 8], max_new_tokens=8)]
        _run_until_done(engine, futs)
        for f in futs:
            with pytest.raises(serving.EngineFailedError):
                f.result(timeout=0)
        assert inj.fired[0][0] == "decode_fetch"
        s = engine.stats()
        assert s["engine_failures"] == 1 and s["engine_restarts"] == 1
        # recovery: fresh pipeline state, oracle-exact output
        fut = engine.submit([3, 4, 5], max_new_tokens=8)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [3, 4, 5], 8)
        assert engine.decode_compilations == 1

    def test_fetch_hang_trips_watchdog(self, model):
        """A fetch that never returns (device wedged after accepting
        the dispatch): the watchdog resolves in-flight AND queued
        futures inside its budget, and the engine recovers when the
        fetch finally lands."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, n_slots=1, resume=False,
                         tick_timeout=0.25, watchdog_interval=0.02)
        _warm(engine)
        inj.add(serving.FaultSpec(
            site="decode_fetch", kind="hang", delay=1.0,
            skip=inj.visits("decode_fetch") + 1))
        engine.start()
        try:
            t0 = time.monotonic()
            f_run = engine.submit([11, 12], max_new_tokens=30)
            f_queued = engine.submit([13], max_new_tokens=30)
            for f in (f_run, f_queued):
                with pytest.raises(serving.EngineStalledError):
                    f.result(timeout=10.0)
            assert time.monotonic() - t0 < 1.0  # before the hang ends
            assert _wait_for(lambda: engine.health == "healthy")
            fut = engine.submit([11, 12], max_new_tokens=5)
            assert fut.result(timeout=10.0) == _ref_greedy(
                params, cfg, [11, 12], 5)
        finally:
            engine.stop()

    @pytest.mark.slow
    def test_invariant_under_mixed_fetch_faults(self, model):
        """Chaos invariant at the new site with overlap on: raise and
        hang at decode_fetch under load — 100% of requests resolve
        with tokens or a typed error, and the engine ends healthy and
        oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector(seed=3)
        engine = _engine(model, faults=inj, n_slots=2, max_restarts=10,
                         tick_timeout=0.3, watchdog_interval=0.02,
                         max_queue_depth=32)
        # Warm the RESUME buckets too (a resumed prompt is prompt +
        # emitted, i.e. up to 4 + 10 tokens): an unwarmed re-admission
        # would pay XLA compilation inside the 0.3s watchdog budget
        # and read as a second stall.
        _warm(engine, prompt_lens=(3, 7, 15))
        base = inj.visits("decode_fetch")
        inj.add(
            serving.FaultSpec(site="decode_fetch", kind="raise",
                              skip=base + 3),
            serving.FaultSpec(site="decode_fetch", kind="hang",
                              delay=0.8, skip=base + 9),
        )
        engine.start()
        rng = np.random.default_rng(7)
        try:
            futs = []
            for i in range(10):
                prompt = rng.integers(0, cfg.vocab_size,
                                      2 + i % 3).tolist()
                try:
                    futs.append(engine.submit(prompt, max_new_tokens=10))
                except serving.ServingError:
                    pass
            for f in futs:
                try:
                    f.result(timeout=30.0)
                except serving.ServingError:
                    pass  # typed = resolved; TimeoutError would fail
            assert all(f.done() for f in futs)
            burn = time.monotonic() + 20.0
            while not inj.exhausted:
                assert time.monotonic() < burn, "faults never exhausted"
                if engine.health in ("healthy", "degraded"):
                    try:
                        f = engine.submit([1, 2], max_new_tokens=6)
                        try:
                            f.result(timeout=10.0)
                        except serving.ServingError:
                            pass
                    except serving.ServingError:
                        pass
                else:
                    time.sleep(0.05)
            assert _wait_for(lambda: engine.health == "healthy")
            fut = engine.submit([30, 31], max_new_tokens=8)
            assert fut.result(timeout=15.0) == _ref_greedy(
                params, cfg, [30, 31], 8)
            assert engine.stats()["decode_compilations"] == 1
        finally:
            engine.stop()


class TestRestartResume:
    """ACCEPTANCE (ISSUE 9): in-flight requests are DURABLE.  With
    ``resume`` (the default), an engine crash or stall at ANY decode
    depth costs one tick plus one re-prefill, never the request: the
    journaled state (prompt, params, tokens emitted so far) is
    re-admitted after the supervised restart with the ORIGINAL future
    still live, and the concatenated output is byte-identical to the
    no-fault greedy oracle — no ``EngineFailedError`` for resumable
    requests."""

    def _crash_at_depth(self, model, depth, *, site="decode_tick",
                        kind="raise", max_new=8, **kw):
        """Drive a request to ``depth`` emitted tokens, then inject a
        fault on the next visit of ``site``; run to completion."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, **kw)
        fut = engine.submit([3, 4, 5], max_new_tokens=max_new)
        other = engine.submit([7, 8], max_new_tokens=max_new)
        for _ in range(300):
            if len(fut.tokens_so_far()) >= depth or fut.done():
                break
            engine.step()
        assert not fut.done()
        inj.add(serving.FaultSpec(site=site, kind=kind,
                                  skip=inj.visits(site)))
        _run_until_done(engine, [fut, other])
        return engine, fut, other

    # depth 1 rides tier-1; the deeper sweep is budget-marked slow
    # (tests/DURATIONS.md) and runs with the full chaos suite.
    @pytest.mark.parametrize("depth", [
        1,
        pytest.param(2, marks=pytest.mark.slow),
        pytest.param(4, marks=pytest.mark.slow),
        pytest.param(7, marks=pytest.mark.slow),
    ])
    def test_crash_at_every_decode_depth_output_oracle_exact(
            self, model, depth):
        """depth 1 = the first decode tick after admission, 7 =
        the tick producing the LAST token (max_new_tokens=8; token 1
        comes from prefill) — the full sweep the issue demands."""
        params, cfg = model
        engine, fut, other = self._crash_at_depth(model, depth)
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [3, 4, 5], 8)
        assert other.result(timeout=0) == _ref_greedy(params, cfg,
                                                      [7, 8], 8)
        s = engine.stats()
        assert s["engine_restarts"] == 1
        assert s["requests_resumed"] >= 1
        # wasted work is bounded: ONE re-prefill of prompt + emitted
        # per resumed request (plus the crashed tick itself)
        assert s["resume_wasted_tokens"] <= (3 + depth) + (2 + depth + 1)
        assert s["journal_inflight"] == 0  # all entries retired
        assert engine.health == "healthy"

    def test_crash_during_admission_resumes_taken_requests(self, model):
        """Depth 0: a prefill fault hits requests that are TAKEN but
        not yet landed — they resume with zero emitted tokens (a plain
        re-admission) instead of failing typed."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="prefill", kind="raise")])
        engine = _engine(model, faults=inj)
        fut = engine.submit([5, 6, 7], max_new_tokens=6)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [5, 6, 7], 6)
        s = engine.stats()
        assert s["requests_resumed"] == 1
        assert s["engine_restarts"] == 1

    @pytest.mark.slow
    def test_nonfinite_crash_resumes(self, model):
        # Slow (PR 17 budget pass): ~4 s; test_nonfinite_logits_typed_
        # failure keeps the nonfinite detection tier-1 and the resume
        # path is exercised by the rest of TestRestartResume.
        """Non-finite logits poison the tick BEFORE emission — nothing
        from the bad tick is journaled, and the resume replays only
        oracle-emitted tokens."""
        params, cfg = model
        engine, fut, other = self._crash_at_depth(model, 3,
                                                  kind="nonfinite")
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [3, 4, 5], 8)

    def test_fetch_crash_resumes(self, model):
        """A fault at the overlapped pipeline's deferred-fetch boundary
        loses the in-flight tick (the one tick of allowed waste) but
        never an emitted token."""
        params, cfg = model
        engine, fut, other = self._crash_at_depth(model, 2,
                                                  site="decode_fetch")
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [3, 4, 5], 8)
        assert engine.stats()["decode_compilations"] == 1

    def test_repeated_crashes_still_oracle_exact(self, model):
        """Two crashes against the SAME request: emitted tokens
        accumulate in the journal, each resume re-prefills the full
        frontier, output stays exact."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, max_restarts=5)
        fut = engine.submit([9, 10], max_new_tokens=10)
        for depth in (2, 5):
            for _ in range(300):
                if len(fut.tokens_so_far()) >= depth or fut.done():
                    break
                engine.step()
            inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                      skip=inj.visits("decode_tick")))
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [9, 10], 10)
        assert engine.stats()["requests_resumed"] == 2

    def test_fault_in_resume_machinery_degrades_to_typed(self, model):
        """The new ``restart_resume`` fault site: when the resume
        machinery itself fails, the engine falls back to the legacy
        fail-typed restart — in-flight futures resolve with
        EngineFailedError (never a replay from untrusted state), and
        the engine still recovers to oracle-exact output."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise", skip=2),
            serving.FaultSpec(site="restart_resume", kind="raise")])
        engine = _engine(model, faults=inj)
        fut = engine.submit([3, 4, 5], max_new_tokens=8)
        _run_until_done(engine, [fut])
        with pytest.raises(serving.EngineFailedError):
            fut.result(timeout=0)
        assert ("restart_resume", "raise", 0) in inj.fired
        s = engine.stats()
        assert s["requests_resumed"] == 0
        assert s["journal_inflight"] == 0  # still purged, no ghosts
        f2 = engine.submit([3, 4, 5], max_new_tokens=8)
        _run_until_done(engine, [f2])
        assert f2.result(timeout=0) == _ref_greedy(params, cfg,
                                                   [3, 4, 5], 8)

    @pytest.mark.slow
    def test_stall_within_grace_resumes(self, model):
        """A hung tick that RETURNS inside stall_grace: the watchdog
        holds the in-flight futures (no EngineStalledError), and the
        supervised restart resumes them to oracle-exact output."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, n_slots=2,
                         tick_timeout=0.3, watchdog_interval=0.02,
                         stall_grace=15.0)
        _warm(engine, prompt_lens=(3, 5, 9, 17))  # resume buckets too
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=1.0,
            skip=inj.visits("decode_tick") + 2))
        engine.start()
        try:
            fut = engine.submit([11, 12, 13], max_new_tokens=8)
            assert fut.result(timeout=30.0) == _ref_greedy(
                params, cfg, [11, 12, 13], 8)
            s = engine.stats()
            assert s["requests_resumed"] >= 1
            assert "failed" in s["state_transitions"]  # the stall
            assert _wait_for(lambda: engine.health == "healthy")
        finally:
            engine.stop()

    def test_stall_past_grace_hard_fails_bounded(self, model):
        """The bounded-resolution backstop: a stall that outlives
        budget + stall_grace resolves every future typed from the
        watchdog thread, purges the journal (a zombie tick returning
        later finds NOTHING to resume), and the engine still
        recovers."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, n_slots=2,
                         tick_timeout=0.2, watchdog_interval=0.02,
                         stall_grace=0.2)
        _warm(engine)
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=1.5,
            skip=inj.visits("decode_tick") + 2))
        engine.start()
        try:
            t0 = time.monotonic()
            f_run = engine.submit([11, 12, 13], max_new_tokens=30)
            f_q = engine.submit([14, 15], max_new_tokens=30)
            f_q2 = engine.submit([16], max_new_tokens=30)
            for f in (f_run, f_q, f_q2):
                with pytest.raises(serving.EngineStalledError):
                    f.result(timeout=10.0)
            assert time.monotonic() - t0 < 1.5  # before the hang ends
            assert engine.stats()["journal_inflight"] == 0
            # zombie tick returns -> restart finds nothing to resume
            assert _wait_for(lambda: engine.health == "healthy")
            assert engine.stats()["requests_resumed"] == 0
            fut = engine.submit([11, 12], max_new_tokens=5)
            assert fut.result(timeout=15.0) == _ref_greedy(
                params, cfg, [11, 12], 5)
        finally:
            engine.stop()

    def test_deadline_survives_resume(self, model):
        """SATELLITE: the deadline is the REMAINING budget, never a
        fresh one — a deadline that lapses during the restart backoff
        resolves when the resumed request reaches the queue head.
        Since PR 14 an ADMITTED-ONCE request honors the
        deadline-after-admission contract there: it FINISHES with the
        partial tokens a previous life emitted (reason "deadline"),
        never a 504 that discards paid-for output."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, restart_backoff=0.4,
                         restart_backoff_max=0.4)
        _warm(engine)
        fut = engine.submit([3, 4, 5], max_new_tokens=20,
                            deadline=time.monotonic() + 0.3)
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2 or fut.done():
                break
            engine.step()
        assert not fut.done()
        emitted = len(fut.tokens_so_far())
        assert emitted >= 2
        inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=inj.visits("decode_tick")))
        _run_until_done(engine, [fut])
        assert fut.finish_reason == "deadline"
        out = fut.result(timeout=0)  # partial result, no exception
        assert len(out) >= emitted and len(out) < 20
        assert out == _ref_greedy(model[0], model[1],
                                  [3, 4, 5], 20)[:len(out)]

    def test_cancelled_request_not_resumed(self, model):
        """A cancellation pending at crash time resolves as
        "cancelled" (tokens so far) — never re-admitted."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj)
        fut = engine.submit([21, 22], max_new_tokens=20)
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2:
                break
            engine.step()
        fut.cancel()
        inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=inj.visits("decode_tick")))
        _run_until_done(engine, [fut])
        assert fut.finish_reason == "cancelled"
        assert engine.stats()["requests_resumed"] == 0
        assert engine.stats()["journal_inflight"] == 0

    def test_retired_request_never_ghost_readmitted(self, model):
        """SATELLITE (no ghosts): a request that retired BEFORE the
        crash stays retired — its journal entry died with its
        resolution, so the restart re-admits nothing."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj)
        done = engine.submit([5, 6], max_new_tokens=3)
        _run_until_done(engine, [done])
        assert done.result(timeout=0) == _ref_greedy(params, cfg,
                                                     [5, 6], 3)
        admitted_before = engine.metrics.admitted.value
        inj.add(serving.FaultSpec(site="watchdog", kind="raise",
                                  skip=inj.visits("watchdog")))
        fresh = engine.submit([7, 8], max_new_tokens=3)  # drives ticks
        _run_until_done(engine, [fresh])
        s = engine.stats()
        assert s["requests_resumed"] <= 1  # only `fresh` may resume
        # `done` was never re-admitted
        assert engine.metrics.admitted.value <= admitted_before + 1
        assert done.result(timeout=0) == _ref_greedy(params, cfg,
                                                     [5, 6], 3)

    @pytest.mark.slow
    def test_resume_invariant_under_chaos_load(self, model):
        """The PR 3 chaos invariant, upgraded: faults at every site
        under load, and every request whose future was never
        hard-failed completes with tokens ORACLE-EXACT — durability
        composes with the bounded-resolution guarantee."""
        params, cfg = model
        inj = serving.FaultInjector(seed=1)
        engine = _engine(model, faults=inj, n_slots=4, max_restarts=20,
                         max_queue_depth=64)
        _warm(engine, prompt_lens=(3, 7, 15, 29))
        pre, dec = inj.visits("prefill"), inj.visits("decode_tick")
        fetch = inj.visits("decode_fetch")
        inj.add(
            serving.FaultSpec(site="prefill", kind="raise", skip=pre + 1),
            serving.FaultSpec(site="decode_tick", kind="raise",
                              skip=dec + 4),
            serving.FaultSpec(site="decode_fetch", kind="raise",
                              skip=fetch + 9),
            serving.FaultSpec(site="decode_tick", kind="nonfinite",
                              skip=dec + 14),
        )
        rng = np.random.default_rng(5)
        futs, prompts = [], []
        for i in range(12):
            prompt = rng.integers(0, cfg.vocab_size, 2 + i % 7).tolist()
            prompts.append(prompt)
            futs.append(engine.submit(prompt, max_new_tokens=10))
        for _ in range(3000):
            if all(f.done() for f in futs):
                break
            engine.step()
        for prompt, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg,
                                                      prompt, 10)
        s = engine.stats()
        assert s["engine_failures"] >= 4
        assert s["requests_resumed"] >= 4
        assert s["decode_compilations"] == 1  # restarts swap the cache,
        assert s["journal_inflight"] == 0     # never the program
        assert engine.health == "healthy"


class TestJournalDurability:
    """The file-backed journal (EngineConfig.journal_path): what a
    SIGKILL'd replica leaves behind, and what the router reads
    post-mortem (tests/test_router.py proves the cross-process arc)."""

    def test_live_entries_match_futures_and_survive_reread(
            self, model, tmp_path):
        params, cfg = model
        jp = str(tmp_path / "req.journal.jsonl")
        engine = _engine(model, journal_path=jp)
        fut = engine.submit([3, 4, 5], max_new_tokens=8,
                            trace_id="tr-live",
                            deadline=time.monotonic() + 30.0)
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 3:
                break
            engine.step()
        live = serving.RequestJournal.read_live(jp)
        desc = live["tr-live"]
        assert desc["emitted_tokens"] == fut.tokens_so_far()
        assert desc["prompt"] == [3, 4, 5]
        assert desc["max_new_tokens"] == 8
        assert 0 < desc["deadline_remaining_ms"] <= 30000
        _run_until_done(engine, [fut])
        assert serving.RequestJournal.read_live(jp) == {}

    def test_terminate_purges_journal_no_ghosts(self, model, tmp_path):
        """SATELLITE: terminate() of a resumable request purges its
        journal entry — the post-mortem reader sees nothing to
        resume."""
        jp = str(tmp_path / "req.journal.jsonl")
        engine = _engine(model, journal_path=jp)
        fut = engine.submit([3, 4, 5], max_new_tokens=20,
                            trace_id="tr-term")
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2:
                break
            engine.step()
        assert len(serving.RequestJournal.read_live(jp)) == 1
        engine.terminate("operator shutdown")
        with pytest.raises(serving.EngineFailedError):
            fut.result(timeout=0)
        assert serving.RequestJournal.read_live(jp) == {}
        assert len(engine.journal) == 0

    def test_journal_links_resume_into_the_originating_span(
            self, model, tmp_path):
        """SATELLITE (ISSUE 12): journal entries carry the originating
        SPAN id, so a post-mortem lookup after a SIGKILL hands the
        router the dead attempt's span — the resumed attempt links
        into the SAME trace tree instead of starting an orphan.  The
        id must survive the full round trip: begin record, compaction
        rewrite, and the read_live descriptor."""
        jp = str(tmp_path / "req.journal.jsonl")
        engine = _engine(model, journal_path=jp)
        fut = engine.submit([3, 4, 5], max_new_tokens=12,
                            trace_id="tr-span")
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2:
                break
            engine.step()
        span_id = fut.trace.span_id
        assert span_id  # minted at submit, with or without a recorder
        live = serving.RequestJournal.read_live(jp)
        assert live["tr-span"]["span_id"] == span_id
        # compaction preserves it (the rewrite path re-serializes)
        engine.journal._dead_lines = engine.journal.COMPACT_AFTER
        engine.journal.end(-1)  # no-op purge, but triggers nothing
        with engine.journal._lock:
            engine.journal._compact_locked()
        live = serving.RequestJournal.read_live(jp)
        assert live["tr-span"]["span_id"] == span_id
        _run_until_done(engine, [fut])

    def test_arrival_and_stream_survive_roundtrip_and_compaction(
            self, model, tmp_path):
        """SATELLITE (ISSUE 17): begin lines carry the request's
        ARRIVAL (monotonic offset from journal open + wall clock) and
        streaming flag, so a journaled trace replays at original
        spacing (horovod_tpu/tuning/replay.py).  Both must survive
        the full round trip — begin record, compaction rewrite,
        read_live — and stay OPTIONAL for old journals (a begin line
        without them still parses)."""
        jp = str(tmp_path / "req.journal.jsonl")
        engine = _engine(model, journal_path=jp)
        fut = engine.submit([3, 4, 5], max_new_tokens=12,
                            trace_id="tr-arr",
                            on_token=lambda t, p: None)
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2:
                break
            engine.step()
        raw = [json.loads(ln) for ln in open(jp)]
        begin = [ev for ev in raw if ev["e"] == "b"][0]
        mono, wall = begin["arr"]
        assert 0.0 <= mono < 60.0          # offset from journal open
        assert abs(wall - time.time()) < 60.0
        assert begin["stream"] == 1        # on_token was set
        # compaction re-serializes live entries: both fields survive
        with engine.journal._lock:
            engine.journal._compact_locked()
        raw = [json.loads(ln) for ln in open(jp)]
        begin2 = [ev for ev in raw if ev["e"] == "b"][0]
        assert begin2["arr"] == [mono, wall]
        assert begin2["stream"] == 1
        # ... and through the replay-trace reader
        from horovod_tpu.tuning.replay import read_trace

        req = read_trace(jp)[0]
        assert (req.arrival, req.stream) == (mono, True)
        # byte-compat: a pre-arrival begin line (no arr/stream keys)
        # still parses, replaying at zero offset, non-streamed
        with open(jp, "w") as f:
            f.write('{"e":"b","id":9,"prompt":[1,2],"max_new":4,'
                    '"trace":"tr-old"}\n')
        old = read_trace(jp)[0]
        assert (old.arrival, old.stream) == (0.0, False)
        assert serving.RequestJournal.read_live(jp)  # old reader path
        _run_until_done(engine, [fut])

    def test_torn_final_line_tolerated(self, model, tmp_path):
        """A SIGKILL can land mid-write: every complete line before
        the torn one still parses."""
        jp = str(tmp_path / "req.journal.jsonl")
        engine = _engine(model, journal_path=jp)
        fut = engine.submit([3, 4], max_new_tokens=8, trace_id="tr-torn")
        for _ in range(300):
            if len(fut.tokens_so_far()) >= 2:
                break
            engine.step()
        with open(jp, "a") as f:
            f.write('{"e":"t","id":')  # torn mid-write
        live = serving.RequestJournal.read_live(jp)
        assert live["tr-torn"]["emitted_tokens"] == fut.tokens_so_far()

    def test_http_engine_failed_carries_resume_descriptor(self, model):
        """SATELLITE (contract upward): a terminal engine failure's
        503 carries the resume descriptor — emitted tokens and the
        REMAINING deadline budget — so a front tier can continue the
        request elsewhere."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, max_restarts=0)
        _warm(engine)
        inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=inj.visits("decode_tick") + 2))
        with serving.ServingServer(engine, port=0,
                                   request_timeout=30.0) as srv:
            host, port = srv.address
            code, out = _post(
                f"http://{host}:{port}/generate",
                {"tokens": [1, 2], "max_new_tokens": 30,
                 "timeout_ms": 25000})
            assert (code, out["type"]) == (503, "engine_failed")
            res = out["resume"]
            assert len(res["emitted_tokens"]) >= 1
            assert 0 < res["deadline_remaining_ms"] <= 25000


class TestCancellation:
    def test_cancel_midstream_reclaims_slot(self, model):
        params, cfg = model
        engine = _engine(model)
        fut = engine.submit([21, 22], max_new_tokens=30)
        engine.step()
        engine.step()
        n_before = len(fut.tokens_so_far())
        assert 0 < n_before < 30
        assert fut.cancel() is True
        engine.step()  # reclamation tick
        assert fut.done() and fut.finish_reason == "cancelled"
        assert fut.cancelled
        toks = fut.result(timeout=0)  # resolves with partial tokens
        assert len(toks) == n_before < 30
        assert engine.slots.active_count == 0  # slot reclaimed
        assert engine.stats()["requests_cancelled"] == 1
        # the freed slot serves the next request, oracle-exact
        fut = engine.submit([21, 22], max_new_tokens=5)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [21, 22], 5)

    def test_cancel_queued_never_admitted(self, model):
        engine = _engine(model, n_slots=1)
        f_run = engine.submit([1, 2], max_new_tokens=20)
        f_queued = engine.submit([3, 4], max_new_tokens=20)
        engine.step()  # f_run takes the only slot
        assert f_queued.cancel() is True
        engine.step()  # queue purge: cancelled head never takes a slot
        assert f_queued.done() and f_queued.finish_reason == "cancelled"
        assert f_queued.result(timeout=0) == []
        assert engine.stats()["requests_admitted"] == 1
        f_run.cancel()
        engine.step()
        assert f_run.done()

    def test_cancel_after_done_is_noop(self, model):
        engine = _engine(model)
        fut = engine.submit([5, 6], max_new_tokens=2)
        _run_until_done(engine, [fut])
        assert fut.cancel() is False
        assert fut.finish_reason == "length"


class TestChaosInvariant:
    @pytest.mark.slow
    def test_no_submitted_request_ever_hangs(self, model):
        """ACCEPTANCE: faults at every site — raise, non-finite, and a
        watchdog-tripping hang — against a loaded background engine.
        100% of submitted requests resolve with tokens or a typed
        error within a bounded wall-clock (zero hung futures), the
        engine recovers, serves oracle-identical greedy output, and
        the restarts + health transitions are visible in stats."""
        params, cfg = model
        inj = serving.FaultInjector(seed=0)
        engine = _engine(model, faults=inj, n_slots=4, max_restarts=10,
                         tick_timeout=0.3, watchdog_interval=0.02,
                         max_queue_depth=64)
        # Every prompt bucket AND every resume bucket (prompt + up to
        # 16 emitted tokens -> bucket 32), every k: a resumed
        # re-admission must never pay XLA compilation inside the 0.3s
        # watchdog budget.
        _warm(engine, prompt_lens=(3, 7, 15, 29))
        # Faults scheduled RELATIVE to the post-warm visit counts so
        # every spec fires under the load phase, not during warmup.
        pre, dec = inj.visits("prefill"), inj.visits("decode_tick")
        inj.add(
            serving.FaultSpec(site="prefill", kind="raise", skip=pre + 1),
            serving.FaultSpec(site="decode_tick", kind="raise",
                              skip=dec + 4),
            serving.FaultSpec(site="decode_tick", kind="nonfinite",
                              skip=dec + 9),
            serving.FaultSpec(site="decode_tick", kind="hang",
                              delay=0.8, skip=dec + 14),
        )
        engine.start()
        rng = np.random.default_rng(5)
        t0 = time.monotonic()
        try:
            futs = []
            for i in range(16):
                prompt = rng.integers(0, cfg.vocab_size,
                                      2 + i % 7).tolist()
                try:
                    futs.append(engine.submit(prompt, max_new_tokens=16))
                except serving.ServingError:
                    pass  # typed submit-time rejection = resolved too
            # THE invariant: every future resolves inside the bound —
            # tokens or a typed ServingError, never a hang.
            outcomes = {"ok": 0, "typed_error": 0}
            for f in futs:
                try:
                    f.result(timeout=30.0)
                    outcomes["ok"] += 1
                except serving.ServingError:
                    outcomes["typed_error"] += 1
            # (TimeoutError would propagate and fail the test: a hang.)
            assert outcomes["ok"] + outcomes["typed_error"] == len(futs)
            assert time.monotonic() - t0 < 60.0

            # Burn off any fault that hasn't fired yet (e.g. the hang,
            # if earlier failures emptied the pool first) so recovery
            # is tested on a genuinely fault-free engine.
            burn_deadline = time.monotonic() + 30.0
            while not inj.exhausted:
                assert time.monotonic() < burn_deadline, \
                    "faults never exhausted"
                if engine.health in ("healthy", "degraded"):
                    try:
                        f = engine.submit([1, 2, 3], max_new_tokens=8)
                        try:
                            f.result(timeout=10.0)
                        except serving.ServingError:
                            pass
                    except serving.ServingError:
                        pass
                else:
                    time.sleep(0.05)

            assert _wait_for(lambda: engine.health == "healthy")
            # Recovery correctness: oracle-identical greedy output.
            prompt = [30, 31, 32]
            fut = engine.submit(prompt, max_new_tokens=10)
            assert fut.result(timeout=15.0) == _ref_greedy(
                params, cfg, prompt, 10)
            s = engine.stats()
            assert s["engine_failures"] >= 4   # all four specs fired
            assert s["engine_restarts"] >= 3
            assert s["state"] == "healthy"
            assert "degraded" in s["state_transitions"]
            assert "failed" in s["state_transitions"]  # the stall
            # the decode executable NEVER recompiled — restarts swap
            # the cache, not the program
            assert s["decode_compilations"] == 1
        finally:
            engine.stop()


class TestChunkedPrefillChaos:
    """The ``prefill_chunk`` FaultInjector site (PR 14): chunk-
    boundary crashes are in the chaos invariant — a fault at ANY
    chunk of a chunked prompt ingestion suspends the request through
    the ordinary resume path and the re-ingested output is
    token-identical to the no-fault oracle."""

    # chunks 1/3 are slow (PR 17 budget pass): chunk 0 keeps the
    # crash-at-a-chunk-boundary resume path tier-1; the later
    # boundaries re-run the same site with landed pages to discard.
    @pytest.mark.parametrize(
        "chunk_idx",
        [0,
         pytest.param(1, marks=pytest.mark.slow),
         pytest.param(3, marks=pytest.mark.slow)])
    def test_crash_at_each_chunk_boundary_oracle_exact(self, model,
                                                       chunk_idx):
        params, cfg = model
        inj = serving.FaultInjector([serving.FaultSpec(
            site="prefill_chunk", kind="raise", skip=chunk_idx)])
        engine = _engine(model, faults=inj, prefill_chunk_tokens=8,
                         tick_timeout=0)
        rng = np.random.default_rng(31 + chunk_idx)
        long_p = rng.integers(1, cfg.vocab_size, 30).tolist()
        short_p = [4, 2]
        vic = engine.submit(long_p, max_new_tokens=4)
        sh = engine.submit(short_p, max_new_tokens=3)
        _run_until_done(engine, [vic, sh], max_ticks=600)
        assert inj.fired == [("prefill_chunk", "raise", chunk_idx)]
        assert vic.result(timeout=0) == _ref_greedy(
            params, cfg, long_p, 4)
        assert sh.result(timeout=0) == _ref_greedy(
            params, cfg, short_p, 3)
        s = engine.stats()
        assert s["engine_restarts"] == 1
        assert s["decode_compilations"] <= 1
        assert s["slots_ingesting"] == 0 and s["queue_depth"] == 0

    @pytest.mark.slow
    def test_chunk_hang_trips_watchdog_and_resumes(self, model):
        # Slow (PR 17 budget pass): hang + watchdog grace is ~8 s;
        # test_fetch_hang_trips_watchdog keeps the hang-site watchdog
        # path tier-1 and chunk crashes are covered just above.
        """A HANG inside a chunk dispatch trips the watchdog like any
        stalled tick; the tick returns inside the resume grace, the
        supervised restart re-ingests, and output stays
        oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, prefill_chunk_tokens=8,
                         tick_timeout=0.3, watchdog_interval=0.02,
                         stall_grace=10.0)
        _warm(engine, prompt_lens=(3,))
        # warm the chunk shapes too, fault-free, then schedule the
        # hang relative to the post-warm visit count
        rng = np.random.default_rng(37)
        warm_p = rng.integers(1, cfg.vocab_size, 30).tolist()
        f0 = engine.submit(warm_p, max_new_tokens=2)
        _run_until_done(engine, [f0], max_ticks=600)
        inj.add(serving.FaultSpec(site="prefill_chunk", kind="hang",
                                  delay=0.8,
                                  skip=inj.visits("prefill_chunk") + 1))
        engine.start()
        try:
            long_p = rng.integers(1, cfg.vocab_size, 30).tolist()
            fut = engine.submit(long_p, max_new_tokens=4)
            assert fut.result(timeout=30.0) == _ref_greedy(
                params, cfg, long_p, 4)
            assert engine.metrics.engine_failures.value >= 1
        finally:
            engine.stop()


class TestTraceFailurePaths:
    """Trace-id + breakdown propagation through the FAILURE paths: the
    whole point of Dapper-style ids is answering "where did request X
    go" when it did NOT come back clean — so cancel, 504, watchdog
    stall, and supervised restart must all resolve with the id and the
    timing stamps intact."""

    def test_trace_survives_cancel(self, model):
        engine = _engine(model)
        fut = engine.submit([21, 22], max_new_tokens=30,
                            trace_id="tr-cancel")
        engine.step()
        engine.step()
        assert fut.cancel() is True
        engine.step()  # reclamation tick
        assert fut.done() and fut.finish_reason == "cancelled"
        assert fut.trace_id == "tr-cancel"
        b = fut.breakdown()
        assert b["finish"] == "cancelled"
        assert b["queue_wait_s"] >= 0 and b["prefill_s"] >= 0
        assert b["tokens"] == len(fut.result(timeout=0))
        assert b["total_s"] >= b["queue_wait_s"]

    def test_trace_survives_restart(self, model):
        """A mid-decode device fault: the doomed future resolves typed
        with its trace intact (error name in the breakdown), and the
        post-restart request traces independently."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise", skip=1)])
        engine = _engine(model, faults=inj, resume=False)
        doomed = engine.submit([3, 4, 5], max_new_tokens=8,
                               trace_id="tr-doomed")
        _run_until_done(engine, [doomed])
        with pytest.raises(serving.EngineFailedError):
            doomed.result(timeout=0)
        assert doomed.trace_id == "tr-doomed"
        b = doomed.breakdown()
        assert b["finish"] == "EngineFailedError"
        assert b["queue_wait_s"] is not None and b["total_s"] > 0
        fut = engine.submit([3, 4, 5], max_new_tokens=4,
                            trace_id="tr-after")
        _run_until_done(engine, [fut])
        assert fut.breakdown()["finish"] == "length"
        assert fut.trace_id == "tr-after"

    def test_trace_survives_watchdog_stall(self, model):
        """The watchdog resolves futures from ITS thread — the trace
        must be stamped there too, with the stall's typed error."""
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, n_slots=1, resume=False,
                         tick_timeout=0.3, watchdog_interval=0.02)
        _warm(engine)
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="hang", delay=1.2,
            skip=inj.visits("decode_tick") + 2))
        engine.start()
        try:
            f_run = engine.submit([11, 12, 13], max_new_tokens=30,
                                  trace_id="tr-stalled")
            # n_slots=1: this one stays QUEUED through the stall
            f_queued = engine.submit([14, 15], max_new_tokens=30,
                                     trace_id="tr-queued")
            for f in (f_run, f_queued):
                with pytest.raises(serving.EngineStalledError):
                    f.result(timeout=10.0)
            assert f_run.trace_id == "tr-stalled"
            assert f_run.breakdown()["finish"] == "EngineStalledError"
            # the queued one was never admitted: queue_wait covers its
            # whole life, prefill/decode stay None
            bq = f_queued.breakdown()
            assert bq["trace_id"] == "tr-queued"
            assert bq["finish"] == "EngineStalledError"
            assert bq["prefill_s"] is None
            assert bq["queue_wait_s"] == bq["total_s"]
        finally:
            engine.stop()

    @pytest.mark.slow
    def test_trace_survives_http_504(self, model):
        # Slow (PR 17 budget pass): ~5 s; test_trace_survives_watchdog
        # _stall keeps the trace-through-failure property tier-1 and
        # the 504 path itself is covered by test_504_cancels_and_
        # frees_slot.
        """The 504-timeout path: the client's X-Trace-Id comes back on
        the error payload with the partial breakdown, and the engine's
        cancel keeps the id through slot reclamation."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="hang",
                              delay=0.05, max_fires=None)])
        engine = _engine(model, faults=inj, n_slots=2)
        _warm(engine)
        with serving.ServingServer(engine, port=0, request_timeout=0.4,
                                   timeout_grace=0.1) as srv:
            host, port = srv.address
            req = urllib.request.Request(
                f"http://{host}:{port}/generate",
                data=json.dumps({"tokens": [1, 2], "max_new_tokens": 38,
                                 "timeout_ms": 60000}).encode(),
                headers={"Content-Type": "application/json",
                         "X-Trace-Id": "tr-504"})
            try:
                urllib.request.urlopen(req, timeout=30)
                raise AssertionError("expected 504")
            except urllib.error.HTTPError as e:
                assert e.code == 504
                out = json.loads(e.read())
                hdr = e.headers["X-Trace-Id"]
            assert out["type"] == "timeout"
            assert out["trace_id"] == hdr == "tr-504"
            assert out["breakdown"]["trace_id"] == "tr-504"
            assert out["breakdown"]["total_s"] > 0
            assert _wait_for(lambda: engine.slots.active_count == 0,
                             timeout=2.0)

    def test_metrics_endpoint_valid_during_failure(self, model):
        """GOLDEN: /metrics still parses as valid Prometheus text on a
        terminally failed engine, and the failure counters are
        visible in the scrape."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise",
                              max_fires=None)])
        engine = _engine(model, faults=inj, max_restarts=0)
        with serving.ServingServer(engine, port=0) as srv:
            host, port = srv.address
            base = f"http://{host}:{port}"
            code, out = _post(base + "/generate",
                              {"tokens": [1, 2], "max_new_tokens": 4})
            assert code == 503
            assert _wait_for(lambda: engine.health == "failed")
            with urllib.request.urlopen(base + "/metrics", timeout=10) as r:
                fams = parse_prometheus_text(r.read().decode())
            assert fams["serving_engine_failures_total"][
                "samples"][0][2] >= 1
            assert "serving_ttft_seconds" in fams
            assert "elastic_restarts_total" in fams  # default registry too


class TestServerFaultTolerance:
    def _serve(self, engine, **kw):
        return serving.ServingServer(engine, port=0, **kw)

    def test_healthz_tracks_state_machine(self, model):
        """healthy -> 200; failed -> 503 (load balancers stop
        routing); stats carry the transition trail."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise",
                              max_fires=None)])
        engine = _engine(model, faults=inj, max_restarts=0)
        with self._serve(engine) as srv:
            host, port = srv.address
            base = f"http://{host}:{port}"
            with urllib.request.urlopen(base + "/healthz",
                                        timeout=10) as r:
                assert json.loads(r.read())["status"] == "healthy"
            code, out = _post(base + "/generate",
                              {"tokens": [1, 2], "max_new_tokens": 4})
            assert code == 503
            assert out["type"] == "engine_failed"
            assert _wait_for(lambda: engine.health == "failed")
            try:
                urllib.request.urlopen(base + "/healthz", timeout=10)
                raise AssertionError("expected 503")
            except urllib.error.HTTPError as e:
                assert e.code == 503
                assert json.loads(e.read())["status"] == "failed"
            with urllib.request.urlopen(base + "/stats", timeout=10) as r:
                s = json.loads(r.read())
            assert s["state"] == "failed"
            assert s["engine_failures"] >= 1

    def test_504_cancels_and_frees_slot(self, model):
        """The 504 slot-leak fix: an HTTP timeout cancels the request,
        so the slot frees on the next tick instead of decoding to
        max_new_tokens for a caller that already got its error page."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="hang",
                              delay=0.05, max_fires=None)])
        engine = _engine(model, faults=inj, n_slots=2)
        _warm(engine)
        # explicit timeout_ms >> request_timeout: the engine deadline
        # never fires, so only the HTTP timeout (and its cancel) can
        # free the slot.
        with self._serve(engine, request_timeout=0.4,
                         timeout_grace=0.1) as srv:
            host, port = srv.address
            t0 = time.monotonic()
            code, out = _post(
                f"http://{host}:{port}/generate",
                {"tokens": [1, 2], "max_new_tokens": 38,
                 "timeout_ms": 60000})
            assert (code, out["type"]) == (504, "timeout")
            # 38 tokens x >=50ms/tick ~= 2s of decoding left; the
            # cancel must free the slot in ~one tick instead.
            assert _wait_for(lambda: engine.slots.active_count == 0,
                             timeout=1.0)
            assert time.monotonic() - t0 < 1.8
            assert engine.stats()["requests_cancelled"] == 1

    @pytest.mark.slow
    def test_default_deadline_from_request_timeout(self, model):
        # Slow (PR 17 budget pass): ~5 s; test_deadline_survives_resume
        # keeps deadline plumbing tier-1 end to end.
        """No client timeout_ms: the engine deadline defaults to the
        server's request_timeout, so the request deadline-retires with
        a partial result instead of running to max_new_tokens."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="hang",
                              delay=0.05, max_fires=None)])
        engine = _engine(model, faults=inj, n_slots=2)
        _warm(engine)
        with self._serve(engine, request_timeout=0.4) as srv:
            host, port = srv.address
            code, out = _post(f"http://{host}:{port}/generate",
                              {"tokens": [1, 2], "max_new_tokens": 38})
            assert code == 200
            assert out["finish_reason"] == "deadline"
            assert 1 <= len(out["tokens"]) < 38

    def test_drain_under_load(self, model):
        """stop(drain_timeout): a burst in flight completes, new
        requests get 503 draining, /healthz goes non-200, and teardown
        lands inside the budget."""
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="hang",
                              delay=0.03, max_fires=None)])
        engine = _engine(model, faults=inj, n_slots=4)
        _warm(engine)
        warm_admitted = engine.metrics.admitted.value
        srv = self._serve(engine, request_timeout=60.0).start()
        host, port = srv.address
        base = f"http://{host}:{port}"

        results = [None] * 6
        def client(i):
            results[i] = _post(base + "/generate",
                               {"tokens": [1 + i, 2 + i],
                                "max_new_tokens": 12})
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        # every client is IN the system (admitted or queued) before the
        # drain starts — none may be shed as 503 by a racing stop()
        # (admissions counted relative to the warm-up's)
        assert _wait_for(lambda: engine.metrics.admitted.value
                         - warm_admitted
                         + engine.scheduler.depth >= 6)

        t0 = time.monotonic()
        stopper = threading.Thread(target=lambda: srv.stop(
            drain_timeout=20.0))
        stopper.start()
        assert _wait_for(lambda: engine.health == "draining")
        # burst still decoding (>=8 ticks x 30ms left): probe the
        # draining server while it is provably mid-drain
        code, out = _post(base + "/generate", {"tokens": [9],
                                               "max_new_tokens": 2})
        assert (code, out["type"]) == (503, "draining")
        try:
            urllib.request.urlopen(base + "/healthz", timeout=10)
            raise AssertionError("expected 503")
        except urllib.error.HTTPError as e:
            assert e.code == 503
            assert json.loads(e.read())["status"] == "draining"
        stopper.join(25.0)
        assert not stopper.is_alive()
        assert time.monotonic() - t0 < 22.0  # teardown inside budget
        for t in threads:
            t.join(10.0)
        # every admitted request completed normally through the drain
        assert all(r is not None and r[0] == 200
                   and r[1]["finish_reason"] == "length"
                   for r in results)
        assert engine.slots.active_count == 0
        assert engine.scheduler.depth == 0

    @pytest.mark.slow
    def test_chaos_soak_http(self, model):
        """Long soak: rolling faults under concurrent HTTP traffic;
        every response is 200 or a typed error payload, and the engine
        ends healthy and oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector([
            serving.FaultSpec(site="decode_tick", kind="raise",
                              skip=9, max_fires=3, p=0.5),
            serving.FaultSpec(site="prefill", kind="raise",
                              skip=12, max_fires=2, p=0.5),
        ], seed=11)
        engine = _engine(model, faults=inj, n_slots=4, max_restarts=50)
        _warm(engine, prompt_lens=(3, 7))
        rng = np.random.default_rng(13)
        with self._serve(engine, request_timeout=30.0) as srv:
            host, port = srv.address
            base = f"http://{host}:{port}"
            results = [None] * 32

            def client(i):
                p = rng.integers(0, cfg.vocab_size, 2 + i % 6).tolist()
                results[i] = _post(base + "/generate",
                                   {"tokens": p, "max_new_tokens":
                                    2 + i % 8}, timeout=60.0)

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(32)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(90.0)
            assert all(r is not None for r in results)  # nothing hung
            assert all(r[0] in (200, 429, 503, 504) for r in results)
            assert _wait_for(lambda: engine.health == "healthy")
            prompt = [40, 41]
            code, out = _post(base + "/generate",
                              {"tokens": prompt, "max_new_tokens": 6})
            assert code == 200
            assert out["tokens"] == _ref_greedy(params, cfg, prompt, 6)


@pytest.mark.slow
class TestTunerResetOnRecover:
    """Regression (docs/serving.md "Self-tuning"): a supervised
    restart must DROP the online tuner's scoring-window baseline.
    The baseline predates the crash, so scoring the first post-restart
    window against it would charge the dead time + resume re-prefills
    to whatever knob setting happened to be live — garbage that can
    trip a spurious SLO rollback.  Slow (an autotune engine's full
    warm sweep); tier-1 siblings: test_tuning.py's
    test_reset_window_drops_baseline covers the reset itself, and
    TestSupervisedRestart here covers the _recover path every run."""

    def test_recover_resets_tuner_window(self, model):
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(model, faults=inj, autotune=True)
        _warm(engine)                      # installs the tuner
        tuner = engine._tuner
        assert tuner is not None
        # a couple of worked ticks so a window baseline is OPEN
        fut = engine.submit([9, 10], max_new_tokens=4)
        _run_until_done(engine, [fut])
        assert tuner._window is not None
        resets = []
        orig = tuner.reset_window
        tuner.reset_window = lambda: (resets.append(1), orig())[-1]
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="raise",
            skip=inj.visits("decode_tick") + 1))
        futs = [engine.submit([3, 4, 5], max_new_tokens=8)]
        _run_until_done(engine, futs)
        assert engine.stats()["engine_restarts"] == 1
        assert resets, "_recover never reset the tuner window"
        # recovery still serves the oracle, and the resumed request's
        # output is byte-identical through the restart
        assert futs[0].result(timeout=0) == _ref_greedy(
            params, cfg, [3, 4, 5], 8)
        fut = engine.submit([6, 7], max_new_tokens=6)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [6, 7], 6)
