"""Overlapped decode pipeline (EngineConfig.overlap) A/B oracle suite.

THE acceptance check for the pipelined engine: with ``overlap=True``
(device-resident token loop, one-tick-lag retirement, batched prefill)
every request's greedy output is TOKEN-IDENTICAL to the synchronous
path (``overlap=False``) and to per-request ``greedy_decode`` — across
staggered admissions, EOS / length retirement, cancellation, and
supervised restart — while the decode executable still never
recompiles and the batched-prefill compile set stays bounded by
buckets x max_prefills_per_tick.

The ``perf``-marked test is the hot-path regression guard: steady-state
overlapped decode performs at most ONE host sync per dispatched tick
(the deferred fetch of the previous tick) — an accidental
``np.asarray`` / ``block_until_ready`` creeping back onto the hot path
shows up as a ratio above 1.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T

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


def _ref_greedy(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _engine(model, overlap, **kw):
    params, cfg = model
    defaults = dict(n_slots=4, max_len=40, min_prefill_bucket=4,
                    max_prefills_per_tick=2, max_queue_depth=16,
                    restart_backoff=0.01, restart_backoff_max=0.05,
                    overlap=overlap)
    defaults.update(kw)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**defaults))


def _run_until_done(engine, futs, max_ticks=400):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


# Mixed workload exercised identically in both modes: unequal prompt
# lengths (two buckets), unequal completion lengths (slot reuse), an
# explicit EOS stop, and more requests than slots.
_CASES = [
    ([3, 4, 5, 6], 9, None),
    ([10, 11], 5, None),
    ([7, 8, 9, 1, 2, 3, 4, 5, 6], 7, None),  # second bucket
    ([12, 13, 14], 11, None),
    ([5, 6], 4, None),
    ([20, 21, 22], 12, "eos"),  # eos_id patched to a really-emitted token
]


class TestOverlapOracle:
    @pytest.mark.slow
    def test_ab_token_identity_staggered(self, model):
        """ACCEPTANCE: the same staggered workload through overlap=True
        and overlap=False produces identical token streams, both equal
        to per-request greedy_decode; EOS and length retirements land
        identically; decode never recompiles in either mode."""
        params, cfg = model
        # Resolve the EOS case against the oracle first: stop at a
        # token greedy really emits mid-stream.
        cases = []
        for prompt, steps, kind in _CASES:
            ref = _ref_greedy(params, cfg, prompt, steps)
            eos = ref[2] if kind == "eos" else None
            cases.append((prompt, steps, eos, ref))

        outs = {}
        for overlap in (True, False):
            engine = _engine(model, overlap)
            futs = []
            for prompt, steps, eos, _ in cases:
                futs.append(engine.submit(prompt, max_new_tokens=steps,
                                          eos_id=eos))
                engine.step()  # staggered: admissions land mid-decode
                engine.step()
            _run_until_done(engine, futs)
            assert engine.decode_compilations == 1
            outs[overlap] = [(f.result(timeout=0), f.finish_reason)
                             for f in futs]

        assert outs[True] == outs[False]  # the A/B identity
        for (prompt, steps, eos, ref), (toks, reason) in zip(
                cases, outs[True]):
            if eos is None:
                assert toks == ref
                assert reason == "length"
            else:
                assert toks == ref[:ref.index(eos) + 1]
                assert reason == "eos"

    @pytest.mark.slow
    def test_ab_with_cancellation(self, model):
        """Mid-stream cancellation at the same emission point in both
        modes: the cancelled future resolves with the same partial
        tokens, and the reused slot's later output stays
        oracle-exact."""
        params, cfg = model
        outs = {}
        for overlap in (True, False):
            engine = _engine(model, overlap, n_slots=2)
            victim = engine.submit([9, 8, 7], max_new_tokens=30)
            other = engine.submit([3, 4], max_new_tokens=8)
            while len(victim.tokens_so_far()) < 3:
                engine.step()
            n_at_cancel = len(victim.tokens_so_far())
            assert victim.cancel() is True
            late = engine.submit([5, 6, 7, 8], max_new_tokens=6)
            _run_until_done(engine, [victim, other, late])
            assert victim.finish_reason == "cancelled"
            outs[overlap] = (victim.result(timeout=0)[:n_at_cancel],
                             other.result(timeout=0),
                             late.result(timeout=0))
        assert outs[True][0] == outs[False][0][:len(outs[True][0])]
        assert outs[True][1] == outs[False][1] == _ref_greedy(
            params, cfg, [3, 4], 8)
        assert outs[True][2] == outs[False][2] == _ref_greedy(
            params, cfg, [5, 6, 7, 8], 6)

    def test_ab_across_restart(self, model):
        """A mid-decode device fault in each mode: the in-flight
        request RESUMES across the restart (journaled decode state,
        same future) and its output is oracle-exact in both modes —
        the pipeline state (device tokens, in-flight tick) is rebuilt
        from scratch, and the one-tick-lag identity snapshot keeps the
        overlapped path's journal identical to the sync path's."""
        params, cfg = model
        for overlap in (True, False):
            inj = serving.FaultInjector([
                serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=2)])
            engine = _engine(model, overlap, faults=inj)
            survivor = engine.submit([1, 2, 3], max_new_tokens=10)
            _run_until_done(engine, [survivor])
            assert survivor.result(timeout=0) == _ref_greedy(
                params, cfg, [1, 2, 3], 10)
            fut = engine.submit([1, 2, 3], max_new_tokens=10)
            _run_until_done(engine, [fut])
            assert fut.result(timeout=0) == _ref_greedy(
                params, cfg, [1, 2, 3], 10)
            s = engine.stats()
            assert s["engine_restarts"] == 1
            assert s["requests_resumed"] == 1
            # restarts swap the cache, never the compiled tick
            assert engine.decode_compilations == 1

    @pytest.mark.slow
    def test_ab_across_restart_legacy_fail_typed(self, model):
        """resume=False (the pre-journal contract): the in-flight
        batch fails typed in both modes, and post-restart output is
        oracle-exact.  Slow (PR 17 budget pass): ~7 s; test_chaos's
        typed-failure tests keep the resume=False contract tier-1."""
        params, cfg = model
        for overlap in (True, False):
            inj = serving.FaultInjector([
                serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=2)])
            engine = _engine(model, overlap, faults=inj, resume=False)
            doomed = engine.submit([1, 2, 3], max_new_tokens=10)
            _run_until_done(engine, [doomed])
            with pytest.raises(serving.EngineFailedError):
                doomed.result(timeout=0)
            fut = engine.submit([1, 2, 3], max_new_tokens=10)
            _run_until_done(engine, [fut])
            assert fut.result(timeout=0) == _ref_greedy(
                params, cfg, [1, 2, 3], 10)
            assert engine.stats()["engine_restarts"] == 1
            assert engine.decode_compilations == 1

    def test_prefill_compile_set_bounded(self, model):
        """Batched admission compiles per (bucket, k) pair and nothing
        else: a workload over two buckets with K=2 admissions per tick
        stays within buckets x K compilations, asserted via the
        engine's prefill trace hook."""
        params, cfg = model
        engine = _engine(model, True)
        rng = np.random.default_rng(3)
        futs = []
        for n in (3, 4, 2, 3, 7, 8, 6, 5, 4, 2):  # buckets {4, 8}
            p = rng.integers(0, cfg.vocab_size, n).tolist()
            futs.append(engine.submit(p, max_new_tokens=4))
        _run_until_done(engine, futs)
        for f in futs:
            assert len(f.result(timeout=0)) == 4
        s = engine.stats()
        n_buckets = len({b for b, _ in s["prefill_buckets"]})
        assert n_buckets == 2
        k = engine.engine_cfg.max_prefills_per_tick
        assert s["prefill_compilations"] <= n_buckets * k
        assert s["decode_compilations"] == 1


@pytest.mark.perf
class TestHotPathRegression:
    def test_steady_state_single_host_sync_per_tick(self, model):
        """REGRESSION GUARD: with overlap on, the steady-state decode
        loop (no admissions, no retirements) performs exactly one host
        sync per dispatched tick — the deferred fetch.  A reintroduced
        np.asarray / block_until_ready on the hot path pushes the
        ratio above 1."""
        engine = _engine(model, True, n_slots=2)
        fut = engine.submit([2, 3, 4], max_new_tokens=38)
        for _ in range(6):  # admission + pipeline fill + warmup
            engine.step()
        assert not fut.done()
        syncs0 = engine.metrics.host_syncs.value
        ticks0 = engine.metrics.decode_ticks.value
        n = 12
        for _ in range(n):
            engine.step()
        assert not fut.done()  # still steady-state (no retirement)
        dsync = engine.metrics.host_syncs.value - syncs0
        dtick = engine.metrics.decode_ticks.value - ticks0
        assert dtick == n
        assert dsync <= dtick  # <= 1 host sync per tick
        # and the global ratio /stats exports stays sane
        assert engine.stats()["host_syncs_per_tick"] is not None
        _run_until_done(engine, [fut])

    def test_sync_mode_counts_one_sync_per_tick_too(self, model):
        """The counter itself is mode-agnostic: the synchronous path's
        in-step fetch also counts exactly one sync per tick, so the
        A/B benchmark's host_syncs_per_tick numbers are comparable."""
        engine = _engine(model, False, n_slots=2)
        fut = engine.submit([2, 3, 4], max_new_tokens=20)
        engine.step()
        syncs0 = engine.metrics.host_syncs.value
        ticks0 = engine.metrics.decode_ticks.value
        for _ in range(8):
            engine.step()
        assert (engine.metrics.host_syncs.value - syncs0
                == engine.metrics.decode_ticks.value - ticks0 == 8)
        _run_until_done(engine, [fut])

    def test_phase_timers_populate(self, model):
        """The tick-phase histograms (dispatch / device-wait / host)
        fill for both modes and survive the /stats snapshot."""
        for overlap in (True, False):
            engine = _engine(model, overlap, n_slots=2)
            fut = engine.submit([1, 2], max_new_tokens=6)
            _run_until_done(engine, [fut])
            s = engine.stats()
            for key in ("tick_dispatch_seconds",
                        "tick_device_wait_seconds", "tick_host_seconds"):
                assert s[key]["count"] > 0, (overlap, key)
            assert s["decode_ticks"] > 0
            assert s["host_syncs"] > 0
