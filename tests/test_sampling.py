"""Per-slot vectorized sampling (horovod_tpu/serving/sampling.py +
models/transformer.py:sample_token_rows).

The gold check mirrors the engine's greedy story: whatever MIX of
greedy / temperature / top-k / top-p requests shares the slot pool,
each one's sampled stream must be token-identical to per-request
``sample_decode`` at the same seed — the per-request oracle — with
ZERO decode recompilations across the whole mix (sampling parameters
are data, not structure).  The PRNG key schedule is position-based, so
the same identity must survive a restart-resume (re-prefill of
``prompt + emitted``) unchanged.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.serving import sampling as S
from horovod_tpu.serving.faults import FaultInjector, FaultSpec

pytestmark = pytest.mark.serving


def _cfg(**kw):
    base = T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)
    return dataclasses.replace(base, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


def _oracle(params, cfg, prompt, steps, *, temperature=0.0, top_k=0,
            top_p=0.0, seed=0):
    return np.asarray(T.sample_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg,
        rng=jax.random.PRNGKey(seed), temperature=temperature,
        top_k=top_k, top_p=top_p))[0].tolist()


def _run(engine, futs, max_ticks=600):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


# ---------------------------------------------------------------------------
# kernel units
# ---------------------------------------------------------------------------


class TestSampleTokenRows:
    def _logits(self, rows=4, vocab=32, seed=0):
        return jax.random.normal(jax.random.PRNGKey(seed),
                                 (rows, vocab)).astype(jnp.float32)

    def _pick(self, logits, temp, tk, tp, seeds, positions):
        r = logits.shape[0]
        keys = jnp.asarray(np.stack([S.seed_key(s) for s in seeds]))
        return np.asarray(T.sample_token_rows(
            logits, jnp.asarray(temp, jnp.float32),
            jnp.asarray(tk, jnp.int32), jnp.asarray(tp, jnp.float32),
            keys, jnp.asarray(positions, jnp.int32),
            jnp.zeros((r,), jnp.int32)))

    def test_greedy_rows_are_argmax(self):
        lg = self._logits()
        out = self._pick(lg, [0.0] * 4, [0] * 4, [0.0] * 4,
                         [1, 2, 3, 4], [5] * 4)
        np.testing.assert_array_equal(out, np.argmax(np.asarray(lg), -1))

    def test_top_k_one_is_argmax(self):
        lg = self._logits()
        out = self._pick(lg, [2.0] * 4, [1] * 4, [0.0] * 4,
                         [7, 8, 9, 10], [3] * 4)
        np.testing.assert_array_equal(out, np.argmax(np.asarray(lg), -1))

    def test_top_p_tiny_is_argmax(self):
        # The nucleus always keeps index 0 of the sorted order — a
        # top_p below any single probability keeps ONLY the argmax.
        lg = self._logits()
        out = self._pick(lg, [1.0] * 4, [0] * 4, [1e-9] * 4,
                         [7, 8, 9, 10], [3] * 4)
        np.testing.assert_array_equal(out, np.argmax(np.asarray(lg), -1))

    def test_top_k_masks_to_top_set(self):
        lg = self._logits(rows=8, vocab=32, seed=3)
        out = self._pick(lg, [5.0] * 8, [4] * 8, [0.0] * 8,
                         list(range(8)), list(range(8)))
        top4 = np.argsort(-np.asarray(lg), axis=-1)[:, :4]
        for r in range(8):
            assert out[r] in top4[r]

    def test_deterministic_and_seed_sensitive(self):
        lg = self._logits(rows=8)
        a = self._pick(lg, [3.0] * 8, [0] * 8, [0.0] * 8,
                       list(range(8)), [2] * 8)
        b = self._pick(lg, [3.0] * 8, [0] * 8, [0.0] * 8,
                       list(range(8)), [2] * 8)
        np.testing.assert_array_equal(a, b)
        c = self._pick(lg, [3.0] * 8, [0] * 8, [0.0] * 8,
                       [s + 100 for s in range(8)], [2] * 8)
        assert (a != c).any()  # different seeds, different draws
        d = self._pick(lg, [3.0] * 8, [0] * 8, [0.0] * 8,
                       list(range(8)), [3] * 8)
        assert (a != d).any()  # different positions, different draws

    def test_seed_key_matches_prngkey(self):
        """The drift guard: the host-side key layout must equal the
        real ``jax.random.PRNGKey`` for every legal seed."""
        for seed in (0, 1, 42, 2**20 + 17, S.MAX_SEED - 1):
            np.testing.assert_array_equal(
                S.seed_key(seed), np.asarray(jax.random.PRNGKey(seed)))

    def test_validate_rejects_bad_params(self):
        with pytest.raises(serving.ServingError):
            S.validate(temperature=-0.5)
        with pytest.raises(serving.ServingError):
            S.validate(temperature=float("nan"))
        with pytest.raises(serving.ServingError):
            S.validate(top_k=-1)
        with pytest.raises(serving.ServingError):
            S.validate(top_p=1.5)
        with pytest.raises(serving.ServingError):
            S.validate(seed=-1)
        with pytest.raises(serving.ServingError):
            S.validate(seed=S.MAX_SEED)
        with pytest.raises(serving.ServingError):
            S.validate(temperature="hot")
        assert S.validate(1.0, 5, 0.9, 7) == (1.0, 5, 0.9, 7)
        assert S.validate() == (0.0, 0, 0.0, 0)

    def test_slot_sampling_upload_caching(self):
        cols = serving.SlotSampling(3)
        d1 = cols.device()
        assert cols.device() is d1  # clean: cached
        cols.set(1, temperature=0.8, top_k=3, top_p=0.9, seed=11)
        d2 = cols.device()
        assert d2 is not d1
        assert float(d2[0][1]) == pytest.approx(0.8)
        np.testing.assert_array_equal(np.asarray(d2[3][1]), [0, 11])
        cols.clear(1)
        assert float(cols.device()[0][1]) == 0.0


# ---------------------------------------------------------------------------
# the gates: each stage of the pick runs only where some row asks for it
# ---------------------------------------------------------------------------


def _sample_rows_ungated(logits, temperature, top_k, top_p, rng, positions,
                         rows):
    """``sample_token_rows`` as it stood before its stages were gated
    (PR 29): both sorts, the softmax and the draw for every batch.  The
    reference the gated function must equal bit for bit."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.where(temperature > 0.0, temperature,
                                1.0)[:, None]
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, (jnp.clip(top_k, 1, V) - 1)[:, None],
                              axis=1)
    scaled = jnp.where((top_k > 0)[:, None] & (scaled < kth),
                       -jnp.inf, scaled)
    probs = jax.nn.softmax(scaled, axis=-1)
    ps = jnp.sort(probs, axis=-1)[:, ::-1]
    csum = jnp.cumsum(ps, axis=-1)
    keep = (csum - ps) < top_p[:, None]
    thr = jnp.min(jnp.where(keep, ps, jnp.inf), axis=-1, keepdims=True)
    p_on = (top_p > 0.0) & (top_p < 1.0)
    scaled = jnp.where(p_on[:, None] & (probs < thr), -jnp.inf, scaled)

    def pick(key, pos, row, lrow):
        key = jax.random.fold_in(jax.random.fold_in(key, pos), row)
        return jax.random.categorical(key, lrow)

    sampled = jax.vmap(pick)(rng, positions, rows, scaled)
    return jnp.where(temperature > 0.0, sampled.astype(jnp.int32), greedy)


def _sorts(jaxpr, in_cond=False):
    """Every ``sort`` equation of a jaxpr, sub-jaxprs included, as
    whether it lies inside some ``cond``'s branch."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield in_cond
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _sorts(sub, in_cond or eqn.primitive.name == "cond")


R_GATED, V_GATED = 32, 203  # a vocabulary that is no multiple of 128

#: name -> (temperature, top_k, top_p) columns of R_GATED rows, and the
#: gates they open.
GATE_CASES = {
    "all_greedy": ([0.0] * 32, [0] * 32, [0.0] * 32,
                   (False, False, False)),
    # greedy rows may carry a top-k / top-p: validate() lets them
    "greedy_with_filters": ([0.0] * 32, [5] * 32, [0.5] * 32,
                            (False, True, True)),
    "temperature_only": ([0.7, 1.0, 1.9, 0.0] * 8, [0] * 32,
                         [0.0, 1.0] * 16, (True, False, False)),
    "top_k_only": ([1.3] * 32, [1, 7, 0, 300] * 8, [0.0] * 32,
                   (True, True, False)),
    "top_p_only": ([0.9] * 32, [0] * 32, [0.8, 0.3, 1.0, 0.0] * 8,
                   (True, False, True)),
    "both": ([1.1, 0.6] * 16, [4, 0, 50, 9] * 8, [0.9, 0.5, 0.0, 0.7] * 8,
             (True, True, True)),
    "one_sampled_among_31_greedy": (
        [0.0] * 17 + [1.2] + [0.0] * 14, [0] * 17 + [6] + [0] * 14,
        [0.0] * 17 + [0.85] + [0.0] * 14, (True, True, True)),
}


class TestGatedSampler:
    @staticmethod
    def _args(case, seed):
        temp, tk, tp, _ = GATE_CASES[case]
        key = jax.random.PRNGKey(100 + seed)
        logits = 3.0 * jax.random.normal(key, (R_GATED, V_GATED),
                                         jnp.float32)
        # ties at the k-th value and at the nucleus threshold
        logits = logits.at[:, 5].set(logits[:, 9])
        keys = jnp.asarray(np.stack(
            [S.seed_key(1000 * seed + r) for r in range(R_GATED)]))
        return (logits, jnp.asarray(temp, jnp.float32),
                jnp.asarray(tk, jnp.int32), jnp.asarray(tp, jnp.float32),
                keys, jnp.arange(R_GATED, dtype=jnp.int32) + 7 * seed,
                jnp.zeros((R_GATED,), jnp.int32))

    @pytest.mark.parametrize("seed", [0, 1])
    @pytest.mark.parametrize("case", sorted(GATE_CASES))
    def test_gated_tokens_equal_the_ungated_body(self, case, seed):
        """One executable each, every case through the same two: the
        gated pick returns the ungated body's token in every row."""
        args = self._args(case, seed)
        want = np.asarray(jax.jit(_sample_rows_ungated)(*args))
        got = np.asarray(jax.jit(T.sample_token_rows)(*args))
        np.testing.assert_array_equal(got, want)
        temp = np.asarray(args[1])
        assert (got[temp <= 0]
                == np.argmax(np.asarray(args[0]), -1)[temp <= 0]).all()
        gates = tuple(bool(g) for g in T.sample_gates(*args[1:4]))
        assert gates == GATE_CASES[case][3]

    def test_every_sort_of_the_pick_sits_in_a_cond_branch(self):
        jaxpr = jax.make_jaxpr(T.sample_token_rows)(
            *self._args("both", 0)).jaxpr
        where = list(_sorts(jaxpr))
        assert where == [True, True], where
        # the reader itself: the ungated body's sorts are seen, outside
        assert list(_sorts(jax.make_jaxpr(_sample_rows_ungated)(
            *self._args("both", 0)).jaxpr)) == [False, False]

    def test_every_sort_of_the_engine_tick_sits_in_a_cond_branch(
            self, model):
        """The tick the engine dispatches, traced at the shapes it was
        called with: its only sorts are the pick's, each in a branch —
        and there is ONE tick, whatever the mix."""
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, tick_timeout=0))
        seen, tick = [], eng._tick_fn

        def tap(*args):
            seen.append(jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args))
            return tick(*args)

        eng._tick_fn = tap
        _run(eng, [eng.submit([3, 4, 5], max_new_tokens=3)])
        where = list(_sorts(jax.make_jaxpr(tick)(*seen[0]).jaxpr))
        assert where == [True, True], where

    def test_host_gates_equal_device_gates(self):
        """The host counts with the predicates the device branches on:
        over set, clear and reset they read the same."""
        cols = serving.SlotSampling(4)

        def agree():
            dev = tuple(bool(g)
                        for g in T.sample_gates(*cols.device()[:3]))
            assert cols.gates() == dev
            return dev

        assert agree() == (False, False, False)
        cols.set(2, temperature=0.0, top_k=4, top_p=0.5, seed=1)
        assert agree() == (False, True, True)  # a greedy row's filters
        cols.set(1, temperature=0.9, top_k=0, top_p=1.0, seed=2)
        assert agree() == (True, True, True)
        cols.clear(2)
        assert agree() == (True, False, False)  # top_p 1.0 is off
        cols.set(0, temperature=1.0, top_k=0, top_p=0.3, seed=3)
        assert agree() == (True, False, True)
        cols.clear(0)
        cols.set(3, temperature=1.0, top_k=2, top_p=0.0, seed=4)
        assert agree() == (True, True, False)
        cols.reset()
        assert agree() == (False, False, False)

    def test_stats_count_the_ticks_with_closed_gates(self, model):
        """``/stats``: one more draw-free and one more sort-free tick
        for every tick of an all-greedy engine; a temperature-only
        request stops the first, a top-p request both, for as long as
        it holds a slot."""
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, tick_timeout=0))

        def grown(**kw):
            before = eng.stats()
            # the greedy companion leaves first: the other request's
            # row is in the columns of every tick counted here
            _run(eng, [eng.submit([3, 4, 5], max_new_tokens=6, **kw),
                       eng.submit([7, 8], max_new_tokens=3)])
            after = eng.stats()
            return tuple(after[k] - before[k] for k in (
                "decode_ticks", "sample_ticks_drawfree_total",
                "sample_ticks_sortfree_total"))

        ticks, drawfree, sortfree = grown()
        assert ticks >= 5 and drawfree == sortfree == ticks
        ticks, drawfree, sortfree = grown(temperature=0.8, seed=3)
        assert ticks >= 5 and drawfree == 0 and sortfree == ticks
        ticks, drawfree, sortfree = grown(temperature=0.8, top_p=0.9,
                                          seed=3)
        assert ticks >= 5 and drawfree == 0 and sortfree == 0
        # released: its row is cleared, the gates close again
        ticks, drawfree, sortfree = grown()
        assert ticks >= 5 and drawfree == sortfree == ticks


# ---------------------------------------------------------------------------
# the oracle itself
# ---------------------------------------------------------------------------


class TestSampleDecodeOracle:
    def test_temperature_zero_is_greedy_with_top_p(self, model):
        params, cfg = model
        prompt = jnp.asarray([[3, 4, 5]], jnp.int32)
        g = np.asarray(T.greedy_decode(params, prompt, 5, cfg))
        s = np.asarray(T.sample_decode(
            params, prompt, 5, cfg, rng=jax.random.PRNGKey(1),
            temperature=0.0, top_p=0.9))
        np.testing.assert_array_equal(g, s)

    def test_continuation_identity(self, model):
        """The resume/failover contract at the oracle level: sampling
        ``prompt + first_half`` with the same rng continues the exact
        stream — keys depend on token POSITION, not the prefill
        split."""
        params, cfg = model
        kw = dict(rng=jax.random.PRNGKey(9), temperature=1.3, top_k=8,
                  top_p=0.9)
        prompt = jnp.asarray([[7, 8, 9]], jnp.int32)
        full = np.asarray(T.sample_decode(params, prompt, 8, cfg, **kw))
        head = np.asarray(T.sample_decode(params, prompt, 3, cfg, **kw))
        grown = jnp.concatenate(
            [prompt, jnp.asarray(head, jnp.int32)], axis=1)
        tail = np.asarray(T.sample_decode(params, grown, 5, cfg, **kw))
        np.testing.assert_array_equal(
            np.concatenate([head, tail], axis=1), full)

    def test_batch_rows_draw_independent_streams(self, model):
        params, cfg = model
        prompt = jnp.asarray([[3, 4, 5], [3, 4, 5]], jnp.int32)
        out = np.asarray(T.sample_decode(
            params, prompt, 8, cfg, rng=jax.random.PRNGKey(2),
            temperature=1.5))
        assert (out[0] != out[1]).any()


# ---------------------------------------------------------------------------
# the engine: mixed-parameter batches == per-request oracle
# ---------------------------------------------------------------------------


MIX = [
    ([3, 4, 5], dict()),                                     # greedy
    ([7, 8], dict(temperature=1.1, seed=5)),                 # temp only
    ([1, 2, 3, 4], dict(temperature=0.7, top_k=5, seed=9)),  # top-k
    ([9], dict(temperature=1.5, top_p=0.8, seed=13)),        # top-p
]


class TestEngineSampling:
    @pytest.mark.perf
    @pytest.mark.slow
    def test_mixed_batch_matches_oracle_zero_recompiles(self, model):
        """THE acceptance property: one compiled decode executable
        serves mixed greedy/temperature/top-k/top-p traffic, each
        slot's stream token-identical to ``sample_decode`` at its own
        seed, with zero decode recompiles across churn.  Slow (PR 17
        budget pass): two full waves of the 4-way mix are ~16 s; the
        sampled-prefix-sharers and restart-resume tests below keep
        engine-level per-seed oracle identity tier-1."""
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, tick_timeout=0))
        eng.warmup([1, 4])
        base = eng.decode_compilations
        # two waves of churn over the same slots
        for wave in range(2):
            futs = [eng.submit(p, max_new_tokens=8, **kw)
                    for p, kw in MIX]
            _run(eng, futs)
            for (p, kw), f in zip(MIX, futs):
                assert f.result(1) == _oracle(params, cfg, p, 8, **kw), \
                    f"wave {wave}, params {kw}"
        assert eng.decode_compilations == base, \
            "sampling parameter mix recompiled the decode tick"

    @pytest.mark.slow
    def test_sync_mode_matches_oracle(self, model):
        # Slow (PR 17 budget pass): builds one more engine variant; the
        # default-mode (overlap) oracle tests stay tier-1 and
        # test_serving covers the sync tick.
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, overlap=False, tick_timeout=0))
        eng.warmup([1, 4])
        futs = [eng.submit(p, max_new_tokens=6, **kw)
                for p, kw in MIX[:3]]
        _run(eng, futs)
        for (p, kw), f in zip(MIX, futs):
            assert f.result(1) == _oracle(params, cfg, p, 6, **kw)

    def test_sampled_prefix_sharers_draw_own_tokens(self, model):
        """Attach-only admission (prompt == registered prefix) must
        give each SAMPLED sharer its own first token from the cached
        prefix logits — not the cached greedy token."""
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, tick_timeout=0))
        eng.warmup([1, 4])
        prefix = [5, 6, 7, 8]
        eng.register_prefix(prefix)
        futs = [eng.submit(prefix, max_new_tokens=6,
                           temperature=1.4, seed=s) for s in (3, 17)]
        futs.append(eng.submit(prefix, max_new_tokens=6))  # greedy
        _run(eng, futs)
        for s, f in zip((3, 17), futs[:2]):
            assert f.result(1) == _oracle(params, cfg, prefix, 6,
                                          temperature=1.4, seed=s)
        assert futs[2].result(1) == _oracle(params, cfg, prefix, 6)
        assert [f.result(1) for f in futs[:2]][0] != \
            [f.result(1) for f in futs[:2]][1]

    def test_restart_resume_keeps_sampled_stream(self, model):
        """Crash mid-decode: resumed sampled output is token-identical
        to an uninterrupted run — the journal carries the sampling
        params and the position-keyed PRNG continues the stream."""
        params, cfg = model
        faults = FaultInjector()
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, tick_timeout=0, faults=faults))
        eng.warmup([1, 4])
        faults.add(FaultSpec(site="decode_tick", kind="raise",
                             skip=faults.visits("decode_tick") + 4))
        subs = [([3, 4, 5], dict(temperature=1.3, top_k=8, top_p=0.9,
                                 seed=21)),
                ([7, 8], dict(temperature=0.9, seed=4))]
        futs = [eng.submit(p, max_new_tokens=10, **kw)
                for p, kw in subs]
        _run(eng, futs)
        assert eng.metrics.resumed.value >= 1
        for (p, kw), f in zip(subs, futs):
            assert f.result(1) == _oracle(params, cfg, p, 10, **kw)

    @pytest.mark.slow
    def test_speculative_mixed_sampled_and_greedy(self, model):
        """On a speculative engine a sampled request emits exactly its
        oracle stream (drafts never accepted for it — acceptance
        forced to 0 as data) while greedy slots keep speculating; the
        compile count stays at the spec engine's two executables.
        Slow (PR 17 budget pass): the spec engine build is ~11 s;
        test_speculative's spec_on-mask kernel unit keeps the
        forced-greedy acceptance path tier-1."""
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=4, max_len=32, speculative=True, spec_k=3,
            spec_draft="ngram", spec_adaptive=False, tick_timeout=0))
        eng.warmup([1, 4])
        base = eng.decode_compilations
        subs = [([3, 4, 5], dict()),
                ([7, 8], dict(temperature=1.1, seed=5)),
                ([1, 2, 3, 4], dict(temperature=0.7, top_k=5, seed=9))]
        futs = [eng.submit(p, max_new_tokens=8, **kw)
                for p, kw in subs]
        _run(eng, futs)
        for (p, kw), f in zip(subs, futs):
            assert f.result(1) == _oracle(params, cfg, p, 8, **kw)
        assert eng.decode_compilations == base

    def test_submit_validation_and_defaults(self, model):
        params, cfg = model
        eng = serving.InferenceEngine(params, cfg, serving.EngineConfig(
            n_slots=2, max_len=32, tick_timeout=0))
        with pytest.raises(serving.ServingError):
            eng.submit([1], temperature=-1.0)
        with pytest.raises(serving.ServingError):
            eng.submit([1], top_p=2.0)
        with pytest.raises(serving.ServingError):
            eng.submit([1], seed=-5)


# ---------------------------------------------------------------------------
# journal round trip
# ---------------------------------------------------------------------------


class TestJournalSampling:
    def test_begin_and_read_live_round_trip(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        j = serving.RequestJournal(path)
        fut = serving.GenerationFuture()
        import horovod_tpu.obs.tracing as obs_tracing

        fut.trace = obs_tracing.RequestTrace("a" * 16)
        req = serving.Request(prompt=[1, 2], max_new_tokens=8,
                              future=fut, eos_id=3, trace=fut.trace,
                              temperature=1.25, top_k=4, top_p=0.75,
                              seed=99)
        j.begin(req)
        j.append(req.id, 7)
        live = serving.RequestJournal.read_live(path)
        d = live["a" * 16]
        assert d["emitted_tokens"] == [7]
        assert d["temperature"] == 1.25 and d["seed"] == 99
        entry = j.get(req.id)
        assert (entry.temperature, entry.top_k, entry.top_p,
                entry.seed) == (1.25, 4, 0.75, 99)

    def test_greedy_begin_line_stays_compact(self, tmp_path):
        path = str(tmp_path / "g.jsonl")
        j = serving.RequestJournal(path)
        fut = serving.GenerationFuture()
        req = serving.Request(prompt=[1], max_new_tokens=2, future=fut)
        j.begin(req)
        import json as _json

        line = _json.loads(open(path).read().splitlines()[0])
        assert "samp" not in line  # greedy journals stay pre-sampling
