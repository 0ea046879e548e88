"""Speculative decoding (EngineConfig.speculative) acceptance suite.

THE correctness bar (ISSUE 11): with speculation on — either draft
source — every request's output is BYTE-IDENTICAL to non-speculative
greedy decode (and to per-request ``greedy_decode``), across staggered
admission, EOS inside an accepted run, cancellation, restart-resume
mid-speculation, and paged COW-prefix sharing, while the decode
executable compiles exactly ONCE no matter how per-slot acceptance
lengths vary (acceptance is data, not structure).

Layers:

* kernel unit — ``decode_verify_paged`` against sequential
  ``decode_step_paged`` (acceptance math, NULL-routing of rejected
  drafts, storage round-trip), ``ngram_propose``;
* ``_retire_pending`` multi-token emission as a STANDALONE unit
  (fabricated pending dicts, no device decode): 0 / 1 / k < K / K+1
  tokens per slot, EOS inside the run, stale-slot identity drop;
* whole-engine A/B oracles.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.serving.engine import _SlotState
from horovod_tpu.serving.faults import FaultInjector, FaultSpec
from horovod_tpu.serving.scheduler import Request

pytestmark = [pytest.mark.serving, pytest.mark.spec]

SPEC_K = 3


def _cfg():
    return T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)


def _draft_cfg():
    # The shallow draft: half the layers, same tokenizer/vocab.
    return T.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64,
        max_seq=48, dtype=jnp.float32, attention_impl="reference",
        n_kv_heads=2)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return T.init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.fixture(scope="module")
def draft_model():
    dcfg = _draft_cfg()
    return T.init_params(jax.random.PRNGKey(7), dcfg), dcfg


def _ref(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _engine(model, *, speculative=True, draft=None, **kw):
    params, cfg = model
    defaults = dict(n_slots=4, max_len=40, min_prefill_bucket=4,
                    max_prefills_per_tick=2, max_queue_depth=16,
                    restart_backoff=0.01, restart_backoff_max=0.05,
                    speculative=speculative, spec_k=SPEC_K)
    defaults.update(kw)
    dp, dc = draft if draft is not None else (None, None)
    return serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**defaults),
        draft_params=dp, draft_cfg=dc)


def _drive(engine, futs, max_ticks=500):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


# --- kernel unit --------------------------------------------------------------


class TestVerifyKernel:
    """decode_verify_paged against sequential decode_step_paged."""

    def _prefilled(self, model, prompt, n_slots=2, page_size=8,
                   pages_per_slot=2):
        params, cfg = model
        pc = serving.cache.PagedSlotCache(cfg, n_slots, 32,
                                          page_size=page_size)
        slots = [pc.alloc() for _ in range(n_slots)]
        for s in slots:
            for idx in range(pages_per_slot):
                pc.grant(s, idx)
        cache = T.init_cache(cfg, n_slots, 8)
        logits, pre = T.prefill(
            params, jnp.asarray([prompt] * n_slots, jnp.int32), cache,
            cfg, true_len=jnp.asarray([len(prompt)] * n_slots))
        pc.land(slots, pre, [len(prompt)] * n_slots, start=0)
        first = int(jnp.argmax(logits[0]))
        return pc, first

    def _sequential(self, model, pool, table, first, n, active):
        params, cfg = model
        cur = jnp.asarray([first] * int(active.shape[0]), jnp.int32)
        out = []
        for _ in range(n):
            lg, pool = T.decode_step_paged(params, cur, pool, table,
                                           cfg, active)
            cur = jnp.argmax(lg, -1).astype(jnp.int32)
            out.append(np.asarray(cur))
        return np.stack(out), pool, cur

    def test_perfect_drafts_accept_all(self, model):
        params, cfg = model
        pc, first = self._prefilled(model, [3, 4, 5, 6, 7])
        table = jnp.asarray(pc.table)
        active = jnp.asarray([True, True])
        seq, _, _ = self._sequential(model, pc.cache, table, first, 4,
                                     active)
        window = jnp.concatenate(
            [jnp.full((2, 1), first, jnp.int32),
             jnp.asarray(seq[:3].T, jnp.int32)], axis=1)
        t, mx, acc, pool = T.decode_verify_paged(
            params, window, pc.cache, table, cfg, active)
        assert np.asarray(acc).tolist() == [3, 3]
        assert np.array_equal(np.asarray(t).T, seq)
        assert np.asarray(pool["pos"]).tolist() == [9, 9]
        assert np.isfinite(np.asarray(mx)).all()

    def test_rejected_drafts_accept_none_and_never_contaminate(
            self, model):
        """Garbage drafts: acceptance 0, position 0's token is STILL
        the greedy token, the pool's committed pages are bit-identical
        to a plain one-token tick's (rejected drafts NULL-routed), and
        continuing from the verified pool matches the sequential
        stream exactly."""
        params, cfg = model
        pc, first = self._prefilled(model, [3, 4, 5, 6, 7])
        table = jnp.asarray(pc.table)
        active = jnp.asarray([True, True])
        seq, seq_pool, seq_cur = self._sequential(
            model, pc.cache, table, first, 1, active)
        window = jnp.asarray([[first, 9, 9, 9]] * 2, jnp.int32)
        before_k = np.asarray(pc.cache["k"])
        t, _, acc, pool = T.decode_verify_paged(
            params, window, pc.cache, table, cfg, active)
        assert np.asarray(acc).tolist() == [0, 0]
        assert np.array_equal(np.asarray(t)[:, 0], seq[0])
        # NULL routing, EXACTLY: with every draft rejected, only the
        # committed token's position (pos=5, page offset 5) may change
        # in each slot's own page — offsets 6 and 7, where the
        # rejected drafts WOULD have landed, are bit-identical to the
        # pre-verify pool.  The junk went to physical page 0 only.
        after_k = np.asarray(pool["k"])
        for s in (0, 1):
            pg = int(np.asarray(table)[s, 0])
            np.testing.assert_array_equal(after_k[:, pg, :, 6:],
                                          before_k[:, pg, :, 6:])
            assert (after_k[:, pg, :, 5] != before_k[:, pg, :, 5]).any()
        # And the accepted write agrees with the sequential tick's to
        # reduction-order precision (the verify's W-wide softmax may
        # associate sums differently — ULP noise, not contamination;
        # TOKEN identity is exact, proven by the engine-level A/Bs).
        np.testing.assert_allclose(
            np.asarray(pool["k"][:, 1:]), np.asarray(seq_pool["k"][:, 1:]),
            atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(pool["v"][:, 1:]), np.asarray(seq_pool["v"][:, 1:]),
            atol=1e-5, rtol=1e-5)
        assert np.asarray(pool["pos"]).tolist() == \
            np.asarray(seq_pool["pos"]).tolist()
        # Continue both paths one tick: identical next tokens.
        lg_a, _ = T.decode_step_paged(
            params, t[jnp.arange(2), acc], pool, table, cfg, active)
        lg_b, _ = T.decode_step_paged(
            params, seq_cur, seq_pool, table, cfg, active)
        assert np.array_equal(np.asarray(jnp.argmax(lg_a, -1)),
                              np.asarray(jnp.argmax(lg_b, -1)))

    def test_partial_acceptance_continues_identically(self, model):
        params, cfg = model
        pc, first = self._prefilled(model, [3, 4, 5, 6, 7])
        table = jnp.asarray(pc.table)
        active = jnp.asarray([True, True])
        seq, _, _ = self._sequential(model, pc.cache, table, first, 3,
                                     active)
        window = jnp.concatenate(
            [jnp.full((2, 1), first, jnp.int32),
             jnp.asarray(seq[:1].T, jnp.int32),
             jnp.full((2, 2), 9, jnp.int32)], axis=1)
        t, _, acc, pool = T.decode_verify_paged(
            params, window, pc.cache, table, cfg, active)
        assert np.asarray(acc).tolist() == [1, 1]
        bonus = np.asarray(t[jnp.arange(2), acc])
        assert np.array_equal(bonus, seq[1])
        lg, _ = T.decode_step_paged(
            params, jnp.asarray(bonus), pool, table, cfg, active)
        assert np.array_equal(np.asarray(jnp.argmax(lg, -1)), seq[2])

    def test_spec_on_mask_forces_plain_greedy(self, model):
        """spec_on=False is the per-request opt-out: acceptance forced
        to 0 as data, one greedy token per tick, same executable."""
        params, cfg = model
        pc, first = self._prefilled(model, [3, 4, 5, 6, 7])
        table = jnp.asarray(pc.table)
        active = jnp.asarray([True, True])
        seq, _, _ = self._sequential(model, pc.cache, table, first, 4,
                                     active)
        window = jnp.concatenate(
            [jnp.full((2, 1), first, jnp.int32),
             jnp.asarray(seq[:3].T, jnp.int32)], axis=1)  # perfect
        t, _, acc, pool = T.decode_verify_paged(
            params, window, pc.cache, table, cfg, active,
            jnp.asarray([False, True]))
        assert np.asarray(acc).tolist() == [0, 3]
        assert np.asarray(pool["pos"]).tolist() == [6, 9]

    def test_inactive_rows_untouched(self, model):
        params, cfg = model
        pc, first = self._prefilled(model, [3, 4, 5, 6, 7])
        table = jnp.asarray(pc.table)
        active = jnp.asarray([True, False])
        window = jnp.asarray([[first, 9, 9, 9]] * 2, jnp.int32)
        before = np.asarray(pc.cache["k"])
        t, _, acc, pool = T.decode_verify_paged(
            params, window, pc.cache, table, cfg, active)
        assert np.asarray(acc)[1] == 0
        assert np.asarray(pool["pos"]).tolist() == [6, 5]  # row 1 frozen
        # Row 1's pages (its table maps pages for slot 1) unchanged.
        for pg in pc.table[1]:
            if pg:
                np.testing.assert_array_equal(
                    np.asarray(pool["k"][:, pg]), before[:, pg])

    def test_ngram_propose(self):
        hist = jnp.asarray([[1, 2, 3, 1, 2, 0, 0, 0],
                            [5, 5, 5, 5, 5, 0, 0, 0],
                            [1, 2, 3, 4, 5, 0, 0, 0]], jnp.int32)
        pos = jnp.asarray([4, 4, 4], jnp.int32)
        d = np.asarray(T.ngram_propose(hist, pos, 3))
        # Row 0: final bigram (1,2) seen at 0 -> copy [3, 1, 2].
        assert d[0].tolist() == [3, 1, 2]
        # Row 1: (5,5) most recent at 2 -> copy window runs past the
        # committed region, whose positions fall back to the last
        # token: all 5s (the pure-repeat case must draft the repeat).
        assert d[1].tolist() == [5, 5, 5]
        # Row 2: no earlier (4,5) -> fallback repeats the last token.
        assert d[2].tolist() == [5, 5, 5]


# --- _retire_pending multi-token emission, standalone -------------------------


class TestRetirePendingMultiToken:
    """The deferred-fetch boundary's 0..K+1-tokens-per-slot contract,
    driven with FABRICATED pending dicts — no device decode, no draft
    source: exactly the host-side emission rules in isolation."""

    def _engine_with_slot(self, model, *, max_new=10, eos=None,
                          prompt=(1, 2)):
        # resume=False: no journal, so fabricated requests need no
        # journal entries.
        eng = _engine(model, speculative=True, resume=False)
        fut = serving.GenerationFuture()
        req = Request(prompt=list(prompt), max_new_tokens=max_new,
                      future=fut, eos_id=eos)
        slot = eng.slots.alloc()
        eng._states[slot] = _SlotState(request=req, last_token=5,
                                       n_generated=1)
        eng._page_pos[slot] = len(prompt)
        return eng, slot, req, fut

    def _pending(self, eng, slot, req, row, acc):
        S = eng.engine_cfg.n_slots
        W = eng.engine_cfg.spec_k + 1
        nxt = np.zeros((S, W), np.int32)
        nxt[slot] = row
        active = np.zeros(S, bool)
        active[slot] = True
        accs = np.zeros(S, np.int32)
        accs[slot] = acc
        reqs = [None] * S
        reqs[slot] = req
        return {"nxt": nxt, "mx": np.ones((S, W), np.float32),
                "acc": accs, "active": active, "reqs": reqs,
                "kind": None, "dispatched_at": time.monotonic(),
                "spec": np.ones(S, bool)}

    @pytest.mark.parametrize("acc,want", [(0, 1), (1, 2), (2, 3),
                                          (SPEC_K, SPEC_K + 1)])
    def test_emits_acc_plus_one(self, model, acc, want):
        eng, slot, req, fut = self._engine_with_slot(model)
        eng._retire_pending(self._pending(eng, slot, req,
                                          [11, 12, 13, 14], acc))
        assert fut.tokens_so_far() == [11, 12, 13, 14][:want]
        assert eng._states[slot] is not None  # still running
        assert eng._page_pos[slot] == 2 + acc + 1  # device-pos mirror

    def test_zero_tokens_on_stale_identity(self, model):
        """A slot retired and REUSED between dispatch and fetch emits
        nothing from the stale tick — no token leaks into the new
        tenant."""
        eng, slot, req, fut = self._engine_with_slot(model)
        other = Request(prompt=[9], max_new_tokens=5,
                        future=serving.GenerationFuture())
        p = self._pending(eng, slot, req, [11, 12, 13, 14], SPEC_K)
        # The slot now belongs to someone else (re-admission landed).
        eng._states[slot] = _SlotState(request=other, last_token=1,
                                       n_generated=0)
        eng._retire_pending(p)
        assert fut.tokens_so_far() == []
        assert other.future.tokens_so_far() == []

    def test_eos_inside_run_drops_tail(self, model):
        eng, slot, req, fut = self._engine_with_slot(model, eos=12)
        eng._retire_pending(self._pending(eng, slot, req,
                                          [11, 12, 13, 14], SPEC_K))
        assert fut.tokens_so_far() == [11, 12]  # tail dropped
        assert fut.finish_reason == "eos"
        assert eng._states[slot] is None  # retired, slot reclaimed
        assert eng.slots.free_count == eng.engine_cfg.n_slots

    def test_length_inside_run_drops_tail(self, model):
        # n_generated=1 already; max_new=3 -> only 2 more tokens fit.
        eng, slot, req, fut = self._engine_with_slot(model, max_new=3)
        eng._retire_pending(self._pending(eng, slot, req,
                                          [11, 12, 13, 14], SPEC_K))
        assert fut.tokens_so_far() == [11, 12]
        assert fut.finish_reason == "length"

    def test_plain_single_token_path_unchanged(self, model):
        """Without "acc" the pending dict is the PR 4 contract —
        one token per slot."""
        eng, slot, req, fut = self._engine_with_slot(model)
        S = eng.engine_cfg.n_slots
        nxt = np.zeros(S, np.int32)
        nxt[slot] = 21
        active = np.zeros(S, bool)
        active[slot] = True
        reqs = [None] * S
        reqs[slot] = req
        eng._retire_pending({
            "nxt": nxt, "mx": np.ones(S, np.float32), "active": active,
            "reqs": reqs, "kind": None,
            "dispatched_at": time.monotonic()})
        assert fut.tokens_so_far() == [21]


# --- whole-engine oracle A/Bs -------------------------------------------------


# Same staggered mixed workload as tests/test_overlap.py: two prompt
# buckets, unequal completion lengths, slot reuse, more requests than
# slots, one EOS case resolved against the oracle.
_CASES = [
    ([3, 4, 5, 6], 9, None),
    ([10, 11], 5, None),
    ([7, 8, 9, 1, 2, 3, 4, 5, 6], 7, None),
    ([12, 13, 14], 11, None),
    ([5, 6], 4, None),
    ([20, 21, 22], 12, "eos"),
]


class TestSpeculativeOracle:
    def _resolved_cases(self, model):
        params, cfg = model
        cases = []
        for prompt, steps, kind in _CASES:
            ref = _ref(params, cfg, prompt, steps)
            eos = ref[2] if kind == "eos" else None
            cases.append((prompt, steps, eos, ref))
        return cases

    def _run_staggered(self, engine, cases):
        futs = []
        for prompt, steps, eos, _ in cases:
            futs.append(engine.submit(prompt, max_new_tokens=steps,
                                      eos_id=eos))
            engine.step()
            engine.step()
        _drive(engine, futs)
        return [(f.result(timeout=0), f.finish_reason) for f in futs]

    def _assert_oracle(self, cases, outs):
        for (prompt, steps, eos, ref), (toks, reason) in zip(cases, outs):
            if eos is None:
                assert toks == ref
                assert reason == "length"
            else:
                assert toks == ref[:ref.index(eos) + 1]
                assert reason == "eos"

    @pytest.mark.slow
    def test_ab_identity_staggered_ngram(self, model):
        """ACCEPTANCE: the staggered workload through an n-gram
        speculative engine is byte-identical to the non-speculative
        engine and to greedy_decode — and the decode compile count is
        CONSTANT across varying per-slot acceptance: at most the two
        executables the engine owns (draft/verify + the plain
        fallback adaptive disabling dispatches), with ZERO growth when
        the whole varying-acceptance workload runs again."""
        cases = self._resolved_cases(model)
        eng = _engine(model, speculative=True)
        outs = self._run_staggered(eng, cases)
        c1 = eng.decode_compilations
        assert c1 <= 2
        outs2 = self._run_staggered(eng, cases)
        assert eng.decode_compilations == c1  # acceptance is data
        assert outs2 == outs
        base = self._run_staggered(_engine(model, speculative=False),
                                   cases)
        assert outs == base
        self._assert_oracle(cases, outs)
        snap = eng.metrics.tokens_per_tick.snapshot()
        assert snap["count"] > 0

    @pytest.mark.slow
    def test_ab_identity_staggered_model_draft(self, model, draft_model):
        cases = self._resolved_cases(model)
        eng = _engine(model, speculative=True, draft=draft_model)
        outs = self._run_staggered(eng, cases)
        c1 = eng.decode_compilations
        assert c1 <= 2
        assert self._run_staggered(eng, cases) == outs
        assert eng.decode_compilations == c1
        self._assert_oracle(cases, outs)

    @pytest.mark.slow
    def test_ab_identity_sync_mode(self, model):
        """speculative + overlap=False (the synchronous tick) — same
        oracle."""
        cases = self._resolved_cases(model)
        outs = self._run_staggered(
            _engine(model, speculative=True, overlap=False), cases)
        self._assert_oracle(cases, outs)

    def test_perfect_draft_eos_inside_accepted_run(self, model):
        """Draft = the target itself -> every draft accepted, so the
        EOS genuinely lands INSIDE an accepted run and the tail must
        be dropped (plus the tokens/tick histogram proves multi-token
        ticks actually happened)."""
        params, cfg = model
        full = _ref(params, cfg, [3, 4, 5, 6], 9)
        eos = full[2]
        eng = _engine(model, speculative=True, draft=(params, cfg))
        f = eng.submit([3, 4, 5, 6], max_new_tokens=9, eos_id=eos)
        _drive(eng, [f])
        assert f.result(timeout=0) == full[:3]
        assert f.finish_reason == "eos"
        assert eng.metrics.spec_accepted.value > 0

    def test_perfect_draft_multiplies_tokens_per_tick(self, model):
        params, cfg = model
        eng = _engine(model, speculative=True, draft=(params, cfg))
        f = eng.submit([3, 4, 5, 6], max_new_tokens=12)
        _drive(eng, [f])
        assert f.result(timeout=0) == _ref(params, cfg, [3, 4, 5, 6], 12)
        # A perfect draft accepts everything: mean tokens/tick well
        # above 1 (the speculative multiplier), acceptance ratio 1.
        assert eng.metrics.spec_drafted.value == \
            eng.metrics.spec_accepted.value
        assert eng.metrics.tokens_per_tick.snapshot()["mean"] > 1.5

    def test_cancellation_mid_speculation(self, model):
        params, cfg = model
        eng = _engine(model, speculative=True)
        f1 = eng.submit([3, 4, 5, 6], max_new_tokens=30)
        f2 = eng.submit([10, 11], max_new_tokens=6)
        eng.step()
        eng.step()
        f1.cancel()
        _drive(eng, [f1, f2])
        assert f1.finish_reason == "cancelled"
        got = f1.tokens_so_far()
        assert got == _ref(params, cfg, [3, 4, 5, 6], 30)[:len(got)]
        assert f2.result(timeout=0) == _ref(params, cfg, [10, 11], 6)

    def test_per_request_opt_out(self, model):
        params, cfg = model
        eng = _engine(model, speculative=True, draft=(params, cfg))
        f1 = eng.submit([3, 4, 5, 6], max_new_tokens=9,
                        speculative=False)
        f2 = eng.submit([10, 11], max_new_tokens=5)
        _drive(eng, [f1, f2])
        assert f1.result(timeout=0) == _ref(params, cfg, [3, 4, 5, 6], 9)
        assert f2.result(timeout=0) == _ref(params, cfg, [10, 11], 5)
        # Opt-out is data: at most the engine's two executables (the
        # opted-out request alone in the pool dispatches the plain
        # fallback), never a per-pattern recompile.
        assert eng.decode_compilations <= 2

    def test_adaptive_disable_and_probe_cycle(self, model):
        """Losing speculation is BOUNDED: the random model's stream
        gives the n-gram draft nothing to agree with, so adaptive
        control disables the slot after the evaluation window (plain
        one-token ticks thereafter), probes re-enable it periodically,
        and the output stays byte-identical through every
        disable/probe/re-disable transition."""
        params, cfg = model
        eng = _engine(model, speculative=True, spec_probe_period=8,
                      spec_window=2)
        f = eng.submit([3, 4, 5, 6], max_new_tokens=30)
        saw_disabled = False
        for _ in range(500):
            if f.done():
                break
            eng.step()
            saw_disabled |= not eng._spec_live.all()
        assert f.done()
        assert f.result(timeout=0) == _ref(params, cfg, [3, 4, 5, 6], 30)
        assert saw_disabled
        assert eng.decode_compilations <= 2

    @pytest.mark.chaos
    @pytest.mark.parametrize("skip", [
        1,
        pytest.param(2, marks=pytest.mark.slow),
        pytest.param(3, marks=pytest.mark.slow),
    ])
    def test_restart_resume_mid_speculation(self, model, skip):
        """Crash the decode tick at several depths (measured in
        SPECULATIVE ticks, each worth up to K+1 tokens): resumed
        output stays byte-identical, futures stay live.  Depth 1 is
        the tier-1 sibling; the deeper crashes are slow-marked."""
        params, cfg = model
        want = [_ref(params, cfg, [3, 4, 5, 6], 9),
                _ref(params, cfg, [7, 8, 9, 1, 2, 3, 4, 5, 6], 7)]
        inj = FaultInjector([FaultSpec(site="decode_tick",
                                       kind="raise", skip=skip)])
        eng = _engine(model, speculative=True, faults=inj)
        futs = [eng.submit([3, 4, 5, 6], max_new_tokens=9),
                eng.submit([7, 8, 9, 1, 2, 3, 4, 5, 6],
                           max_new_tokens=7)]
        _drive(eng, futs)
        assert [f.result(timeout=0) for f in futs] == want
        assert inj.fired
        assert eng.metrics.resumed.value > 0

    @pytest.mark.paged
    @pytest.mark.slow
    def test_cow_prefix_sharing_under_speculation(self, model):
        """Registered-prefix sharers (one prefill, refcounted pages,
        COW growth) decode speculatively and stay oracle-identical —
        including the attach-only admission (prompt == prefix).
        Slow (PR 17 budget pass): ~10 s; the plain spec oracle tests
        here and the COW ladder in test_paged keep each axis
        tier-1."""
        params, cfg = model
        eng = _engine(model, speculative=True)
        pre = [9, 9, 9, 9, 9, 1, 2]
        eng.register_prefix(pre)
        futs = [eng.submit(pre + [k], max_new_tokens=8)
                for k in (3, 4, 5)]
        futs.append(eng.submit(pre, max_new_tokens=6))
        _drive(eng, futs)
        for fu, k in zip(futs[:3], (3, 4, 5)):
            assert fu.result(timeout=0) == _ref(params, cfg, pre + [k], 8)
        assert futs[3].result(timeout=0) == _ref(params, cfg, pre, 6)
        assert eng._prefill_calls <= 3  # prefix once + <=2 group fills

    @pytest.mark.slow
    @pytest.mark.paged
    @pytest.mark.parametrize("kvd", ["bf16", "int8"])
    def test_quantized_pages_oracle(self, model, kvd):
        """Speculative output on bf16/int8 pages equals the
        NON-speculative engine on the same storage (the verify kernel
        round-trips window K/V through the storage dtype, so the two
        paths see identical caches)."""
        outs = {}
        for spec in (True, False):
            eng = _engine(model, speculative=spec, kv_dtype=kvd)
            futs = [eng.submit([3, 4, 5, 6], max_new_tokens=9),
                    eng.submit([10, 11], max_new_tokens=6)]
            _drive(eng, futs)
            outs[spec] = [f.result(timeout=0) for f in futs]
        assert outs[True] == outs[False]

    def test_model_draft_requires_shared_vocab(self, model):
        params, cfg = model
        bad = _draft_cfg()
        bad = type(bad)(**{**bad.__dict__, "vocab_size": 32})
        with pytest.raises(ValueError, match="tokenizer|vocab"):
            _engine(model, speculative=True,
                    draft=(T.init_params(jax.random.PRNGKey(1), bad),
                           bad))

    def test_stats_and_metrics_surface(self, model):
        eng = _engine(model, speculative=True)
        f = eng.submit([3, 4, 5, 6], max_new_tokens=6)
        _drive(eng, [f])
        st = eng.stats()
        assert st["speculative"] is True
        assert st["spec_k"] == SPEC_K
        assert st["spec_draft"] == "ngram"
        assert st["spec_drafted_tokens"] >= st["spec_accepted_tokens"]
        assert st["tokens_per_tick"]["count"] > 0
