"""Paged KV cache (serving/cache.py PagedSlotCache +
models/transformer.py decode_step_paged / prefill_with_prefix).

The gold check is the greedy oracle: whatever the allocation pattern —
page churn, on-demand growth, COW prefix sharing, int8/bf16 storage —
the engine's greedy output is token-identical to per-request
``greedy_decode``, with the decode executable compiled exactly once.
Page tables are data, never structure.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import transformer as T
from horovod_tpu.serving.cache import NULL_PAGE

pytestmark = [pytest.mark.serving, pytest.mark.paged]


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


def _ref_greedy(params, cfg, prompt, steps):
    return np.asarray(T.greedy_decode(
        params, jnp.asarray([prompt], jnp.int32), steps, cfg))[0].tolist()


def _run_until_done(engine, futs, max_ticks=400):
    for _ in range(max_ticks):
        if all(f.done() for f in futs):
            return
        engine.step()
    raise AssertionError("engine did not finish within the tick budget")


def _engine(params, cfg, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 40)
    kw.setdefault("min_prefill_bucket", 4)
    kw.setdefault("page_size", 8)
    return serving.InferenceEngine(params, cfg,
                                   serving.EngineConfig(**kw))


class TestPageAllocator:
    def test_grant_free_refcount_cow(self, model):
        _, cfg = model
        pc = serving.PagedSlotCache(cfg, 2, max_len=32, page_size=8,
                                    n_pages=6)
        s = pc.alloc()
        assert pc.grant(s, 0) == 1  # heapq: lowest page id first
        assert pc.grant(s, 1) == 2
        assert pc.free_pages == 4 and pc.pages_high_water == 2
        # sharing: a raw pin + an attach = refcount 2
        pin = pc.grant_raw(1)
        s2 = pc.alloc()
        pc.attach(s2, pin)
        assert pc.pages_shared == 1
        # COW gives s2 a private copy and drops the share
        new = pc.cow(s2, 0)
        assert new != pin[0] and pc.pages_shared == 0
        assert pc.table[s2, 0] == new
        # freeing returns pages to the heap; the pin survives alone
        pc.free(s)
        pc.free(s2)
        assert pc.free_pages == 6 - 1  # only the pin remains out
        pc.release_raw(pin)
        assert pc.free_pages == 6
        assert pc.pages_high_water == 4  # 2 + pin + cow copy

    def test_out_of_pages_typed(self, model):
        _, cfg = model
        pc = serving.PagedSlotCache(cfg, 2, max_len=32, page_size=8,
                                    n_pages=2)
        s = pc.alloc()
        pc.grant(s, 0), pc.grant(s, 1)
        with pytest.raises(serving.CacheOutOfPagesError):
            pc.grant(s, 2)
        with pytest.raises(serving.CacheOutOfPagesError):
            pc.grant_raw(1)

    def test_default_pool_is_capacity_parity(self, model):
        _, cfg = model
        pc = serving.PagedSlotCache(cfg, 3, max_len=40, page_size=8)
        assert pc.n_pages == 3 * 5  # every slot can still grow to max_len

    def test_slot_free_list_is_fcfs_lowest(self, model):
        # lowest free index first — also after a paired pool's acquire
        # took a slot out of the middle of the heap
        _, cfg = model
        slots = serving.PagedSlotCache(cfg, 4, max_len=16)
        slots.acquire(1)
        assert [slots.alloc() for _ in range(3)] == [0, 2, 3]
        slots.free(2), slots.free(0)
        assert slots.alloc() == 0
        with pytest.raises(ValueError, match="already active"):
            slots.acquire(3)


class TestPagedOracle:
    """ACCEPTANCE: greedy output == per-request greedy_decode at fixed
    config, decode compiled exactly once across churn, growth, and
    sharing."""

    @pytest.mark.perf
    @pytest.mark.slow
    def test_token_identity_vs_greedy_decode(self, model):
        params, cfg = model
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (3, 9, 5, 12, 2, 7)]
        steps = 11
        engine = _engine(params, cfg, n_slots=3,
                         max_prefills_per_tick=2, max_queue_depth=8)
        futs = [engine.submit(p, max_new_tokens=steps) for p in prompts]
        _run_until_done(engine, futs)
        assert engine.decode_compilations == 1
        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, steps)

    def test_growth_crosses_page_boundaries(self, model):
        """A long generation grows page by page (prompt 3 + 30 tokens:
        writes at positions 0..31 span exactly 4 pages at page_size 8
        — the final token is emitted, never written, and the stale
        pipeline tick past it must NOT grant a 5th page) and stays
        oracle-exact."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2)
        fut = engine.submit([5, 9, 2], max_new_tokens=30)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    [5, 9, 2], 30)
        assert engine.decode_compilations == 1
        assert engine.stats()["kv_pages_high_water"] == 4

    @pytest.mark.slow
    def test_page_reuse_no_contamination(self, model):
        """SATELLITE: freed pages re-granted to new requests attend
        only their own tokens — write-before-attend re-proven per PAGE.
        More requests than the pool holds at once, so every later
        request decodes out of recycled pages."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=6,
                         max_queue_depth=16, max_prefills_per_tick=2)
        rng = np.random.default_rng(11)
        cases = [(rng.integers(0, cfg.vocab_size, n).tolist(), s)
                 for n, s in ((4, 6), (8, 3), (2, 9), (6, 5), (3, 7),
                              (9, 4), (5, 8))]
        futs = [engine.submit(p, max_new_tokens=s) for p, s in cases]
        _run_until_done(engine, futs)
        for (p, s), f in zip(cases, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, s)
        assert engine.decode_compilations == 1
        # pages really did recycle: total landed tokens exceed the pool
        assert sum(len(p) + s for p, s in cases) > 6 * 8

    @pytest.mark.slow
    def test_fragmentation_beats_slot_contiguous_ceiling(self, model):
        """SATELLITE: at a fixed HBM budget of 48 cache tokens
        (page_size 8 x 6 pages), a layout that reserves max_len a slot
        (the slot-contiguous cache, gone in PR 28) fits
        floor(48 / max_len 40) = ONE worst-case slot; the paged engine
        runs FOUR short requests (each within one page) concurrently
        out of the same bytes."""
        params, cfg = model
        budget_tokens = 48
        ceiling = budget_tokens // 40  # max_len a slot: 1 request
        engine = _engine(params, cfg, n_slots=4, n_pages=6,
                         max_prefills_per_tick=4, max_queue_depth=8)
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, cfg.vocab_size, 3).tolist()
                   for _ in range(4)]
        futs = [engine.submit(p, max_new_tokens=4) for p in prompts]
        peak = 0
        for _ in range(200):
            engine.step()
            peak = max(peak, engine.slots.active_count)
            if all(f.done() for f in futs):
                break
        assert peak > ceiling  # strictly above: 4 > 1
        assert peak == 4
        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, 4)


class TestPrefixSharing:
    @pytest.mark.slow
    def test_shared_prefix_prefilled_once_for_n_requests(self, model):
        """ACCEPTANCE: a registered system prompt is prefilled exactly
        once for N sharers (prefill CALL count asserted), its pages
        refcount-shared, and every output stays oracle-exact — with
        zero decode recompiles across the sharing."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=4, max_queue_depth=8,
                         max_prefills_per_tick=2)
        rng = np.random.default_rng(5)
        prefix = rng.integers(0, cfg.vocab_size, 11).tolist()  # unaligned
        engine.register_prefix(prefix)
        assert engine._prefill_calls == 1
        sufs = [rng.integers(0, cfg.vocab_size, n).tolist()
                for n in (3, 5, 2, 4)]
        futs = [engine.submit(prefix + s, max_new_tokens=7)
                for s in sufs]
        while not all(f.done() for f in futs):
            engine.step()
            # the prefix pages are live-shared while sharers decode
        for s, f in zip(sufs, futs):
            assert f.result(timeout=0) == _ref_greedy(
                params, cfg, prefix + s, 7)
        # 1 prefix prefill + suffix prefills only — NEVER another pass
        # over the prefix tokens (one suffix prefill per admission
        # group; 4 requests / K=2 <= 3 groups under tick timing).
        assert engine._prefill_calls <= 1 + 3
        assert engine.decode_compilations == 1
        assert engine.stats()["requests_completed"] == 4

    def test_prompt_equals_prefix_zero_prefill_admission(self, model):
        """A prompt that IS the prefix admits with NO forward pass at
        all: pages attached, cached first token emitted, decode COWs
        the shared partial page before its first write."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=3, max_queue_depth=8,
                         max_prefills_per_tick=3)
        prefix = [7, 3, 9, 1, 4, 2, 8, 6, 5, 3, 2]  # 11 tokens, unaligned
        engine.register_prefix(prefix)
        calls0 = engine._prefill_calls
        futs = [engine.submit(list(prefix), max_new_tokens=6)
                for _ in range(3)]
        shared_seen = 0
        while not all(f.done() for f in futs):
            engine.step()
            shared_seen = max(shared_seen, engine.slots.pages_shared)
        assert engine._prefill_calls == calls0  # zero admission prefills
        ref = _ref_greedy(params, cfg, prefix, 6)
        for f in futs:
            assert f.result(timeout=0) == ref
        assert shared_seen >= 1  # the full prefix pages were truly shared

    @pytest.mark.slow
    def test_cow_preserves_the_shared_page(self, model):
        """COW semantics: sharers writing into the partial prefix page
        each get a private copy; a LATER sharer still reads the
        original, unclobbered prefix K/V."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8)
        rng = np.random.default_rng(9)
        prefix = rng.integers(0, cfg.vocab_size, 11).tolist()
        engine.register_prefix(prefix)
        # wave 1: two sharers decode INTO their COW'd copies
        w1 = [engine.submit(prefix + rng.integers(0, 64, n).tolist(),
                            max_new_tokens=6) for n in (3, 2)]
        _run_until_done(engine, w1)
        # wave 2: a fresh sharer after wave 1 wrote near the boundary
        suf = rng.integers(0, cfg.vocab_size, 4).tolist()
        f2 = engine.submit(prefix + suf, max_new_tokens=8)
        _run_until_done(engine, [f2])
        assert f2.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + suf, 8)

    @pytest.mark.slow
    def test_sharing_on_vs_off_identical(self, model):
        """ACCEPTANCE: prefix sharing is a pure optimization — the same
        workload with and without the registration is token-identical."""
        params, cfg = model
        rng = np.random.default_rng(13)
        prefix = rng.integers(0, cfg.vocab_size, 8).tolist()  # aligned
        sufs = [rng.integers(0, cfg.vocab_size, n).tolist()
                for n in (4, 2, 6)]
        outs = {}
        for share in (False, True):
            engine = _engine(params, cfg, n_slots=3, max_queue_depth=8,
                             max_prefills_per_tick=2)
            if share:
                engine.register_prefix(prefix)
            futs = [engine.submit(prefix + s, max_new_tokens=9)
                    for s in sufs]
            _run_until_done(engine, futs)
            outs[share] = [f.result(timeout=0) for f in futs]
            assert engine.decode_compilations == 1
        assert outs[True] == outs[False]

    def test_restart_invalidates_and_reprefills_prefix(self, model):
        """A supervised restart replaces the pool: the registry entry
        lazily re-prefills ONCE on next use and sharing keeps working."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8)
        prefix = [1, 2, 3, 4, 5, 6, 7, 8]
        engine.register_prefix(prefix)
        fut = engine.submit(prefix + [9], max_new_tokens=4)
        _run_until_done(engine, [fut])
        calls0 = engine._prefill_calls
        with engine._lock:
            engine._consec_failures = 0
            engine._restart()  # fresh PagedSlotCache, epoch bump
        f2 = engine.submit(prefix + [9, 10], max_new_tokens=4)
        _run_until_done(engine, [f2])
        assert f2.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + [9, 10], 4)
        # exactly one re-registration prefill + one suffix prefill
        assert engine._prefill_calls == calls0 + 2


class TestPrefixRegistryLifecycle:
    def test_terminate_then_unregister_no_refcount_underflow(self, model):
        """REGRESSION: terminate() resets the pool (release_all zeroes
        every refcount) — a later unregister of a pre-terminate prefix
        must be a no-op against the new cache epoch, not a refcount
        underflow."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2)
        prefix = [1, 2, 3, 4, 5, 6, 7, 8]
        engine.register_prefix(prefix)
        engine.terminate("test teardown")
        engine.unregister_prefix(prefix)  # must not raise

    def test_failed_prefix_prefill_releases_its_pages(self, model):
        """REGRESSION: a prefix prefill that dies after its pages were
        pinned must unpin them — otherwise every retry leaks
        pages_for(p0) pages and the pool drains."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=6)
        free0 = engine.slots.free_pages
        boom = RuntimeError("injected prefill failure")
        orig = engine._prefill_fn
        engine._prefill_fn = lambda *a, **k: (_ for _ in ()).throw(boom)
        with pytest.raises(RuntimeError):
            engine.register_prefix([1, 2, 3, 4, 5, 6, 7, 8, 9])
        engine._prefill_fn = orig
        assert engine.slots.free_pages == free0  # nothing pinned/leaked


class TestResumePagedComposition:
    """Restart-resume x paged cache (ISSUE 9 satellites): a resumed
    request re-admits through the SAME paged plumbing — pages
    re-granted, shared prefixes re-attached (suffix prefill, never a
    full pass over the prefix), refcounts balanced — and output stays
    oracle-identical."""

    def _crash_mid_decode(self, engine, fut, inj, min_tokens=2):
        for _ in range(400):
            if len(fut.tokens_so_far()) >= min_tokens or fut.done():
                break
            engine.step()
        assert not fut.done()
        inj.add(serving.FaultSpec(site="decode_tick", kind="raise",
                                  skip=inj.visits("decode_tick")))

    def test_resume_attaches_cow_prefix_refcounts_balance(self, model):
        """SATELLITE: resume a request whose slot used a shared COW
        prefix.  The restart re-prefills the PREFIX once (the pool
        died with the crash — the documented lazy re-ensure), but the
        request itself re-admits via attach + SUFFIX prefill, never a
        full pass over prefix + suffix + emitted; refcounts balance
        down to exactly the registry pin; output is oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8,
                         restart_backoff=0.01, faults=inj)
        rng = np.random.default_rng(21)
        prefix = rng.integers(0, cfg.vocab_size, 11).tolist()  # unaligned
        engine.register_prefix(prefix)
        suf = rng.integers(0, cfg.vocab_size, 3).tolist()
        fut = engine.submit(prefix + suf, max_new_tokens=8)
        self._crash_mid_decode(engine, fut, inj)
        calls_at_crash = engine._prefill_calls
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + suf, 8)
        s = engine.stats()
        assert s["requests_resumed"] == 1
        # ONE lazy prefix re-prefill + ONE suffix prefill — a full
        # prefill of prefix+suffix+emitted would also be +2 calls, so
        # pin the shape via the shared-page gauge: the resumed slot
        # ATTACHED the prefix pages (refcount > 1 while decoding).
        assert engine._prefill_calls == calls_at_crash + 2
        assert s["kv_pages_shared"] == 0  # retired: share collapsed
        # refcounts balance to exactly the registry pin
        pin = engine.slots.pages_for(len(prefix))
        assert engine.slots.free_pages == engine.slots.n_pages - pin
        engine.unregister_prefix(prefix)
        assert engine.slots.free_pages == engine.slots.n_pages
        assert s["journal_inflight"] == 0

    def test_resume_shared_pages_live_during_continuation(self, model):
        """The attach is real sharing, not a copy: while the resumed
        request decodes, the prefix pages are referenced by both the
        registry pin and the slot."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8,
                         restart_backoff=0.01, faults=inj)
        prefix = [7, 3, 9, 1, 4, 2, 8, 6, 5, 3, 2]
        engine.register_prefix(prefix)
        fut = engine.submit(prefix + [9, 9], max_new_tokens=9)
        self._crash_mid_decode(engine, fut, inj)
        shared_seen = 0
        for _ in range(400):
            if fut.done():
                break
            engine.step()
            shared_seen = max(shared_seen, engine.slots.pages_shared)
        assert fut.result(timeout=0) == _ref_greedy(
            params, cfg, prefix + [9, 9], 9)
        assert shared_seen >= 1  # resumed slot truly shared the prefix

    def test_resume_prompt_was_prefix_attach_only(self, model):
        """A request admitted attach-only (prompt IS the prefix) whose
        decode COW'd into the shared partial page: after a crash the
        resume prompt is prefix + emitted — the emitted tokens become
        the SUFFIX against the re-pinned prefix, still oracle-exact."""
        params, cfg = model
        inj = serving.FaultInjector()
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8,
                         restart_backoff=0.01, faults=inj)
        prefix = [5, 1, 6, 2, 7, 3, 8, 4, 9, 5, 1]  # 11, unaligned
        engine.register_prefix(prefix)
        fut = engine.submit(list(prefix), max_new_tokens=8)
        self._crash_mid_decode(engine, fut, inj, min_tokens=3)
        _run_until_done(engine, [fut])
        assert fut.result(timeout=0) == _ref_greedy(params, cfg,
                                                    prefix, 8)
        assert engine.stats()["requests_resumed"] == 1
        pin = engine.slots.pages_for(len(prefix))
        assert engine.slots.free_pages == engine.slots.n_pages - pin

    def test_terminate_purges_resumable_journal_entries(self, model):
        """SATELLITE (alongside the PR 7 refcount-underflow
        regression): terminate()/drain of resumable requests purges
        their journal entries — a dead engine leaves no ghost for any
        later lifetime, and the resumed counter stays untouched."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, max_queue_depth=8)
        done = engine.submit([1, 2, 3], max_new_tokens=3)
        _run_until_done(engine, [done])          # retires -> purged
        mid = engine.submit([4, 5], max_new_tokens=20)
        for _ in range(400):
            if len(mid.tokens_so_far()) >= 2:
                break
            engine.step()
        assert len(engine.journal) == 1          # only `mid` lives
        engine.terminate("test teardown")
        with pytest.raises(serving.EngineFailedError):
            mid.result(timeout=0)
        assert len(engine.journal) == 0          # purged, no ghosts
        assert engine.stats()["requests_resumed"] == 0
        assert engine.metrics.resumed.value == 0


class TestQuantizedPages:
    @pytest.mark.slow
    def test_bf16_pages_token_identical_on_bf16_model(self):
        """ACCEPTANCE: with a bf16 model, bf16 page storage is the same
        rounding the single-request cache of ``greedy_decode`` applies
        — bf16 pages serve the oracle's tokens at fixed config."""
        cfg = _cfg(dtype=jnp.bfloat16)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(17)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (3, 7, 5)]
        engine = _engine(params, cfg, n_slots=3, max_prefills_per_tick=2,
                         kv_dtype="bf16")
        futs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        _run_until_done(engine, futs)
        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, 8)

    def test_bf16_pages_halve_cache_bytes_on_f32_model(self, model):
        params, cfg = model
        full = _engine(params, cfg).slots.bytes_per_token
        half = _engine(params, cfg,
                       kv_dtype="bf16").slots.bytes_per_token
        assert half * 2 == full

    def test_int8_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
        q, s = T.kv_quantize(x)
        back = T.kv_dequantize(q, s, jnp.float32)
        # symmetric per-vector int8: error <= scale/2 = amax/254
        amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
        assert (np.abs(np.asarray(back) - np.asarray(x))
                <= amax / 254 + 1e-7).all()

    @pytest.mark.slow
    def test_int8_engine_completes_and_matches_oracle(self, model):
        """int8 pages are lossy by design; on this config the per-vector
        scales keep greedy argmax on the oracle path (deterministic —
        verified, not guaranteed at scale), and the byte gauge shows
        the ~4x payload shrink (+ scale overhead).  Slow (PR 17 budget
        pass): ~7 s; the int8 quantize/dequantize units above stay
        tier-1, as does the int8 pool under tp in test_tp_serving."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, kv_dtype="int8",
                         max_queue_depth=8)
        rng = np.random.default_rng(19)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (4, 9)]
        futs = [engine.submit(p, max_new_tokens=8) for p in prompts]
        _run_until_done(engine, futs)
        for p, f in zip(prompts, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, 8)
        assert engine.decode_compilations == 1
        snap = engine.stats()
        f32_bytes = _engine(params, cfg).slots.bytes_per_token
        assert snap["kv_bytes_per_token"] < f32_bytes / 2


class TestBackPressure:
    @pytest.mark.slow
    def test_admission_waits_for_pages_then_completes(self, model):
        """Requests that outsize the free heap WAIT (no rejection, FCFS
        intact) and admit as retirements recycle pages — every future
        still resolves with oracle-exact tokens."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=4, n_pages=4,
                         max_queue_depth=16, max_prefills_per_tick=4)
        rng = np.random.default_rng(23)
        cases = [(rng.integers(0, cfg.vocab_size, 8).tolist(), 7)
                 for _ in range(5)]  # each needs ~2 pages; pool holds 4
        futs = [engine.submit(p, max_new_tokens=s) for p, s in cases]
        engine.step()
        assert engine.scheduler.depth > 0  # someone is waiting on pages
        _run_until_done(engine, futs)
        for (p, s), f in zip(cases, futs):
            assert f.result(timeout=0) == _ref_greedy(params, cfg, p, s)

    def test_whole_pool_request_admits_eventually(self, model):
        """REGRESSION: a request whose prompt needs every page the pool
        has — so the admission plan's margin heuristic (prompt pages
        + 1) exceeds n_pages outright — must still admit once the pool
        drains, not park the FCFS head (and everyone behind it)
        forever.  The submit-time fit check accepted it; the admission
        budget must not demand more pages than could ever be free."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=4,
                         max_queue_depth=4)
        rng = np.random.default_rng(31)
        big = rng.integers(0, cfg.vocab_size, 26).tolist()  # 4/4 pages
        small = rng.integers(0, cfg.vocab_size, 3).tolist()
        futs = [engine.submit(big, max_new_tokens=6),
                engine.submit(small, max_new_tokens=4)]
        _run_until_done(engine, futs)
        assert futs[0].result(timeout=0) == _ref_greedy(
            params, cfg, big, 6)
        assert futs[1].result(timeout=0) == _ref_greedy(
            params, cfg, small, 4)

    def test_submit_too_big_for_pool_typed_rejection(self, model):
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=2,
                         max_len=40)
        with pytest.raises(serving.CacheOutOfPagesError):
            engine.submit(list(range(20)), max_new_tokens=8)
        assert engine.stats()["requests_rejected"] == 1

    def test_decode_growth_exhaustion_preempts_youngest(self, model):
        """Pool exhaustion mid-decode preempts the YOUNGEST request;
        since PR 14 the victim SUSPENDS through the resume path
        (journal frontier, pages freed, re-admitted once the pool
        clears) instead of failing typed — the older request keeps its
        pages and BOTH finish oracle-exact, the victim byte-identical
        to an uninterrupted run."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=4,
                         max_queue_depth=4, max_prefills_per_tick=2,
                         overlap=False)
        old = engine.submit([3, 4, 5, 6, 7, 8, 9, 1], max_new_tokens=24)
        young = engine.submit([2, 6, 4, 1, 9, 5, 8, 3], max_new_tokens=24)
        _run_until_done(engine, [old, young])
        assert old.result(timeout=0) == _ref_greedy(
            params, cfg, [3, 4, 5, 6, 7, 8, 9, 1], 24)
        assert young.result(timeout=0) == _ref_greedy(
            params, cfg, [2, 6, 4, 1, 9, 5, 8, 3], 24)
        assert engine.stats()["preemptions"] >= 1
        assert engine.slots.active_count == 0  # nothing leaked

    def test_preemption_without_resume_fails_typed(self, model):
        """``resume=False`` keeps the legacy contract: the preempted
        victim resolves with the typed :class:`CacheOutOfPagesError`
        (no journal frontier to suspend onto)."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=4,
                         max_queue_depth=4, max_prefills_per_tick=2,
                         overlap=False, resume=False)
        old = engine.submit([3, 4, 5, 6, 7, 8, 9, 1], max_new_tokens=24)
        young = engine.submit([2, 6, 4, 1, 9, 5, 8, 3], max_new_tokens=24)
        _run_until_done(engine, [old, young])
        assert old.result(timeout=0) == _ref_greedy(
            params, cfg, [3, 4, 5, 6, 7, 8, 9, 1], 24)
        with pytest.raises(serving.CacheOutOfPagesError):
            young.result(timeout=0)
        assert engine.stats()["preemptions"] == 0
        assert engine.slots.active_count == 0  # nothing leaked


class TestPagedObservability:
    def test_page_gauges_in_stats_and_registry(self, model):
        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=8)
        fut = engine.submit([1, 2, 3], max_new_tokens=3)
        _run_until_done(engine, [fut])
        s = engine.stats()
        assert s["kv_pages_total"] == 8
        assert s["kv_pages_free"] == 8  # all recycled after retirement
        assert s["kv_pages_shared"] == 0
        assert s["kv_bytes_per_token"] == engine.slots.bytes_per_token
        assert s["kv_pages_high_water"] >= 1
        assert s["paged"] is True and s["page_size"] == 8
        text = engine.metrics.registry.to_prometheus()
        for fam in ("serving_kv_pages_total", "serving_kv_pages_free",
                    "serving_kv_pages_shared",
                    "serving_kv_bytes_per_token"):
            assert fam in text

    @pytest.mark.perf
    @pytest.mark.slow
    def test_compile_once_and_one_sync_per_tick_across_sharing(self,
                                                               model):
        """PERF GUARD: across admission churn, page growth, prefix
        attach/COW, and preemption-free steady state, the decode
        executable compiles ONCE and the overlapped loop keeps its
        <= 1 host-sync-per-tick contract — page-table maintenance must
        never add a blocking fetch.  Slow (PR 17 budget pass): the
        churn soak is ~8 s; test_sched's chunk-compile-set guard and
        the decode_compilations asserts across the oracle tests keep
        compile-count regressions tier-1."""
        params, cfg = model
        engine = _engine(params, cfg, n_slots=4, max_queue_depth=16,
                         max_prefills_per_tick=2)
        prefix = [9, 8, 7, 6, 5, 4, 3, 2]
        engine.register_prefix(prefix)
        engine.warmup([4, 8])
        warm = engine.decode_compilations
        m0 = engine.stats()
        rng = np.random.default_rng(29)
        futs = [engine.submit(prefix + rng.integers(0, 64, n).tolist(),
                              max_new_tokens=9)
                for n in (2, 4, 3, 2, 5, 1)]
        futs += [engine.submit(rng.integers(0, 64, 5).tolist(),
                               max_new_tokens=9) for _ in range(3)]
        _run_until_done(engine, futs)
        assert engine.decode_compilations == warm == 1
        m1 = engine.stats()
        ticks = m1["decode_ticks"] - m0["decode_ticks"]
        syncs = m1["host_syncs"] - m0["host_syncs"]
        # one deferred fetch per tick + one per admission group
        assert ticks > 0
        assert syncs <= ticks + m1["requests_admitted"]


class TestPagedDecodeKernel:
    def test_matches_decode_step_rowwise(self, model):
        """Row s of decode_step_paged == the single-request decode_step
        at that slot's own position, for slots at DIFFERENT depths and
        an OUT-OF-ORDER page table — the indirection is exact."""
        params, cfg = model
        ps, max_pages, S = 8, 6, 3
        P = 1 + S * max_pages
        pool = serving.init_page_pool(cfg, S, P, ps)
        table = np.zeros((S, max_pages), np.int32)
        table[0, :3] = [5, 2, 9]
        table[1, :3] = [1, 7, 3]
        prompts = [[3, 4, 5, 6], [10, 11]]
        singles = []
        for s, p in enumerate(prompts):
            _, pre = T.prefill(params, jnp.asarray([p], jnp.int32),
                               T.init_cache(cfg, 1, 16), cfg)
            singles.append(pre)
            pool["pos"] = pool["pos"].at[s].set(len(p))
            for t in range(len(p)):
                pg, off = table[s, t // ps], t % ps
                for n in ("k", "v"):
                    pool[n] = pool[n].at[:, pg, :, off].set(
                        pre[n][:, 0, :, t])
        active = jnp.asarray([True, True, False])
        tokens = jnp.asarray([7, 12, 0], jnp.int32)
        tab = jnp.asarray(table)
        for _ in range(6):  # slot 0 crosses into its second page
            lp, pool = T.decode_step_paged(
                params, tokens, pool, tab, cfg, active)
            for s in range(2):
                ref, singles[s] = T.decode_step(
                    params, tokens[s:s + 1], singles[s], cfg)
                np.testing.assert_allclose(np.asarray(lp[s]),
                                           np.asarray(ref[0]),
                                           atol=1e-4, rtol=1e-4)
            tokens = jnp.argmax(lp, -1).astype(jnp.int32)
        assert np.asarray(pool["pos"]).tolist() == [10, 8, 0]

    def test_inactive_rows_write_only_the_null_page(self, model):
        """An inactive row's stale scatter must land in page 0 — under
        paging a freed slot's old pages may already belong to someone
        else, so 'harmless overwrite' is not available."""
        params, cfg = model
        ps, max_pages, S = 8, 2, 2
        pool = serving.init_page_pool(cfg, S, 5, ps)
        table = np.zeros((S, max_pages), np.int32)
        table[0, 0] = 3  # the inactive slot STILL points at page 3
        pool["pos"] = jnp.asarray([2, 0], jnp.int32)
        before = np.asarray(pool["k"][:, 3]).copy()
        active = jnp.asarray([False, True])
        _, pool = T.decode_step_paged(
            params, jnp.asarray([9, 9], jnp.int32), pool,
            jnp.asarray(table), cfg, active)
        np.testing.assert_array_equal(np.asarray(pool["k"][:, 3]), before)
        assert np.asarray(pool["k"][:, NULL_PAGE]).any()  # routed to trash

    def test_eager_capacity_guard(self, model):
        params, cfg = model
        pool = serving.init_page_pool(cfg, 2, 5, 8)
        table = np.zeros((2, 2), np.int32)
        pool["pos"] = jnp.asarray([16, 0], jnp.int32)
        with pytest.raises(ValueError, match="capacity"):
            T.decode_step_paged(params, jnp.zeros(2, jnp.int32), pool,
                                jnp.asarray(table), cfg,
                                jnp.asarray([True, False]))


@pytest.mark.paged_kernel
class TestFusedPagedKernel:
    """The fused Pallas flash-decoding kernel (ops/paged_attention.py)
    vs the unfused gather->dequant->attend path.

    TOLERANCE CONTRACT (the satellite audit): int8 dequant is pinned to
    f32 compute in BOTH paths (kv_dequantize and the kernel's fused
    load share DEQUANT_COMPUTE), so f32 and int8 pools agree to f32
    rounding (|dlogits| ~1e-6 at this scale; asserted at atol=1e-4).
    bf16 pools round the attention weights at different points (the
    online-softmax accumulator rescales before the final normalize),
    so logits agree only to bf16 noise (atol=2e-2) — but GREEDY TOKENS
    are identical in every case, which is the landing gate.
    """

    _KV = [None, "bf16", "int8"]

    # The walk's edge cases.  ``block`` is the pages one step of the walk
    # takes (None: what the shapes give, the whole toy table); a small
    # one is set on the module, so toy pools walk many blocks.
    _WALKS = {
        # partial last page, a slot at exactly table capacity, an
        # inactive (fully masked) slot, and a REPEATED page id (the
        # refcount>1 / COW-shared shape: two tables on one page)
        "edge_tables": dict(S=4, Hkv=2, R=2, MP=3, block=None,
                            limits=[24, 5, 0, 11], share=True),
        # block = 2 pages = 16 tokens: limit 0, 1, one block, one block
        # + 1, and capacity (4 blocks: both buffers are used twice)
        "block_bounds": dict(S=5, Hkv=2, R=2, MP=8, block=2,
                             limits=[0, 1, 16, 17, 64]),
        # an odd table: the last block is cut by the table's end
        "wrapping_buffers": dict(S=3, Hkv=2, R=2, MP=7, block=2,
                                 limits=[56, 41, 33]),
        # R = G x W rows of a speculative VERIFY window (padded to 16)
        "verify_rows": dict(S=3, Hkv=2, R=2 * 5, MP=6, block=2,
                            limits=[48, 19, 0]),
        # one KV head: what a tp shard of a GQA model sees
        "one_kv_head": dict(S=3, Hkv=1, R=4, MP=6, block=4,
                            limits=[48, 30, 7]),
        # every page that holds no live position is inf, and the table's
        # entries past the live pages point at such pages: a dead page
        # that reached the softmax would turn the output to nan
        "poisoned_dead_pages": dict(S=4, Hkv=2, R=2, MP=7, block=2,
                                    limits=[0, 3, 24, 56], poison=True),
    }

    @staticmethod
    def _walk_case(rng, kv, *, S, Hkv, R, MP, limits, ps=8, Dh=16,
                   share=False):
        """One layer's pool (k, v, k_scale, v_scale), a table over it
        and queries, for the kernel and the reference alike."""
        Pn = S * MP + 1
        qg = jnp.asarray(rng.randn(S, Hkv, R, Dh), jnp.float32)
        kf = rng.randn(Pn, Hkv, ps, Dh).astype(np.float32)
        vf = rng.randn(Pn, Hkv, ps, Dh).astype(np.float32)
        # every slot its own pages, in a shuffled order
        table = (1 + rng.permutation(S * MP)).reshape(S, MP).astype(np.int32)
        if share:
            table[1] = table[0]       # shared pages, refcount > 1
        if kv == "int8":
            kq, ks = T.kv_quantize(jnp.asarray(kf))
            vq, vs = T.kv_quantize(jnp.asarray(vf))
            pool = [kq, vq, ks, vs]
        else:
            dt = jnp.bfloat16 if kv == "bf16" else jnp.float32
            pool = [jnp.asarray(kf, dt), jnp.asarray(vf, dt), None, None]
        return qg, pool, table, jnp.asarray(limits, jnp.int32)

    @staticmethod
    def _poisoned(pool, table, limits, ps):
        """``pool`` with inf in every page no live position references
        (an int8 page is poisoned through its scales)."""
        dead = np.ones(pool[0].shape[0], bool)
        for row, lim in zip(table, limits):
            dead[row[:-(-lim // ps)]] = False
        k, v, ks, vs = pool
        if ks is None:
            return [jnp.where(dead[:, None, None, None], jnp.inf, x)
                    for x in (k, v)] + [None, None]
        return [k, v] + [jnp.where(dead[:, None, None], jnp.inf, x)
                         for x in (ks, vs)]

    @pytest.mark.parametrize("walk", list(_WALKS))
    @pytest.mark.parametrize("kv", _KV)
    def test_kernel_matches_reference_edge_tables(self, model, kv, walk,
                                                  monkeypatch):
        """Unit: Pallas kernel == pure-JAX reference over one layer's
        pool, for each of :attr:`_WALKS`."""
        from horovod_tpu.ops import paged_attention as PA

        _, cfg = model
        case = dict(self._WALKS[walk])
        block, poison = case.pop("block"), case.pop("poison", False)
        qg, pool, table, limit = self._walk_case(
            np.random.RandomState(3), kv, **case)
        _, Hkv, ps, Dh = pool[0].shape
        if block is not None:
            width = max(pool[0].dtype.itemsize, 2)
            monkeypatch.setattr(PA, "_BLOCK_BYTES",
                                block * Hkv * ps * Dh * width)
            assert PA.block_pages(ps, Hkv, Dh, pool[0].dtype,
                                  case["MP"]) == block
        tab = jnp.asarray(table)
        o_r, l_r = PA.paged_attend_reference(qg, *pool, tab, limit,
                                             compute_dtype=cfg.dtype)
        if poison:
            pool = self._poisoned(pool, table, case["limits"], ps)
        o_k, l_k = PA.paged_attend(qg, *pool, tab, limit,
                                    compute_dtype=cfg.dtype)
        tol = 2e-2 if kv == "bf16" else 1e-4
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=tol, rtol=tol)
        live = np.asarray(limit) > 0
        np.testing.assert_allclose(np.asarray(l_k)[live],
                                   np.asarray(l_r)[live],
                                   atol=tol, rtol=tol)
        # fully-masked rows: zero output, NEG_INF logsumexp — the
        # combine-neutral element
        assert not np.asarray(o_k)[~live].any()
        assert (np.asarray(l_k)[~live] <= PA.NEG_INF / 2).all()

    def test_dequant_compute_dtype_pinned(self):
        """The satellite audit: the kernel's fused dequant and
        kv_dequantize must round IDENTICALLY — both promote int8
        payload and scale through f32 (DEQUANT_COMPUTE) and cast once,
        even when the target dtype is bf16."""
        from horovod_tpu.ops import paged_attention as PA

        assert PA.DEQUANT_COMPUTE == jnp.float32
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(5, 7, 16), jnp.float32)
        q, s = T.kv_quantize(x)
        for dt in (jnp.float32, jnp.bfloat16):
            np.testing.assert_array_equal(
                np.asarray(PA._dequant(q, s, dt).astype(jnp.float32)),
                np.asarray(T.kv_dequantize(q, s, dt).astype(jnp.float32)))

    @pytest.mark.slow  # ~18 s/variant eager-loop A/B (DURATIONS.md);
    # tier-1 siblings: the kernel-vs-reference edge-table units above
    # (all three pool dtypes) + test_engine_fused_oracle_and_compile_set
    @pytest.mark.parametrize("kv", _KV)
    def test_decode_step_fused_greedy_identical(self, model, kv):
        """decode_step_paged(kernel=True) greedy-matches kernel=False
        over ticks that cross a page boundary, with an inactive row and
        an out-of-order table."""
        params, cfg = model
        rng = np.random.RandomState(1)
        S, Pn, ps, MP = 4, 12, 8, 4
        pool = serving.init_page_pool(cfg, S, Pn, ps, kv_dtype=kv)
        table = jnp.asarray(rng.randint(1, Pn, (S, MP)), jnp.int32)
        active = jnp.asarray([True, True, False, True])
        tu = tk = jnp.asarray(rng.randint(0, 64, (S,)), jnp.int32)
        pool_u, pool_k = dict(pool), dict(pool)
        tol = 2e-2 if kv == "bf16" else 1e-4
        for _ in range(10):  # crosses the ps=8 page boundary
            lu, pool_u = T.decode_step_paged(params, tu, pool_u, table,
                                             cfg, active)
            lk, pool_k = T.decode_step_paged(params, tk, pool_k, table,
                                             cfg, active, kernel=True)
            np.testing.assert_allclose(np.asarray(lk)[np.asarray(active)],
                                       np.asarray(lu)[np.asarray(active)],
                                       atol=tol, rtol=tol)
            au = jnp.argmax(lu, -1).astype(jnp.int32)
            ak = jnp.argmax(lk, -1).astype(jnp.int32)
            assert bool((au[active] == ak[active]).all())
            tu, tk = au, ak
        assert int(pool_k["pos"][2]) == 0  # inactive froze under kernel

    @pytest.mark.slow  # ~18 s eager verify A/B (DURATIONS.md); tier-1
    # sibling: test_engine_speculative_fused_oracle drives the same
    # kernel+LSE-combine verify path through the compiled engine tick
    def test_verify_fused_matches_unfused(self, model):
        """decode_verify_paged(kernel=True): the committed-pages kernel
        + in-window LSE combine produces the same target tokens AND the
        same acceptance as the unfused concat path — including a fresh
        slot at pos 0 (no committed context: the combine's a_c
        underflows to exactly zero)."""
        params, cfg = model
        rng = np.random.RandomState(1)
        S, Pn, ps, MP, W = 4, 12, 8, 4, 4
        table = jnp.asarray(rng.randint(1, Pn, (S, MP)), jnp.int32)
        active = jnp.asarray([True, True, False, True])
        for kv in (None, "int8"):
            pool = serving.init_page_pool(cfg, S, Pn, ps, kv_dtype=kv)
            t = jnp.asarray(rng.randint(0, 64, (S,)), jnp.int32)
            for _ in range(9):
                l, pool = T.decode_step_paged(params, t, pool, table,
                                              cfg, active)
                t = jnp.argmax(l, -1).astype(jnp.int32)
            pool = dict(pool)
            pool["pos"] = pool["pos"].at[3].set(0)  # fresh slot
            win = jnp.asarray(rng.randint(0, 64, (S, W)), jnp.int32)
            tu, mu, accu, _ = T.decode_verify_paged(
                params, win, dict(pool), table, cfg, active)
            tk, mk, acck, _ = T.decode_verify_paged(
                params, win, dict(pool), table, cfg, active, kernel=True)
            a = np.asarray(active)
            np.testing.assert_array_equal(np.asarray(tk)[a],
                                          np.asarray(tu)[a])
            np.testing.assert_array_equal(np.asarray(acck),
                                          np.asarray(accu))
            np.testing.assert_allclose(np.asarray(mk)[a],
                                       np.asarray(mu)[a],
                                       atol=1e-4, rtol=1e-4)

    def test_engine_fused_oracle_and_compile_set(self, model):
        """ACCEPTANCE: a paged_kernel=True engine is token-identical to
        per-request greedy_decode, compiles decode EXACTLY once (the
        fused path adds new executables, not per-tick retraces — the
        compile-set guard re-asserted after a second traffic round),
        and reports paged_kernel_engaged in /stats."""
        from conftest import assert_compile_set

        params, cfg = model
        engine = _engine(params, cfg, paged_kernel=True)
        engine.start()
        try:
            prompts = [[3, 5, 7], [11, 2], [9, 9, 1, 4]]
            futs = [engine.submit(p, max_new_tokens=8) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
            for p, o in zip(prompts, outs):
                assert o == _ref_greedy(params, cfg, p, 8)
            assert engine.stats()["paged_kernel_engaged"] is True
            got = assert_compile_set(engine, decode=1)
            # churn: a new admission in an already-warmed bucket must
            # reuse every executable — same compile set, verbatim
            futs = [engine.submit([1, 2, 3], max_new_tokens=6)]
            assert futs[0].result(timeout=120) == _ref_greedy(
                params, cfg, [1, 2, 3], 6)
            assert_compile_set(engine, decode=1, prefill=got["prefill"],
                               sample=got["sample"])
        finally:
            engine.stop()

    def test_engine_defaults_off_on_cpu_and_disable_works(self, model):
        """paged_kernel=None auto-resolves OFF on a CPU backend (the
        interpreter would own the tick otherwise); False pins it off
        explicitly — both report engaged=False."""
        params, cfg = model
        for flag in (None, False):
            engine = _engine(params, cfg, paged_kernel=flag)
            assert engine.stats()["paged_kernel_engaged"] is False

    @pytest.mark.slow  # ~7 s whole-engine drive (DURATIONS.md); tier-1
    # siblings: test_engine_fused_oracle_and_compile_set (fused engine
    # path) + the COW-shared-rows case in the edge-table units + the
    # TestResumePagedComposition refcount-balance tests
    def test_cow_shared_prefix_fused(self, model):
        """COW-shared prefix pages (refcount > 1) under the fused
        kernel: two requests sharing a registered prefix stream the
        SAME physical pages through the kernel and still match the
        per-request oracle."""
        params, cfg = model
        engine = _engine(params, cfg, paged_kernel=True)
        prefix = [7, 8, 9, 10, 11, 12, 13, 14]  # one full page
        engine.register_prefix(prefix)
        engine.start()
        try:
            suffixes = [[1, 2], [3, 4, 5]]
            futs = [engine.submit(prefix + s, max_new_tokens=6)
                    for s in suffixes]
            outs = [f.result(timeout=120) for f in futs]
            for s, o in zip(suffixes, outs):
                assert o == _ref_greedy(params, cfg, prefix + s, 6)
            assert engine.stats()["prefixes_registered"] == 1
        finally:
            engine.stop()

    @pytest.mark.spec
    @pytest.mark.slow  # ~7 s spec-engine drive (DURATIONS.md); tier-1
    # siblings: test_engine_fused_oracle_and_compile_set (fused engine)
    # + test_speculative's plain spec oracles; the slow verify A/B
    # above covers the kernel+LSE-combine verify math directly
    def test_engine_speculative_fused_oracle(self, model):
        """Spec-decode VERIFY inherits the kernel: a speculative
        paged_kernel=True engine stays token-identical to the plain
        unfused oracle (greedy byte-identity is a property of the
        verify kernel alone)."""
        params, cfg = model
        engine = _engine(params, cfg, paged_kernel=True,
                         speculative=True, spec_k=3)
        engine.start()
        try:
            prompts = [[3, 5, 7], [9, 9, 1, 4]]
            futs = [engine.submit(p, max_new_tokens=8) for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
            for p, o in zip(prompts, outs):
                assert o == _ref_greedy(params, cfg, p, 8)
            assert engine.stats()["paged_kernel_engaged"] is True
        finally:
            engine.stop()


def _junk_pool(pc, rng):
    """Fill a cache's pool arrays with junk: what a sequence does not
    write must still be there at its end."""
    def junk(a):
        if a.dtype == jnp.int8:
            return jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)
    pc.cache = {n: a if n == "pos" else junk(a)
                for n, a in pc.cache.items()}


def _kv_block(rng, cfg, K, bucket, pos, n_layers=None):
    shape = (n_layers or cfg.n_layers, K, cfg.kv_heads, bucket,
             cfg.head_dim)
    return {"k": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "v": jnp.asarray(rng.standard_normal(shape), jnp.float32),
            "pos": jnp.asarray(pos, jnp.int32)}


class TestPoolWrittenInPlace:
    """The pool's write discipline (``serving.cache.write_pages``): the
    pool is loop state, every write into it is whole pages addressed by
    the leading ``(layer, page)`` dims, and what lands where is what a
    position-by-position writer would have put there."""

    S, PS, PAGES, MAX_LEN = 4, 4, 22, 32

    def _pool(self, cfg, kv):
        return serving.init_page_pool(cfg, self.S, self.PAGES + 1, self.PS,
                                      kv)

    @pytest.mark.parametrize("what,kv,kernel", [
        ("tick", None, True), ("tick", None, False),
        ("tick", "bf16", True), ("tick", "int8", False),
        ("verify", None, True), ("verify", "int8", False),
        ("draft", None, True), ("landing", None, None),
        ("landing", "int8", None), ("cow", "int8", None)])
    def test_no_operation_the_size_of_a_layer_of_the_pool(self, model, what,
                                                           kv, kernel):
        """From the jaxpr: the pool is among no scan's xs or ys, no
        slice, gather or squeeze yields a layer of it, and every scatter
        into it indexes ``(layer, page)`` alone."""
        from conftest import pool_structure_faults

        params, cfg = model
        pool = self._pool(cfg, kv)
        table = jnp.zeros((self.S, self.MAX_LEN // self.PS), jnp.int32)
        active = jnp.ones((self.S,), bool)
        tok = jnp.zeros((self.S,), jnp.int32)
        if what == "tick":
            jaxpr = jax.make_jaxpr(lambda pl: T.decode_step_paged(
                params, tok, pl, table, cfg, active, kernel=kernel))(pool)
        elif what == "draft":
            jaxpr = jax.make_jaxpr(lambda pl: T.draft_propose_paged(
                params, tok, pl, table, cfg, active, 3, kernel=kernel))(pool)
        elif what == "cow":
            jaxpr = jax.make_jaxpr(serving.cache.copy_page)(
                pool, jnp.int32(3), jnp.int32(5))
        elif what == "verify":
            jaxpr = jax.make_jaxpr(lambda pl: T.decode_verify_paged(
                params, jnp.zeros((self.S, 4), jnp.int32), pl, table, cfg,
                active, kernel=kernel))(pool)
        else:
            blk = _kv_block(np.random.default_rng(0), cfg, 2, 8, [5, 8])
            jaxpr = jax.make_jaxpr(
                lambda pl, pages, first, lens: serving.cache.paged_insert(
                    pl, jnp.asarray([0, 1]), blk["pos"], pages, first, lens,
                    blk))(
                pool, jnp.zeros((2, serving.cache.landing_pages(8, self.PS)),
                                jnp.int32), jnp.int32(0),
                jnp.asarray([5, 8], jnp.int32))
        shapes = [a.shape for n, a in pool.items() if n != "pos"]
        assert pool_structure_faults(jaxpr, shapes) == []

    def test_the_structure_check_finds_the_old_forms(self, model):
        """The check is not vacuous: a pool scanned as xs -> ys and
        written at ``[page, :, offset]`` is found on all three counts."""
        from conftest import pool_structure_faults

        _, cfg = model
        pool = self._pool(cfg, None)["k"]
        phys = jnp.arange(self.S)

        def old(pool):
            def layer(c, pool_l):
                return c, pool_l.at[phys, :, phys % self.PS, :].set(1.0)
            return jax.lax.scan(layer, 0, pool)[1]

        faults = " ".join(pool_structure_faults(jax.make_jaxpr(old)(pool),
                                                [pool.shape]))
        assert "among its xs" in faults and "among its ys" in faults
        # under a scan the old write is a scatter into ONE layer:
        assert pool_structure_faults(
            jax.make_jaxpr(lambda p: p.at[:, phys, :, phys % self.PS].set(
                1.0))(pool), [pool.shape])
        assert any("whole layer" in f for f in pool_structure_faults(
            jax.make_jaxpr(lambda p, l: jax.lax.dynamic_index_in_dim(
                p, l, 0, keepdims=False))(pool, 1), [pool.shape]))

    @pytest.mark.parametrize("kv", [None, "bf16", "int8"])
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernel"])
    def test_pool_bytes_match_a_page_offset_writer(self, model, monkeypatch,
                                                   kv, kernel):
        """A slotless prefix registration, a suffix that starts mid-page
        after a COW, two rows of one landing with bucket padding, a
        second chunk, then ticks with an idle slot among the active ones
        and slots crossing page boundaries: every pool array, byte for
        byte, the NULL page excepted."""
        from conftest import KVSpy, PoolMirror

        params, cfg = model
        ps, names = self.PS, ("k", "v")
        rng = np.random.default_rng(7)
        spy = KVSpy(monkeypatch)
        pc = serving.PagedSlotCache(cfg, self.S, max_len=self.MAX_LEN,
                                    page_size=ps, n_pages=self.PAGES,
                                    kv_dtype=kv)
        _junk_pool(pc, rng)
        mirror = PoolMirror(pc.cache, ps)

        def land(slots, lens, start, bucket):
            for s, n in zip(slots, lens):
                for idx in range(start // ps, -(-(start + n) // ps)):
                    if pc.table[s, idx] == NULL_PAGE:
                        pc.grant(s, idx)
            blk = _kv_block(rng, cfg, len(slots), bucket,
                            [start + n for n in lens])
            pc.land(slots, blk, lens, start=start)
            mirror.land(names, [pc.table[s].copy() for s in slots], start,
                        lens, blk["k"], blk["v"])

        # a prefix of 6 tokens registered with no slot, in a bucket of 8
        pin = pc.grant_raw(2)
        blk = _kv_block(rng, cfg, 1, 8, [6])
        pc.land_raw(pin, blk, 6)
        mirror.land(names, [pin], 0, [6], blk["k"], blk["v"])
        # slot a shares it, splits its half-filled page, lands 5 more
        a = pc.alloc()
        pc.attach(a, pin)
        split = pc.cow(a, 1)
        assert split != pin[1]
        for arr in mirror.a.values():
            arr[:, split] = arr[:, pin[1]]
        land([a], [5], 6, 8)
        # two rows in one landing: 5 of 8 columns and 8 of 8
        b, c = pc.alloc(), pc.alloc()
        land([b, c], [5, 8], 0, 8)
        land([c], [3], 8, 4)            # c's second chunk
        assert pc.positions().tolist() == [11, 5, 11, 0]

        tick = jax.jit(lambda tok, pool, table, active: T.decode_step_paged(
            params, tok, pool, table, cfg, active, kernel=kernel)[1])
        for step in range(5):
            pos, active = pc.positions(), pc.active_mask()
            active[c] &= step != 1       # idle for one tick, among active
            for s in np.nonzero(active)[0]:
                if pc.table[s, pos[s] // ps] == NULL_PAGE:
                    pc.grant(s, pos[s] // ps)
            table = pc.table.copy()
            pc.cache = tick(jnp.asarray(rng.integers(0, 64, self.S),
                                        jnp.int32),
                            pc.cache, jnp.asarray(table), jnp.asarray(active))
            calls = spy.take()
            assert len(calls) == cfg.n_layers
            for l, (_, at, k, v) in enumerate(calls):
                assert at[:, 0].tolist() == pos.tolist()
                mirror.write(names, l, table, pos, active[:, None], k, v)
        assert pc.positions().tolist() == [16, 10, 15, 0]  # b crossed 8
        mirror.assert_holds(pc.cache)

    @pytest.mark.parametrize("kv", [None, "int8"])
    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernel"])
    def test_verify_writes_the_accepted_positions_alone(self, model,
                                                        monkeypatch, kv,
                                                        kernel):
        """A W-wide verify whose slots accept 0..W-1 drafts from
        positions that straddle a page, fill one, end at the table's
        capacity, or belong to an idle slot: the accepted offsets are in
        their pages, every other byte of the pool is as it was."""
        from conftest import KVSpy, PoolMirror

        params, cfg = model
        ps, W, S = self.PS, 4, self.S
        rng = np.random.default_rng(11)
        pc = serving.PagedSlotCache(cfg, S, max_len=16, page_size=ps,
                                    n_pages=self.PAGES, kv_dtype=kv)
        _junk_pool(pc, rng)
        for s in range(S):
            pc.alloc()
            for idx in range(pc.max_pages):
                pc.grant(s, idx)
        # straddles pages 0|1; fills page 1; runs off the table; idle
        pos = np.asarray([2, 4, 14, 6], np.int32)
        active = np.asarray([True, True, True, False])
        pc.set_pos(range(S), pos)
        table = pc.table.copy()
        spy = KVSpy(monkeypatch)
        verify = jax.jit(lambda win, pool: T.decode_verify_paged(
            params, win, pool, jnp.asarray(table), cfg, jnp.asarray(active),
            kernel=kernel))
        # drafts that agree with the target for 1, 3, 2 positions:
        # found greedily, one column at a time, on the untouched pool
        window = np.asarray(rng.integers(0, 64, (S, W)), np.int32)
        for i in range(W - 1):
            t = np.asarray(verify(jnp.asarray(window), pc.cache)[0])
            window[:, i + 1] = t[:, i]
        want = np.asarray([1, 3, 2, 0])
        for s in range(S):
            if want[s] < W - 1:
                window[s, want[s] + 1] ^= 1
        spy.take()                   # the search's calls: not these
        mirror = PoolMirror(pc.cache, ps)
        _, _, acc, out = verify(jnp.asarray(window), pc.cache)
        assert np.asarray(acc).tolist() == [1, 3, 2, 0]
        assert np.asarray(out["pos"]).tolist() == [4, 8, 17, 6]
        calls = spy.take()
        assert len(calls) == cfg.n_layers
        ok = active[:, None] & (np.arange(W) <= np.asarray(acc)[:, None])
        for l, (_, _, k, v) in enumerate(calls):
            mirror.write(("k", "v"), l, table, pos, ok, k, v)
        mirror.assert_holds(out)


class TestPagedHTTP:
    def test_out_of_pages_maps_to_429(self, model):
        from conftest import http_post_json as _post

        params, cfg = model
        engine = _engine(params, cfg, n_slots=2, n_pages=2)
        with serving.ServingServer(engine, port=0) as srv:
            host, port = srv.address
            code, out = _post(f"http://{host}:{port}/generate",
                              {"tokens": list(range(20)),
                               "max_new_tokens": 8})
        assert (code, out["type"]) == (429, "out_of_pages")
