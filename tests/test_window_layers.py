"""A patterned expert model through the serving path: window and full
layers in one stack, rope by layer kind (YaRN on the full layers), a
norm on q and k, top-k dropless experts, and the two-kind paged cache.

The program's LOGITS are held to ``horovod_tpu.models.plain_reference``
(straightforward float32 ``jax.numpy``, nothing of the program in it) at
a small size on seeded weights: hidden 64, 4 query / 2 KV heads of 16,
8 experts of width 32 with 2 a token, window 8, the period (sliding,
sliding, sliding, full) twice, YaRN with ``original_max_position`` 16 so
the ramp is crossed inside the head.

TOLERANCE: ``LOGIT_TOL`` = 2e-4 absolute on logits of magnitude ~1.
Both sides compute in float32 with float32 accumulation; what differs is
the ORDER of sums — the flash kernel's online softmax by blocks, the
paged path's softmax over gathered pages, the chunked prefill's prefix +
suffix concatenation, the experts as grouped products of sorted rows
against every expert under a mask — which moves a logit by a few 1e-6
(1.8e-6 observed).  The same comparison with the program in bfloat16
misses by ~1e-2: ``test_bf16_program_fails_the_tolerance`` holds the
tolerance to that.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import plain_reference as R
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import attention as A
from horovod_tpu.ops import moe
from horovod_tpu.ops import paged_attention as PA
from horovod_tpu.serving.cache import NULL_PAGE, PagedSlotCache

LOGIT_TOL = 2e-4
WINDOW = 8
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386}
DIMS = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=128, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, rms_norm_eps=1e-6,
    sliding_window=WINDOW,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    rope_parameters={
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000.0}})


def _cfg(**over):
    kw = dict(
        vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, n_layers=8,
        d_ff=32, max_seq=128, n_experts=8, n_experts_per_tok=2,
        d_expert=32, norm_topk_prob=True, d_head=16, qk_norm=True,
        layer_pattern=("sliding", "sliding", "sliding", "full"),
        window=WINDOW, rope_theta=10000.0,
        rope_yarn=(YARN["factor"], YARN["original_max_position_embeddings"],
                   YARN["beta_fast"], YARN["beta_slow"],
                   YARN["attention_factor"]),
        dtype=jnp.float32, attention_impl="flash", moe_impl="dropless")
    kw.update(over)
    return T.TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for i, name in enumerate(("q_norm", "k_norm", "ln1", "ln2")):
        params["layers"][name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(k, i), params["layers"][name].shape)
    return params, cfg


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, n).tolist() for n in lens]


def _engine(params, cfg, **kw):
    base = dict(n_slots=3, max_len=96, paged=True, page_size=4,
                prefill_chunk_tokens=4, max_prefills_per_tick=2,
                min_prefill_bucket=4, overlap=False)
    base.update(kw)
    return serving.InferenceEngine(params, cfg, serving.EngineConfig(**base))


class _LogitTap:
    """Every logit row an engine computes for a request, with no change
    to the engine: the admission's (or last chunk's) logits as
    ``_first_tokens`` sees them, and each decode tick's ``(S, V)``
    logits from a second, non-donating ``decode_step_paged`` on the
    tick's own inputs.  ``rows[id(future)]`` is the request's list."""

    def __init__(self, engine):
        self.rows = {}
        first, tick = engine._first_tokens, engine._tick_fn
        cfg = engine.cfg

        def tap_first(reqs, logits):
            for r, row in zip(reqs, np.asarray(logits)):
                self.rows.setdefault(id(r.future), []).append(row)
            return first(reqs, logits)

        @jax.jit
        def peek(params, tokens, active, table, pool):
            table, wtable = table if cfg.has_window else (table, None)
            return T.decode_step_paged(params, tokens, pool, table, cfg,
                                       active, wtable=wtable,
                                       kernel=engine._paged_kernel)[0]

        def tap_tick(params, tokens, active, table, pool, *samp):
            logits = np.asarray(peek(params, tokens, active, table, pool))
            for s in np.nonzero(np.asarray(active))[0]:
                fut = engine._states[s].request.future
                self.rows.setdefault(id(fut), []).append(logits[s])
            return tick(params, tokens, active, table, pool, *samp)

        engine._first_tokens, engine._tick_fn = tap_first, tap_tick


def _serve_and_compare(params, cfg, prompts, new=14, **kw):
    """Serve ``prompts`` and return ``(engine, worst, same)``: the
    largest |program logit - reference logit| over every logit row that
    produced a served token, and whether every served token is the
    reference's own pick."""
    engine = _engine(params, cfg, **kw)
    tap = _LogitTap(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    while not all(f.done() for f in futs):
        engine.step()
    worst, same = 0.0, True
    for p, f in zip(prompts, futs):
        toks = f.result()
        ref = np.asarray(R.forward(params, jnp.asarray(p + toks), DIMS))
        rows = tap.rows[id(f)]
        assert len(rows) == len(toks)
        for j, tok in enumerate(toks):
            want = ref[len(p) - 1 + j]
            worst = max(worst, float(np.abs(rows[j] - want).max()))
            same &= int(np.argmax(want)) == tok
    return engine, worst, same


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


class TestLogitsAgainstThePlainReference:
    def test_whole_prefill_several_windows_long(self, model, highest):
        """Unchunked: the flash forward with the window's lower bound
        (and the XLA form for lengths it cannot tile), prompts up to
        five windows long; the first served token's logits."""
        params, cfg = model
        _, worst, same = _serve_and_compare(
            params, cfg, _prompts((40, 32, 9, 3)), new=1,
            prefill_chunk_tokens=0)
        assert worst < LOGIT_TOL and same

    def test_chunked_prefill_chunks_shorter_than_the_window(self, model,
                                                            highest):
        """Chunks of 4 under a window of 8, prompts of 37 and 21 tokens
        (4.6 and 2.6 windows): every chunk after the first attends the
        landed pages of both pools, the window layers' from the first
        page their window still reaches."""
        params, cfg = model
        _, worst, same = _serve_and_compare(params, cfg,
                                            _prompts((37, 21)), new=1)
        assert worst < LOGIT_TOL and same

    def test_prefill_then_decode_beyond_the_window(self, model, highest):
        """Prefill, then 20 decode ticks — 2.5 windows — through both
        caches with three slots live: every tick's logits against the
        reference's full forward of prompt + served tokens.  And the
        tick computed 2 expert rows a token in each of 8 layers."""
        params, cfg = model
        engine, worst, same = _serve_and_compare(
            params, cfg, _prompts((37, 3, 21)), new=20)
        assert worst < LOGIT_TOL and same
        st = engine.stats()
        # every tick's active rows: each request decodes new - 1 tokens
        assert st["moe_rows_total"] == 8 * 2 * 3 * 19
        assert st["moe_load_mean_rows_total"] == st["moe_rows_total"] / 8
        assert (st["moe_load_max_rows_total"]
                >= st["moe_load_mean_rows_total"])
        assert 0 < st["moe_experts_touched_total"] <= st["moe_rows_total"]

    def test_a_slot_reused_after_retirement(self, model, highest):
        """One slot, three requests in turn: the second and third tenant
        find the first's pages of both kinds behind them."""
        params, cfg = model
        engine, worst, same = _serve_and_compare(
            params, cfg, _prompts((21, 37, 5)), new=12, n_slots=1)
        assert worst < LOGIT_TOL and same
        assert engine.wslots.free_pages == engine.wslots.n_pages
        assert engine.slots.free_pages == engine.slots.n_pages

    @pytest.mark.paged_kernel
    def test_decode_through_the_fused_kernel(self, model, highest):
        """The same comparison with the Pallas kernel in the tick (the
        interpreter runs its body): the window layers' call carries the
        lower bound, the full layers' none."""
        params, cfg = model
        _, worst, same = _serve_and_compare(
            params, cfg, _prompts((21, 3)), new=12, paged_kernel=True)
        assert worst < LOGIT_TOL and same

    def test_bf16_program_fails_the_tolerance(self, model, highest):
        """The precision below the stated one must NOT pass: the same
        weights served in bfloat16 miss the float32 tolerance."""
        params, _ = model
        _, worst, _ = _serve_and_compare(
            params, _cfg(dtype=jnp.bfloat16), _prompts((21,)), new=4)
        assert worst > 10 * LOGIT_TOL


# --- top-k dropless experts --------------------------------------------------


def _experts(rng, T_=24, D=16, E=8, F=12, dtype=jnp.float32):
    k = jax.random.split(rng, 5)
    return (jax.random.normal(k[0], (T_, D), dtype),
            jax.random.normal(k[1], (D, E), jnp.float32),
            jax.random.normal(k[2], (E, D, F), dtype) * 0.3,
            jax.random.normal(k[3], (E, D, F), dtype) * 0.3,
            jax.random.normal(k[4], (E, F, D), dtype) * 0.3)


def _all_experts_oracle(x, router, wg, wu, wd, k, norm):
    """Every expert for every token, weighted by the top-k softmax
    scores (0 for the experts not picked)."""
    p = jax.nn.softmax(x.astype(jnp.float32) @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(p, k)
    if norm:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    w = jnp.zeros_like(p).at[jnp.arange(x.shape[0])[:, None],
                             top_e].set(top_p)
    y = jnp.einsum("esf,efd->esd",
                   jax.nn.silu(jnp.einsum("sd,edf->esf", x, wg))
                   * jnp.einsum("sd,edf->esf", x, wu), wd)
    return jnp.einsum("esd,se->sd", y.astype(jnp.float32), w)


def _seed_dropless_top1(x, router, w_gate, w_up, w_down):
    """``dropless_moe`` as the parent commit had it (top-1, argmax),
    copied verbatim: the k = 1 case must still give its bits."""
    from jax import lax

    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    E = router.shape[1]
    dt = x.dtype
    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    e_star = jnp.argmax(probs, axis=-1).astype(jnp.int32)
    gate = jnp.max(probs, axis=-1)
    order = jnp.argsort(e_star, stable=True)
    xs = xt[order]
    es = e_star[order]
    eye = jnp.arange(E, dtype=jnp.int32)
    counts = (jnp.searchsorted(es, eye, side="right")
              - jnp.searchsorted(es, eye)).astype(jnp.int32)
    g = lax.ragged_dot(xs, w_gate.astype(dt), counts)
    u = lax.ragged_dot(xs, w_up.astype(dt), counts)
    y_s = lax.ragged_dot(jax.nn.silu(g) * u, w_down.astype(dt), counts)
    inv = jnp.argsort(order)
    y = y_s[inv] * gate[:, None].astype(dt)
    return y.reshape(*lead, D)


class TestDroplessTopK:
    @pytest.mark.parametrize("k,norm", [(1, False), (2, True), (2, False),
                                        (4, True), (8, True)])
    def test_matches_the_all_experts_oracle(self, k, norm, highest):
        """Float32 on both sides; grouped products of sorted rows against
        every expert under a mask differ by summation order only."""
        x, router, wg, wu, wd = _experts(jax.random.PRNGKey(k))
        y, counts = moe.dropless_moe(x, router, wg, wu, wd, k=k,
                                     norm_topk=norm, return_counts=True)
        want = _all_experts_oracle(x, router, wg, wu, wd, k, norm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        assert int(counts.sum()) == x.shape[0] * k  # k rows a token

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_k1_is_the_seed_output_bit_for_bit(self, dtype):
        x, router, wg, wu, wd = _experts(jax.random.PRNGKey(9), dtype=dtype)
        x = x.reshape(2, 12, -1)
        got = moe.dropless_moe(x, router, wg, wu, wd)
        want = _seed_dropless_top1(x, router, wg, wu, wd)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))

    def test_masked_tokens_cost_no_row_and_come_back_zero(self, highest):
        x, router, wg, wu, wd = _experts(jax.random.PRNGKey(3))
        mask = jnp.arange(x.shape[0]) % 3 != 0
        y, counts = moe.dropless_moe(x, router, wg, wu, wd, k=2,
                                     norm_topk=True, token_mask=mask,
                                     return_counts=True)
        want = _all_experts_oracle(x, router, wg, wu, wd, 2, True)
        m = np.asarray(mask)
        np.testing.assert_allclose(np.asarray(y)[m], np.asarray(want)[m],
                                   atol=1e-5, rtol=1e-5)
        assert not np.asarray(y)[~m].any()
        assert int(counts.sum()) == 2 * int(m.sum())

    def test_model_dense_oracle_agrees_with_dropless(self, model, highest):
        """``_moe_mlp``'s two serving dispatches on the model's own
        layer: every expert under a mask, and the grouped products."""
        params, cfg = model
        p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
        a = T._moe_mlp(x, p, cfg, impl="dense")
        b = T._moe_mlp(x, p, cfg, impl="dropless")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


# --- the lower bound in the attention paths ----------------------------------


def _paged_case(rng, kv, *, S, Hkv, R, MP, ps=8, Dh=16):
    Pn = S * MP + 1
    qg = jnp.asarray(rng.randn(S, Hkv, R, Dh), jnp.float32)
    kf = rng.randn(Pn, Hkv, ps, Dh).astype(np.float32)
    vf = rng.randn(Pn, Hkv, ps, Dh).astype(np.float32)
    table = (1 + rng.permutation(S * MP)).reshape(S, MP).astype(np.int32)
    if kv == "int8":
        kq, ks = T.kv_quantize(jnp.asarray(kf))
        vq, vs = T.kv_quantize(jnp.asarray(vf))
        return qg, [kq, vq, ks, vs], table
    dt = jnp.bfloat16 if kv == "bf16" else jnp.float32
    return qg, [jnp.asarray(kf, dt), jnp.asarray(vf, dt), None, None], table


class TestLowerBound:
    # pages of 8, blocks of 2 pages = 16 tokens, a table of 8 pages
    _CASES = {
        # the window ends mid-block and starts mid-block / mid-page
        "mid_block": dict(limits=[41, 64, 30, 17], lowers=[21, 40, 29, 1]),
        # the window is longer than the context: no bound bites
        "longer_than_context": dict(limits=[5, 16, 33, 64],
                                    lowers=[0, 0, 0, 0]),
        # limit 0 (an idle slot), a window of one token, lower == limit
        "empty_and_single": dict(limits=[0, 1, 24, 40],
                                 lowers=[0, 0, 23, 40]),
    }

    @pytest.mark.paged_kernel
    @pytest.mark.parametrize("case", list(_CASES))
    @pytest.mark.parametrize("kv", [None, "bf16", "int8"])
    def test_paged_kernel_against_reference(self, kv, case, monkeypatch):
        """The Pallas kernel with ``lower`` == the pure-JAX reference,
        with every page that lies WHOLLY behind a slot's window (or past
        its limit) poisoned with inf and its table entry pointed at the
        NULL page: the kernel must neither fetch it nor read the
        entry."""
        c = self._CASES[case]
        S, Hkv, R, MP, ps, Dh, block = 4, 2, 2, 8, 8, 16, 2
        qg, pool, table = _paged_case(np.random.RandomState(5), kv, S=S,
                                      Hkv=Hkv, R=R, MP=MP)
        monkeypatch.setattr(PA, "_BLOCK_BYTES", block * Hkv * ps * Dh
                            * max(pool[0].dtype.itemsize, 2))
        limit = jnp.asarray(c["limits"], jnp.int32)
        lower = jnp.asarray(c["lowers"], jnp.int32)
        tab = jnp.asarray(table)
        o_r, l_r = PA.paged_attend_reference(
            qg, *pool, tab, limit, compute_dtype=jnp.float32, lower=lower)
        dead = np.ones(pool[0].shape[0], bool)
        released = table.copy()
        for s, (lo, hi) in enumerate(zip(c["lowers"], c["limits"])):
            live = range(lo // ps, -(-hi // ps)) if hi > lo else ()
            dead[table[s, list(live)]] = False
            released[s, [i for i in range(MP) if i not in live]] = NULL_PAGE
        k, v, ks, vs = pool
        if ks is None:
            pool = [jnp.where(dead[:, None, None, None], jnp.inf, x)
                    for x in (k, v)] + [None, None]
        else:
            pool = [k, v] + [jnp.where(dead[:, None, None], jnp.inf, x)
                             for x in (ks, vs)]
        o_k, l_k = PA.paged_attend(qg, *pool, jnp.asarray(released), limit,
                                   compute_dtype=jnp.float32, lower=lower)
        tol = 2e-2 if kv == "bf16" else 1e-4
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=tol, rtol=tol)
        some = np.asarray(limit) > np.asarray(lower)
        np.testing.assert_allclose(np.asarray(l_k)[some],
                                   np.asarray(l_r)[some], atol=tol, rtol=tol)
        assert not np.asarray(o_k)[~some].any()
        assert (np.asarray(l_k)[~some] <= PA.NEG_INF / 2).all()

    def test_walk_with_a_bound_is_the_kernels_trip_count(self):
        """``walk`` is one statement for the kernel and the counter:
        blocks from the one holding ``lower`` to the one holding the
        last live position; nothing where the bound passes the limit;
        the unbounded walk unchanged."""
        limit = np.array([0, 1, 16, 17, 41, 64, 64, 30])
        lower = np.array([0, 0, 0, 16, 21, 40, 64, 31])
        blocks, tokens = PA.walk(limit, 16, lower)
        np.testing.assert_array_equal(blocks, [0, 1, 1, 1, 2, 2, 0, 0])
        np.testing.assert_array_equal(tokens, blocks * 16)
        np.testing.assert_array_equal(PA.first_block(lower, 16),
                                      [0, 0, 0, 1, 1, 2, 4, 1])
        np.testing.assert_array_equal(PA.walk(limit, 16)[0],
                                      [0, 1, 1, 2, 3, 4, 4, 2])
        # traced, as the kernel reads it from SMEM
        got = jax.jit(lambda a, b: PA.walk(a, 16, b)[0])(
            jnp.asarray(limit), jnp.asarray(lower))
        np.testing.assert_array_equal(np.asarray(got), blocks)

    def test_engine_counter_is_the_walk(self, model, highest):
        """``window_walked_tokens_total`` is ``walk`` summed over the
        dispatched ticks' positions, the full layers' pair beside it as
        before; a window layer's live tokens never pass the window."""
        params, cfg = model
        engine = _engine(params, cfg)
        seen = []
        count = engine._count_paged_walk

        def spy(active):
            seen.append(engine._page_pos[active] + 1)
            return count(active)

        engine._count_paged_walk = spy
        futs = [engine.submit(p, max_new_tokens=12)
                for p in _prompts((21, 5))]
        while not all(f.done() for f in futs):
            engine.step()
        limit = np.concatenate(seen)
        lower = np.maximum(limit - WINDOW, 0)
        bt = engine._walk_block_tokens
        st = engine.stats()
        assert st["window_walked_tokens_total"] == int(
            PA.walk(limit, bt, lower)[1].sum())
        assert st["window_live_tokens_total"] == int((limit - lower).sum())
        assert st["paged_walked_tokens_total"] == int(
            PA.walk(limit, bt)[1].sum())
        assert st["paged_live_tokens_total"] == int(limit.sum())
        assert (limit - lower).max() == WINDOW

    @pytest.mark.parametrize("S,bq,bk,window", [
        (64, 16, 16, 8), (64, 16, 8, 24), (64, 32, 16, 40), (48, 48, 48, 5)])
    def test_flash_forward_with_a_window(self, S, bq, bk, window, highest):
        """The flash forward's window: blocks wholly behind it skipped,
        the rest masked — against the O(S^2) oracle, over several block
        shapes (window inside a block, across blocks, one block)."""
        rng = np.random.RandomState(2)
        q, k, v = (jnp.asarray(rng.randn(2, 3, S, 16), jnp.float32)
                   for _ in range(3))
        got = A.flash_attention_windowed(q, k, v, window, block_q=bq,
                                         block_k=bk)
        want = A.reference_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        # and the window really bites: it is not plain causal attention
        plain = A.reference_attention(q, k, v, causal=True)
        assert float(jnp.abs(want - plain).max()) > 1e-2


# --- the two-kind cache's allocator ------------------------------------------


class TestWindowAllocator:
    def test_release_behind_and_the_bound(self, model):
        """The allocator told its window: a slot walking a long context
        page by page never holds more than ceil(window / page) + 1, and
        every page comes back at retirement."""
        _, cfg = model
        c = PagedSlotCache(cfg, 2, 96, page_size=4, n_layers=6,
                           window=WINDOW)
        assert c.window_pages_bound == 3 and c.n_pages == 2 * 3
        assert c.cache["k"].shape[0] == 6
        slot = c.alloc()
        for pos in range(96):               # decode: release, then claim
            c.release_behind(slot, pos)
            if c.table[slot, pos // 4] == NULL_PAGE:
                c.grant(slot, pos // 4)
            held = np.nonzero(c.table[slot])[0]
            assert len(held) <= c.window_pages_bound
            assert held.min() == c.first_live(pos)
        assert c.slot_pages_max == 3
        c.free(slot)
        assert c.free_pages == c.n_pages
        # a cache with no window releases nothing
        full = PagedSlotCache(cfg, 1, 32, page_size=4, n_layers=2)
        s = full.alloc()
        full.grant(s, 0)
        full.release_behind(s, 31)
        assert full.first_live(31) == 0 and full.table[s, 0] != NULL_PAGE

    def test_no_page_leaks_through_preemption_and_resume(self, model,
                                                         highest):
        """Admission, preemption under page pressure, resume by
        re-prefill and retirement return BOTH kinds of page: a full
        pool too small for three long requests at once preempts, every
        request still finishes with the reference's tokens, no slot
        ever held more window pages than the bound, and both pools end
        whole."""
        params, cfg = model
        engine = _engine(params, cfg, n_pages=22, resume=True,
                         overlap=True)
        prompts = _prompts((30, 26, 22, 9), seed=4)
        futs = [engine.submit(p, max_new_tokens=16) for p in prompts]
        while not all(f.done() for f in futs):
            engine.step()
        st = engine.stats()
        assert engine.metrics.preemptions.value >= 1
        for p, f in zip(prompts, futs):
            toks = f.result()
            ref = np.asarray(R.forward(params, jnp.asarray(p + toks), DIMS))
            assert toks == ref[len(p) - 1:-1].argmax(-1).tolist()
        assert engine.slots.free_pages == engine.slots.n_pages
        assert engine.wslots.free_pages == engine.wslots.n_pages
        assert not engine.wslots.table.any() and not engine.slots.table.any()
        assert (st["kv_window_pages_per_slot_max"]
                <= st["kv_window_pages_per_slot_bound"]
                == -(-WINDOW // 4) + 1)
        assert st["kv_window_pages_total"] == 3 * 3
        assert st["kv_pages_in_use"] == st["kv_window_pages_in_use"] == 0


class TestTwoKindPoolsWrittenInPlace:
    """The write discipline of ``serving.cache.write_pages`` for a model
    with two kinds of layer: both kinds' stacked pools ride the period
    scan's carry, each layer writes its own kind's stack at its index
    among that kind, and the bytes are a ``(page, offset)`` writer's."""

    S, PS, MAX_LEN = 3, 4, 32

    def _caches(self, cfg, n_pages=17):
        fc = PagedSlotCache(cfg, self.S, self.MAX_LEN, page_size=self.PS,
                            n_pages=n_pages, n_layers=cfg.kind_count("full"))
        wc = PagedSlotCache(cfg, self.S, self.MAX_LEN, page_size=self.PS,
                            n_layers=cfg.kind_count("sliding"),
                            window=WINDOW)
        return fc, wc

    @staticmethod
    def _pool(fc, wc):
        return {**fc.cache, "wk": wc.cache["k"], "wv": wc.cache["v"]}

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernel"])
    def test_no_operation_the_size_of_a_layer_of_either_pool(self, model,
                                                             kernel):
        """The patterned case of ``tests/test_paged.py``'s structure
        test: neither stack is among the period scan's xs or ys, no
        layer of either is cut out, every scatter indexes ``(layer,
        page)``."""
        from conftest import pool_structure_faults

        params, cfg = model
        pool = self._pool(*self._caches(cfg))
        table = jnp.zeros((self.S, self.MAX_LEN // self.PS), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda pl: T.decode_step_paged(
            params, jnp.zeros((self.S,), jnp.int32), pl, table, cfg,
            jnp.ones((self.S,), bool), kernel=kernel, wtable=table,
            return_moe_load=True))(pool)
        shapes = {a.shape for n, a in pool.items() if n != "pos"}
        assert len(shapes) == 2          # two kinds, two pool shapes
        assert pool_structure_faults(jaxpr, shapes) == []

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernel"])
    def test_both_pools_bytes_match_a_page_offset_writer(self, model,
                                                         monkeypatch, kernel):
        """Two chunks of one prompt (the window cache gives the page
        behind the window back between them, and what the second chunk
        holds of it lands nowhere), a second slot's padded landing, then
        ticks through which the window slot releases a page behind it,
        the other slot takes it up, and one slot idles: both kinds'
        arrays, byte for byte, the NULL page excepted."""
        from conftest import KVSpy, PoolMirror

        params, cfg = model
        ps, S = self.PS, self.S
        names = {"full": ("k", "v"), "sliding": ("wk", "wv")}
        count = {k: cfg.kind_count(k) for k in names}
        rng = np.random.default_rng(3)
        spy = KVSpy(monkeypatch)
        fc, wc = self._caches(cfg)
        for c in (fc, wc):
            c.cache = {n: a if n == "pos" else jnp.asarray(
                rng.standard_normal(a.shape), a.dtype)
                for n, a in c.cache.items()}
        mirror = PoolMirror(self._pool(fc, wc), ps)

        def claim(c, slot, lo, hi):
            for idx in range(max(lo // ps, c.first_live(lo)),
                             -(-hi // ps)):
                if c.table[slot, idx] == NULL_PAGE:
                    c.grant(slot, idx)

        def land(slots, lens, start, bucket, nxt):
            """``nxt``: the first query after this block, whose window
            the window cache keeps."""
            for c, kind in ((fc, "full"), (wc, "sliding")):
                for s, n in zip(slots, lens):
                    c.release_behind(s, nxt)
                    claim(c, s, max(start, nxt - WINDOW + 1)
                          if c.window else start, start + n)
                shape = (count[kind], len(slots), cfg.kv_heads, bucket,
                         cfg.head_dim)
                blk = {x: jnp.asarray(rng.standard_normal(shape),
                                      jnp.float32) for x in "kv"}
                blk["pos"] = jnp.asarray([start + n for n in lens])
                c.land(slots, blk, lens, start=start)
                mirror.land(names[kind], [c.table[s].copy() for s in slots],
                            start, lens, blk["k"], blk["v"])

        a, b = fc.alloc(), fc.alloc()
        wc.acquire(a), wc.acquire(b)
        land([a], [8], 0, 8, nxt=8)
        land([a], [5], 8, 8, nxt=13)     # window page 0 is given back
        assert wc.table[a, 0] == NULL_PAGE and wc.table[a, 1] != NULL_PAGE
        land([b], [3], 0, 4, nxt=3)

        tick = jax.jit(lambda tok, pool, t, wt, active: T.decode_step_paged(
            params, tok, pool, t, cfg, active, kernel=kernel, wtable=wt)[1])
        for step in range(6):
            pos, active = fc.positions(), fc.active_mask()
            active[b] &= step != 2       # idle for one tick
            for s in np.nonzero(active)[0]:
                wc.release_behind(s, pos[s])
                claim(fc, s, pos[s], pos[s] + 1)
                claim(wc, s, pos[s], pos[s] + 1)
            tables = {"full": fc.table.copy(), "sliding": wc.table.copy()}
            out = tick(jnp.asarray(rng.integers(0, 128, S), jnp.int32),
                       self._pool(fc, wc), jnp.asarray(tables["full"]),
                       jnp.asarray(tables["sliding"]), jnp.asarray(active))
            wc.cache = {**wc.cache, "k": out.pop("wk"), "v": out.pop("wv")}
            fc.cache = out
            calls = spy.take()
            assert [c[0] for c in calls] == list(cfg.layer_pattern) * 2
            seen = dict.fromkeys(names, 0)
            for kind, at, k, v in calls:
                assert at[:, 0].tolist() == pos.tolist()
                mirror.write(names[kind], seen[kind], tables[kind], pos,
                             active[:, None], k, v)
                seen[kind] += 1
        assert fc.positions().tolist() == [19, 8, 0]
        # a walked past 16: its window pages behind 16 - 8 went back
        assert not wc.table[a, :2].any() and wc.table[a, 4] != NULL_PAGE
        assert wc.slot_pages_max <= wc.window_pages_bound
        mirror.assert_holds(self._pool(fc, wc))


# --- what is refused, and what is unchanged ----------------------------------


class TestRefusals:
    @pytest.mark.parametrize("kw,why", [
        (dict(tp=2), "tp > 1"),
        (dict(speculative=True), "speculative"),
        (dict(kv_dtype="int8"), "int8"),
    ])
    def test_engine_modes_refuse_window_layers(self, model, kw, why):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError, match=why):
            _engine(params, cfg, **kw)

    def test_prefix_registration_is_refused(self, model):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError, match="prefix"):
            _engine(params, cfg).register_prefix([1, 2, 3, 4, 5])

    def test_bodies_off_the_normal_path_refuse(self, model):
        """The bodies the engine's normal path does not run for this
        configuration refuse it; none computes another model."""
        params, cfg = model
        toks = jnp.zeros((1, 8), jnp.int32)
        act = jnp.ones((1,), bool)
        pool = serving.init_page_pool(cfg, 1, 4, 4)
        table = jnp.zeros((1, 2), jnp.int32)
        refuse = pytest.raises(T.UnsupportedModelConfigError)
        with refuse:
            T.forward(params, toks, cfg)
        with refuse:
            T.decode_step(params, toks[:, 0], T.init_cache(cfg, 1, 8), cfg)
        with refuse:
            T.decode_verify_paged(params, toks[:, :2], pool, table, cfg, act)
        with refuse:  # one pool for both kinds: no window layers' pages
            T.decode_step_paged(params, toks[:, 0], pool, table, cfg, act)
        with refuse:  # training's dispatch routes one expert a token
            T._moe_mlp(jnp.zeros((1, 2, cfg.d_model)),
                       jax.tree_util.tree_map(lambda a: a[0],
                                              params["layers"]),
                       cfg, impl="switch")

    def test_bad_patterns_are_refused_at_construction(self):
        with pytest.raises(ValueError, match="layer kind"):
            _cfg(layer_pattern=("sliding", "ring"))
        with pytest.raises(ValueError, match="periods"):
            _cfg(n_layers=6)
        with pytest.raises(ValueError, match="window"):
            _cfg(window=0)


class TestUniformDenseModelServesAsBefore:
    """A configuration with full layers and a dense MLP only — the
    block the benchmark's Mistral cells run — through the changed
    engine: the tokens and compile counts below were produced by the
    PARENT commit (4077827) with this very script."""

    GREEDY = [[76, 33, 68, 17, 69, 13, 93, 69, 13, 95],
              [43, 76, 64, 77, 45, 58, 48, 10, 7, 93]]
    SAMPLED = [[23, 13, 28, 65, 89, 43, 82, 21, 23, 95],
               [36, 89, 43, 90, 73, 33, 5, 8, 94, 34]]

    def test_tokens_and_compile_counts_are_the_parents(self):
        from conftest import assert_compile_set

        cfg = T.TransformerConfig(
            vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
            d_ff=64, max_seq=64, dtype=jnp.float32, attention_impl="flash")
        params = T.init_params(jax.random.PRNGKey(7), cfg)
        engine = _engine(params, cfg, max_len=48, prefill_chunk_tokens=8,
                         overlap=True)
        assert engine.wslots is None
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 96, n).tolist() for n in (3, 11, 20, 6)]
        futs = [engine.submit(p, max_new_tokens=10) for p in prompts[:2]]
        futs += [engine.submit(p, max_new_tokens=10, temperature=0.8,
                               top_k=20, seed=3 + i)
                 for i, p in enumerate(prompts[2:])]
        while not all(f.done() for f in futs):
            engine.step()
        assert [f.result() for f in futs] == self.GREEDY + self.SAMPLED
        assert_compile_set(engine, decode=1, prefill=4, sample=1)
        st = engine.stats()
        assert st["moe_rows_total"] == st["window_walked_tokens_total"] == 0
        assert st["kv_window_pages_total"] == 0
