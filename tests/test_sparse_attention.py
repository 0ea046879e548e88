"""Learned sparse attention through the serving path: a latent-attention
expert model whose every layer carries a LIGHTNING INDEXER — one cached
index key a token beside the latent row, each query attending only its
``index_topk`` best-scored positions — and whose router chooses its
experts under a score-correction bias.

The program's LOGITS are held to ``plain_reference.sparse_forward``
(straightforward float32 ``jax.numpy``: every pair's index score, a
``lax.top_k`` per query, a masked non-absorbed softmax; nothing of the
program in it) at a small size on seeded weights:
``tests/test_latent_attention.py``'s model with 4 index heads of 16 (8
of them roped) and ``index_topk`` 12 — FAR below the contexts served
here (up to ~60), so that from the 13th position on the selection drops
tokens, and a 16-wide row of picks holds 12 (the width the attend is
padded to).

TOLERANCE: ``LOGIT_TOL`` = 2e-4 absolute on logits of magnitude ~1, the
latent test's, for its reason: both sides compute in float32 and differ
in the ORDER of sums (absorbed rows against expanded heads, blocks of an
online softmax, grouped expert products) — a few 1e-6 observed.  The
SELECTED SET is the same set on both sides as long as no two index
scores lie within float32 rounding of each other at the cut; with seeded
normal weights none do (``TestTheSelectedSets`` compares the sets
themselves, exactly).  A program that skips its indexer (dense
attention) misses by ~1, one that selects on un-roped index vectors by
~1e-1, a bfloat16 cache by ~1e-2: ``TestTheToleranceIsTight``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import plain_reference as R
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import moe
from horovod_tpu.ops import paged_attention as PA
from horovod_tpu.serving import cache as C

from test_latent_attention import DIMS as LATENT_DIMS
from test_latent_attention import _LogitTap, _cfg as _latent_cfg, _prompts

LOGIT_TOL = 2e-4
TOPK = 12
DIMS = dict(LATENT_DIMS, index_n_heads=4, index_head_dim=16,
            index_topk=TOPK)


def _cfg(**over):
    kw = dict(index_n_heads=4, index_head_dim=16, index_topk=TOPK,
              moe_score_bias=True)
    kw.update(over)
    return _latent_cfg(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for stack in ("dense_layers", "layers"):
        for i, name in enumerate(("q_a_norm", "kv_a_norm", "ln1", "ln2",
                                  "i_k_norm", "i_k_bias")):
            a = params[stack][name]
            params[stack][name] = (name != "i_k_bias") + 0.1 * \
                jax.random.normal(jax.random.fold_in(k, i), a.shape)
    return params, cfg


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _engine(params, cfg, **kw):
    base = dict(n_slots=3, max_len=96, paged=True, page_size=4,
                prefill_chunk_tokens=8, max_prefills_per_tick=2,
                min_prefill_bucket=4, overlap=False)
    base.update(kw)
    return serving.InferenceEngine(params, cfg, serving.EngineConfig(**base))


def _serve_and_compare(params, cfg, prompts, new=8, controls=None, **kw):
    """Serve ``prompts``; the largest |program logit - reference logit|
    over every logit row that produced a served token."""
    engine = _engine(params, cfg, **kw)
    tap = _LogitTap(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    while not all(f.done() for f in futs):
        engine.step()
    worst = 0.0
    for p, f in zip(prompts, futs):
        toks = f.result()
        ref = np.asarray(R.sparse_forward(params, jnp.asarray(p + toks),
                                          DIMS, **(controls or {})))
        rows = tap.rows[id(f)]
        assert len(rows) == len(toks)
        for j in range(len(toks)):
            worst = max(worst, float(np.abs(
                rows[j] - ref[len(p) - 1 + j]).max()))
    return engine, worst


class TestLogitsAgainstThePlainReference:
    @pytest.mark.parametrize("impl", ["flash", "reference"])
    def test_forward(self, model, highest, impl):
        """40 positions, 28 of them past ``index_topk``: the scores as
        one kernel (flash) or one einsum, selection and the selected
        attend in blocks of queries."""
        params, cfg = model
        cfg = dataclasses.replace(cfg, attention_impl=impl)
        toks = jnp.asarray(_prompts([40, 40], 3))
        got = np.asarray(T.forward(params, toks, cfg))
        for b in range(2):
            want = np.asarray(R.sparse_forward(params, toks[b], DIMS))
            assert np.abs(got[b] - want).max() < LOGIT_TOL

    @pytest.mark.parametrize("kernel", [None, True],
                             ids=["unfused", "kernels"])
    def test_contexts_below_and_above_index_topk(self, model, highest,
                                                 kernel):
        """Whole prefills under the chunk budget (5 and 8 tokens: the
        dense path, the index keys landed all the same), then ticks
        that carry the contexts from below ``index_topk`` across it:
        the index walk, the selection and the selected attend."""
        params, cfg = model
        eng, worst = _serve_and_compare(
            params, cfg, _prompts([5, 8, 7]), new=14, paged_kernel=kernel)
        assert worst < LOGIT_TOL
        s = eng.stats()
        assert s["paged_kernel_engaged"] is bool(kernel)
        assert 0 < s["dsa_full_rows_total"] < 3 * 14
        assert s["dsa_selected_tokens_total"] < s["dsa_scored_tokens_total"]

    @pytest.mark.parametrize("lens", [[19, 30], [9, 33, 21]],
                             ids=["mid_page", "three_slots"])
    def test_chunked_prefill_then_decode(self, model, highest, lens):
        """Chunks of 8: the second chunk's queries (positions 8..15)
        CROSS ``index_topk`` mid-chunk — four of them see everything,
        four select — and every later chunk selects from a landed
        prefix gathered in a power of two of pages plus its own rows."""
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts(lens, 5))
        assert worst < LOGIT_TOL

    def test_decode_step_on_a_contiguous_cache(self, model, highest):
        params, cfg = model
        p = _prompts([20], 9)[0]
        toks = np.asarray(T.greedy_decode(params, jnp.asarray([p]), 6,
                                          cfg))[0].tolist()
        ref = np.asarray(R.sparse_forward(params, jnp.asarray(p + toks),
                                          DIMS))
        assert [int(np.argmax(ref[len(p) - 1 + j]))
                for j in range(6)] == toks


class TestTheToleranceIsTight:
    """The same comparison, loosened three ways, FAILS each time."""

    def test_a_program_that_ignored_its_indexer_fails(self, model, highest):
        """Against the reference with selection off (dense attention)
        the program misses: so a program that attended everything would
        miss the real reference by as much."""
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5),
                                      controls={"select": False})
        assert worst > 100 * LOGIT_TOL

    def test_selection_on_unroped_index_vectors_fails(self, model, highest):
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5),
                                      controls={"rope_index": False})
        assert worst > 100 * LOGIT_TOL

    def test_a_bf16_cache_fails(self, model, highest):
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5),
                                      kv_dtype="bf16")
        assert worst > 10 * LOGIT_TOL


def _sets(mask):
    return [set(np.nonzero(r)[0].tolist()) for r in np.asarray(mask)]


class TestTheSelectedSets:
    @pytest.mark.parametrize("S", [TOPK, TOPK + 1, 45],
                             ids=["exactly_topk", "topk_plus_one", "45"])
    def test_are_the_references_exactly(self, model, highest, S):
        """Layer 0's selection, program against reference, float32:
        the SAME set for every query — at a context of exactly
        ``index_topk`` (everything) and one more (the first drop)."""
        params, cfg = model
        p = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
        toks = jnp.asarray(_prompts([S], 11))
        h = T._attn_norm(T._embed(params, toks, cfg), p, cfg)
        _, _, cq = T._mla_q(h, p, cfg, with_cq=True)
        qi, ki, w = T._dsa_proj(h, cq, p, cfg)
        scores = PA.index_scores_rows(qi[0], w[0], ki[0], kernel=True)
        idx, count = PA.select_topk(scores, jnp.arange(S) + 1, TOPK, 16)
        n = R.rmsnorm(params["embed"][toks[0]], p["ln1"], 1e-6)
        rcq = R.rmsnorm(n @ p["wq_a"], p["q_a_norm"], 1e-6)
        want = _sets(R.sparse_select(
            R.sparse_index_scores(n, rcq, p, DIMS), TOPK))
        for t in range(S):
            assert int(count[t]) == min(TOPK, t + 1)
            assert set(np.asarray(idx[t, :int(count[t])]).tolist()) \
                == want[t]
            assert not np.asarray(idx[t, int(count[t]):]).any()

    def test_a_tie_goes_to_the_lower_position(self):
        """Two positions with the SAME index key score the same for any
        query: of the pair at the cut, the lower position is kept — in
        the program's counting selection as in ``lax.top_k``."""
        rng = np.random.default_rng(0)
        sc = rng.standard_normal((6, 300)).astype(np.float32)
        sc[:, 200] = sc[:, 17]                   # equal keys, equal scores
        sc[2, :] = np.round(sc[2, :], 1)         # ... and a row full of ties
        sc[3, :] = 0.0
        sc[4, 5] = -0.0
        for k, width in ((12, 16), (128, 128), (299, 304)):
            n_valid = np.array([300, 250, 300, 300, 300, 7], np.int32)
            idx, count = PA.select_topk(jnp.asarray(sc),
                                        jnp.asarray(n_valid), k, width)
            for r in range(6):
                n = min(k, int(n_valid[r]))
                _, want = jax.lax.top_k(jnp.asarray(sc[r, :n_valid[r]]), n)
                got = np.asarray(idx[r, :n])
                assert int(count[r]) == n
                assert got.tolist() == sorted(np.asarray(want).tolist())
        # the pair: with room for one of the two, position 17 is in
        order = np.argsort(-sc[0], kind="stable")
        cut = int(np.nonzero(order == 17)[0][0]) + 1
        idx, _ = PA.select_topk(jnp.asarray(sc[:1]), jnp.asarray([300]),
                                cut)
        assert 17 in np.asarray(idx[0]) and 200 not in np.asarray(idx[0])

    def test_the_reference_selects_by_top_k(self):
        sc = jnp.asarray(np.random.default_rng(1).standard_normal((9, 9)),
                         jnp.float32)
        got = _sets(R.sparse_select(sc, 3))
        for t in range(9):
            want = np.argsort(-np.asarray(sc[t, :t + 1]),
                              kind="stable")[:3]
            assert got[t] == set(want.tolist())


class TestThePicksPages:
    @pytest.mark.parametrize("mp", [24, 21], ids=["whole_groups",
                                                  "a_ragged_group"])
    @pytest.mark.parametrize("n_pages", [4096, 49152, 2 ** 20])
    @pytest.mark.parametrize("ps", [16, 32])
    def test_are_take_along_axis_exactly(self, ps, n_pages, mp):
        """The tick's lookup of each pick's physical page
        (``pages_of``: a one-hot product over the table row in groups
        of ``128 // page`` pages, the ids a byte at a time) against
        ``jnp.take_along_axis(table, idx // page, axis=1)``, element for
        element: ids past bfloat16's 2^8 and past 2^16, a table whose
        width is not whole groups (21 pages: groups of 8 or of 4), rows
        that picked nothing, fewer than ``k`` and ``k`` — the places
        behind ``count`` name the row's page 0, as ``idx`` 0 does."""
        rng = np.random.default_rng(ps + n_pages + mp)
        k, T_ = 48, mp * ps
        n_valid = np.array([0, 5, k, T_ - 3, T_, 1], np.int32)
        table = rng.integers(0, n_pages, (n_valid.size, mp)).astype(np.int32)
        table[2, :3] = n_pages - 1, 0, 255        # the ends, a byte's edge
        scores = jnp.asarray(rng.standard_normal((n_valid.size, T_)),
                             jnp.float32)
        idx, count = PA.select_topk(scores, jnp.asarray(n_valid), k)
        assert np.asarray(count).tolist() == np.minimum(n_valid, k).tolist()
        got = jax.jit(PA.pages_of, static_argnums=2)(
            jnp.asarray(table), idx, ps)
        want = jnp.take_along_axis(jnp.asarray(table), idx // ps, axis=1)
        assert got.dtype == jnp.int32 and got.shape == idx.shape
        assert np.array_equal(np.asarray(got), np.asarray(want))
        # ... and at the LAST position of the table too, picked or not
        last = jnp.full((n_valid.size, 1), T_ - 1, jnp.int32)
        assert np.array_equal(
            np.asarray(PA.pages_of(jnp.asarray(table), last, ps))[:, 0],
            table[:, -1])


def _pool_and_table(rng, L=2, P=40, ps=4, S=3, mp=9, width=128,
                    dtype=jnp.float32):
    pool = jnp.asarray(rng.standard_normal((L, P, 1, ps, width)), dtype)
    # non-contiguous pages, slots 0 and 1 SHARING their first two
    table = rng.permutation(np.arange(1, P))[:S * mp].reshape(S, mp)
    table[1, :2] = table[0, :2]
    return pool, jnp.asarray(table, jnp.int32)


class TestTheKernelsAgainstTheirUnfusedTwins:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_the_index_walk(self, dtype, monkeypatch):
        """Blocks of two pages (the budget shrunk), limits that end
        mid-page, at a block's edge and at 0, over shared and
        non-contiguous pages; and a layer of a stack."""
        monkeypatch.setattr(PA, "_INDEX_BLOCK_BYTES",
                            2 * 4 * 128 * jnp.dtype(dtype).itemsize)
        rng = np.random.default_rng(2)
        pool, table = _pool_and_table(rng, dtype=dtype)
        assert PA.index_block_pages(4, 128, dtype, 9) == 2
        qi = jnp.asarray(rng.standard_normal((3, 4, 128)), dtype)
        w = jnp.asarray(rng.standard_normal((3, 4)), jnp.float32)
        for limit in ([0, 19, 36], [8, 1, 33]):
            limit = jnp.asarray(limit, jnp.int32)
            got = PA.index_scores(qi, w, pool, table, limit, layer=1)
            want = PA.index_scores_reference(qi, w, pool, table, limit,
                                             layer=1)
            assert got.shape == want.shape == (3, 36)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)
            dead = np.arange(36)[None, :] >= np.asarray(limit)[:, None]
            assert (np.asarray(got)[dead] == PA.NEG_INF).all()
        # slots 0 and 1 share two pages: the same keys, their own queries
        one = PA.index_scores(jnp.stack([qi[0]] * 3), jnp.stack([w[0]] * 3),
                              pool, table, jnp.full((3,), 8), layer=0)
        np.testing.assert_array_equal(np.asarray(one[0, :8]),
                                      np.asarray(one[1, :8]))

    def test_a_chunks_scores(self):
        rng = np.random.default_rng(3)
        qi = jnp.asarray(rng.standard_normal((40, 4, 16)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((40, 4)), jnp.float32)
        keys = jnp.asarray(rng.standard_normal((70, 16)), jnp.float32)
        got = PA.index_scores_rows(qi, w, keys, kernel=True)
        want = np.einsum("qh,qhk->qk", np.asarray(w), np.maximum(
            np.einsum("qhd,kd->qhk", np.asarray(qi), np.asarray(keys)), 0))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_the_selected_attend(self, dtype):
        """Each query over ITS OWN gathered rows, some with fewer real
        rows than the width, one with none."""
        rng = np.random.default_rng(4)
        q = jnp.asarray(rng.standard_normal((5, 4, 128)), dtype)
        rows = jnp.asarray(rng.standard_normal((5, 32, 128)), dtype)
        count = jnp.asarray([32, 12, 1, 0, 17], jnp.int32)
        kw = dict(v_dim=32, sm_scale=0.3)
        got, _ = PA.selected_attend(q, rows, count, kernel=True, **kw)
        want, _ = PA.selected_attend(q, rows, count, kernel=False, **kw)
        s = np.einsum("rhw,rkw->rhk", np.asarray(q, np.float32),
                      np.asarray(rows, np.float32)) * 0.3
        s = np.where(np.arange(32)[None, None] < np.asarray(count)[
            :, None, None], s, -np.inf)
        p = np.exp(s - np.where(np.asarray(count) > 0,
                                s.max(-1).T, 0).T[..., None])
        plain = np.einsum("rhk,rkv->rhv", p / np.maximum(
            p.sum(-1, keepdims=True), 1e-30),
            np.asarray(rows, np.float32)[..., :32])
        tol = 1e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)
        np.testing.assert_allclose(np.asarray(want), plain, rtol=tol,
                                   atol=tol)
        assert not np.asarray(got[3]).any()

    def test_the_kernels_names(self):
        rng = np.random.default_rng(5)
        pool, table = _pool_and_table(rng)
        qi = jnp.zeros((3, 4, 128))
        txt = str(jax.make_jaxpr(lambda: PA.index_scores(
            qi, jnp.zeros((3, 4)), pool, table, jnp.full((3,), 9),
            layer=0))())
        assert "hvd_dsa_score" in txt
        txt = str(jax.make_jaxpr(lambda: PA.selected_attend(
            qi, jnp.zeros((3, 16, 128)), jnp.full((3,), 9), v_dim=32,
            sm_scale=1.0, kernel=True))())
        assert "hvd_dsa_attend" in txt and "hvd_mla_decode" not in txt


class TestTwoArraysUnderOneTable:
    def test_the_pool_and_what_a_token_costs(self, model):
        params, cfg = model
        eng = _engine(params, cfg)
        pool = eng.slots.cache
        assert pool["k"].shape == (3, eng.slots.n_pages + 1, 1, 4, 128)
        assert pool["ik"].shape == (3, eng.slots.n_pages + 1, 1, 4, 16)
        s = eng.stats()
        assert s["kv_latent_bytes_per_token"] == 3 * 128 * 4
        assert s["kv_index_bytes_per_token"] == 3 * 16 * 4
        assert s["kv_bytes_per_token"] == eng.slots.bytes_per_token \
            == 3 * (128 + 16) * 4

    def test_copy_on_write_copies_both_and_a_release_frees_both(self,
                                                                model):
        """One allocator, one table: a page id names a page of BOTH
        arrays — a COW copies both, a retired slot gives both back."""
        _, cfg = model
        slots = C.PagedSlotCache(cfg, 2, 16, page_size=4, n_pages=8)
        rng = np.random.default_rng(6)
        slots.cache = {**slots.cache, **{
            n: jnp.asarray(rng.standard_normal(slots.cache[n].shape),
                           jnp.float32) for n in ("k", "ik")}}
        a, b = slots.alloc(), slots.alloc()
        src = slots.grant(a, 0)
        slots.attach(b, [src])
        dst = slots.cow(b, 0)
        assert dst != src and slots.table[b, 0] == dst
        for n in ("k", "ik"):
            np.testing.assert_array_equal(
                np.asarray(slots.cache[n][:, dst]),
                np.asarray(slots.cache[n][:, src]))
        free = slots.free_pages
        slots.free(b)
        slots.free(a)
        assert slots.free_pages == free + 2 == 8

    def test_a_landing_writes_the_index_keys_where_the_rows_go(self, model):
        params, cfg = model
        eng = _engine(params, cfg)
        p = _prompts([7], 1)[0]
        f = eng.submit(p, max_new_tokens=4)
        while not eng.slots.table[0, 1]:
            eng.step()
        _, pre = T.prefill(params, jnp.asarray([p + [0]]),
                           T.init_cache(cfg, 1, 8), cfg, true_len=7)
        page = int(eng.slots.table[0, 1])
        for n in ("k", "ik"):
            np.testing.assert_allclose(
                np.asarray(eng.slots.cache[n][:, page, 0, :3]),
                np.asarray(pre[n][:, 0, 0, 4:7]), atol=1e-6)
        while not f.done():
            eng.step()

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernels"])
    def test_the_tick_writes_both_in_place(self, model, kernel):
        from conftest import pool_structure_faults

        params, cfg = model
        pool = C.init_page_pool(cfg, 3, 17, 4)
        table = jnp.zeros((3, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda pl: T.decode_step_paged(
            params, jnp.zeros((3,), jnp.int32), pl, table, cfg,
            jnp.ones((3,), bool), kernel=kernel,
            return_moe_load=True))(pool)
        assert pool_structure_faults(
            jaxpr, {pool["k"].shape, pool["ik"].shape}) == []


def _route_loop(logits, bias, k, n_group, topk_group, scale):
    """The published ``noaux_tc`` choice as a loop: groups and experts
    on ``sigmoid + bias``, weights from the raw sigmoid."""
    out_e, out_g = [], []
    for row in np.asarray(logits, np.float64):
        sc = 1.0 / (1.0 + np.exp(-row))
        ch = sc + np.asarray(bias, np.float64)
        per = len(sc) // n_group
        gs = [np.sort(ch[g * per:(g + 1) * per])[-2:].sum()
              for g in range(n_group)]
        keep = sorted(range(n_group), key=lambda g: (-gs[g], g))[:topk_group]
        cand = [e for e in range(len(sc)) if e // per in keep]
        sel = sorted(cand, key=lambda e: (-ch[e], e))[:k]
        out_e.append(sel)
        out_g.append(sc[sel] / sc[sel].sum() * scale)
    return np.asarray(out_e), np.asarray(out_g)


class TestTheRoutersBias:
    def test_chosen_on_scores_plus_bias_weighted_by_raw_scores(self,
                                                               highest):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 12), jnp.float32)
        router = jnp.asarray(rng.randn(12, 24), jnp.float32)
        bias = jnp.asarray(0.3 * rng.randn(24), jnp.float32)
        kw = dict(score="sigmoid", n_group=4, topk_group=2, scale=2.5)
        e, g = moe.route_topk(x, router, 4, True, bias=bias, **kw)
        want_e, want_g = _route_loop(np.asarray(x) @ np.asarray(router),
                                     bias, 4, 4, 2, 2.5)
        np.testing.assert_array_equal(np.asarray(e), want_e)
        np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-5)
        # the bias moved the choice in a share of the rows one can see
        e0, _ = moe.route_topk(x, router, 4, True, **kw)
        moved = np.mean(np.any(np.sort(e0, -1) != np.sort(e, -1), -1))
        assert 0.2 < moved < 1.0

    def test_a_zero_bias_is_no_bias_bit_for_bit(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(40, 12), jnp.float32)
        router = jnp.asarray(rng.randn(12, 16), jnp.float32)
        for kw in (dict(score="sigmoid", n_group=4, topk_group=2,
                        scale=2.5), dict()):
            e0, g0 = moe.route_topk(x, router, 4, True, **kw)
            e1, g1 = moe.route_topk(x, router, 4, True,
                                    bias=jnp.zeros((16,)), **kw)
            np.testing.assert_array_equal(np.asarray(e0), np.asarray(e1))
            np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))

    def test_the_share_ties_to_the_model_with_the_bias_on(self, model,
                                                          highest):
        """``test_latent_attention.TestTheShareTiesToTheModel``'s case
        under the biased choice: four shares' routed parts and the
        shared expert ONCE add up to the uncut reference's layer."""
        _, cfg = model
        whole = T.init_params(jax.random.PRNGKey(3), dataclasses.replace(
            cfg, n_experts_held=0, expert_offset=0))
        p = jax.tree_util.tree_map(lambda a: a[0], whole["layers"])
        assert float(jnp.abs(p["router_bias"]).max()) > 0.05
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
        want = np.stack([np.asarray(R.sparse_experts(
            xb, p, dict(DIMS, expert_offset=0))) for xb in x])
        unbiased = np.stack([np.asarray(R.latent_experts(
            xb, p, dict(DIMS, expert_offset=0))) for xb in x])
        assert np.abs(want - unbiased).max() > 1e-2    # the bias bites
        shared = np.asarray(T._dense_mlp(
            x, {k: p["ws_" + k[2:]] for k in T._EXPERT_LEAVES}, cfg))
        total = np.zeros_like(want)
        rows = 0
        for off in (0, 4, 8, 12):
            scfg = dataclasses.replace(cfg, expert_offset=off)
            part = {**p, **{k: p[k][off:off + 4] for k in T._EXPERT_LEAVES}}
            y, counts = T._moe_mlp(x, part, scfg, return_counts=True)
            total += np.asarray(y) - shared
            rows += int(counts.sum())
        assert rows == 2 * 9 * 4
        assert np.abs(total + shared - want).max() < 2e-5


class TestIndexTopkZeroIsTheLatentModel:
    def test_the_jaxprs_are_the_latent_models(self, model):
        """``index_topk = 0``: the tick, a chunk and a whole prefill
        trace to what the latent model traces to, equation for
        equation — no indexer, no second pool array."""
        params, cfg = model
        off = dataclasses.replace(cfg, index_n_heads=0, index_head_dim=0,
                                  index_topk=0, moe_score_bias=False)
        assert off == _latent_cfg() and not off.sparse
        p_off = T.init_params(jax.random.PRNGKey(0), off)
        assert "wi_q" not in p_off["layers"] \
            and "router_bias" not in p_off["layers"]
        pool = C.init_page_pool(off, 3, 17, 4)
        assert set(pool) == {"k", "pos"}
        table = jnp.zeros((3, 8), jnp.int32)
        txt = str(jax.make_jaxpr(lambda pl: T.decode_step_paged(
            p_off, jnp.zeros((3,), jnp.int32), pl, table, off,
            jnp.ones((3,), bool)))(pool))
        assert "hvd_dsa" not in txt and "top_k" in txt   # the router's own
        on = str(jax.make_jaxpr(lambda pl: T.decode_step_paged(
            params, jnp.zeros((3,), jnp.int32), pl, table, cfg,
            jnp.ones((3,), bool), kernel=True))(
                C.init_page_pool(cfg, 3, 17, 4)))
        assert "hvd_dsa_score" in on and "hvd_dsa_attend" in on

    def test_the_tokens_are_the_latent_models(self, model):
        """... and serves the latent model's tokens from its weights."""
        _, cfg = model
        off = _latent_cfg()
        params = T.init_params(jax.random.PRNGKey(0), off)
        eng = _engine(params, off)
        p = _prompts([19], 4)[0]
        f = eng.submit(p, max_new_tokens=6)
        while not f.done():
            eng.step()
        assert f.result() == np.asarray(T.greedy_decode(
            params, jnp.asarray([p]), 6, off))[0].tolist()
        assert eng.stats()["kv_index_bytes_per_token"] == 0
        assert eng.stats()["dsa_scored_tokens_total"] == 0


class TestTheEngineServesIt:
    @pytest.mark.parametrize("kw", [{}, {"overlap": True},
                                    {"paged_kernel": True}],
                             ids=["sync", "overlap", "kernels"])
    def test_tokens_are_greedy_decodes(self, model, kw):
        params, cfg = model
        eng = _engine(params, cfg, **kw)
        prompts = _prompts([5, 19, 30, 7, 14])
        futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        while not all(f.done() for f in futs):
            eng.step()
        for p, f in zip(prompts, futs):
            want = np.asarray(T.greedy_decode(
                params, jnp.asarray([p]), 9, cfg))[0].tolist()
            assert f.result() == want
        s = eng.stats()
        assert s["decode_compilations"] == 1
        assert s["kv_pages_in_use"] == 0

    def test_the_counters_are_the_layouts(self, model):
        """One request, alone: every tick scores its whole context and
        selects ``min(index_topk, context)``."""
        params, cfg = model
        eng = _engine(params, cfg)
        f = eng.submit(_prompts([9], 8)[0], max_new_tokens=8)
        while not f.done():
            eng.step()
        s = eng.stats()
        ctx = [9 + j + 1 for j in range(s["decode_ticks"])]
        assert s["dsa_scored_tokens_total"] == sum(ctx)
        assert s["dsa_selected_tokens_total"] == sum(
            min(TOPK, c) for c in ctx)
        assert s["dsa_full_rows_total"] == sum(c <= TOPK for c in ctx)
        blk = eng._index_block_tokens
        assert s["dsa_walked_tokens_total"] == sum(
            -(-c // blk) * blk for c in ctx)
        assert s["paged_live_tokens_total"] == 0


class TestRefusals:
    @pytest.mark.parametrize("kw,why", [
        ({"tp": 2}, "tp > 1"),
        ({"speculative": True}, "speculative=True"),
        ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ])
    def test_engine_modes_refuse_sparse_selection(self, model, kw, why):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError) as e:
            _engine(params, cfg, **kw)
        assert why in str(e.value) and "sparse selection" in str(e.value)

    def test_prefix_sharing_and_bad_sizes_are_refused(self, model):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="prefix sharing is not written for sparse"):
            _engine(params, cfg).register_prefix([1, 2, 3, 4])
        with pytest.raises(ValueError, match="an indexer's three sizes"):
            _cfg(index_head_dim=0)
        with pytest.raises(ValueError, match="an indexer's three sizes"):
            _cfg(index_head_dim=4)           # narrower than the rope part
        with pytest.raises(ValueError, match="over latent attention"):
            T.TransformerConfig(index_n_heads=2, index_head_dim=16,
                                index_topk=4)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="a score-correction bias"):
            T.forward(params, jnp.zeros((1, 8), jnp.int32),
                      dataclasses.replace(cfg, moe_impl="switch"))
