"""LINEAR-ATTENTION layers between BLOCK-SPARSE attention layers, served
(MiniCPM-SALA's two blocks): a fifth and a sixth layer kind — a float32
matrix state a head and slot beside a pool of another dtype and no pages;
pages of K and V with a third pool array of ONE ROW A PAGE of a SLOT's
table (by the page's logical index: the tick reads a slot's rows as they
lie), the compressed keys whose scores choose the blocks a query attends
— under the published muP scales.

The program's LOGITS are held to ``horovod_tpu.models.plain_reference``
(``sala_forward``: straightforward float32 ``jax.numpy``, the recurrence
a SEQUENTIAL scan over the tokens, the selection by brute force from its
definition, nothing of the program in it) at a small size on seeded
weights: hidden 64, 4 query / 2 KV heads of 16, four layers (sparse,
linear, linear, sparse), windows of 8 keys every 4 (the page), blocks of
8, top-2 of the rest behind a window of two blocks and one first block,
the switch at 24 tokens — so prompts of 30 and 41 cross it in chunks,
and every served token is behind it.

TOLERANCE: ``LOGIT_TOL`` = 2e-5 absolute on logits of std 0.3.  Both
sides compute in float32 with float32 accumulation; what differs is the
ORDER of sums (the dual form's blocks against the token-by-token
recurrence, a softmax over gathered pages against one over a masked
row): 4e-7 to 6e-7 observed.  A selection that differed in ONE block
would move a logit by 1e-2 or more (``test_the_selection_ignored``)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import plain_reference as R
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import ssm as SSM
from horovod_tpu.serving import cache as C

from test_window_layers import _LogitTap

pytestmark = [pytest.mark.serving, pytest.mark.paged]

LOGIT_TOL = 2e-5
V = 97
PATTERN = ("block_sparse", "linear", "linear", "block_sparse")
SC = dict(kernel_size=8, kernel_stride=4, block_size=8, topk=2,
          window_size=16, init_blocks=1, dense_len=24)
DIMS = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, lightning_nh=4, lightning_head_dim=16, qk_norm=True,
    lightning_use_rope=True, attn_use_rope=False, attn_use_output_gate=True,
    rms_norm_eps=1e-6, rope_theta=10000.0, num_hidden_layers=4,
    scale_emb=3.0, scale_depth=1.4, dim_model_base=32,
    published={"num_hidden_layers": 8}, sparse_config=SC,
    mixer_types=["minicpm4" if k == "block_sparse" else "lightning-attn"
                 for k in PATTERN])
RES = R.sala_residual_scale(DIMS)


def _cfg(**over):
    kw = dict(
        vocab_size=V, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
        n_layers=4, d_ff=96, layer_pattern=PATTERN, qk_norm=True,
        bsa_kernel=8, bsa_stride=4,
        bsa_block=8, bsa_topk=2, bsa_window=16, bsa_init_blocks=1,
        bsa_dense_len=24, ssm_chunk=4, embed_multiplier=3.0,
        head_multiplier=0.5, attn_out_multiplier=RES,
        mlp_multipliers=(1.0, RES), norm_eps=1e-6, rope_theta=10000.0,
        max_seq=96, dtype=jnp.float32, attention_impl="flash")
    kw.update(over)
    return T.TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for i, name in enumerate(("ln1", "ln2", "lin_norm", "q_norm",
                              "lin_k_norm")):
        a = params["layers"][name]
        params["layers"][name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(k, i), a.shape)
    return params, cfg


@pytest.fixture()
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, V, n).tolist() for n in lens]


def _engine(params, cfg, **kw):
    base = dict(n_slots=3, max_len=96, paged=True, page_size=4,
                prefill_chunk_tokens=8, max_prefills_per_tick=2,
                min_prefill_bucket=8, overlap=False)
    base.update(kw)
    return serving.InferenceEngine(params, cfg, serving.EngineConfig(**base))


def _run(engine, futs):
    while not all(f.done() for f in futs):
        engine.step()


def _worst(params, tap, prompts, futs, **controls):
    worst = 0.0
    for p, f in zip(prompts, futs):
        toks = f.result()
        ref = np.asarray(R.sala_forward(params, jnp.asarray(p + toks), DIMS,
                                        **controls))
        rows = np.stack(tap.rows[id(f)])[:len(toks)]
        assert rows.shape[0] == len(toks)
        want = ref[len(p) - 1:len(p) - 1 + len(toks)]
        worst = max(worst, float(np.abs(rows - want).max()))
    return worst


def _serve_and_compare(params, cfg, prompts, new=10, **kw):
    engine = _engine(params, cfg, **kw)
    tap = _LogitTap(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    _run(engine, futs)
    return engine, _worst(params, tap, prompts, futs)


class TestLogitsAgainstThePlainReference:
    # chunk 0: the whole prompt in one prefill; 8: chunks of two pages
    # and one block; 7: neither (states handed over mid-block of the
    # scan, pages filled by two landings: RAGGED); then decoding through
    # the cache — prompts of 5 (dense all the way), 30 and 41 (the
    # chunks cross the switch at 24; every tick behind it)
    @pytest.mark.parametrize("chunk,kernel", [(0, None), (8, None),
                                              (7, None), (8, True)])
    def test_prefill_then_decode_through_the_cache(self, model, highest,
                                                   chunk, kernel):
        params, cfg = model
        engine, worst = _serve_and_compare(
            params, cfg, _prompts((5, 30, 41)), prefill_chunk_tokens=chunk,
            paged_kernel=kernel)
        assert worst < LOGIT_TOL, worst
        st = engine.stats()
        assert st["paged_kernel_engaged"] is bool(kernel)
        assert st["lin_state_bytes_per_slot"] == 2 * 4 * 16 * 16 * 4
        assert st["kv_compressed_bytes_per_page"] == 2 * 2 * 16 * 4
        assert st["lin_updated_slots_total"] > 0
        assert st["lin_scanned_tokens_total"] == 2 * (5 + 30 + 41)
        assert 0 < st["bsa_attended_tokens_total"] \
            < st["bsa_live_tokens_total"]
        assert st["bsa_scored_rows_total"] > 0

    def test_the_state_survives_preemption(self, model, highest):
        """A pool too small for three long requests at once: the
        youngest is preempted, its pages and its state given up, and
        prefilled again from its prompt and what it had emitted — the
        tokens are the reference's all the same."""
        params, cfg = model
        prompts = _prompts((30, 41, 37), seed=3)
        engine = _engine(params, cfg, n_pages=30)
        futs = [engine.submit(p, max_new_tokens=12) for p in prompts]
        _run(engine, futs)
        assert engine.stats()["preemptions"] >= 1
        for p, f in zip(prompts, futs):
            toks = f.result()
            ref = np.asarray(R.sala_forward(
                params, jnp.asarray(p + toks), DIMS))
            want = ref[len(p) - 1:len(p) - 1 + len(toks)]
            # a served token is within the tolerance of the reference's
            # best at its position
            gap = want.max(-1) - want[np.arange(len(toks)), toks]
            assert gap.max() < LOGIT_TOL, gap.max()

    def test_the_selection_ignored_or_a_state_lost_is_another_model(
            self, model, highest):
        params, cfg = model
        prompts = _prompts((41,))
        engine = _engine(params, cfg)
        tap = _LogitTap(engine)
        futs = [engine.submit(p, max_new_tokens=10) for p in prompts]
        _run(engine, futs)
        assert _worst(params, tap, prompts, futs) < LOGIT_TOL
        assert _worst(params, tap, prompts, futs, select=False) > 1e-2
        lost = jnp.arange(51) % 8 == 0
        assert _worst(params, tap, prompts, futs, reset=lost) > 1e-2


class TestCompressedKeys:
    def test_every_full_pages_row_is_the_mean_of_its_windows_keys(
            self, model, highest):
        """After RAGGED landings (chunks of 7 over pages of 4) and some
        ticks: ``ck[slot, head, r]`` of every full page ``r >= 1`` of a
        live slot — the page's LOGICAL index, whatever physical page the
        table names there — is the mean over pages ``r - 1`` and ``r``
        of the keys the pool holds, a layer and KV head."""
        params, cfg = model
        engine = _engine(params, cfg, prefill_chunk_tokens=7)
        futs = [engine.submit(p, max_new_tokens=30)
                for p in _prompts((30, 41))]
        while min(engine._page_pos[:2]) < 50:
            engine.step()
        pool, table = engine.slots.cache, engine.slots.table
        k, ck = np.asarray(pool["k"]), np.asarray(pool["ck"])
        assert ck.shape == (2, 3, 2, engine.slots.max_pages, 16)
        pos = np.asarray(pool["pos"])
        checked = 0
        for s in range(2):
            for r in range(1, int(pos[s]) // 4):
                two = k[:, [table[s, r - 1], table[s, r]]]  # (L,2,Hkv,4,Dh)
                np.testing.assert_allclose(
                    ck[:, s, :, r], two.mean(axis=(1, 3)), atol=1e-6)
                checked += 1
        assert checked > 20
        assert not ck[:, 2].any()       # the slot no request was granted
        _run(engine, futs)

    def test_the_tick_scores_a_slots_rows_as_they_lie(self, monkeypatch,
                                                      highest):
        """One tick of four slots over rows laid by slot — a context
        within ``dense_len`` whose page fills in THIS tick, one beyond
        it whose page fills in this tick, one beyond it mid-page, an
        idle slot — under a page table that scatters the physical
        pages: the block scores and the chosen blocks are those of
        ``_bsa_compress`` of the keys in logical order, the two filled
        pages' rows are written at their logical index, and nothing else
        of the array moves but the slots' rows 0.  Every row that no
        landing or tick has written yet holds NaN: none is scored."""
        cfg = _cfg()
        kind, ps, mp, layer = cfg.kind("block_sparse"), 4, 24, 1
        rng = np.random.default_rng(4)
        pos = np.asarray([19, 43, 57, 30], np.int32)
        active = np.asarray([True, True, True, False])
        k_log, v_log = (rng.normal(size=(4, 2, mp * ps, 16)).astype(
            np.float32) for _ in range(2))
        q = jnp.asarray(rng.normal(size=(4, 4, 1, 16)), jnp.float32)
        table = 1 + rng.permutation(4 * mp).reshape(4, mp).astype(np.int32)
        pool = C.init_page_pool(cfg, 4, 4 * mp + 1, ps, None, 2, mp)
        rows = np.asarray(T._bsa_compress(jnp.asarray(k_log), cfg))
        k, v = np.zeros(pool["k"].shape, np.float32), np.zeros(
            pool["v"].shape, np.float32)
        ck = np.full(pool["ck"].shape, np.nan, np.float32)
        for s in range(4):      # what the landings and ticks before left
            held = np.arange(mp * ps) < pos[s]
            for log, arr in ((k_log, k), (v_log, v)):
                arr[layer, table[s]] = np.moveaxis(
                    np.where(held[:, None], log[s], 0).reshape(2, mp, ps, 16),
                    0, 1)
            full = np.arange(1, pos[s] // ps)
            ck[layer, s, :, full] = np.moveaxis(rows[s][:, full], 1, 0)
        seen = {}
        for name in ("_bsa_block_scores", "_bsa_chosen"):
            def tapped(*a, _f=getattr(T, name), _n=name):
                seen[_n] = _f(*a)
                return seen[_n]

            monkeypatch.setattr(T, name, tapped)
        tick = T._Tick(cfg, jnp.asarray(table), None, jnp.asarray(pos),
                       jnp.asarray(active), False, None).at(
            pools={"k": jnp.asarray(k), "v": jnp.asarray(v),
                   "ck": jnp.asarray(ck)}, layer=jnp.int32(layer))
        at = np.arange(4), slice(None), pos
        o, _, _, new = tick.select_attend(
            q, jnp.asarray(k_log[at])[:, :, None],
            jnp.asarray(v_log[at])[:, :, None], kind)
        monkeypatch.undo()
        live = jnp.asarray(np.where(active, pos, -1))
        want = T._bsa_block_scores(q.reshape(4, 2, 2, 1, 16),
                                   jnp.asarray(rows), live[:, None], mp // 2,
                                   cfg)
        got = np.asarray(seen["_bsa_block_scores"])
        np.testing.assert_allclose(got, want, atol=1e-6)
        assert (got[3] == -1).all() and (got[:3, :, :, 0] >= 0).all()
        chosen, n = T._bsa_chosen(want.reshape(8, -1), jnp.repeat(
            jnp.maximum(live, 0), 2), cfg)
        np.testing.assert_array_equal(seen["_bsa_chosen"][0], chosen)
        np.testing.assert_array_equal(seen["_bsa_chosen"][1], n)
        assert n.tolist() == [3, 3, 5, 5, 5, 5, 1, 1]   # dense; 1 + 2 + 2
        assert np.isfinite(np.asarray(o)[:3]).all()
        for s in (0, 1):        # the page this token fills: 4 and 10
            ck[layer, s, :, pos[s] // ps] = rows[s][:, pos[s] // ps]
        np.testing.assert_array_equal(np.asarray(new)[..., 1:, :],
                                      ck[..., 1:, :])

    @pytest.mark.parametrize("case", ["mid_page", "several_pages",
                                      "two_rows"])
    def test_a_landing_lays_each_filled_pages_row_at_its_logical_index(
            self, case):
        """``paged_insert`` alone: a landing that starts mid-page
        (positions 6-12: it fills pages 1 and 2, not 3), one that spans
        several pages from 0 (14 tokens in a bucket of 16: pages 0-2),
        and two rows of one landing into two slots — each filled page's
        row at ``[layer, slot, head, start // page + j]``, and nothing
        else of the array moved but the landed slots' rows 0."""
        cfg = _cfg()
        start, lens, bucket, slots = {
            "mid_page": (6, [7], 8, [1]),
            "several_pages": (0, [14], 16, [2]),
            "two_rows": (8, [8, 5], 8, [2, 0])}[case]
        K, n_pg = len(slots), C.landing_pages(bucket, 4)
        pool = C.init_page_pool(cfg, 3, 3 * 24 + 1, 4, None, 2, 24)
        before = np.asarray(pool["ck"]) + 7.0
        rng = np.random.default_rng(1)
        block = {"k": rng.normal(size=(2, K, 2, bucket, 16)),
                 "v": rng.normal(size=(2, K, 2, bucket, 16)),
                 "ck": rng.normal(size=(2, K, 2, n_pg, 16)),
                 "lin": np.zeros((2, K, 4, 16, 16))}
        pages = 1 + np.arange(K * n_pg, dtype=np.int32).reshape(K, n_pg)
        out = C.paged_insert(
            {**pool, "ck": jnp.asarray(before)}, np.asarray(slots, np.int32),
            jnp.asarray(lens, jnp.int32) + start, pages, np.int32(start % 4),
            np.asarray(lens, np.int32),
            {n: jnp.asarray(a, jnp.float32) for n, a in block.items()})
        want, filled = before.copy(), 0
        for i, s in enumerate(slots):
            for j in range(n_pg):
                if (start // 4 + j + 1) * 4 <= start + lens[i]:
                    want[:, s, :, start // 4 + j] = block["ck"][:, i, :, j]
                    filled += 1
        assert filled == {"mid_page": 2, "several_pages": 3,
                          "two_rows": 3}[case]
        got = np.asarray(out["ck"])
        np.testing.assert_array_equal(got[..., 1:, :], want[..., 1:, :])
        idle = [s for s in range(3) if s not in slots]
        np.testing.assert_array_equal(got[:, idle], before[:, idle])
        assert out["pos"].tolist() == [
            dict(zip(slots, np.add(lens, start))).get(s, 0) for s in range(3)]

    def test_a_slotless_landing_is_refused(self):
        """Prefix registration lands into pages no slot holds: a pool
        with an array of a row a page of a SLOT's table has nowhere to
        put its rows (and no page of it is ever shared)."""
        cfg = _cfg()
        pool = C.init_page_pool(cfg, 3, 9, 4, None, 2, 24)
        block = {"k": jnp.zeros((2, 1, 2, 8, 16)),
                 "v": jnp.zeros((2, 1, 2, 8, 16)),
                 "ck": jnp.zeros((2, 1, 2, 3, 16)),
                 "lin": jnp.zeros((2, 1, 4, 16, 16))}
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="slotless landing"):
            C.paged_insert(pool, np.zeros((0,), np.int32),
                           jnp.zeros((0,), jnp.int32),
                           np.ones((1, 3), np.int32), np.int32(0),
                           np.asarray([8], np.int32), block)

    @pytest.mark.parametrize("chunk", [0, 7])
    def test_no_row_of_the_last_tenant_is_scored(self, model, highest,
                                                 chunk):
        """ONE slot, granted to a request of 41 + 30 tokens and then to
        one of 30 + 12: nothing is scrubbed between them (the first
        tenant's rows lie there still), and with EVERY row of the array
        then turned to NaN the second tenant's logits are the
        reference's all the same — a query scores row ``r`` only once
        ``(r + 1) stride <= pos + 1``, and each such page was filled by
        this tenant's own landings or ticks first (the pages'
        write-before-attend argument, a row a page)."""
        params, cfg = model
        long, short = _prompts((41, 30), seed=7)
        engine = _engine(params, cfg, n_slots=1, prefill_chunk_tokens=chunk)
        tap = _LogitTap(engine)
        first = engine.submit(long, max_new_tokens=30)
        _run(engine, [first])
        ck = np.asarray(engine.slots.cache["ck"])
        assert ck[:, 0, :, 10:17].all()   # pages 10-16: the first tenant's
        second = engine.submit(short, max_new_tokens=12)
        _run(engine, [second])
        assert _worst(params, tap, [short], [second]) < LOGIT_TOL
        ck = np.asarray(engine.slots.cache["ck"])
        assert ck[:, 0, :, 11:17].all()   # ... still: 42 tokens fill 0-9
        cache = engine.slots.cache   # a row scored unwritten would show
        engine.slots.cache = {**cache,
                              "ck": jnp.full_like(cache["ck"], jnp.nan)}
        third = engine.submit(short, max_new_tokens=12)
        _run(engine, [third])
        assert third.result() == second.result()
        assert _worst(params, tap, [short], [third]) < LOGIT_TOL


def _brute_blocks(score, pos, sc):
    """The blocks a row attends, from its block scores, by the
    definition: numpy, a row at a time, ``argsort`` stable (ties to the
    lower block)."""
    blk, init = sc["block_size"], sc["init_blocks"]
    W = sc["window_size"] // blk
    out = []
    for s, t in zip(np.asarray(score), np.asarray(pos)):
        own = t // blk
        if t + 1 <= sc["dense_len"]:
            out.append(list(range(own + 1)))
            continue
        forced = {b for b in range(own + 1) if b < init or b > own - W}
        rest = [b for b in range(init, own - W + 1)]
        order = sorted(rest, key=lambda b: (-s[b], b))[:sc["topk"]]
        out.append(sorted(forced | set(order)))
    return out


class TestSelection:
    CFG = _cfg(bsa_topk=3, bsa_dense_len=0)
    SC3 = dict(SC, topk=3, dense_len=0)

    @pytest.mark.parametrize("case", ["random", "tied", "few_blocks",
                                      "forced_are_the_best"])
    def test_element_for_element_against_brute_force(self, case):
        rng = np.random.default_rng(5)
        nB = 16
        pos = np.asarray([127, 100, 64, 47, 31, 17, 9, 3], np.int32)
        score = rng.random((8, nB)).astype(np.float32)
        if case == "tied":          # three values only: ties everywhere
            score = rng.integers(0, 3, (8, nB)).astype(np.float32) / 4
        if case == "few_blocks":    # fewer candidates than topk
            pos = np.asarray([39, 33, 32, 31, 25, 24, 23, 8], np.int32)
        if case == "forced_are_the_best":
            own = pos // 8
            score[:, 0] = 9.0
            for i, o in enumerate(own):
                score[i, max(o - 1, 0):o + 1] = 8.0
        want = _brute_blocks(score, pos, self.SC3)
        mask = np.asarray(T._bsa_block_mask(jnp.asarray(score),
                                            jnp.asarray(pos), self.CFG))
        chosen, n = (np.asarray(a) for a in T._bsa_chosen(
            jnp.asarray(score), jnp.asarray(pos), self.CFG))
        for i in range(8):
            assert np.nonzero(mask[i])[0].tolist() == want[i], (case, i)
            assert chosen[i, :n[i]].tolist() == want[i], (case, i)
            assert chosen[i, n[i] - 1] == pos[i] // 8    # its own: last
            assert not chosen[i, n[i]:].any()

    def test_a_short_context_attends_every_block(self):
        cfg = _cfg()
        score = jnp.zeros((3, 6))
        pos = jnp.asarray([23, 24, 5], jnp.int32)
        chosen, n = T._bsa_chosen(score, pos, cfg)
        assert n.tolist() == [3, 4, 1]      # 24 tokens: dense; 25: sparse
        assert np.asarray(chosen)[0, :3].tolist() == [0, 1, 2]

    def test_the_programs_masks_are_the_references(self, highest):
        """From q and k to the tokens a query may attend: the program's
        compressed rows, block scores and selection against
        ``plain_reference.sala_selected``, every (query, KV head, key)."""
        cfg = _cfg()
        rng = np.random.default_rng(2)
        S = 61
        q = jnp.asarray(rng.normal(size=(S, 4, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(S, 2, 16)), jnp.float32)
        want = np.asarray(R.sala_selected(q, k, SC))        # (S, Hkv, S)
        rows = T._bsa_compress(jnp.moveaxis(k, 0, 1)[None], cfg)
        pos = jnp.arange(S, dtype=jnp.int32)
        qg = jnp.moveaxis(q, 0, 1).reshape(1, 2, 2, S, 16)
        nB = -(-S // 8)
        score = T._bsa_block_scores(qg, rows, pos[None], nB, cfg)
        mask = T._bsa_block_mask(score.reshape(-1, nB),
                                 jnp.tile(pos, 2), cfg).reshape(2, S, nB)
        got = (np.repeat(np.asarray(mask), 8, axis=-1)[..., :S]
               & (np.arange(S)[None, :] <= np.arange(S)[:, None]))
        np.testing.assert_array_equal(np.moveaxis(got, 0, 1), want)


class TestTheRecurrenceIsTheStateSpaceMixers:
    """``ops/ssm.py`` at a group A HEAD (``G = H``), ``dt = 1``: the
    lightning recurrence ``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t =
    q_t S_t`` token by token."""

    @staticmethod
    def _tokens(q, k, v, lam, h0):
        def step(h, a):
            q_t, k_t, v_t = a
            h = lam[:, None, None] * h + v_t[:, :, None] * k_t[:, None, :]
            return h, jnp.einsum("hpn,hn->hp", h, q_t)

        return jax.lax.scan(step, h0, (q, k, v))

    @pytest.mark.parametrize("kernel", [False, True])
    def test_update_and_scan(self, highest, kernel):
        rng = np.random.default_rng(0)
        S, H, Dh = 13, 4, 8
        q, k, v = (jnp.asarray(rng.normal(size=(S, H, Dh)), jnp.float32)
                   for _ in range(3))
        a_neg = -jnp.asarray([0.5, 0.1, 0.01, 0.0005], jnp.float32)
        h0 = jnp.asarray(rng.normal(size=(H, Dh, Dh)), jnp.float32)
        h_want, y_want = self._tokens(q, k, v, jnp.exp(a_neg), h0)
        y, h = SSM.ssm_scan(v[None], jnp.ones((1, S, H)), a_neg, k[None],
                            q[None], h0[None], chunk=4)
        np.testing.assert_allclose(y[0], y_want, atol=2e-5)
        np.testing.assert_allclose(h[0], h_want, atol=2e-5)
        # the tick: two layers' states of three slots, the second
        # layer's updated in place, slot 1 idle
        states = jnp.zeros((2, 3, H, Dh, Dh)).at[1].set(h0)
        active = jnp.asarray([True, False, True])
        y1, new = SSM.ssm_update(
            states, jnp.int32(1), jnp.stack([v[0]] * 3), jnp.ones((3, H)),
            a_neg, jnp.stack([k[0]] * 3), jnp.stack([q[0]] * 3), active,
            kernel=kernel)
        h1, o1 = self._tokens(q[:1], k[:1], v[:1], jnp.exp(a_neg), h0)
        np.testing.assert_allclose(y1[0], o1[0], atol=2e-5)
        np.testing.assert_allclose(new[1, 2], h1, atol=2e-5)
        np.testing.assert_array_equal(new[1, 1], h0)
        np.testing.assert_array_equal(new[0], states[0])


class TestRefusalsByName:
    def test_the_modes_that_do_not_compute_it(self, model):
        params, cfg = model
        E = T.UnsupportedModelConfigError
        for kw, what in ((dict(tp=2), "tp > 1"),
                         (dict(speculative=True), "speculative"),
                         (dict(kv_dtype="int8"), "int8")):
            with pytest.raises(E, match="linear-attention layers") as e:
                _engine(params, cfg, **kw)
            assert what in str(e.value)
        with pytest.raises(E, match="page_size must be its stride"):
            _engine(params, cfg, page_size=8)
        engine = _engine(params, cfg)
        with pytest.raises(E, match="prefix sharing is not written"):
            engine.register_prefix([1, 2, 3, 4, 5])
        ids = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(E, match="forward computes one kind"):
            T.forward(params, ids, cfg)
        with pytest.raises(E):
            T.loss_fn(params, {"tokens": ids, "targets": ids}, cfg)

    def test_the_configurations_that_are_not_written(self):
        E = T.UnsupportedModelConfigError
        with pytest.raises(E, match="'linear' and 'block_sparse' layers"):
            _cfg(layer_pattern=("linear", "full"), n_layers=2)
        with pytest.raises(E, match="'linear' and 'block_sparse' layers"):
            _cfg(n_experts=4)
        with pytest.raises(ValueError, match="bsa_kernel = 2"):
            _cfg(bsa_kernel=12)

    def test_a_pool_of_one_kind_only(self, model):
        """``lin`` is float32 beside a bfloat16 pool; ``ck`` a row a
        page of a slot's table, as wide as the pool is told the table
        is (``max_seq`` / page if it is not); neither kind's layers
        count the other's arrays."""
        cfg = _cfg(dtype=jnp.bfloat16)
        pool = C.init_page_pool(cfg, 3, 9, 4, None, cfg.layers_with("k"), 5)
        assert {n: (a.shape, a.dtype.name) for n, a in pool.items()} == {
            "pos": ((3,), "int32"),
            "k": ((2, 9, 2, 4, 16), "bfloat16"),
            "v": ((2, 9, 2, 4, 16), "bfloat16"),
            "ck": ((2, 3, 2, 5, 16), "bfloat16"),
            "lin": ((2, 3, 4, 16, 16), "float32")}
        assert C.init_page_pool(cfg, 3, 9, 4, None, 2)["ck"].shape == (
            2, 3, 2, 24, 16)


with open(os.path.join(os.path.dirname(__file__), "data",
                       "served_program_digests_pr45.json")) as _f:
    _BEFORE = json.load(_f)


@pytest.mark.parametrize("program", ["tick", "chunk", "prompt"])
@pytest.mark.parametrize("config", sorted(_BEFORE))
def test_the_six_served_programs_are_as_before(config, program):
    """With every field these two kinds added left off, each of the six
    architectures served before — the hybrid one among them — traces to
    the jaxpr it traced to before (``tests/served_program_digests.py``;
    the file is what the tree before PR 46 printed)."""
    import served_program_digests as D

    got = D.programs(T.TransformerConfig(**D.CONFIGS[config]))[program]
    assert got == _BEFORE[config][program]
