"""A latent-attention expert model through the serving path: latent
attention (MLA) over a latent page pool — expanded for prompts and
chunks, absorbed for decode ticks — a shared expert beside one chip's
SHARE of sigmoid, group-limited routed experts, and a leading dense
layer in a stack of its own.

The program's LOGITS are held to ``plain_reference.latent_forward``
(straightforward float32 ``jax.numpy``, NON-absorbed attention, every
held expert under a mask, nothing of the program in it) at a small size
on seeded weights: hidden 48, 4 heads of 16 nope + 8 rope and 16 value,
q rank 24, kv rank 32, one dense layer of width 96 then two expert
layers of 16 experts (4 a token, 4 groups of which 2 stay, sigmoid
scores x 2.5) of which experts 4..7 are held, one shared expert, YaRN
with ``original_max_position`` 16 and ``mscale_all_dim`` 1 (``m^2`` =
1.30 in the softmax scale).

TOLERANCE: ``LOGIT_TOL`` = 2e-4 absolute on logits of magnitude ~1.
Both sides compute in float32; what differs is the ORDER of sums — the
absorbed form dots 576-wide rows where the reference dots expanded
heads, the flash kernel's and the chunk's online softmax go by blocks,
the experts are grouped products of sorted rows — which moves a logit by
a few 1e-6 (2.1e-6 observed).  A cache held in bfloat16 misses by ~1e-2,
one read as fp8 by ~1e-1, and a softmax scale without ``m^2`` by ~1e-1:
``TestTheToleranceIsTight`` holds the tolerance to each.
"""

import dataclasses

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
from horovod_tpu.serving import cache as C

LOGIT_TOL = 2e-4
V = 96
DIMS = dict(
    first_k_dense_replace=1, num_hidden_layers=3, rms_norm_eps=1e-6,
    qk_nope_head_dim=16, qk_rope_head_dim=8, kv_lora_rank=32,
    rope_theta=10000.0,
    rope_scaling=dict(type="yarn", factor=4.0, beta_fast=32, beta_slow=1,
                      original_max_position_embeddings=16, mscale=1,
                      mscale_all_dim=1),
    scoring_func="sigmoid", n_group=4, topk_group=2, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=2.5, n_shared_experts=1,
    expert_offset=4)


def _cfg(**over):
    kw = dict(
        vocab_size=V, d_model=48, n_heads=4, n_layers=3, d_ff=96,
        max_seq=128, dtype=jnp.float32, q_lora_rank=24, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_yarn=(4.0, 16.0, 32.0, 1.0, 1.0, 1.0), n_dense_layers=1,
        n_experts=16, n_experts_per_tok=4, d_expert=32, n_shared_experts=1,
        moe_score="sigmoid", routed_scaling_factor=2.5, n_group=4,
        topk_group=2, norm_topk_prob=True, moe_impl="dropless",
        n_experts_held=4, expert_offset=4, attention_impl="flash")
    kw.update(over)
    return T.TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for stack in ("dense_layers", "layers"):
        for i, name in enumerate(("q_a_norm", "kv_a_norm", "ln1", "ln2")):
            a = params[stack][name]
            params[stack][name] = 1.0 + 0.1 * jax.random.normal(
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
                min_prefill_bucket=4, overlap=False)
    base.update(kw)
    return serving.InferenceEngine(params, cfg, serving.EngineConfig(**base))


class _LogitTap:
    """Every logit row an engine computes for a request (as
    ``tests/test_window_layers.py``'s): the admission's or last chunk's
    logits, and each decode tick's from a second, non-donating
    ``decode_step_paged`` on the tick's own inputs."""

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
            return T.decode_step_paged(params, tokens, pool, table, cfg,
                                       active,
                                       kernel=engine._paged_kernel)[0]

        def tap_tick(params, tokens, active, table, pool, *samp):
            logits = np.asarray(peek(params, tokens, active, table, pool))
            for s in np.nonzero(np.asarray(active))[0]:
                fut = engine._states[s].request.future
                self.rows.setdefault(id(fut), []).append(logits[s])
            return tick(params, tokens, active, table, pool, *samp)

        engine._first_tokens, engine._tick_fn = tap_first, tap_tick


def _serve_and_compare(params, cfg, prompts, new=10, dims=DIMS, **kw):
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
        ref = np.asarray(R.latent_forward(params, jnp.asarray(p + toks),
                                          dims))
        rows = tap.rows[id(f)]
        assert len(rows) == len(toks)
        for j in range(len(toks)):
            worst = max(worst, float(np.abs(
                rows[j] - ref[len(p) - 1 + j]).max()))
    return engine, worst


class TestLogitsAgainstThePlainReference:
    @pytest.mark.parametrize("impl", ["flash", "reference"])
    def test_forward(self, model, highest, impl):
        params, cfg = model
        cfg = dataclasses.replace(cfg, attention_impl=impl)
        toks = jnp.asarray(_prompts([40, 40], 3))
        got = np.asarray(T.forward(params, toks, cfg))
        for b in range(2):
            want = np.asarray(R.latent_forward(params, toks[b], DIMS))
            assert np.abs(got[b] - want).max() < LOGIT_TOL

    @pytest.mark.parametrize("kernel", [None, True],
                             ids=["unfused", "hvd_mla_decode"])
    def test_whole_prefill_then_paged_decode(self, model, highest, kernel):
        """Prompts under the chunk budget: one flash prefill lands the
        latent rows, every later token reads them back absorbed."""
        params, cfg = model
        eng, worst = _serve_and_compare(
            params, cfg, _prompts([5, 8, 7]), paged_kernel=kernel)
        assert worst < LOGIT_TOL
        assert eng.stats()["paged_kernel_engaged"] is bool(kernel)

    @pytest.mark.parametrize("lens", [[19, 30], [9, 33, 21]],
                             ids=["mid_page", "three_slots"])
    def test_chunked_prefill_then_decode(self, model, highest, lens):
        """Chunks of 8 that do not divide the prompts, pages of 4 and a
        page-size-agnostic prefix: a chunk's landed prefix ends mid-page
        of the power-of-two gather, is expanded in blocks and attended
        with the chunk's own block by one online softmax."""
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts(lens, 5))
        assert worst < LOGIT_TOL

    def test_prefix_blocks_smaller_than_the_prefix(self, model, highest,
                                                   monkeypatch):
        """The landed prefix in SEVERAL expansion blocks (8 rows each),
        the last of them cut by the prefix length."""
        monkeypatch.setattr(T, "_MLA_PREFIX_BLOCK", 8)
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([45], 7),
                                      page_size=2)
        assert worst < LOGIT_TOL

    def test_decode_step_on_a_contiguous_cache(self, model, highest):
        """``greedy_decode``'s own path (the engine tests' oracle) is
        held to the reference too."""
        params, cfg = model
        p = _prompts([12], 9)[0]
        toks = np.asarray(T.greedy_decode(params, jnp.asarray([p]), 6,
                                          cfg))[0].tolist()
        ref = np.asarray(R.latent_forward(params, jnp.asarray(p + toks),
                                          DIMS))
        assert [int(np.argmax(ref[len(p) - 1 + j]))
                for j in range(6)] == toks


class TestTheToleranceIsTight:
    def test_a_bf16_cache_fails(self, model, highest):
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5),
                                      kv_dtype="bf16")
        assert worst > 10 * LOGIT_TOL

    def test_a_cache_read_as_fp8_fails(self, model, highest, monkeypatch):
        real = PA.mla_decode_reference

        def fp8(q, pool, *a, **kw):
            return real(q, pool.astype(jnp.float8_e4m3fn).astype(
                pool.dtype), *a, **kw)

        monkeypatch.setattr(PA, "mla_decode_reference", fp8)
        params, cfg = model
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5))
        assert worst > 100 * LOGIT_TOL

    def test_a_scale_without_m_squared_fails(self, model, highest,
                                             monkeypatch):
        monkeypatch.setattr(
            T.TransformerConfig, "mla_scale",
            property(lambda self: (self.qk_nope_head_dim
                                   + self.qk_rope_head_dim) ** -0.5))
        params, cfg = model
        assert cfg.mla_scale == pytest.approx(24 ** -0.5)
        _, worst = _serve_and_compare(params, cfg, _prompts([19, 30], 5))
        assert worst > 100 * LOGIT_TOL

    def test_the_scale_is_the_published_one(self):
        """A.X-K1's: 192^-0.5 x (0.1 x ln 32 + 1)^2 = 0.130861."""
        cfg = _cfg(qk_nope_head_dim=128, qk_rope_head_dim=64,
                   rope_yarn=(32.0, 4096.0, 32.0, 1.0, 1.0, 1.0))
        assert cfg.mla_scale == pytest.approx(0.130861, rel=1e-5)
        assert (_cfg().latent_width, _cfg().latent_row) == (40, 128)


class TestAbsorbedIsExpanded:
    def test_on_the_same_inputs(self, model, highest):
        """Scores and outputs of the two forms of one attention, from
        the same projections: ``[q_nope W_k^T | q_rope] . [ckv | k_rope]``
        and ``(sum p ckv) W_v`` against expanded heads."""
        params, cfg = model
        p = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 11, cfg.d_model))
        q_nope, q_rope = T._mla_q(x, p, cfg)
        lat = T._mla_kv(x, p, cfg)
        assert lat.shape[-1] == cfg.latent_row
        assert not np.asarray(lat[..., cfg.latent_width:]).any()
        k, v = T._mla_expand(lat, p, cfg)
        want = A.reference_attention(T._mla_heads(q_nope, q_rope), k, v,
                                     causal=True, sm_scale=cfg.mla_scale)
        want = T._mla_out(jnp.moveaxis(want, 1, 2), p, cfg)
        qa = jnp.moveaxis(T._mla_absorb_q(q_nope, q_rope, p, cfg), 2, 1)
        got = A.reference_attention(
            qa, lat[:, None], lat[:, None, :, :cfg.kv_lora_rank],
            causal=True, sm_scale=cfg.mla_scale)
        got = T._mla_out(jnp.moveaxis(got, 1, 2), p, cfg, absorbed=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("S,T_,bq,bk", [(32, 32, 16, 16),
                                            (16, 48, 16, 16)])
    def test_flash_forward_with_two_head_sizes(self, S, T_, bq, bk,
                                               highest):
        """q/k 24 wide, v 16 wide: the kernel against the O(S^2) form;
        its backward is refused by name."""
        ks = jax.random.split(jax.random.PRNGKey(S + T_), 3)
        q = jax.random.normal(ks[0], (1, 2, S, 24))
        k = jax.random.normal(ks[1], (1, 2, T_, 24))
        v = jax.random.normal(ks[2], (1, 2, T_, 16))
        causal = S == T_
        o, lse = A._flash_fwd(q, k, v, 0 if causal else None, 0.3, bq, bk)
        want, lse_w = A._reference_attention_lse(
            q, k, v, 0 if causal else None, 0.3)
        assert o.shape == (1, 2, S, 16)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_w),
                                   atol=2e-5)
        if causal:
            with pytest.raises(NotImplementedError, match="one head size"):
                jax.grad(lambda q: A.flash_attention(
                    q, k, v, True, 0.3, bq, bk).sum())(q)


# --- the kernel against its unfused twin --------------------------------------

_WALKS = {
    # partial last page, a slot at exactly table capacity, an inactive
    # slot, and a REPEATED page id (two tables on one page)
    "edge_tables": dict(S=4, MP=3, block=None, limits=[24, 5, 0, 11],
                        share=True),
    # block = 2 pages = 16 tokens: limit 0, 1, one block, one block + 1,
    # and capacity (4 blocks: both buffers are used twice)
    "block_bounds": dict(S=5, MP=8, block=2, limits=[0, 1, 16, 17, 64]),
    # an odd table: the last block is cut by the table's end
    "wrapping_buffers": dict(S=3, MP=7, block=2, limits=[56, 41, 33]),
    # every page that holds no live position is inf
    "poisoned_dead_pages": dict(S=4, MP=7, block=2, limits=[0, 3, 24, 56],
                                poison=True),
}


class TestMlaDecodeKernel:
    @pytest.mark.parametrize("walk", list(_WALKS))
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    def test_kernel_matches_its_unfused_twin(self, dtype, walk,
                                             monkeypatch):
        """``hvd_mla_decode`` (interpreted) == gather + masked softmax,
        over ``tests/test_paged.py``'s edge tables: rows 40 wide (32
        latent + 8 rope) stored in 128 lanes, 4 heads, the value the
        row's first 32 lanes."""
        case = dict(_WALKS[walk])
        block, poison = case.pop("block"), case.pop("poison", False)
        S, MP, limits = case["S"], case["MP"], case["limits"]
        ps, H, W, Vd = 8, 4, 128, 32
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(S, H, W), jnp.float32).at[..., 40:].set(0)
        pool = jnp.asarray(rng.randn(S * MP + 1, 1, ps, W), dtype)
        pool = pool.at[..., 40:].set(0)
        table = (1 + rng.permutation(S * MP)).reshape(S, MP).astype(np.int32)
        if case.get("share"):
            table[1] = table[0]
        if block is not None:
            monkeypatch.setattr(PA, "_LATENT_BLOCK_BYTES",
                                block * ps * W * max(pool.dtype.itemsize, 2))
            assert PA.block_pages(ps, 1, W, pool.dtype, MP, True) == block
        limit = jnp.asarray(limits, jnp.int32)
        o_r, l_r = PA.mla_decode_reference(q, pool, jnp.asarray(table),
                                           limit, v_dim=Vd, sm_scale=0.21)
        if poison:
            dead = np.ones(pool.shape[0], bool)
            for row, lim in zip(table, limits):
                dead[row[:-(-lim // ps)]] = False
            pool = jnp.where(dead[:, None, None, None], jnp.inf, pool)
        o_k, l_k = PA.mla_decode(q, pool, jnp.asarray(table), limit,
                                 v_dim=Vd, sm_scale=0.21)
        assert o_k.shape == (S, H, Vd)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   atol=tol, rtol=tol)
        live = np.asarray(limit) > 0
        np.testing.assert_allclose(np.asarray(l_k)[live],
                                   np.asarray(l_r)[live], atol=tol,
                                   rtol=tol)
        assert not np.asarray(o_k)[~live].any()
        assert (np.asarray(l_k)[~live] <= PA.NEG_INF / 2).all()

    def test_a_layer_of_the_stack_and_the_kernels_name(self):
        """The stacked pool read at ``[layer, table]``, in place; the
        call's name is the one the trace's readers look for; and the
        published layout's block."""
        rng = np.random.RandomState(0)
        pool = jnp.asarray(rng.randn(3, 9, 1, 4, 128), jnp.float32)
        q = jnp.asarray(rng.randn(2, 4, 128), jnp.float32)
        table = jnp.asarray([[3, 5], [8, 1]], jnp.int32)
        limit = jnp.asarray([7, 2], jnp.int32)
        kw = dict(v_dim=32, sm_scale=0.2)
        want, _ = PA.mla_decode_reference(q, pool[2], table, limit, **kw)
        for fn in (PA.mla_decode, PA.mla_decode_reference):
            got, _ = fn(q, pool, table, limit, layer=jnp.int32(2), **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5)
        text = str(jax.make_jaxpr(lambda *a: PA.mla_decode(*a, **kw))(
            q, pool[0], table, limit))
        assert PA.MLA_KERNEL_NAME == "hvd_mla_decode" in text
        assert PA.block_pages(16, 1, 640, jnp.bfloat16, 1152, True) == 48
        assert PA.kernel_supported(jnp.bfloat16, 16, 640, 512)
        assert not PA.kernel_supported(jnp.bfloat16, 16, 640, 500)
        assert not PA.kernel_supported(jnp.bfloat16, 16, 576)


# --- the router ---------------------------------------------------------------


def _route_loop(logits, k, n_group, topk_group, scale):
    """The published selection as a loop: sigmoid; a group's score the
    sum of its two largest; the best groups stay (ties to the lower
    index); among their experts the k largest; weights normalised over
    ALL k and scaled."""
    out_e, out_g = [], []
    for row in np.asarray(logits, np.float64):
        sc = 1.0 / (1.0 + np.exp(-row))
        per = len(sc) // n_group
        gs = [np.sort(sc[g * per:(g + 1) * per])[-2:].sum()
              for g in range(n_group)]
        keep = sorted(range(n_group), key=lambda g: (-gs[g], g))[:topk_group]
        cand = [e for e in range(len(sc)) if e // per in keep]
        sel = sorted(cand, key=lambda e: (-sc[e], e))[:k]
        w = sc[sel] / sc[sel].sum() * scale
        out_e.append(sel)
        out_g.append(w)
    return np.asarray(out_e), np.asarray(out_g)


class TestRouter:
    def test_sigmoid_groups_normalisation_and_scale(self, highest):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(64, 12), jnp.float32)
        router = jnp.asarray(rng.randn(12, 24), jnp.float32)
        e, g = moe.route_topk(x, router, 4, True, score="sigmoid",
                              n_group=4, topk_group=2, scale=2.5)
        want_e, want_g = _route_loop(np.asarray(x) @ np.asarray(router), 4,
                                     4, 2, 2.5)
        np.testing.assert_array_equal(np.asarray(e), want_e)
        np.testing.assert_allclose(np.asarray(g), want_g, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(g).sum(-1), 2.5, rtol=1e-5)
        # the chosen experts lie in two groups of six at most
        assert all(len({i // 6 for i in row}) <= 2 for row in want_e)

    def test_ties_go_to_the_lower_index(self):
        """Equal logits everywhere: groups 0 and 1 stay, experts 0..3."""
        x = jnp.ones((3, 2), jnp.float32)
        e, g = moe.route_topk(x, jnp.zeros((2, 16)), 4, True,
                              score="sigmoid", n_group=4, topk_group=2,
                              scale=2.5)
        np.testing.assert_array_equal(np.asarray(e), [[0, 1, 2, 3]] * 3)
        np.testing.assert_allclose(np.asarray(g), 2.5 / 4)

    def test_softmax_top_k_is_the_seed_routing_bit_for_bit(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(40, 12), jnp.float32)
        router = jnp.asarray(rng.randn(12, 8), jnp.float32)
        probs = jax.nn.softmax(x @ router, axis=-1)
        gate, e = jax.lax.top_k(probs, 2)
        got_e, got_g = moe.route_topk(x, router, 2, True)
        np.testing.assert_array_equal(np.asarray(got_e), np.asarray(e))
        np.testing.assert_array_equal(
            np.asarray(got_g),
            np.asarray(gate / jnp.sum(gate, axis=-1, keepdims=True)))

    def test_bad_routing_is_refused(self):
        x, r = jnp.ones((2, 4)), jnp.ones((4, 6))
        with pytest.raises(ValueError, match="unknown router score"):
            moe.route_topk(x, r, 2, score="tanh")
        with pytest.raises(ValueError, match="do not split into 4 groups"):
            moe.route_topk(x, r, 2, n_group=4, topk_group=2)
        with pytest.raises(ValueError, match="unknown moe_score"):
            _cfg(moe_score="tanh")


# --- a chip's share of the experts --------------------------------------------


class TestTheShareTiesToTheModel:
    def test_four_shares_and_the_shared_expert_once_are_the_layer(
            self, model, highest):
        """16 experts in 4 shares of 4: the four chips' routed parts,
        plus what every chip computes alike (the shared expert) ONCE,
        add up to the uncut reference's whole layer."""
        _, cfg = model
        whole = T.init_params(jax.random.PRNGKey(3), dataclasses.replace(
            cfg, n_experts_held=0, expert_offset=0))
        p = jax.tree_util.tree_map(lambda a: a[0], whole["layers"])
        x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
        want = np.stack([np.asarray(R.latent_experts(
            xb, p, dict(DIMS, expert_offset=0))) for xb in x])
        shared = np.asarray(T._dense_mlp(
            x, {k: p["ws_" + k[2:]] for k in T._EXPERT_LEAVES}, cfg))
        total = np.zeros_like(want)
        rows = 0
        for off in (0, 4, 8, 12):
            scfg = dataclasses.replace(cfg, expert_offset=off)
            part = {**p, **{k: p[k][off:off + 4] for k in T._EXPERT_LEAVES}}
            y, counts = T._moe_mlp(x, part, scfg, return_counts=True)
            # the shared expert is in every chip's output: take it ONCE
            total += np.asarray(y) - shared
            rows += int(counts.sum())
            ref = np.stack([np.asarray(R.latent_experts(
                xb, part, dict(DIMS, expert_offset=off))) for xb in x])
            assert np.abs(np.asarray(y) - ref).max() < 2e-5
        assert rows == 2 * 9 * 4          # every pick is held somewhere
        assert np.abs(total + shared - want).max() < 2e-5

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("k", [1, 2])
    def test_all_held_is_the_seed_dispatch_bit_for_bit(self, k, dtype):
        """With every expert held and softmax scores the dispatch is the
        one written before a share was: the same function of the same
        arguments, to the bit — and a held range that happens to cover
        every expert gives those bits too."""
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(3, 7, 16), dtype)
        router = jnp.asarray(rng.randn(16, 8), jnp.float32)
        wg, wu = (jnp.asarray(rng.randn(8, 16, 12), dtype) for _ in "ab")
        wd = jnp.asarray(rng.randn(8, 12, 16), dtype)
        mask = jnp.asarray(rng.rand(21) > 0.3)

        def seed(x):  # the body of dropless_moe at the parent commit
            xt = x.reshape(-1, 16)
            e_top, gate = moe.route_topk(xt, router, k, k > 1)
            e_rows = jnp.where(jnp.repeat(mask, k), e_top.reshape(-1), 8)
            order = jnp.argsort(e_rows, stable=True)
            xs, es = xt[order if k == 1 else order // k], e_rows[order]
            eye = jnp.arange(8, dtype=jnp.int32)
            counts = (jnp.searchsorted(es, eye, side="right")
                      - jnp.searchsorted(es, eye)).astype(jnp.int32)
            mm = lambda r, w: jax.lax.ragged_dot(r, w, counts)  # noqa: E731
            y_s = mm(jax.nn.silu(mm(xs, wg)) * mm(xs, wu), wd)
            inv = jnp.argsort(order)
            y = (y_s[inv] * gate.astype(dtype) if k == 1 else jnp.sum(
                (y_s[inv].astype(jnp.float32) * gate.reshape(-1, 1)
                 ).reshape(21, k, 16), axis=1).astype(dtype))
            return jnp.where(mask[:, None], y, 0).reshape(x.shape)

        kw = dict(k=k, norm_topk=k > 1, token_mask=mask)
        got = moe.dropless_moe(x, router, wg, wu, wd, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(seed(x)))
        held = moe.dropless_moe(x, router, wg, wu, wd, held_offset=0, **kw)
        np.testing.assert_array_equal(np.asarray(held), np.asarray(got))

    def test_a_model_that_holds_every_expert_takes_no_share_path(self):
        cfg = _cfg(n_experts_held=16, expert_offset=0)
        assert cfg.held_offset is None and cfg.experts_held == 16
        assert _cfg(n_experts_held=0).held_offset is None
        assert _cfg().held_offset == 4
        with pytest.raises(ValueError, match="not among the router's 16"):
            _cfg(n_experts_held=8, expert_offset=12)
        with pytest.raises(ValueError, match="needs held_offset"):
            moe.dropless_moe(jnp.ones((2, 4)), jnp.ones((4, 8)),
                             jnp.ones((2, 4, 3)), jnp.ones((2, 4, 3)),
                             jnp.ones((2, 3, 4)), k=2)

    def test_the_engine_counts_rows_here_and_routed_away(self, model):
        params, cfg = model
        eng = _engine(params, cfg)
        futs = [eng.submit(p, max_new_tokens=6) for p in _prompts([5, 9])]
        while not all(f.done() for f in futs):
            eng.step()
        s = eng.stats()
        here, away = s["moe_rows_total"], s["moe_rows_routed_away_total"]
        # every active slot picks 4 experts in each of the 2 expert
        # layers; 4 of the 16 are held
        assert here > 0 and away > here
        assert (here + away) % (4 * 2) == 0
        assert s["kv_latent_bytes_per_token"] == 3 * 128 * 4 \
            == s["kv_bytes_per_token"] == eng.slots.bytes_per_token


# --- the stacks, the pool and the engine --------------------------------------


class TestDenseThenExpertLayers:
    def test_the_pools_layer_index_runs_through_both_stacks(self, model,
                                                            highest):
        """One tick writes position 0 of every layer's rows, the dense
        layer's at pool layer 0 and the expert layers' at 1 and 2, and
        each is that layer's own ``_mla_kv`` of its own input."""
        params, cfg = model
        pool = C.init_page_pool(cfg, 2, 5, 4)
        assert set(pool) == {"k", "pos"}
        assert pool["k"].shape == (3, 5, 1, 4, 128)
        table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
        tok = jnp.asarray([7, 9], jnp.int32)
        active = jnp.asarray([True, True])
        _, out = T.decode_step_paged(params, tok, pool, table, cfg, active)
        rows = np.asarray(out["k"])[:, [1, 3], 0, 0]       # (L, S, 128)
        assert all(np.abs(rows[l]).max() > 0 for l in range(3))
        assert not np.asarray(out["k"])[:, [2, 4]].any()   # unwritten pages
        x = T._embed(params, tok, cfg)[:, None]
        p0 = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
        want = T._mla_kv(T._attn_norm(x, p0, cfg), p0, cfg,
                         positions=jnp.zeros((2, 1), jnp.int32))
        np.testing.assert_allclose(rows[0], np.asarray(want)[:, 0],
                                   atol=1e-6)
        assert np.abs(rows[1] - rows[0]).max() > 1e-3
        np.testing.assert_array_equal(np.asarray(out["pos"]), [1, 1])

    def test_params_and_specs_have_both_stacks(self, model):
        params, cfg = model
        specs = T.param_specs(cfg)
        assert jax.tree_util.tree_structure(
            jax.tree_util.tree_map(lambda a: 0, params)
        ) == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda s: 0, specs, is_leaf=lambda s: isinstance(
                s, jax.sharding.PartitionSpec)))
        assert params["dense_layers"]["w_gate"].shape == (1, 48, 96)
        assert params["layers"]["w_gate"].shape == (2, 4, 48, 32)
        assert params["layers"]["router"].shape == (2, 48, 16)
        assert "router" not in params["dense_layers"]

    def test_a_uniform_models_parameters_are_the_seeds(self):
        """The keys of a model without the new fields did not move."""
        cfg = T.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                  n_layers=2, d_ff=64)
        p = T.init_params(jax.random.PRNGKey(0), cfg)
        assert float(p["layers"]["wq"].sum()) == pytest.approx(
            -10.865497589111328, rel=1e-6)
        assert float(p["embed"].sum()) == pytest.approx(56.2249755859375,
                                                        rel=1e-6)


class TestTheEngineServesIt:
    @pytest.mark.parametrize("kw", [{}, {"overlap": True},
                                    {"paged_kernel": True}],
                             ids=["sync", "overlap", "kernel"])
    def test_tokens_are_greedy_decodes(self, model, kw):
        """Admit (whole and chunked), tick, retire, a slot reused."""
        params, cfg = model
        eng = _engine(params, cfg, **kw)
        prompts = _prompts([5, 19, 30, 7, 12])
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

    def test_preempt_and_resume_on_a_pool_too_small(self, model):
        """Three requests that cannot all grow in 14 pages: the
        youngest is preempted, resumed by re-prefilling prompt +
        emitted, and every stream is still greedy_decode's."""
        params, cfg = model
        eng = _engine(params, cfg, n_pages=14)
        prompts = _prompts([13, 14, 15], 2)
        futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        while not all(f.done() for f in futs):
            eng.step()
        for p, f in zip(prompts, futs):
            want = np.asarray(T.greedy_decode(
                params, jnp.asarray([p]), 12, cfg))[0].tolist()
            assert f.result() == want
        s = eng.stats()
        assert s["preemptions"] >= 1
        assert s["kv_pages_in_use"] == 0

    @pytest.mark.parametrize("kernel", [False, True],
                             ids=["unfused", "kernel"])
    def test_the_tick_writes_the_pool_in_place(self, model, kernel):
        """``tests/test_paged.py``'s structure test on the latent pool:
        the stack is not among either scan's xs or ys, no layer of it is
        cut out, every scatter indexes ``(layer, page)`` — through the
        dense stack's scan and the expert stack's alike."""
        from conftest import pool_structure_faults

        params, cfg = model
        pool = C.init_page_pool(cfg, 3, 17, 4)
        table = jnp.zeros((3, 8), jnp.int32)
        jaxpr = jax.make_jaxpr(lambda pl: T.decode_step_paged(
            params, jnp.zeros((3,), jnp.int32), pl, table, cfg,
            jnp.ones((3,), bool), kernel=kernel,
            return_moe_load=True))(pool)
        assert pool_structure_faults(jaxpr, {pool["k"].shape}) == []


class TestRefusals:
    @pytest.mark.parametrize("kw,why", [
        ({"tp": 2}, "tp > 1"),
        ({"speculative": True}, "speculative=True"),
        ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ])
    def test_engine_modes_refuse_latent_attention(self, model, kw, why):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError) as e:
            _engine(params, cfg, **kw)
        assert why in str(e.value) and "latent attention" in str(e.value)

    def test_bodies_off_the_normal_path_refuse(self, model):
        params, cfg = model
        toks = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="loss_fn .training"):
            T.loss_fn(params, {"tokens": toks, "targets": toks}, cfg)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="expert_load"):
            T.expert_load(params, toks, cfg)
        pool = C.init_page_pool(cfg, 1, 4, 4)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="decode_verify_paged"):
            T.decode_verify_paged(
                params, jnp.zeros((1, 3), jnp.int32), pool,
                jnp.zeros((1, 2), jnp.int32), cfg, jnp.ones((1,), bool))
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="int8 pages"):
            C.init_page_pool(cfg, 1, 4, 4, "int8")
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="switch dispatch"):
            T.forward(params, toks,
                      dataclasses.replace(cfg, moe_impl="switch"))
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="'flash' or 'reference'"):
            T.forward(params, toks,
                      dataclasses.replace(cfg, attention_impl="ring"))

    def test_each_mechanism_alone_is_refused_where_it_is_not_written(self):
        """Leading dense layers, or a share of the experts, without
        latent attention: the same refusals, each by its own name."""
        dense = T.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                    n_layers=2, d_ff=32, n_dense_layers=1,
                                    n_experts=4, d_expert=8,
                                    moe_impl="dropless")
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="leading dense layers"):
            T._require_no_latent(dense, "the pipeline schedules")
        share = dataclasses.replace(dense, n_dense_layers=0,
                                    n_experts_held=2)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="share of the experts"):
            T._require_no_latent(share, "decode_verify_paged")

    def test_bad_configurations_are_refused_at_construction(self):
        with pytest.raises(ValueError, match="five sizes together"):
            _cfg(v_head_dim=0)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="latent attention together with window"):
            _cfg(n_layers=2, n_dense_layers=0, window=8,
                 layer_pattern=("sliding", "full"))
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="leading dense layers together with"):
            T.TransformerConfig(n_layers=4, n_dense_layers=2, window=8,
                                layer_pattern=("sliding", "full"))
        with pytest.raises(ValueError, match="n_dense_layers=4 of"):
            _cfg(n_dense_layers=4)
