"""Gated short-convolution layers between attention layers, served: a
third layer kind whose per-request state (the last two gated inputs of
every conv layer) lives per SLOT beside the page pool, a leading dense
stack together with a pattern, attention heads of 64 stored two to a
128-lane row, a sigmoid router that chooses under a bias, a tied head.

The program's LOGITS are held to ``horovod_tpu.models.plain_reference``
(``conv_forward``: straightforward float32 ``jax.numpy``, no state — its
convolution reads the whole sequence — nothing of the program in it) at
a small size on seeded weights: hidden 256, 4 query / 2 KV heads of 64,
the ten layers (conv, conv | full, conv, conv, conv, full, conv, conv,
conv) with the first two dense (width 96) and eight experts of width 48
with 2 a token in the rest, kernel of 3 taps.

TOLERANCE: ``LOGIT_TOL`` = 5e-4 absolute on logits of magnitude ~16 (a
tied head reads the embedding, std 1, so the last token's own logit is
~hidden/sqrt(hidden)).  Both sides compute in float32 with float32
accumulation; what differs is the ORDER of sums — the flash kernel's
online softmax by blocks, the paged kernel's over two heads' rows at
once, the chunked prefill's prefix + suffix, the experts as grouped
products — which moves a logit by a few 1e-5 (3e-5 observed).  The same
comparison with the program in bfloat16 misses by ~1e-1
(``test_bf16_program_fails_the_tolerance``), and with a request's state
lost at a boundary by more than 1 (``test_a_lost_state_fails``).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import serving
from horovod_tpu.models import plain_reference as R
from horovod_tpu.models import transformer as T
from horovod_tpu.ops import attention as A
from horovod_tpu.ops import moe
from horovod_tpu.serving.cache import PagedSlotCache

from test_paged import TestFusedPagedKernel as _Walks
from test_window_layers import _LogitTap

LOGIT_TOL = 5e-4
V = 97
KINDS = ("conv", "conv", "full", "conv", "conv", "conv", "full", "conv",
         "conv", "conv")
DIMS = dict(
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
    norm_eps=1e-5, num_dense_layers=2, num_experts=8,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1.0,
    rope_parameters={"rope_theta": 1e6, "rope_type": "default"},
    layer_types=["conv" if k == "conv" else "full_attention"
                 for k in KINDS])


def _cfg(**over):
    kw = dict(
        vocab_size=V, d_model=256, n_heads=4, n_kv_heads=2, d_head=64,
        n_layers=10, n_dense_layers=2, d_ff=96, d_expert=48, n_experts=8,
        n_experts_per_tok=2, norm_topk_prob=True, norm_topk_eps=1e-6,
        moe_impl="dropless", moe_score="sigmoid", moe_score_bias=True,
        qk_norm=True, norm_eps=1e-5,
        layer_pattern=("conv", "conv", "full", "conv"), conv_kernel=3,
        tie_embeddings=True, kv_lane_dense=True, rope_theta=1e6,
        max_seq=96, dtype=jnp.float32, attention_impl="flash")
    kw.update(over)
    return T.TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    assert cfg.layer_kinds == KINDS
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for stack in ("dense_layers", "layers"):
        for i, name in enumerate(("ln1", "ln2", "q_norm", "k_norm")):
            if name in params[stack]:
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
    base = dict(n_slots=3, max_len=96, paged=True, page_size=8,
                prefill_chunk_tokens=8, max_prefills_per_tick=2,
                min_prefill_bucket=8, overlap=False)
    base.update(kw)
    return serving.InferenceEngine(params, cfg, serving.EngineConfig(**base))


def _run(engine, futs):
    while not all(f.done() for f in futs):
        engine.step()


def _serve_and_compare(params, cfg, prompts, new=9, ref_params=None,
                       **kw):
    """Serve ``prompts``: ``(engine, worst)``, the largest |program
    logit - reference logit| over every row that produced a token."""
    engine = _engine(params, cfg, **kw)
    tap = _LogitTap(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    _run(engine, futs)
    worst = 0.0
    for p, f in zip(prompts, futs):
        toks = f.result()
        ref = np.asarray(R.conv_forward(
            ref_params or params, jnp.asarray(p + toks), DIMS))
        # (an overlapped engine dispatches one tick past the last token)
        rows = np.stack(tap.rows[id(f)])[:len(toks)]
        assert rows.shape[0] == len(toks)
        want = ref[len(p) - 1:len(p) - 1 + len(toks)]
        worst = max(worst, float(np.abs(rows - want).max()))
    return engine, worst


class TestLogitsAgainstThePlainReference:
    # chunk 0: the whole prompt in one prefill; 7, 8, 9: chunked ingest
    # with the chunk boundary at three consecutive offsets (a state
    # handed over mid-page, at a page's end, past it), then decoding
    # through the cache and the state
    @pytest.mark.parametrize("kernel", [False, True])
    @pytest.mark.parametrize("chunk", [0, 7, 8, 9])
    def test_prompt_chunks_and_ticks(self, model, highest, chunk, kernel):
        params, cfg = model
        engine, worst = _serve_and_compare(
            params, cfg, _prompts((29, 5, 18)), prefill_chunk_tokens=chunk,
            paged_kernel=kernel)
        assert worst < LOGIT_TOL, worst
        st = engine.stats()
        assert st["paged_kernel_engaged"] is kernel
        if chunk:
            assert st["prefill_calls"] >= 4 + 3

    def test_the_overlapped_engine_serves_the_same(self, model, highest):
        params, cfg = model
        _, worst = _serve_and_compare(
            params, cfg, _prompts((29, 5, 18, 11), seed=3), overlap=True,
            n_slots=2)
        assert worst < LOGIT_TOL, worst

    def test_bf16_program_fails_the_tolerance(self, model, highest):
        """The tolerance is tight enough for the precision: the program
        in bfloat16 against the float32 reference misses it by far."""
        params, cfg = model
        import dataclasses
        _, worst = _serve_and_compare(
            params, dataclasses.replace(cfg, dtype=jnp.bfloat16),
            _prompts((29,)), new=4)
        assert worst > 20 * LOGIT_TOL, worst

    def test_a_lost_state_fails(self, model, highest):
        """... and for the mechanism: the reference with every conv
        layer's two past taps zeroed — what a state lost at each chunk
        and tick boundary serves — is far outside it."""
        params, cfg = model
        p = _prompts((29,))[0]
        a = np.asarray(R.conv_forward(params, jnp.asarray(p), DIMS))
        b = np.asarray(R.conv_forward(params, jnp.asarray(p), DIMS,
                                      zero_taps=True))
        assert np.abs(a - b).max() > 1000 * LOGIT_TOL


class TestTheStateUnderTheCacheManager:
    def test_pool_holds_the_state_beside_dense_pages(self, model):
        """K and V of 64-wide heads two to a 128-lane row (no padding:
        the bytes a token costs are the arrays' own), and the state
        ``(L_conv, S, taps, D)`` under the same manager."""
        _, cfg = model
        slots = PagedSlotCache(cfg, 3, 96, page_size=8,
                               n_layers=cfg.kind_count("full"))
        k = slots.cache["k"]
        assert k.shape == (2, slots.n_pages + 1, 1, 8, 128)
        per_token = (k.nbytes + slots.cache["v"].nbytes) // (
            (slots.n_pages + 1) * 8)
        assert slots.bytes_per_token == per_token == 2 * 2 * 2 * 64 * 4
        assert slots.cache["conv"].shape == (8, 3, 2, 256)
        assert slots.conv_state_bytes_per_slot == 8 * 2 * 256 * 4

    def test_a_granted_slots_state_is_zero(self, model):
        _, cfg = model
        slots = PagedSlotCache(cfg, 3, 96, page_size=8,
                               n_layers=cfg.kind_count("full"))
        a = slots.alloc()
        slots.cache = {**slots.cache,
                       "conv": jnp.ones_like(slots.cache["conv"])}
        slots.free(a)
        b, c = slots.alloc(), slots.alloc()
        assert (a, b, c) == (0, 0, 1)
        conv = np.asarray(slots.cache["conv"])
        assert not conv[:, :2].any() and conv[:, 2].all()

    def test_a_freed_slots_next_request_starts_from_zeros(self, model,
                                                          highest):
        """One slot, three requests one after another through it (whole
        prompt, chunked, whole): each one's logits are the reference's
        for it ALONE, whatever the tenant before left."""
        params, cfg = model
        engine, worst = _serve_and_compare(
            params, cfg, _prompts((7, 29, 6), seed=5), n_slots=1)
        assert worst < LOGIT_TOL, worst
        assert engine.stats()["conv_state_slots_live"] == 0

    def test_the_tick_leaves_an_ingesting_slots_state_alone(self, model,
                                                            highest):
        """A row outside the decode mask (idle, or between two chunks
        of its prompt) keeps its state through a tick."""
        params, cfg = model
        pool = PagedSlotCache(cfg, 3, 96, page_size=8,
                              n_layers=cfg.kind_count("full")).cache
        pool = {**pool, "conv": jnp.full_like(pool["conv"], 0.5)}
        active = jnp.asarray([True, False, True])
        table = jnp.zeros((3, 12), jnp.int32).at[:, 0].set(
            jnp.asarray([1, 2, 3]))
        _, out = T.decode_step_paged(
            T.lay_out_projections(params)[0], jnp.asarray([3, 4, 5]), pool,
            table, cfg, active)
        conv = np.asarray(out["conv"])
        assert (conv[:, 1] == 0.5).all()
        assert (conv[:, 0, 0] == 0.5).all() and (conv[:, 0, 1] != 0.5).any()

    def test_stats(self, model):
        params, cfg = model
        engine = _engine(params, cfg)
        fut = engine.submit(_prompts((12,))[0], max_new_tokens=3)
        engine.step()
        st = engine.stats()
        assert st["conv_state_bytes_per_slot"] == 8 * 2 * 256 * 4
        assert st["conv_state_slots_live"] == 1
        assert st["kv_bytes_per_token"] == 2 * 2 * 2 * 64 * 4
        _run(engine, [fut])
        st = engine.stats()
        assert st["moe_rows_total"] > 0 and st["paged_live_tokens_total"] > 0

    def test_a_preempted_request_resumes_to_the_same_logits(self, model,
                                                            highest):
        """Pool exhaustion preempts the younger request mid-decode; it
        is re-prefilled (prompt + emitted) into a ZEROED state and every
        logit row of both lives is the reference's."""
        params, cfg = model
        engine, worst = _serve_and_compare(
            params, cfg, _prompts((8, 8), seed=7), new=24, n_slots=2,
            n_pages=6, max_queue_depth=4)
        assert worst < LOGIT_TOL, worst
        assert engine.stats()["preemptions"] >= 1
        assert engine.slots.active_count == 0


class TestRefusedByName:
    def test_register_prefix(self, model):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError, match="conv layer"):
            _engine(params, cfg).register_prefix([1, 2, 3, 4])

    @pytest.mark.parametrize("kw,why", [
        (dict(speculative=True), "speculative=True"),
        (dict(tp=2), "tp > 1"),
        (dict(kv_dtype="int8"), "int8"),
    ])
    def test_engine_modes(self, model, kw, why):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="conv layers.*" + why):
            _engine(params, cfg, **kw)

    def test_packed_heads_alone_refuse_speculation(self, model):
        cfg = T.TransformerConfig(vocab_size=V, d_model=256, n_heads=4,
                                  n_kv_heads=2, d_head=64, n_layers=2,
                                  d_ff=64, kv_lane_dense=True,
                                  dtype=jnp.float32)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="kv_lane_dense.*speculative"):
            _engine(params, cfg, speculative=True)

    @pytest.mark.parametrize("what", ["forward", "decode_verify_paged"])
    def test_one_kind_entry_points(self, model, what):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError):
            if what == "forward":
                T.forward(params, jnp.zeros((1, 4), jnp.int32), cfg)
            else:
                T.decode_verify_paged(params, jnp.zeros((3, 2), jnp.int32),
                                      {}, None, cfg, None, None)

    @pytest.mark.parametrize("kw", [
        dict(layer_pattern=("conv", "sliding"), window=4, n_dense_layers=0),
        dict(conv_kernel=0),
        dict(n_layers=9),
        dict(kv_lane_dense=True, d_head=48),
        dict(kv_lane_dense=True, n_kv_heads=1),
    ])
    def test_configurations(self, kw):
        with pytest.raises(ValueError):
            _cfg(**kw)


class TestHeadsOf64:
    """The paged kernel over rows two heads share, and the flash
    forward, at heads of 64 against the unfused attend."""

    @pytest.mark.parametrize("walk", list(_Walks._WALKS))
    @pytest.mark.parametrize("kv", [None, "bf16"])
    def test_paged_kernel_matches_the_unfused_attend(self, model, kv, walk,
                                                     monkeypatch):
        """``tests/test_paged.py``'s edge tables with KV heads of 64:
        the pool packed two heads a row, the kernel (queries in their
        head's lanes) against gather -> unpack -> ``_cache_attend``."""
        from horovod_tpu.ops import paged_attention as PA

        _, cfg = model
        case = dict(_Walks._WALKS[walk])
        block, poison = case.pop("block"), case.pop("poison", False)
        G = case.pop("R")
        Hkv = 2 * case.pop("Hkv")
        cfg = _cfg(n_heads=Hkv * G, n_kv_heads=Hkv, d_model=Hkv * G * 64,
                   dtype=jnp.bfloat16 if kv else jnp.float32)
        qg, pool, table, limit = _Walks._walk_case(
            np.random.RandomState(3), kv, Hkv=Hkv, R=G, Dh=64, **case)
        pool = [T._pack_heads(a, 2)[None] for a in pool[:2]]
        ps = pool[0].shape[3]
        if block is not None:
            monkeypatch.setattr(
                PA, "_BLOCK_BYTES", block * (Hkv // 2) * ps * 128
                * max(pool[0].dtype.itemsize, 2))
        if poison:
            pool = [a[None] for a in _Walks._poisoned(
                [a[0] for a in pool] + [None, None], table,
                case["limits"], ps)[:2]]
        S = qg.shape[0]
        qh = qg.reshape(S, Hkv * G, 1, 64).astype(cfg.dtype)
        pos, active = jnp.maximum(limit - 1, 0), limit > 0
        args = (qh, pool[0], pool[1], None, None, jnp.int32(0),
                jnp.asarray(table), pos, active, cfg)
        o_k = T._paged_decode_attend(*args, True, None)
        if poison:     # the unfused attend reads every page: clean pool
            clean = _Walks._walk_case(np.random.RandomState(3), kv, Hkv=Hkv,
                                      R=G, Dh=64, **case)[1]
            args = (qh, *(T._pack_heads(a, 2)[None] for a in clean[:2]),
                    *args[3:])
        o_r = T._paged_decode_attend(*args, False, None)
        tol = 2e-2 if kv == "bf16" else 1e-4
        live = np.asarray(active)
        np.testing.assert_allclose(np.asarray(o_k, np.float32)[live],
                                   np.asarray(o_r, np.float32)[live],
                                   atol=tol, rtol=tol)
        assert not np.asarray(o_k)[~live].any()

    def test_pack_and_unpack_are_inverse(self):
        x = jnp.arange(2 * 4 * 3 * 64, dtype=jnp.float32).reshape(2, 4, 3, 64)
        p = T._pack_heads(x, 2)
        assert p.shape == (2, 2, 3, 128)
        np.testing.assert_array_equal(p[1, 1, 2, 64:], x[1, 3, 2])
        np.testing.assert_array_equal(T._unpack_heads(p, 2), x)

    @pytest.mark.parametrize("S,bq,bk", [(128, 1024, 512), (256, 64, 32)])
    def test_flash_forward(self, S, bq, bk):
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(2, 4, S, 64), jnp.float32)
                   for _ in range(3))
        assert A._tileable(S, S, 64, min(bq, S), min(bk, S))
        got = A.flash_attention(q, k, v, True, None, bq, bk)
        want = A.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestRouter:
    def test_bias_chooses_raw_score_weighs(self, model, highest):
        """``route_topk`` as this model calls it equals the reference's
        router: the experts are the top of ``sigmoid + bias``, their
        weights the raw sigmoid over ``sum + 1e-6``."""
        params, cfg = model
        rng = np.random.RandomState(0)
        n = jnp.asarray(rng.randn(33, 256), jnp.float32)
        router = params["layers"]["router"][0]
        # a bias strong enough to change the choice
        bias = jnp.asarray(rng.randn(8) * 0.5, jnp.float32)
        top, gate = moe.route_topk(n, router, 2, True, bias=bias,
                                   **cfg.moe_routing)
        assert cfg.moe_routing == {"score": "sigmoid", "norm_eps": 1e-6}
        got = np.zeros((33, 8), np.float32)
        np.put_along_axis(got, np.asarray(top), np.asarray(gate), axis=1)
        want = np.asarray(R.conv_route(n, router, bias, DIMS))
        np.testing.assert_allclose(got, want, atol=1e-6)
        unbiased = moe.route_topk(n, router, 2, True, **cfg.moe_routing)[0]
        assert (np.sort(np.asarray(unbiased)) != np.sort(
            np.asarray(top))).any()
        # the weights are over sum + 1e-6, not over sum
        s = np.asarray(gate).sum(-1)
        assert (s < 1.0).all() and (s > 1.0 - 2e-6).all()


with open(os.path.join(os.path.dirname(__file__), "data",
                       "served_program_digests_pr38.json")) as _f:
    _BEFORE = json.load(_f)


@pytest.mark.parametrize("program", ["tick", "chunk", "prompt"])
@pytest.mark.parametrize("config", sorted(_BEFORE))
def test_the_four_served_programs_are_as_before(config, program):
    """With every field this architecture added left off, each of the
    four architectures served before traces to the jaxpr it traced to
    before (``tests/served_program_digests.py``)."""
    import served_program_digests as D

    got = D.programs(T.TransformerConfig(**D.CONFIGS[config]))[program]
    assert got == _BEFORE[config][program]
