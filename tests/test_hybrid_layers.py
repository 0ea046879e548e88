"""Layers of TWO mixers, served: attention and a state-space mixer
(Mamba-2) side by side on one normed input, summed before the residual —
a fourth layer kind whose per-request state (a matrix a head, MBs at the
published sizes, and a short convolution's taps) lives per SLOT beside
the page pool, every layer owning pages AND state; a GQA group of five;
the published width-transfer multipliers on every stream.

The program's LOGITS are held to ``horovod_tpu.models.plain_reference``
(``hybrid_forward``: straightforward float32 ``jax.numpy``, the
recurrence a SEQUENTIAL scan over the tokens, never the chunked dual
form, nothing of the program in it) at a small size on seeded weights:
hidden 64, 10 query / 2 KV heads of 16 (a group of 5), three layers, a
mixer of 4 heads of 8 with a state of 16 columns in 2 groups and 4 taps
— its convolution 96 wide, NOT the hidden size — dual-form blocks of 4,
every multiplier set and none of them 1 but the attention's input.

TOLERANCE: ``LOGIT_TOL`` = 2e-5 absolute on logits of std 0.5 (up to 2).
Both sides compute in float32 with float32 accumulation; what differs is
the ORDER of sums — the flash kernel's online softmax by blocks, the
paged kernel's over a group padded to eight rows, the chunked prefill's
prefix + suffix, and above all the state-space recurrence as masked
``(Q, Q)`` products and a carried state where the reference steps token
by token — which moves a logit by under 1e-6 (6e-7 to 8e-7 observed
over the eight cases).  The same comparison with the program in bfloat16
misses by 1.3e-2 (``test_bf16_program_fails_the_tolerance``), and with a
request's state lost at a boundary by 1.6 (``test_a_lost_state_fails``).
"""

import dataclasses
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
from horovod_tpu.serving.cache import PagedSlotCache

from test_paged import TestFusedPagedKernel as _Walks
from test_window_layers import _LogitTap

LOGIT_TOL = 2e-5
V = 97
MULT = dict(embedding_multiplier=2.0, lm_head_multiplier=0.5,
            attention_in_multiplier=1.0, attention_out_multiplier=0.7,
            key_multiplier=0.6, ssm_in_multiplier=0.8,
            ssm_out_multiplier=1.2, ssm_multipliers=(0.9, 1.1, 0.7, 1.3, 0.8),
            mlp_multipliers=(0.6, 1.4))
DIMS = dict(
    hidden_size=64, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-5, rope_theta=1e6, num_hidden_layers=3,
    mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, **MULT)


def _cfg(**over):
    kw = dict(
        vocab_size=V, d_model=64, n_heads=10, n_kv_heads=2, d_head=16,
        n_layers=3, d_ff=96, layer_pattern=("hybrid",), conv_kernel=4,
        ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
        ssm_chunk=4, embed_multiplier=2.0, head_multiplier=0.5,
        attn_in_multiplier=1.0, attn_out_multiplier=0.7, key_multiplier=0.6,
        ssm_in_multiplier=0.8, ssm_out_multiplier=1.2,
        ssm_multipliers=MULT["ssm_multipliers"],
        mlp_multipliers=MULT["mlp_multipliers"], norm_eps=1e-5,
        rope_theta=1e6, max_seq=96, dtype=jnp.float32,
        attention_impl="flash")
    kw.update(over)
    return T.TransformerConfig(**kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    assert cfg.ssm_conv_width == 96 != cfg.d_model
    assert cfg.n_heads // cfg.kv_heads == 5
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    k = jax.random.PRNGKey(1)
    for i, name in enumerate(("ln1", "ln2", "ssm_norm", "ssm_D",
                              "ssm_conv_b")):
        a = params["layers"][name]
        params["layers"][name] = (name != "ssm_conv_b") + 0.1 * \
            jax.random.normal(jax.random.fold_in(k, i), a.shape)
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


def _serve_and_compare(params, cfg, prompts, new=9, **kw):
    """Serve ``prompts``: ``(engine, worst)``, the largest |program
    logit - reference logit| over every row that produced a token."""
    engine = _engine(params, cfg, **kw)
    tap = _LogitTap(engine)
    futs = [engine.submit(p, max_new_tokens=new) for p in prompts]
    _run(engine, futs)
    worst = 0.0
    for p, f in zip(prompts, futs):
        toks = f.result()
        ref = np.asarray(R.hybrid_forward(params, jnp.asarray(p + toks),
                                          DIMS))
        # (an overlapped engine dispatches one tick past the last token)
        rows = np.stack(tap.rows[id(f)])[:len(toks)]
        assert rows.shape[0] == len(toks)
        want = ref[len(p) - 1:len(p) - 1 + len(toks)]
        worst = max(worst, float(np.abs(rows - want).max()))
    return engine, worst


class TestLogitsAgainstThePlainReference:
    # chunk 0: the whole prompt in one prefill; 7, 8, 9: chunked ingest
    # with the chunk boundary at three consecutive offsets — 8 a whole
    # number of the scan's blocks of 4 and a page's end, 7 and 9 neither
    # (both states handed over mid-block) — then decoding through the
    # cache and both states
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
        # 52 prompt tokens and (9 - 1) ticks of three rows, three layers
        assert st["ssm_scanned_tokens_total"] == 52 * 3
        assert st["ssm_updated_slots_total"] == 8 * 3 * 3
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
        _, worst = _serve_and_compare(
            params, dataclasses.replace(cfg, dtype=jnp.bfloat16),
            _prompts((29,)), new=4)
        assert worst > 20 * LOGIT_TOL, worst

    @pytest.mark.parametrize("where", ["chunk_boundaries", "ticks"])
    def test_a_lost_state_fails(self, model, highest, where):
        """... and for the mechanism: the reference with every layer's
        matrix state and taps zeroed at the chunk boundaries of a prompt
        (or at every token, as a tick that lost them would) is far
        outside it."""
        params, cfg = model
        p = _prompts((29,))[0]
        t = np.arange(29)
        reset = (t > 0) & (t % 8 == 0) if where == "chunk_boundaries" \
            else t >= 20
        a = np.asarray(R.hybrid_forward(params, jnp.asarray(p), DIMS))
        b = np.asarray(R.hybrid_forward(params, jnp.asarray(p), DIMS,
                                        jnp.asarray(reset)))
        first = int(np.argmax(reset))
        assert np.abs(a - b)[:first].max() == 0
        assert np.abs(a - b).max() > 1000 * LOGIT_TOL


class TestTheTwoBodies:
    """``ops/ssm.py`` alone against the recurrence stepped token by
    token."""

    @staticmethod
    def _case(B=2, S=37, H=4, P=8, G=2, N=16, seed=0):
        k = jax.random.split(jax.random.PRNGKey(seed), 6)
        return dict(
            x=jax.random.normal(k[0], (B, S, H, P)),
            dt=jax.nn.softplus(jax.random.normal(k[1], (B, S, H))),
            a=-jnp.exp(jax.random.normal(k[2], (H,))),
            b=jax.random.normal(k[3], (B, S, G, N)),
            c=jax.random.normal(k[4], (B, S, G, N)),
            h0=jax.random.normal(k[5], (B, H, P, N)))

    @staticmethod
    def _step(h, x, dt, a, b, c):
        rep = h.shape[0] // b.shape[0]
        b, c = jnp.repeat(b, rep, 0), jnp.repeat(c, rep, 0)
        h = jnp.exp(dt * a)[:, None, None] * h \
            + (dt[:, None] * x)[:, :, None] * b[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, c)

    @pytest.mark.parametrize("chunk", [4, 16, 64])
    @pytest.mark.parametrize("lens", [(37, 20), (1, 36), (0, 17)])
    def test_the_chunked_scan_from_a_state_is_the_recurrence(
            self, highest, chunk, lens):
        """From a GIVEN state to the state at each row's true length —
        at blocks that divide the length, that do not, and one longer
        than it — and the padding behind a row's length (``dt`` 0)
        leaves its state alone: a row of length 0 hands its state back
        to the bit."""
        z = self._case()
        real = jnp.arange(37)[None, :] < jnp.asarray(lens)[:, None]
        y, h = SSM.ssm_scan(z["x"], jnp.where(real[..., None], z["dt"], 0),
                            z["a"], z["b"], z["c"], z["h0"], chunk=chunk)
        for r, n in enumerate(lens):
            hs = z["h0"][r]
            for t in range(n):
                hs, ys = self._step(hs, z["x"][r, t], z["dt"][r, t], z["a"],
                                    z["b"][r, t], z["c"][r, t])
                np.testing.assert_allclose(y[r, t], ys, atol=3e-5, rtol=1e-5)
            np.testing.assert_allclose(h[r], hs, atol=2e-5, rtol=1e-5)
            if n == 0:
                np.testing.assert_array_equal(h[r], z["h0"][r])

    @pytest.mark.parametrize("kernel", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_the_tick_update_in_place(self, highest, kernel, dtype):
        """One token a slot at ONE layer of the stacked states: the
        active rows step the recurrence, the others and every other
        layer keep their states to the bit; the kernel and the XLA form
        agree."""
        z = self._case(B=1, S=5)
        states = jax.random.normal(jax.random.PRNGKey(9), (3, 5, 4, 8, 16)
                                   ).astype(dtype)
        active = jnp.asarray([True, False, True, True, False])
        y, new = SSM.ssm_update(states, jnp.int32(1), z["x"][0], z["dt"][0],
                                z["a"], z["b"][0], z["c"][0], active,
                                kernel=kernel)
        assert new.dtype == dtype
        np.testing.assert_array_equal(new[0], states[0])
        np.testing.assert_array_equal(new[2], states[2])
        np.testing.assert_array_equal(new[1][~active], states[1][~active])
        for s in np.nonzero(np.asarray(active))[0]:
            hs, ys = self._step(states[1, s].astype(jnp.float32), z["x"][0, s],
                                z["dt"][0, s], z["a"], z["b"][0, s],
                                z["c"][0, s])
            np.testing.assert_allclose(y[s], ys, atol=2e-5, rtol=1e-5)
            # (rounded once, where it is stored: a bfloat16's last bit)
            np.testing.assert_allclose(
                np.asarray(new[1, s], np.float32), hs,
                atol=2e-5 if dtype == jnp.float32 else 3e-2, rtol=1e-5)

    # (H, G, _BLOCK_BYTES, groups a step): a group of float32 states is
    # H / G x 8 x 16 x 4 bytes, of bfloat16 half that
    BLOCKS = {
        "a_group_a_head_several_a_step": (8, 8, 2048, {4: 4, 2: 8}),
        "groups_of_heads_one_a_step": (4, 2, 1024, {4: 1, 2: 2}),
        "a_count_the_bytes_do_not_divide": (6, 6, 2560, {4: 3, 2: 6}),
        "the_whole_slot_a_step": (8, 8, 1 << 20, {4: 8, 2: 8}),
        "a_group_over_the_block_alone": (4, 2, 256, {4: 1, 2: 1}),
    }

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", list(BLOCKS))
    def test_the_tick_kernel_takes_a_block_by_its_bytes(
            self, highest, monkeypatch, case, dtype):
        """A grid step of the kernel takes as many whole GROUPS of a
        slot as ``_BLOCK_BYTES`` of stored state hold (the largest
        divisor of ``G``): whatever that count, the active rows step the
        recurrence — every group with its OWN ``b`` and ``c`` — the
        idle rows and every other layer keep their states to the bit,
        and the stored states are the XLA body's."""
        H, G, block, groups = self.BLOCKS[case]
        monkeypatch.setattr(SSM, "_BLOCK_BYTES", block)
        size = jnp.dtype(dtype).itemsize
        gb = SSM._groups_a_step(G, H // G * 8 * 16 * size)
        assert gb == groups[size] and G % gb == 0
        z = self._case(B=1, S=5, H=H, G=G)
        b, c = z["b"][0], z["c"][0]
        assert all(np.abs(np.asarray(v[:, i] - v[:, j])).max() > 0.1
                   for v in (b, c) for i in range(G) for j in range(i))
        states = jax.random.normal(jax.random.PRNGKey(9), (3, 5, H, 8, 16)
                                   ).astype(dtype)
        active = jnp.asarray([True, False, True, True, False])
        args = (states, jnp.int32(1), z["x"][0], z["dt"][0], z["a"], b, c,
                active)
        y, new = SSM.ssm_update(*args, kernel=True)
        y_xla, new_xla = SSM.ssm_update(*args, kernel=False)
        assert new.dtype == dtype and y.dtype == jnp.float32
        np.testing.assert_array_equal(new[0], states[0])
        np.testing.assert_array_equal(new[2], states[2])
        np.testing.assert_array_equal(new[1][~active], states[1][~active])
        # the state's arithmetic an element is one multiply-add and one
        # store on either side (a compiler may fuse the two: an ulp)
        np.testing.assert_allclose(np.asarray(new, np.float32),
                                   np.asarray(new_xla, np.float32),
                                   atol=1e-6 if size == 4 else 3e-2,
                                   rtol=1e-6)
        for s in np.nonzero(np.asarray(active))[0]:
            hs, ys = self._step(states[1, s].astype(jnp.float32), z["x"][0, s],
                                z["dt"][0, s], z["a"], b[s], c[s])
            np.testing.assert_allclose(y[s], ys, atol=2e-5, rtol=1e-5)
            np.testing.assert_allclose(y[s], y_xla[s], atol=2e-5, rtol=1e-5)
            np.testing.assert_allclose(
                np.asarray(new[1, s], np.float32), hs,
                atol=2e-5 if size == 4 else 3e-2, rtol=1e-5)

    @pytest.mark.parametrize("heads, dtype, G, groups", [
        ((32, 128, 128), jnp.float32, 32, 16),     # minicpm-sala-serve
        ((32, 128, 256), jnp.bfloat16, 2, 1),      # falcon-h1-34b-serve
    ])
    def test_the_block_at_the_served_shapes(self, heads, dtype, G, groups):
        """1 MiB of stored state a step: sixteen of MiniCPM-SALA's
        float32 heads (64 KiB each, a group a head), ONE of Falcon-H1's
        two groups of sixteen bfloat16 heads."""
        H, P, N = heads
        group = H // G * P * N * jnp.dtype(dtype).itemsize
        assert SSM._groups_a_step(G, group) == groups
        assert groups * group == SSM._BLOCK_BYTES == 1 << 20


class TestTheStateUnderTheCacheManager:
    def test_pool_holds_both_states_beside_the_pages(self, model):
        """Every layer owns pages AND state: ``k``/``v`` over the three
        layers, the taps ``(L, S, 3, 96)`` — the convolution's width,
        not the hidden size — and the matrix states ``(L, S, H, P, N)``
        under the same manager; the counts are by what a kind carries."""
        _, cfg = model
        assert cfg.kind_count("full") == 0 and cfg.kind_count("hybrid") == 3
        assert [cfg.layers_with(a) for a in ("k", "conv", "ssm", "wk")] \
            == [3, 3, 3, 0]
        slots = PagedSlotCache(cfg, 3, 96, page_size=8,
                               n_layers=cfg.layers_with("k"))
        assert slots.cache["k"].shape == (3, slots.n_pages + 1, 2, 8, 16)
        assert slots.bytes_per_token == 3 * 2 * 2 * 16 * 4
        assert slots.cache["conv"].shape == (3, 3, 3, 96)
        assert slots.cache["ssm"].shape == (3, 3, 4, 8, 16)
        assert slots.conv_state_bytes_per_slot == 3 * 3 * 96 * 4
        assert slots.ssm_state_bytes_per_slot == 3 * 4 * 8 * 16 * 4

    def test_a_granted_slots_states_are_zero(self, model):
        _, cfg = model
        slots = PagedSlotCache(cfg, 3, 96, page_size=8,
                               n_layers=cfg.layers_with("k"))
        a = slots.alloc()
        slots.cache = {**slots.cache,
                       "conv": jnp.ones_like(slots.cache["conv"]),
                       "ssm": jnp.ones_like(slots.cache["ssm"])}
        slots.free(a)
        b, c = slots.alloc(), slots.alloc()
        assert (a, b, c) == (0, 0, 1)
        for name in ("conv", "ssm"):
            state = np.asarray(slots.cache[name])
            assert not state[:, :2].any() and state[:, 2].all()
        conv, ssm = slots.slot_state(2), slots.slot_state(2, "ssm")
        assert conv.shape == (3, 1, 3, 96) and ssm.shape == (3, 1, 4, 8, 16)

    def test_a_freed_slots_next_request_starts_from_zeros(self, model,
                                                          highest):
        """One slot, three requests one after another through it (whole
        prompt, chunked, whole): each one's logits are the reference's
        for it ALONE, whatever the tenant before left in either state."""
        params, cfg = model
        engine, worst = _serve_and_compare(
            params, cfg, _prompts((7, 29, 6), seed=5), n_slots=1)
        assert worst < LOGIT_TOL, worst
        assert engine.stats()["ssm_state_slots_live"] == 0

    @pytest.mark.parametrize("kernel", [False, True])
    def test_the_tick_leaves_an_ingesting_slots_states_alone(
            self, model, highest, kernel):
        """A row outside the decode mask (idle, or between two chunks
        of its prompt) keeps both states through a tick."""
        params, cfg = model
        pool = PagedSlotCache(cfg, 3, 96, page_size=8,
                              n_layers=cfg.layers_with("k")).cache
        pool = {**pool, "conv": jnp.full_like(pool["conv"], 0.5),
                "ssm": jnp.full_like(pool["ssm"], 0.25)}
        active = jnp.asarray([True, False, True])
        table = jnp.zeros((3, 12), jnp.int32).at[:, 0].set(
            jnp.asarray([1, 2, 3]))
        _, out = T.decode_step_paged(
            T.lay_out_projections(params)[0], jnp.asarray([3, 4, 5]), pool,
            table, cfg, active, kernel=kernel)
        conv, ssm = np.asarray(out["conv"]), np.asarray(out["ssm"])
        assert (conv[:, 1] == 0.5).all() and (ssm[:, 1] == 0.25).all()
        assert (conv[:, 0, 0] == 0.5).all() and (conv[:, 0, 2] != 0.5).any()
        assert (ssm[:, 0] != 0.25).all() and (ssm[:, 2] != 0.25).all()

    def test_stats(self, model):
        params, cfg = model
        engine = _engine(params, cfg)
        fut = engine.submit(_prompts((12,))[0], max_new_tokens=3)
        engine.step()
        st = engine.stats()
        assert st["ssm_state_bytes_per_slot"] == 3 * 4 * 8 * 16 * 4
        assert st["conv_state_bytes_per_slot"] == 3 * 3 * 96 * 4
        assert st["ssm_state_slots_live"] == st["conv_state_slots_live"] == 1
        assert st["kv_bytes_per_token"] == 3 * 2 * 2 * 16 * 4
        _run(engine, [fut])
        st = engine.stats()
        assert st["ssm_scanned_tokens_total"] == 12 * 3
        assert st["ssm_updated_slots_total"] == 2 * 3
        assert st["paged_live_tokens_total"] > 0
        text = engine.metrics.registry.to_prometheus()
        assert "serving_ssm_updated_slots_total 6" in text

    def test_a_preempted_request_resumes_to_the_same_logits(self, model,
                                                            highest):
        """Pool exhaustion preempts the younger request mid-decode; it
        is re-prefilled (prompt + emitted) into ZEROED states and every
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
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="hybrid layers.*snapshot"):
            _engine(params, cfg).register_prefix([1, 2, 3, 4])

    @pytest.mark.parametrize("kw,why", [
        (dict(speculative=True), "speculative=True"),
        (dict(tp=2), "tp > 1"),
        (dict(kv_dtype="int8"), "int8"),
    ])
    def test_engine_modes(self, model, kw, why):
        params, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="hybrid layers.*" + why):
            _engine(params, cfg, **kw)

    @pytest.mark.parametrize("what", ["forward", "loss_fn", "decode_step",
                                      "decode_verify_paged",
                                      "pipelined_forward"])
    def test_one_kind_entry_points(self, model, what):
        """Training, the single-request decode, speculation's verify
        and the pipeline schedules keep refusing a two-mixer layer."""
        params, cfg = model
        ids = jnp.zeros((1, 4), jnp.int32)
        with pytest.raises(T.UnsupportedModelConfigError):
            if what == "forward":
                T.forward(params, ids, cfg)
            elif what == "loss_fn":
                T.loss_fn(params, {"tokens": ids, "targets": ids}, cfg)
            elif what == "decode_step":
                T.decode_step(params, ids[:, 0], T.init_cache(cfg, 1, 8), cfg)
            elif what == "pipelined_forward":
                T.pipelined_forward(params, ids, cfg, n_microbatches=1)
            else:
                T.decode_verify_paged(params, jnp.zeros((3, 2), jnp.int32),
                                      {}, None, cfg, None, None)

    @pytest.mark.parametrize("kw", [
        dict(layer_pattern=("hybrid", "full"), n_layers=4),
        dict(layer_pattern=("hybrid", "sliding"), window=4, n_layers=4),
        dict(layer_pattern=("conv", "hybrid"), n_layers=4),
        dict(kv_lane_dense=True),
        dict(ssm_heads=0),
        dict(ssm_groups=3),
        dict(conv_kernel=1),
        dict(ssm_multipliers=(1.0, 2.0)),
        dict(mlp_multipliers=(1.0,)),
    ])
    def test_configurations(self, kw):
        with pytest.raises(ValueError):
            _cfg(**kw)

    def test_an_int8_pool_is_refused_by_the_cache(self, model):
        _, cfg = model
        with pytest.raises(T.UnsupportedModelConfigError,
                           match="per-slot state"):
            PagedSlotCache(cfg, 3, 96, page_size=8, kv_dtype="int8",
                           n_layers=3)


class TestAGroupOfFive:
    """The paged kernel at FIVE query heads a KV head (its rows padded
    to eight) against the unfused attend."""

    @pytest.mark.parametrize("walk", list(_Walks._WALKS))
    @pytest.mark.parametrize("kv", [None, "bf16"])
    def test_paged_kernel_matches_the_unfused_attend(self, kv, walk,
                                                     monkeypatch):
        """``tests/test_paged.py``'s edge tables at a group of 5 (15
        rows where a table has a verify window's): the kernel against
        gather -> ``_cache_attend``."""
        from horovod_tpu.ops import paged_attention as PA

        case = dict(_Walks._WALKS[walk])
        block, poison = case.pop("block"), case.pop("poison", False)
        G = 15 if case.pop("R") > 4 else 5
        Hkv = case.pop("Hkv")
        cfg = _cfg(n_heads=Hkv * G, n_kv_heads=Hkv, d_model=64,
                   dtype=jnp.bfloat16 if kv else jnp.float32)
        qg, pool, table, limit = _Walks._walk_case(
            np.random.RandomState(3), kv, Hkv=Hkv, R=G, **case)
        pool = [a[None] for a in pool[:2]]
        ps = pool[0].shape[3]
        if block is not None:
            monkeypatch.setattr(
                PA, "_BLOCK_BYTES", block * Hkv * ps * 128
                * max(pool[0].dtype.itemsize, 2))
        clean = pool
        if poison:
            pool = [a[None] for a in _Walks._poisoned(
                [a[0] for a in pool] + [None, None], table,
                case["limits"], ps)[:2]]
        S = qg.shape[0]
        qh = qg.reshape(S, Hkv * G, 1, 16).astype(cfg.dtype)
        pos, active = jnp.maximum(limit - 1, 0), limit > 0
        tail = (None, None, jnp.int32(0), jnp.asarray(table), pos, active,
                cfg)
        o_k = T._paged_decode_attend(qh, *pool, *tail, True, None)
        # (the unfused attend reads every page: the clean pool)
        o_r = T._paged_decode_attend(qh, *clean, *tail, False, None)
        tol = 2e-2 if kv == "bf16" else 1e-4
        live = np.asarray(active)
        np.testing.assert_allclose(np.asarray(o_k, np.float32)[live],
                                   np.asarray(o_r, np.float32)[live],
                                   atol=tol, rtol=tol)
        assert not np.asarray(o_k)[~live].any()


class TestTheBenchmarksCopyOfTheReference:
    """``chipbench/reference_hybrid.py`` (blocks of rows at one width,
    the length a traced scalar, one layer's weights at a time) against
    ``plain_reference.hybrid_forward`` on ``chipbench/weights_hybrid``'s
    seeded tree, at the small size."""

    DIMS = dict(
        DIMS, vocab_size=V, intermediate_size=96, mamba_chunk_size=4,
        ssm_multipliers=list(MULT["ssm_multipliers"]),
        mlp_multipliers=list(MULT["mlp_multipliers"]),
        engine={"max_len": 64, "prefill_chunk_tokens": 8})

    @pytest.mark.parametrize("control", [False, True])
    def test_they_agree(self, highest, control):
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from chipbench import reference_hybrid, weights_hybrid

        dims = self.DIMS
        params = weights_hybrid.make_params(11, dims, jnp.float32)
        assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
            == weights_hybrid.param_count(dims)
        rng = np.random.default_rng(0)
        toks = np.zeros((2, 64), np.int32)
        plens, served = [19, 5], [7, 30]
        for i, (p, n) in enumerate(zip(plens, served)):
            toks[i, :p + n] = rng.integers(0, V, p + n)
        got, picked, valid = reference_hybrid.served_logits(
            11, dims, jnp.float32, toks, plens, served, q_block=16,
            lose_state=control)
        for i, (p, n) in enumerate(zip(plens, served)):
            reset = jnp.asarray(reference_hybrid.lost_state(
                p + n, p, 8)) if control else None
            want = np.asarray(R.hybrid_forward(
                params, jnp.asarray(toks[i, :p + n]), dims, reset))
            np.testing.assert_allclose(got[i, :n], want[p - 1:p - 1 + n],
                                       atol=2e-5, rtol=1e-5)
            np.testing.assert_array_equal(picked[i, :n], toks[i, p:p + n])
            assert valid[i].sum() == n


with open(os.path.join(os.path.dirname(__file__), "data",
                       "served_program_digests_pr41.json")) as _f:
    _BEFORE = json.load(_f)


@pytest.mark.parametrize("program", ["tick", "chunk", "prompt"])
@pytest.mark.parametrize("config", sorted(_BEFORE))
def test_the_five_served_programs_are_as_before(config, program):
    """With every field this architecture added left off (the hybrid
    kind, the mixer's sizes, every multiplier), each of the five
    architectures served before — the conv one among them — traces to
    the jaxpr it traced to before (``tests/served_program_digests.py``)."""
    import served_program_digests as D

    got = D.programs(T.TransformerConfig(**D.CONFIGS[config]))[program]
    assert got == _BEFORE[config][program]
