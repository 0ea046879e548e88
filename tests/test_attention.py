"""Flash attention (Pallas, interpreted on CPU) and ring attention
(sequence parallelism over the 8-device mesh) tests.

The reference has no attention ops (SURVEY.md §5.7) — these cover the
TPU-native long-context extensions.  Oracle: O(S^2) reference_attention.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import spmd
from horovod_tpu.ops import attention as A

N = 8


def _qkv(b=2, h=2, s=128, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = A.flash_attention(q, k, v, causal, None, 64, 64)
        ref = A.reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_single_block(self):
        q, k, v = _qkv(s=64)
        out = A.flash_attention(q, k, v, False, None, 64, 64)
        ref = A.reference_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_many_blocks_long_seq(self):
        q, k, v = _qkv(b=1, h=1, s=512, d=32)
        out = A.flash_attention(q, k, v, True, None, 64, 128)
        ref = A.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_scale_override(self):
        q, k, v = _qkv(s=64)
        out = A.flash_attention(q, k, v, False, 0.5, 64, 64)
        ref = A.reference_attention(q, k, v, sm_scale=0.5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_fallback_untileable(self):
        # S=100 doesn't tile by 64: silently uses the XLA reference path.
        q, k, v = _qkv(s=100, d=20)
        out = A.flash_attention(q, k, v, True, None, 64, 64)
        ref = A.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        q, k, v = _qkv(s=128)

        def loss_flash(q, k, v):
            return jnp.sum(A.flash_attention(q, k, v, causal, None, 64, 64) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(A.reference_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-4, rtol=5e-4, err_msg=name)

    def test_grad_under_jit(self):
        q, k, v = _qkv(s=64)
        g = jax.jit(jax.grad(
            lambda q: jnp.sum(A.flash_attention(q, k, v, True, None, 64, 64))
        ))(q)
        assert np.isfinite(np.asarray(g)).all()


class TestFlashWithLse:
    def test_outputs_and_both_cotangents(self):
        """(o, lse) forward matches the reference, and gradients through
        BOTH outputs (the dlse term: delta -= dlse) are exact."""
        q, k, v = _qkv(b=1, h=2, s=128, d=32)

        def loss_flash(q, k, v):
            o, lse = A.flash_attention_with_lse(q, k, v, True)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse ** 2)

        def loss_ref(q, k, v):
            o, lse = A._reference_attention_lse(
                q, k, v, 0, A._sm_scale(q, None))
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(lse ** 2)

        o, lse = jax.jit(
            lambda q, k, v: A.flash_attention_with_lse(q, k, v, True)
        )(q, k, v)
        o_r, lse_r = A._reference_attention_lse(
            q, k, v, 0, A._sm_scale(q, None))
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   atol=2e-4, rtol=2e-4)
        g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=2e-3, err_msg=name)


class TestFlashShifted:
    """The runtime shifted-causal mask: one kernel serves every ring chunk
    kind (full / diagonal-causal / dead) via an SMEM int32 shift."""

    @pytest.mark.parametrize("shift", [-128, -64, 0, 64])
    def test_matches_reference_shift(self, shift):
        q, k, v = _qkv(b=1, h=2, s=128, d=32)
        o, lse = jax.jit(
            lambda q, k, v, s: A.flash_attention_shifted(q, k, v, s,
                                                         None, 64, 64)
        )(q, k, v, jnp.int32(shift))
        o_r, lse_r = A._reference_attention_lse(
            q, k, v, shift, A._sm_scale(q, None))
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_r),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r),
                                   atol=2e-4, rtol=2e-4)

    def test_dead_chunk_yields_zero_and_neg_inf(self):
        """shift >= S masks everything: o == 0, lse == NEG_INF, so the
        chunk vanishes under a logsumexp merge."""
        q, k, v = _qkv(b=1, h=1, s=64, d=16)
        o, lse = A.flash_attention_shifted(q, k, v, jnp.int32(64),
                                           None, 64, 64)
        np.testing.assert_array_equal(np.asarray(o), 0.0)
        assert (np.asarray(lse) <= A.NEG_INF / 2).all()

    def test_gradients_match_reference_shift(self):
        q, k, v = _qkv(b=1, h=1, s=128, d=16)
        shift = jnp.int32(-64)  # half-window: exercises partial masking

        def loss_flash(q, k, v):
            o, lse = A.flash_attention_shifted(q, k, v, shift, None, 64, 64)
            return jnp.sum(o ** 2) + jnp.sum(lse ** 2)

        def loss_ref(q, k, v):
            o, lse = A._reference_attention_lse(
                q, k, v, shift, A._sm_scale(q, None))
            return jnp.sum(o ** 2) + jnp.sum(lse ** 2)

        g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=2e-3, err_msg=name)


def _pallas_kernels(jaxpr):
    """Every Pallas call of a traced program, by its ``name=``."""
    from conftest import _sub_jaxprs

    names = [eqn.params["name"] for eqn in jaxpr.eqns
             if eqn.primitive.name == "pallas_call"]
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            names.extend(_pallas_kernels(sub))
    return sorted(names)


class TestFlashUnderRemat:
    """What a checkpointed layer keeps of the forward kernel: under
    ``"dots"`` its output and log-sum-exp are saved residuals, so the
    backward pass holds the two backward kernels and NO second forward;
    ``"full"`` saves nothing and runs it again."""

    B, S, H, D = 1, 128, 2, 32

    def _layer(self, wrapper):
        """Projections, one of the three custom-vjp wrappers, the output
        projection: a layer whose matmul outputs ``"dots"`` saves."""
        B, S, H, D = self.B, self.S, self.H, self.D

        def attend(q, k, v):
            if wrapper == "plain":
                return A.flash_attention(q, k, v, True, None, 64, 64)
            if wrapper == "with_lse":
                o, lse = A.flash_attention_with_lse(q, k, v, True, None,
                                                    64, 64)
            else:
                o, lse = A.flash_attention_shifted(q, k, v, jnp.int32(-32),
                                                   None, 64, 64)
            # the lse reaches the output: its cotangent is not zero
            return o * jax.nn.sigmoid(lse)[..., None]

        def layer(x, w):
            q, k, v = (
                (x @ w[n]).reshape(B, S, H, D).transpose(0, 2, 1, 3)
                for n in ("wq", "wk", "wv"))
            o = attend(q, k, v)
            return x + o.transpose(0, 2, 1, 3).reshape(B, S, H * D) @ w["wo"]

        return layer

    def _inputs(self):
        d = self.H * self.D
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        w = {n: jax.random.normal(k, (d, d)) * d ** -0.5
             for n, k in zip(("wq", "wk", "wv", "wo"), ks)}
        return jax.random.normal(ks[4], (self.B, self.S, d)), w

    @pytest.mark.parametrize("wrapper", ["plain", "with_lse", "shifted"])
    def test_dots_saves_the_forward_kernels_results(self, wrapper):
        from horovod_tpu.models import transformer as T

        layer, (x, w) = self._layer(wrapper), self._inputs()
        cfg = T.TransformerConfig(
            vocab_size=8, d_model=8, n_heads=1, n_layers=1, d_ff=8)

        def grad_of(policy):
            f = layer if policy is None else T._remat(
                layer, dataclasses.replace(cfg, remat_policy=policy))
            return jax.grad(lambda x, w: jnp.sum(f(x, w) ** 2),
                            argnums=(0, 1))

        bwd = ["hvd_flash_bwd_dkv", "hvd_flash_bwd_dq"]
        for policy, forwards in ((None, 1), ("dots", 1), ("full", 2)):
            kernels = _pallas_kernels(
                jax.make_jaxpr(grad_of(policy))(x, w).jaxpr)
            assert kernels == bwd + ["hvd_flash_fwd"] * forwards, (
                policy, kernels)
        # the saved arrays ARE the ones a rerun would give: equal, not close
        plain = jax.jit(grad_of(None))(x, w)
        dots = jax.jit(grad_of("dots"))(x, w)
        for a, b in zip(jax.tree_util.tree_leaves(dots),
                        jax.tree_util.tree_leaves(plain)):
            assert np.abs(np.asarray(b)).max() > 0
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRingAttention:
    def _run_ring(self, q, k, v, causal, impl="flash"):
        """q/k/v are (B, H, S_total, D); shard the sequence over the mesh."""
        B, H, S, D = q.shape

        def inner(qs, ks, vs):
            return A.ring_attention(
                qs, ks, vs, axis_name=hvd.AXIS, causal=causal, impl=impl)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        return jax.jit(f)(q, k, v)

    @pytest.mark.parametrize("impl", ["flash", "reference"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal, impl):
        q, k, v = _qkv(b=1, h=2, s=N * 16, d=32)
        out = self._run_ring(q, k, v, causal, impl)
        ref = A.reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_differentiable(self):
        q, k, v = _qkv(b=1, h=1, s=N * 8, d=16)

        def loss(q, k, v):
            def inner(qs, ks, vs):
                return A.ring_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                        causal=True)
            f = spmd.shard(
                inner,
                in_specs=(P(None, None, hvd.AXIS, None),) * 3,
                out_specs=P(None, None, hvd.AXIS, None),
            )
            return jnp.sum(f(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(A.reference_attention(q, k, v, causal=True) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3, err_msg=name)

    def test_lse_merge_handles_masked_chunks(self):
        """Causal ring: the first shard receives only future chunks from
        others — their contributions must vanish, not NaN."""
        q, k, v = _qkv(b=1, h=1, s=N * 4, d=16)
        out = self._run_ring(q, k, v, True)
        assert np.isfinite(np.asarray(out)).all()


@pytest.mark.slow
class TestZigzagRingAttention:
    """Causal ring with the zigzag chunk layout (device i holds global
    chunks (i, 2P-1-i)): must equal full causal attention after
    unpermuting, with gradients, incl. GQA shards."""

    def _zigzag(self, x, perm):
        return x[:, :, perm]

    def _run(self, q, k, v, impl="flash"):
        B, H, S, D = q.shape
        perm, inv = A.zigzag_perm(S, N)
        qz, kz, vz = (self._zigzag(t, perm) for t in (q, k, v))

        def inner(qs, ks, vs):
            return A.zigzag_ring_attention(
                qs, ks, vs, axis_name=hvd.AXIS, impl=impl)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        return jax.jit(f)(qz, kz, vz)[:, :, inv]

    @pytest.mark.parametrize("impl", ["flash", "reference"])
    def test_matches_full_causal_attention(self, impl):
        q, k, v = _qkv(b=1, h=2, s=N * 16, d=32)
        out = self._run(q, k, v, impl)
        ref = A.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_differentiable(self):
        q, k, v = _qkv(b=1, h=1, s=N * 8, d=16)
        perm, inv = A.zigzag_perm(q.shape[2], N)

        def loss(q, k, v):
            qz, kz, vz = (t[:, :, perm] for t in (q, k, v))

            def inner(qs, ks, vs):
                return A.zigzag_ring_attention(qs, ks, vs,
                                               axis_name=hvd.AXIS)

            f = spmd.shard(
                inner,
                in_specs=(P(None, None, hvd.AXIS, None),) * 3,
                out_specs=P(None, None, hvd.AXIS, None),
            )
            return jnp.sum(f(qz, kz, vz) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(A.reference_attention(q, k, v, causal=True) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3, err_msg=name)

    def test_gqa_matches_full(self):
        H, H_kv = 4, 2
        q, _, _ = _qkv(b=1, h=H, s=N * 8, d=16)
        _, k, v = _qkv(b=1, h=H_kv, s=N * 8, d=16)
        out = self._run(q, k, v)
        ref = A.reference_attention(
            q, A.expand_kv(k, H), A.expand_kv(v, H), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_perm_inverse_roundtrip(self):
        perm, inv = A.zigzag_perm(32, 4)
        np.testing.assert_array_equal(perm[inv], np.arange(32))
        # Device i's block = global chunks (i, 2P-1-i).
        Sc = 32 // 8
        blk0 = perm[:2 * Sc]
        np.testing.assert_array_equal(
            blk0, np.concatenate([np.arange(0, Sc), np.arange(28, 32)]))

    def test_odd_shard_raises(self):
        with pytest.raises(ValueError, match="divide"):
            A.zigzag_perm(30, 4)

    def test_model_ring_zigzag_matches_unsharded(self):
        """Flagship model with attention_impl='ring_zigzag' over sp=8:
        loss and every parameter gradient match the single-device
        reference model (batch columns permuted by zigzag_perm; the
        token/target pairing and the mean are permutation-invariant,
        RoPE uses the explicit global positions)."""
        import dataclasses

        from jax.sharding import Mesh

        from horovod_tpu.models import transformer as T

        S = 64
        cfg = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=S, dtype=jnp.float32, n_kv_heads=2,
            attention_impl="ring_zigzag")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = T.synthetic_batch(1, cfg, batch=2, seq=S)
        perm, _ = A.zigzag_perm(S, N)
        zbatch = {k: v[:, perm] for k, v in batch.items()}

        mesh = Mesh(np.array(jax.devices()[:N]), axis_names=("sp",))

        def inner(pr, b):
            loss, grads = jax.value_and_grad(
                lambda p: T.loss_fn(p, b, cfg))(pr)
            return (jax.lax.pmean(loss, "sp"),
                    jax.tree_util.tree_map(
                        lambda g: jax.lax.pmean(g, "sp"), grads))

        loss_z, grads_z = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(P(), P(None, "sp")),
            out_specs=(P(), P()), check_vma=False))(params, zbatch)

        rcfg = dataclasses.replace(cfg, attention_impl="reference")
        loss_r, grads_r = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, rcfg))(params)
        np.testing.assert_allclose(float(loss_z), float(loss_r),
                                   rtol=1e-5)
        flat_z = dict(jax.tree_util.tree_leaves_with_path(grads_z))
        for path, ref in jax.tree_util.tree_leaves_with_path(grads_r):
            np.testing.assert_allclose(
                np.asarray(flat_z[path]), np.asarray(ref),
                atol=2e-4, rtol=2e-4, err_msg=jax.tree_util.keystr(path))


class TestGroupedQueryAttention:
    """GQA: K/V carry fewer heads; kernels see jnp.repeat-expanded heads
    (whose VJP is the per-group sum), and the ring rotates the SMALL
    shards.  Oracle: reference attention on manually repeated K/V."""

    def _qkv_gqa(self, h=4, h_kv=2, s=64, d=16):
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, h, s, d), jnp.float32)
        k = jax.random.normal(ks[1], (1, h_kv, s, d), jnp.float32)
        v = jax.random.normal(ks[2], (1, h_kv, s, d), jnp.float32)
        return q, k, v

    def test_expand_matches_manual_repeat(self, ):
        q, k, v = self._qkv_gqa()
        out = A.flash_attention(q, A.expand_kv(k, 4), A.expand_kv(v, 4),
                                True, None, 64, 64)
        ref = A.reference_attention(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
            causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_gradients_group_sum(self):
        """d/dk of the GQA attention == the group-sum of the MHA grads —
        the repeat VJP must deliver exact shared-head gradients."""
        q, k, v = self._qkv_gqa()

        def loss_gqa(k):
            o = A.flash_attention(q, A.expand_kv(k, 4), A.expand_kv(v, 4),
                                  True, None, 64, 64)
            return jnp.sum(o ** 2)

        def loss_ref(k):
            o = A.reference_attention(
                q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
                causal=True)
            return jnp.sum(o ** 2)

        g = jax.grad(loss_gqa)(k)
        gr = jax.grad(loss_ref)(k)
        assert g.shape == k.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   atol=2e-3, rtol=2e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_gqa_matches_full(self, causal):
        """Ring attention with H_kv=2 < H=4: the small shards rotate, the
        merged output must equal full attention on repeated K/V."""
        q, k, v = self._qkv_gqa(s=N * 8)

        def inner(qs, ks, vs):
            return A.ring_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                    causal=causal)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        out = jax.jit(f)(q, k, v)
        ref = A.reference_attention(
            q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
            causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("h,h_kv", [
        (8, 2),   # h_kv doesn't divide the 8-device axis: pre-expand path
        (16, 8),  # h_kv divides the axis: reshard-small-then-expand path
    ])
    def test_ulysses_gqa_matches_full(self, h, h_kv):
        q, k, v = self._qkv_gqa(h=h, h_kv=h_kv, s=N * 8)
        g = h // h_kv

        def inner(qs, ks, vs):
            return A.ulysses_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                       causal=True)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        out = jax.jit(f)(q, k, v)
        ref = A.reference_attention(
            q, jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1),
            causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.slow
    def test_ring_gqa_gradients(self):
        """The diff's central gradient claim: the repeat VJP (group-sum)
        composed with the transposed ppermute ring must deliver exact
        shared-KV-head gradients vs the repeated-K/V full-attention
        oracle."""
        q, k, v = self._qkv_gqa(h=4, h_kv=2, s=N * 4)

        def loss_ring(q, k, v):
            def inner(qs, ks, vs):
                return A.ring_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                        causal=True)
            f = spmd.shard(
                inner,
                in_specs=(P(None, None, hvd.AXIS, None),) * 3,
                out_specs=P(None, None, hvd.AXIS, None),
            )
            return jnp.sum(f(q, k, v) ** 2)

        def loss_ref(q, k, v):
            o = A.reference_attention(
                q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1),
                causal=True)
            return jnp.sum(o ** 2)

        g = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            assert a.shape == b.shape, name  # kv grads stay H_kv-shaped
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=2e-3, err_msg=name)

    def test_bad_group(self):
        with pytest.raises(ValueError, match="multiple"):
            A.expand_kv(jnp.zeros((1, 3, 8, 4)), 4)

    def test_ring_gqa_permutes_small_shards(self):
        """The central GQA traffic claim, checked at the HLO level: the
        ring's collective-permute must move the UNEXPANDED (H_kv-wide)
        K/V shards, not the repeated full-head tensors."""
        h, h_kv, s, d = 4, 2, N * 8, 16
        q = jnp.zeros((1, h, s // N, d), jnp.float32)
        kv = jnp.zeros((1, h_kv, s // N, d), jnp.float32)

        def inner(qs, ks, vs):
            return A.ring_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                    causal=True)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        # Per-shard shapes inside shard_map: K/V are (1, h_kv, s/N, d).
        hlo = jax.jit(f).lower(
            jnp.zeros((1, h, s, d), jnp.float32),
            jnp.zeros((1, h_kv, s, d), jnp.float32),
            jnp.zeros((1, h_kv, s, d), jnp.float32),
        ).compile().as_text()
        small = f"f32[1,{h_kv},{s // N},{d}]"
        big = f"f32[1,{h},{s // N},{d}]"
        permutes = [l for l in hlo.splitlines() if "collective-permute" in l
                    and "start" not in l]
        assert permutes, "ring must emit collective-permutes"
        assert all(small in l for l in permutes), permutes[:2]
        assert not any(big in l for l in permutes), (
            "ppermute must carry the unexpanded H_kv shards", permutes[:2])


class TestUlyssesAttention:
    def _run(self, q, k, v, causal, impl="reference"):
        def inner(qs, ks, vs):
            return A.ulysses_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                       causal=causal, impl=impl)

        f = spmd.shard(
            inner,
            in_specs=(P(None, None, hvd.AXIS, None),) * 3,
            out_specs=P(None, None, hvd.AXIS, None),
        )
        return jax.jit(f)(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        # heads divisible by the 8-device axis
        q, k, v = _qkv(b=1, h=N, s=N * 8, d=32)
        out = self._run(q, k, v, causal)
        ref = A.reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_flash_inner_matches(self):
        q, k, v = _qkv(b=1, h=N, s=N * 16, d=32)
        out = self._run(q, k, v, True, impl="flash")
        ref = A.reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3, rtol=2e-3)

    def test_differentiable(self):
        q, k, v = _qkv(b=1, h=N, s=N * 4, d=16)

        def loss(q, k, v):
            def inner(qs, ks, vs):
                return A.ulysses_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                           causal=True)
            f = spmd.shard(
                inner,
                in_specs=(P(None, None, hvd.AXIS, None),) * 3,
                out_specs=P(None, None, hvd.AXIS, None),
            )
            return jnp.sum(f(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(A.reference_attention(q, k, v, causal=True) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-3, rtol=1e-3, err_msg=name)

    def test_indivisible_heads_raise(self):
        q, k, v = _qkv(b=1, h=3, s=N * 2, d=16)
        with pytest.raises(Exception, match="divisible|ring_attention"):
            self._run(q, k, v, False)

    def test_flash_inner_differentiable_under_shard_map(self):
        """The Pallas custom-vjp kernels must transpose correctly inside
        shard_map (the ulysses production path)."""
        q, k, v = _qkv(b=1, h=N, s=N * 16, d=32)

        def loss(q, k, v):
            def inner(qs, ks, vs):
                return A.ulysses_attention(qs, ks, vs, axis_name=hvd.AXIS,
                                           causal=True, impl="flash")
            f = spmd.shard(
                inner,
                in_specs=(P(None, None, hvd.AXIS, None),) * 3,
                out_specs=P(None, None, hvd.AXIS, None),
            )
            return jnp.sum(f(q, k, v) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(A.reference_attention(q, k, v, causal=True) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-3, rtol=2e-3, err_msg=name)


class TestTransformerIntegration:
    """attention_impl config: flash and ring must match the reference
    implementation through the full model forward."""

    def _cfg(self, impl, dtype=jnp.float32):
        from horovod_tpu.models import transformer as T

        return T.TransformerConfig(
            vocab_size=64, d_model=64, n_heads=2, n_layers=2, d_ff=128,
            max_seq=64, dtype=dtype, attention_impl=impl)

    def test_flash_matches_reference_forward(self):
        from horovod_tpu.models import transformer as T

        cfg_ref = self._cfg("reference")
        cfg_fl = self._cfg("flash")
        params = T.init_params(jax.random.PRNGKey(0), cfg_ref)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 64)
        ref = T.forward(params, tokens, cfg_ref)
        fl = T.forward(params, tokens, cfg_fl)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_ring_matches_reference_forward(self):
        """Sequence-parallel forward over the sp axis == full-sequence
        reference forward."""
        from horovod_tpu.models import transformer as T
        from jax.sharding import Mesh

        cfg_ref = self._cfg("reference")
        cfg_ring = self._cfg("ring")
        params = T.init_params(jax.random.PRNGKey(0), cfg_ref)
        S = 64
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 64)
        ref = T.forward(params, tokens, cfg_ref)

        mesh = Mesh(np.array(jax.devices()[:N]), axis_names=("sp",))

        def inner(params, tokens):
            return T.forward(params, tokens, cfg_ring)

        # check_vma=False: the production wrapper (spmd.shard) disables
        # vma tracking too — the Pallas CPU interpreter can't slice
        # varying-over-axis operands (jax suggests this exact workaround).
        f = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        ))
        out = f(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_ring_gqa_matches_reference_forward(self):
        """GQA model (n_kv_heads=1 < n_heads=2): ring over sp ==
        full-sequence reference, both running the grouped projections."""
        import dataclasses

        from horovod_tpu.models import transformer as T
        from jax.sharding import Mesh

        cfg_ref = dataclasses.replace(self._cfg("reference"), n_kv_heads=1)
        cfg_ring = dataclasses.replace(cfg_ref, attention_impl="ring")
        params = T.init_params(jax.random.PRNGKey(0), cfg_ref)
        assert params["layers"]["wk"].shape[2] == 1  # grouped projection
        S = 64
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 64)
        ref = T.forward(params, tokens, cfg_ref)

        mesh = Mesh(np.array(jax.devices()[:N]), axis_names=("sp",))

        def inner(params, tokens):
            return T.forward(params, tokens, cfg_ring)

        f = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        ))
        out = f(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    def test_ulysses_matches_reference_forward(self):
        """alltoall sequence-parallel forward over sp == full-sequence
        reference forward (needs heads % sp == 0)."""
        import dataclasses

        from horovod_tpu.models import transformer as T
        from jax.sharding import Mesh

        cfg_ref = dataclasses.replace(self._cfg("reference"), n_heads=N)
        cfg_uly = dataclasses.replace(cfg_ref, attention_impl="ulysses")
        params = T.init_params(jax.random.PRNGKey(0), cfg_ref)
        S = 64
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, 64)
        ref = T.forward(params, tokens, cfg_ref)

        mesh = Mesh(np.array(jax.devices()[:N]), axis_names=("sp",))

        def inner(params, tokens):
            return T.forward(params, tokens, cfg_uly)

        f = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,  # Pallas CPU interpreter vs varying operands
        ))
        out = f(params, tokens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)
