"""Expert-parallel MoE dispatch (horovod_tpu.ops.moe): exactness vs the
dense oracle, capacity-drop semantics, the ep all_to_all exchange under
shard_map, and the flat-in-E compute claim."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd  # noqa: F401 — device count via conftest
from horovod_tpu.ops import moe


def _params(key, E, D, F):
    ks = jax.random.split(key, 4)
    return dict(
        router=jax.random.normal(ks[0], (D, E)) * 0.5,
        w_gate=jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D),
        w_up=jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D),
        w_down=jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F),
    )


def _dense_oracle(x, p):
    """Dense top-1 dispatch (the transformer's _moe_mlp_dense math)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    onehot = jax.nn.one_hot(top, p["router"].shape[1], dtype=x.dtype)
    g = jnp.einsum("td,edf->tef", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x, p["w_up"].astype(x.dtype))
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u,
                   p["w_down"].astype(x.dtype))
    y = jnp.einsum("ted,te->td", y, onehot)
    return y * gate[:, None].astype(x.dtype)


class TestSwitchDispatchLocal:
    def test_exact_vs_dense_oracle_no_drops(self):
        """With capacity_factor >= E no token can be dropped, and the
        sparse dispatch must equal the dense oracle — outputs AND every
        gradient (router included)."""
        E, D, F, T = 4, 16, 32, 24
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

        def loss_sparse(p):
            y = moe.switch_moe(x, p["router"], p["w_gate"], p["w_up"],
                               p["w_down"], capacity_factor=float(E))
            return jnp.sum(y ** 2)

        def loss_dense(p):
            return jnp.sum(_dense_oracle(x, p) ** 2)

        l_s, g_s = jax.value_and_grad(loss_sparse)(p)
        l_d, g_d = jax.value_and_grad(loss_dense)(p)
        np.testing.assert_allclose(float(l_s), float(l_d), rtol=1e-5)
        for k in p:
            np.testing.assert_allclose(
                np.asarray(g_s[k]), np.asarray(g_d[k]),
                atol=1e-4, rtol=1e-4, err_msg=k)

    def test_capacity_drops_zero_overflow_tokens(self):
        """Force every token onto expert 0: tokens past the capacity must
        contribute ZERO (residual-only), earlier ones must match the
        oracle."""
        E, D, F, T = 2, 8, 16, 10
        p = _params(jax.random.PRNGKey(0), E, D, F)
        # Router hugely biased to expert 0.
        p["router"] = jnp.zeros((D, E)).at[:, 0].set(100.0)
        x = jnp.ones((T, D)) * 0.1
        cf = 1.0  # cap = ceil(1.0 * 10 / 2) = 5 -> tokens 5..9 dropped
        y = moe.switch_moe(x, p["router"], p["w_gate"], p["w_up"],
                           p["w_down"], capacity_factor=cf)
        oracle = _dense_oracle(x, p)
        np.testing.assert_allclose(np.asarray(y[:5]), np.asarray(oracle[:5]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(y[5:]), 0.0, atol=1e-7)

    def test_aux_loss_balance(self):
        """Perfectly balanced routing gives aux ~= 1 (its minimum)."""
        E, D, F = 4, 8, 16
        p = _params(jax.random.PRNGKey(0), E, D, F)
        p["router"] = jnp.eye(D, E) * 100.0  # token i%... route by argmax dim
        # Tokens one-hot on dims 0..E-1 in equal numbers -> balanced.
        x = jnp.tile(jnp.eye(E, D), (3, 1)).astype(jnp.float32)
        _, aux = moe.switch_moe(x, p["router"], p["w_gate"], p["w_up"],
                                p["w_down"], capacity_factor=4.0,
                                return_aux=True)
        np.testing.assert_allclose(float(aux), 1.0, atol=0.05)

    def test_sort_dispatch_identical_to_cumsum(self):
        """The sort-based fast dispatch must reproduce the cumsum oracle
        EXACTLY — outputs, every gradient, and the drop pattern (stable
        sort preserves each expert's arrival order) — both dropless and
        under forced overflow."""
        E, D, F, T = 4, 16, 32, 24
        p = _params(jax.random.PRNGKey(0), E, D, F)

        def run(x, cf, dispatch):
            def loss(p):
                y, aux = moe.switch_moe(
                    x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                    capacity_factor=cf, dispatch=dispatch, return_aux=True)
                return jnp.sum(y ** 2) + 0.1 * aux, y

            (l, y), g = jax.value_and_grad(loss, has_aux=True)(p)
            return l, y, g

        x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
        for cf in (float(E), 1.0, 0.5):  # dropless, tight, overflowing
            l_s, y_s, g_s = run(x, cf, "sort")
            l_c, y_c, g_c = run(x, cf, "cumsum")
            np.testing.assert_array_equal(np.asarray(y_s), np.asarray(y_c))
            np.testing.assert_allclose(float(l_s), float(l_c), rtol=1e-7)
            for k in p:
                np.testing.assert_allclose(
                    np.asarray(g_s[k]), np.asarray(g_c[k]),
                    atol=1e-6, rtol=1e-6, err_msg=f"cf={cf} {k}")

    def test_sort_dispatch_ep2_matches_local(self):
        """Sort dispatch under the ep all_to_all exchange (the buffer
        contract is dispatch-mechanism independent)."""
        E, D, F, T_loc, EP = 4, 16, 32, 12, 2
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jax.random.normal(jax.random.PRNGKey(1), (EP, T_loc, D))
        mesh = Mesh(np.array(jax.devices()[:EP]), axis_names=("ep",))

        out = jax.jit(jax.shard_map(
            lambda x, r, wg, wu, wd: moe.switch_moe(
                x[0], r, wg, wu, wd, capacity_factor=1.25, axis_name="ep",
                dispatch="sort")[None],
            mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep")))(
            x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        for s in range(EP):
            ref = moe.switch_moe(x[s], p["router"], p["w_gate"], p["w_up"],
                                 p["w_down"], capacity_factor=1.25,
                                 dispatch="cumsum")
            np.testing.assert_allclose(np.asarray(out[s]), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_bad_dispatch_raises(self):
        p = _params(jax.random.PRNGKey(0), 2, 8, 16)
        with pytest.raises(ValueError, match="dispatch"):
            moe.switch_moe(jnp.zeros((4, 8)), p["router"], p["w_gate"],
                           p["w_up"], p["w_down"], dispatch="bogus")

    def test_flops_flat_in_experts(self):
        """The headline claim, statically: dense dispatch FLOPs grow with
        E; switch dispatch FLOPs stay ~flat (total expert compute is
        cf*T*FFN regardless of E)."""
        D, F, T = 64, 128, 256

        def flops(fn, *args):
            # _cost_dict normalizes the list-wrapped cost_analysis()
            # shape older jax returns — the ONE copy of that rule
            from horovod_tpu.obs.xprof import _cost_dict

            c = jax.jit(fn).lower(*args).compile()
            return _cost_dict(c)["flops"]

        def sparse(E):
            p = _params(jax.random.PRNGKey(0), E, D, F)
            x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
            return flops(
                lambda x: moe.switch_moe(
                    x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                    capacity_factor=1.25), x)

        def dense(E):
            p = _params(jax.random.PRNGKey(0), E, D, F)
            x = jax.random.normal(jax.random.PRNGKey(1), (T, D))
            return flops(lambda x: _dense_oracle(x, p), x)

        s2, s8 = sparse(2), sparse(8)
        d2, d8 = dense(2), dense(8)
        assert d8 > d2 * 3, (d2, d8)  # dense: ~linear in E
        assert s8 < s2 * 1.5, (s2, s8)  # switch: ~flat in E
        assert s8 < d8 / 2.5, (s8, d8)  # and far below dense at E=8


class TestDroplessMoE:
    def test_matches_dense_oracle_outputs_and_grads(self):
        """Grouped ragged-matmul dispatch is EXACT (nothing dropped): it
        must match the dense every-expert oracle at 1/E of its FLOPs —
        the serving/prefill dispatch."""
        E, D, F, T = 4, 16, 32, 24
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

        def loss_dl(p):
            return jnp.sum(moe.dropless_moe(
                x, p["router"], p["w_gate"], p["w_up"], p["w_down"]) ** 2)

        def loss_dense(p):
            return jnp.sum(_dense_oracle(x, p) ** 2)

        l_d, g_d = jax.value_and_grad(loss_dl)(p)
        l_o, g_o = jax.value_and_grad(loss_dense)(p)
        np.testing.assert_allclose(float(l_d), float(l_o), rtol=1e-5)
        for k in p:
            np.testing.assert_allclose(
                np.asarray(g_d[k]), np.asarray(g_o[k]),
                atol=1e-4, rtol=1e-4, err_msg=k)

    def test_skewed_routing_still_exact(self):
        """All tokens on one expert — the case capacity dispatch drops;
        dropless must still equal the oracle."""
        E, D, F, T = 2, 8, 16, 10
        p = _params(jax.random.PRNGKey(0), E, D, F)
        p["router"] = jnp.eye(D, E) * 50.0
        x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (T, D)))
        y = moe.dropless_moe(x, p["router"], p["w_gate"], p["w_up"],
                             p["w_down"])
        np.testing.assert_allclose(np.asarray(y), np.asarray(_dense_oracle(x, p)),
                                   atol=1e-5, rtol=1e-5)

    def test_dropless_flops_fraction_of_dense(self):
        """Static cost: dropless FFN FLOPs must be ~1/E of dense's.

        Platform-dependent: the TPU lowering of ragged_dot is truly
        grouped (measured on chip: 2.1 GF vs dense's 17.2 GF at E=8 —
        docs/benchmarks.md), but the CPU lowering masks full matmuls, so
        the assertion only holds off-CPU.  The exactness tests above run
        everywhere."""
        if jax.default_backend() == "cpu":
            pytest.skip("CPU lowers ragged_dot to masked dense matmuls; "
                        "the 1/E cost claim is asserted on TPU")
        E, D, F, T = 8, 64, 128, 256
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, D))

        def flops(fn):
            from horovod_tpu.obs.xprof import _cost_dict

            return _cost_dict(jax.jit(fn).lower(x).compile())["flops"]

        fd = flops(lambda x: _dense_oracle(x, p))
        fl = flops(lambda x: moe.dropless_moe(
            x, p["router"], p["w_gate"], p["w_up"], p["w_down"]))
        assert fl < fd / (E / 2), (fl, fd)


class TestSwitchDispatchExpertParallel:
    EP = 2

    def _shard_run(self, x_shards, p, cf, with_grad=False):
        """Run switch_moe under shard_map: experts sharded over ep, each
        device owning its token shard."""
        E = p["router"].shape[1]
        mesh = Mesh(np.array(jax.devices()[:self.EP]), axis_names=("ep",))

        def inner(x, router, wg, wu, wd):
            return moe.switch_moe(x[0], router, wg, wu, wd,
                                  capacity_factor=cf, axis_name="ep")[None]

        fn = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep"))
        args = (x_shards, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        if not with_grad:
            return jax.jit(fn)(*args)

        def loss(wg, wu, wd, router):
            y = fn(x_shards, router, wg, wu, wd)
            return jnp.sum(y ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            p["w_gate"], p["w_up"], p["w_down"], p["router"])

    @pytest.mark.slow
    def test_ep2_matches_local_dispatch(self):
        """ep=2 all_to_all dispatch == per-shard local dispatch (drops
        depend only on the shard-local token order), outputs and grads."""
        E, D, F, T_loc = 4, 16, 32, 12
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jax.random.normal(jax.random.PRNGKey(1), (self.EP, T_loc, D))
        cf = 1.25

        out = self._shard_run(x, p, cf)
        for s in range(self.EP):
            ref = moe.switch_moe(x[s], p["router"], p["w_gate"], p["w_up"],
                                 p["w_down"], capacity_factor=cf)
            np.testing.assert_allclose(np.asarray(out[s]), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

        l_ep, g_ep = self._shard_run(x, p, cf, with_grad=True)

        def loss_local(wg, wu, wd, router):
            tot = 0.0
            for s in range(self.EP):
                y = moe.switch_moe(x[s], router, wg, wu, wd,
                                   capacity_factor=cf)
                tot = tot + jnp.sum(y ** 2)
            return tot

        l_ref, g_ref = jax.value_and_grad(loss_local, argnums=(0, 1, 2, 3))(
            p["w_gate"], p["w_up"], p["w_down"], p["router"])
        np.testing.assert_allclose(float(l_ep), float(l_ref), rtol=1e-5)
        for a, b, name in zip(g_ep, g_ref, ("w_gate", "w_up", "w_down",
                                            "router")):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4, err_msg=name)

    def test_ep_path_emits_all_to_all(self):
        """The exchange must be a true all_to_all in the compiled HLO —
        the ep axis shards compute, not just storage."""
        E, D, F, T_loc = 4, 16, 32, 8
        p = _params(jax.random.PRNGKey(0), E, D, F)
        x = jnp.zeros((self.EP, T_loc, D))
        mesh = Mesh(np.array(jax.devices()[:self.EP]), axis_names=("ep",))

        fn = jax.jit(jax.shard_map(
            lambda x, r, wg, wu, wd: moe.switch_moe(
                x[0], r, wg, wu, wd, capacity_factor=1.25,
                axis_name="ep")[None],
            mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep")))
        hlo = fn.lower(x, p["router"], p["w_gate"], p["w_up"],
                       p["w_down"]).compile().as_text()
        assert "all-to-all" in hlo, hlo[:2000]


class TestModelSwitchMoE:
    def _cfg(self, **kw):
        import dataclasses

        from horovod_tpu.models import transformer as T

        base = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=16, n_experts=4, dtype=jnp.float32,
            attention_impl="reference")
        return T, dataclasses.replace(base, **kw)

    def test_forward_switch_vs_dense_no_drops(self):
        """Model-level: switch dispatch with dropless capacity equals the
        dense oracle forward."""
        import dataclasses

        T, cfg = self._cfg(capacity_factor=4.0)  # cf = E -> dropless
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        out_s = T.forward(params, tokens, cfg)
        out_d = T.forward(params, tokens,
                          dataclasses.replace(cfg, moe_impl="dense"))
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d),
                                   atol=1e-4, rtol=1e-4)

    def test_forward_with_drops_diverges_from_dense_but_stays_finite(self):
        """When capacity drops DO occur (biased router, tight capacity),
        switch forward legitimately diverges from the dense oracle (the
        dropped tokens' MLP contributions are gone) but must stay finite
        — the documented training-time behavior."""
        import dataclasses

        T, cfg = self._cfg(capacity_factor=0.5)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        # Bias every layer's router hard toward expert 0 -> guaranteed
        # overflow at cf=0.5.
        L, D, E = params["layers"]["router"].shape
        params["layers"]["router"] = (
            jnp.zeros((L, D, E)).at[:, :, 0].set(10.0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
        out_s = T.forward(params, tokens, cfg)
        out_d = T.forward(params, tokens,
                          dataclasses.replace(cfg, moe_impl="dense"))
        assert np.isfinite(np.asarray(out_s)).all()
        assert not np.allclose(np.asarray(out_s), np.asarray(out_d),
                               atol=1e-4), "drops must be observable"

    def test_bad_impl_raises(self):
        T, cfg = self._cfg(moe_impl="bogus")
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="moe_impl"):
            T.forward(params, tokens, cfg)


class TestModelAuxLoss:
    """The Switch balance term wired into the FLAGSHIP training loss
    (cfg.moe_aux_coeff), and the routed-fraction observability that
    proves it keeps the router from collapsing."""

    def _cfg(self, **kw):
        import dataclasses

        from horovod_tpu.models import transformer as T

        base = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=16, n_experts=4, dtype=jnp.float32,
            attention_impl="reference")
        return T, dataclasses.replace(base, **kw)

    def test_loss_fn_adds_exactly_coeff_times_aux(self):
        """loss_fn(coeff) == loss_fn(0) + coeff * sum-of-layer-aux — the
        wiring is arithmetic, not approximate."""
        import dataclasses

        T, cfg = self._cfg(moe_aux_coeff=0.0)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = T.synthetic_batch(1, cfg, batch=4)
        base = float(T.loss_fn(params, batch, cfg))
        _, aux = T.forward(params, batch["tokens"], cfg, return_aux=True)
        with_aux = float(T.loss_fn(
            params, batch, dataclasses.replace(cfg, moe_aux_coeff=0.02)))
        np.testing.assert_allclose(
            with_aux, base + 0.02 * float(aux), rtol=1e-6)

    def test_aux_nonzero_for_moe_zero_for_dense(self):
        T, cfg = self._cfg()
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = T.synthetic_batch(1, cfg, batch=2)
        _, aux = T.forward(params, batch["tokens"], cfg, return_aux=True)
        assert float(aux) >= 2.0 - 1e-4  # >= n_layers * 1.0 (min per layer)

        Td, dcfg = self._cfg(n_experts=0)
        dparams = Td.init_params(jax.random.PRNGKey(0), dcfg)
        _, daux = Td.forward(dparams, batch["tokens"], dcfg, return_aux=True)
        assert float(daux) == 0.0

    def test_router_gradient_flows_from_aux(self):
        """With every token hard-routed to one expert, the plain LM loss
        gives the router no balance pressure; the aux term must produce a
        router gradient pushing load off the overloaded expert."""
        import dataclasses

        T, cfg = self._cfg(moe_aux_coeff=0.01, capacity_factor=1.0)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        L, D, E = params["layers"]["router"].shape
        params["layers"]["router"] = (
            jnp.asarray(params["layers"]["router"]).at[:, :, 0].add(3.0))
        batch = T.synthetic_batch(1, cfg, batch=4)
        g = jax.grad(lambda p: T.loss_fn(p, batch, cfg))(params)
        g0 = np.asarray(g["layers"]["router"])[:, :, 0]
        assert np.abs(g0).max() > 0, "aux must reach the router"

    @pytest.mark.slow
    def test_training_with_aux_keeps_load_uniform(self):
        """Train a small switch model under TIGHT capacity (cf=1.0, where
        every point of imbalance costs dropped tokens): with the aux term
        the routed-fraction histogram stays near uniform; the no-aux
        control drifts measurably less balanced.  (A linear bias-free
        router cannot be force-collapsed deterministically at this scale
        — rmsnorm'd activations kill constant logit offsets — so the
        assertion is the measured uniformity GAP, not a staged
        collapse.)"""
        import dataclasses

        import optax

        T, cfg0 = self._cfg(capacity_factor=1.0)
        rng = np.random.RandomState(0)
        toks = rng.randint(0, 64, size=(8, 16)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks),
                 "targets": jnp.asarray(np.roll(toks, -1, 1))}

        def train(coeff, steps=200):
            cfg = dataclasses.replace(cfg0, moe_aux_coeff=coeff)
            params = T.init_params(jax.random.PRNGKey(0), cfg)
            opt = optax.adam(1e-2)
            state = opt.init(params)

            @jax.jit
            def step(params, state):
                loss, g = jax.value_and_grad(
                    lambda p: T.loss_fn(p, batch, cfg))(params)
                up, state = opt.update(g, state, params)
                return optax.apply_updates(params, up), state, loss

            for _ in range(steps):
                params, state, loss = step(params, state)
            assert np.isfinite(float(loss))
            return np.asarray(T.expert_load(params, batch["tokens"], cfg))

        load_aux = train(0.02)
        load_ctrl = train(0.0)
        E = cfg0.n_experts
        # Aux run: near-uniform (ideal 1/E = 0.25) — no expert hoards,
        # every expert carries real load in every layer.
        assert load_aux.max() < 0.32, load_aux
        assert load_aux.min() > 0.10, load_aux
        # Control: measurably less balanced than the aux run.
        assert load_ctrl.max() > load_aux.max() + 0.02, (load_ctrl, load_aux)


def _spread(E, touched, rows):
    """``rows`` rows over ``touched`` of ``E`` experts, spread evenly."""
    counts = np.zeros(E, np.int32)
    own = np.linspace(0, E - 1, touched).round().astype(int)
    counts[own] = rows // touched
    counts[own[:rows % touched]] += 1
    return counts


# name: (M, tm, K, N, counts, real items)
GROUPED_WALKS = {
    # A.X-K1's tick: 32 slots x 8 picks, 16 rows on 9 of the 12 held
    "axk1_tick": (256, 32, 7168, 2048, _spread(12, 9, 16), 9),
    # DeepSeek-V3.2-Exp's tick: 24 slots x 8 picks, 3 of the 8 held
    "dsv32_tick": (192, 32, 7168, 2048, _spread(8, 3, 5), 3),
    # a chunk of 512 x 8 picks, 258 rows HERE: experts 5 and 11
    # straddle a row tile of 128 and are visited once in each
    "chunk": (4096, 128, 7168, 2048, _spread(12, 12, 258), 14),
    "one_expert": (256, 32, 2048, 7168,
                   np.eye(12, dtype=np.int32)[4] * 256, 8),
    "no_row": (256, 32, 7168, 2048, np.zeros(12, np.int32), 0),
    # Mellum2: a matrix is ONE block, the maps are the parent's
    "mellum2_tick": (256, 32, 2304, 896, _spread(64, 40, 256), None),
}


class TestGroupedProduct:
    @pytest.mark.parametrize("case", sorted(GROUPED_WALKS))
    def test_only_an_item_that_owns_a_row_asks_for_a_transfer(self, case):
        """Walk ``grouped_matmul``'s grid as the pipeline does and count
        the steps whose block is not the one the step before left
        resident: each real item's runs, and nothing for the
        ``m_tiles + E - 1 - num`` items that run no product (with ``k``
        left to walk there, each fetched the last expert's whole matrix
        again: 19 matrices a call in A.X-K1's tick, not 9)."""
        M, tm, K, N, counts, items = GROUPED_WALKS[case]
        E, m_tiles = len(counts), -(-M // tm)
        k_tiles = K // moe._k_tile(K, N, 2)
        scalars = (np.array([1]),) + tuple(
            np.asarray(a) for a in
            moe._work_items(jnp.asarray(counts), m_tiles, tm))
        num = int(scalars[-1][0])
        t, k = (a.ravel() for a in np.meshgrid(
            np.arange(m_tiles + E - 1), np.arange(k_tiles), indexing="ij"))

        def transfers(index_map):
            blocks = np.stack(np.broadcast_arrays(
                *(np.asarray(i) for i in index_map(t, k, *scalars))), 1)
            return 1 + np.count_nonzero(
                (blocks[1:] != blocks[:-1]).any(axis=1))

        matrix = transfers(functools.partial(moe._matrix_block,
                                             k_tiles=k_tiles))
        rows = transfers(functools.partial(moe._rows_block,
                                           k_tiles=k_tiles))
        if items is None:
            # one run a matrix: consecutive items of one expert share
            # it, as they did before the runs came
            assert k_tiles == 1
            assert matrix == np.count_nonzero(counts)
            assert matrix == transfers(
                lambda t, k, l, o, g, m, n: (l[0], g[t], k, 0))
            assert rows == transfers(
                lambda t, k, l, o, g, m, n: (m[t], k))
        else:
            assert k_tiles > 1 and num == items
            assert matrix == rows == max(items * k_tiles, 1)

    @pytest.mark.parametrize("counts", [
        (0, 20, 0, 30, 5, 0),        # empty experts, first and last too
        (0, 0, 0, 0, 0, 0),          # no pick landed here
        (0, 0, 70, 0, 0, 0),         # one expert over three row tiles
        (16, 16, 16, 16, 16, 16),    # every row taken, tiles shared
    ])
    def test_runs_and_skipped_items_together_give_the_same_products(
            self, monkeypatch, counts):
        """A matrix in 4 runs, items skipped: every row of a visited
        tile is its expert's ``x @ w[layer, e]``, or zero past the
        groups.  Whole numbers, so that no order of summation shows."""
        M, K, N, E, tm = 96, 512, 64, 6, 16
        monkeypatch.setattr(moe, "_EXPERT_BLOCK_BYTES", 128 * N * 2)
        assert K // moe._k_tile(K, N, 2) == 4
        rng = np.random.RandomState(sum(counts))
        x = rng.randint(-3, 4, (M, K)).astype(np.float32)
        w = rng.randint(-3, 4, (3, E, K, N)).astype(np.float32)
        got = np.asarray(moe.grouped_matmul(
            jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16), 1,
            jnp.asarray(counts, jnp.int32)), np.float32)
        want = np.zeros((M, N), np.float32)
        ends = np.cumsum(counts)
        for e in range(E):
            rows = slice(ends[e] - counts[e], ends[e])
            want[rows] = np.asarray(jnp.asarray(x[rows] @ w[1, e],
                                                jnp.bfloat16), np.float32)
        visited = -(-ends[-1] // tm) * tm     # the tiles that hold a row
        np.testing.assert_array_equal(got[:visited], want[:visited])

    @pytest.mark.parametrize("held_offset", [None, 0, 4])
    def test_a_share_over_the_stack_is_ragged_dots_form(self, monkeypatch,
                                                        held_offset):
        """``dropless_moe(layer=)`` over matrices that come in runs, with
        experts of the share left empty, against the ``lax.ragged_dot``
        form over that layer's slice."""
        D, F, T, k = 256, 256, 6, 2
        E, held = 8, 8 if held_offset is None else 4
        monkeypatch.setattr(moe, "_EXPERT_BLOCK_BYTES", 128 * 256 * 4)
        assert D // moe._k_tile(D, F, 4) == 2
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(T, D), jnp.float32)
        router = jnp.asarray(rng.randn(D, E), jnp.float32)
        wg, wu = (jnp.asarray(rng.randn(3, held, D, F) / 16, jnp.float32)
                  for _ in "ab")
        wd = jnp.asarray(rng.randn(3, held, F, D) / 16, jnp.float32)
        kw = dict(k=k, norm_topk=True, held_offset=held_offset,
                  token_mask=jnp.asarray(rng.rand(T) > 0.3),
                  return_counts=True)
        got, counts = moe.dropless_moe(x, router, wg, wu, wd, layer=2, **kw)
        want, counts_r = moe.dropless_moe(x, router, wg[2], wu[2], wd[2],
                                          **kw)
        np.testing.assert_array_equal(np.asarray(counts),
                                      np.asarray(counts_r))
        assert 0 in np.asarray(counts) and np.asarray(counts).sum() > 2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
