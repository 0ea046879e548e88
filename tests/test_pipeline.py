"""True pipeline parallelism (GPipe microbatching over pp via ppermute):
outputs and gradients must match plain sequential layer application."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd  # noqa: F401 — device count setup via conftest
from horovod_tpu.parallel import pipeline

NDEV = 8


def _mesh(p):
    return Mesh(np.array(jax.devices()[:p]), axis_names=("pp",))


def _stage_fn(w_stack, x):
    """One stage = a scan over this stage's layer weights (tanh MLP)."""
    def layer(h, w):
        return jnp.tanh(h @ w), None

    out, _ = jax.lax.scan(layer, x, w_stack)
    return out


def _assert_grad_trees_match(g, g_ref, *, atol=2e-4, rtol=2e-4):
    """Leaf-for-leaf gradient comparison with path-keyed lookup and a
    structure check (zip would silently truncate on tree mismatch)."""
    flat_pipe = dict(jax.tree_util.tree_leaves_with_path(g))
    flat_ref = jax.tree_util.tree_leaves_with_path(g_ref)
    assert set(flat_pipe) == {p for p, _ in flat_ref}
    for path, ref_leaf in flat_ref:
        np.testing.assert_allclose(
            np.asarray(flat_pipe[path]), np.asarray(ref_leaf),
            atol=atol, rtol=rtol, err_msg=jax.tree_util.keystr(path))


EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _ep_shard_params(pr, n_experts, ep):
    """Slice this device's resident experts out of the replicated stacks
    (layer layout ``(L, E, ...)``, experts on axis 1)."""
    e = jax.lax.axis_index("ep")
    e_loc = n_experts // ep
    return {**pr, "layers": {
        k: (jax.lax.dynamic_slice_in_dim(v, e * e_loc, e_loc, 1)
            if k in EXPERT_KEYS else v)
        for k, v in pr["layers"].items()}}


def _ep_unshard_grads(grads, n_experts, ep):
    """Reassemble full-model grads from ep-resident pieces: resident-
    expert grads are COMPLETE (every token's cotangent returns through
    the all_to_all), so psum assembles the stack and /ep matches the
    pmean-over-ep loss scaling applied to the non-expert params."""
    e = jax.lax.axis_index("ep")
    e_loc = n_experts // ep

    def unshard(k, gv):
        if k in EXPERT_KEYS:
            full = jnp.zeros((gv.shape[0], n_experts) + gv.shape[2:],
                             gv.dtype)
            full = jax.lax.dynamic_update_slice_in_dim(full, gv,
                                                       e * e_loc, 1)
            return jax.lax.psum(full, "ep") / ep
        return jax.lax.pmean(gv, "ep")

    lg = {k: unshard(k, v) for k, v in grads["layers"].items()}
    return {**{k: jax.tree_util.tree_map(
        lambda x: jax.lax.pmean(x, "ep"), v)
        for k, v in grads.items() if k != "layers"}, "layers": lg}


def _sequential(w_all, x):
    def layer(h, w):
        return jnp.tanh(h @ w), None

    out, _ = jax.lax.scan(layer, x, w_all)
    return out


class TestPipelineApply:
    @pytest.mark.parametrize("p,layers,m", [(4, 8, 4), (8, 8, 2), (2, 6, 5)])
    def test_matches_sequential(self, p, layers, m):
        d = 16
        key = jax.random.PRNGKey(0)
        w_all = jax.random.normal(key, (layers, d, d)) * (0.5 / np.sqrt(d))
        mb = 3
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))

        staged = pipeline.stack_to_stages(w_all, p)
        mesh = _mesh(p)

        def run(staged, x):
            def inner(wst, xs):
                return pipeline.pipeline_apply(
                    _stage_fn, wst[0], xs, axis_name="pp")

            return jax.jit(jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P("pp"), P()),
                out_specs=P(),
            ))(staged, x)

        out = run(staged, x)
        ref = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_gradients_match_sequential(self):
        p, layers, m, mb, d = 4, 8, 4, 2, 8
        w_all = jax.random.normal(jax.random.PRNGKey(0), (layers, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
        mesh = _mesh(p)

        def loss_pipe(w_all, x):
            staged = pipeline.stack_to_stages(w_all, p)

            def inner(wst, xs):
                out = pipeline.pipeline_apply(
                    _stage_fn, wst[0], xs, axis_name="pp")
                return jnp.sum(out ** 2)

            return jax.shard_map(
                inner, mesh=mesh, in_specs=(P("pp"), P()),
                out_specs=P(),
            )(staged, x)

        def loss_seq(w_all, x):
            out = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
            return jnp.sum(out ** 2)

        g_pipe = jax.jit(jax.grad(loss_pipe))(w_all, x)
        g_seq = jax.grad(loss_seq)(w_all, x)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                                   atol=1e-4, rtol=1e-4)

    def test_indivisible_layers_raise(self):
        w_all = jnp.zeros((7, 4, 4))
        with pytest.raises(ValueError, match="divide"):
            pipeline.stack_to_stages(w_all, 4)


class TestInterleavedApply:
    @pytest.mark.parametrize("p,v,layers,m", [(4, 2, 8, 4), (2, 3, 6, 4),
                                              (4, 1, 4, 8)])
    def test_matches_sequential(self, p, v, layers, m):
        """The virtual-stage schedule must be a pure re-scheduling: same
        outputs as sequential application, for v in {1, 2, 3}."""
        d = 16
        w_all = jax.random.normal(
            jax.random.PRNGKey(0), (layers, d, d)) * (0.5 / np.sqrt(d))
        x = jax.random.normal(jax.random.PRNGKey(1), (m, 3, d))
        mesh = _mesh(p)

        def inner(w_full, xs):
            s = jax.lax.axis_index("pp")
            chunks = pipeline.stack_to_chunks(w_full, p, v, s)
            return pipeline.interleaved_apply(
                _stage_fn, chunks, xs, axis_name="pp", n_virtual=v)

        out = jax.jit(jax.shard_map(
            inner, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        ))(w_all, x)
        ref = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_gradients_match_sequential(self):
        p, v, layers, m, mb, d = 4, 2, 8, 4, 2, 8
        w_all = jax.random.normal(jax.random.PRNGKey(0), (layers, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
        mesh = _mesh(p)

        def loss_pipe(w_all, x):
            def inner(w_full, xs):
                s = jax.lax.axis_index("pp")
                chunks = pipeline.stack_to_chunks(w_full, p, v, s)
                out = pipeline.interleaved_apply(
                    _stage_fn, chunks, xs, axis_name="pp", n_virtual=v)
                # Gate to the last chunk's device so the replicated-stack
                # VJP psum sums one real contribution with zeros.
                raw = jnp.sum(out ** 2)
                return jax.lax.psum(
                    jnp.where(s == p - 1, raw, 0.0), "pp")

            return jax.shard_map(
                inner, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            )(w_all, x)

        def loss_seq(w_all, x):
            out = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
            return jnp.sum(out ** 2)

        g_pipe = jax.jit(jax.grad(loss_pipe))(w_all, x)
        g_seq = jax.grad(loss_seq)(w_all, x)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_seq),
                                   atol=1e-4, rtol=1e-4)

    def test_microbatch_divisibility_enforced(self):
        mesh = _mesh(4)
        w = jnp.zeros((8, 4, 4))
        x = jnp.zeros((6, 2, 4))  # 6 % 4 != 0

        def inner(w_full, xs):
            s = jax.lax.axis_index("pp")
            chunks = pipeline.stack_to_chunks(w_full, 4, 2, s)
            return pipeline.interleaved_apply(
                _stage_fn, chunks, xs, axis_name="pp", n_virtual=2)

        with pytest.raises(ValueError, match="divisible"):
            jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            ))(w, x)


def _loss_fn(y, tgt):
    return jnp.sum((y - tgt) ** 2)


class TestPipeline1F1B:
    def _run_schedule(self, schedule, p, layers, m, mb=2, d=8):
        w_all = jax.random.normal(jax.random.PRNGKey(0), (layers, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
        tgt = jax.random.normal(jax.random.PRNGKey(2), (m, mb, d)) * 0.1
        staged = pipeline.stack_to_stages(w_all, p)
        mesh = _mesh(p)

        def inner(wst, xs, ts):
            loss, g = pipeline.pipeline_value_and_grad(
                _stage_fn, wst[0], xs, ts, _loss_fn, axis_name="pp",
                schedule=schedule)
            return loss, g[None]

        fn = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("pp"), P(), P()),
            out_specs=(P(), P("pp")),
        ))
        loss, g = fn(staged, x, tgt)
        return w_all, x, tgt, float(loss), np.asarray(g).reshape(w_all.shape)

    @pytest.mark.parametrize("p,layers,m", [(4, 8, 6), (2, 6, 5), (8, 8, 3)])
    def test_1f1b_exact_vs_sequential_and_gpipe(self, p, layers, m):
        """1F1B loss and EVERY stage gradient must match both the GPipe
        schedule and plain sequential autodiff."""
        w_all, x, tgt, loss_1, g_1 = self._run_schedule("1f1b", p, layers, m)

        def loss_seq(w_all):
            outs = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
            return jnp.sum(jax.vmap(_loss_fn)(outs, tgt))

        l_ref, g_ref = jax.value_and_grad(loss_seq)(w_all)
        np.testing.assert_allclose(loss_1, float(l_ref), rtol=1e-5)
        np.testing.assert_allclose(g_1, np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)

        _, _, _, loss_g, g_g = self._run_schedule("gpipe", p, layers, m)
        np.testing.assert_allclose(loss_1, loss_g, rtol=1e-5)
        np.testing.assert_allclose(g_1, g_g, atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    @pytest.mark.parametrize("with_lp,with_xg", [
        (True, True), (True, False), (False, True)])
    def test_loss_params_and_input_grads_exact(self, schedule, with_lp,
                                               with_xg):
        """loss_params (readout head) gradients and input cotangents from
        BOTH schedules must match direct autodiff — including the VMA
        subtlety that the VJP of a replicated operand inside shard_map
        implicitly psums over the axis (regression for the bug where
        non-last stages' garbage loss grads leaked into the sum)."""
        p, layers, m, mb, d = 4, 8, 6, 2, 8
        w_all = jax.random.normal(jax.random.PRNGKey(0), (layers, d, d)) * 0.3
        x = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
        tgt = jax.random.normal(jax.random.PRNGKey(2), (m, mb, d)) * 0.1
        head = jax.random.normal(jax.random.PRNGKey(3), (d, d)) * 0.5

        def lfn_lp(lp, y, t):
            return jnp.sum((y @ lp["head"] - t) ** 2)

        def lfn_plain(y, t):
            return lfn_lp({"head": head}, y, t)

        def ref():
            def loss(w_all, head, x):
                outs = jax.vmap(lambda xb: _sequential(w_all, xb))(x)
                return jnp.sum(jax.vmap(
                    lambda y, t: lfn_lp({"head": head}, y, t))(outs, tgt))

            return jax.value_and_grad(loss, argnums=(0, 1, 2))(w_all, head, x)

        staged = pipeline.stack_to_stages(w_all, p)
        mesh = _mesh(p)

        def inner(wst, xs, ts, lp):
            loss, g, ex = pipeline.pipeline_value_and_grad(
                _stage_fn, wst[0], xs, ts,
                lfn_lp if with_lp else lfn_plain, axis_name="pp",
                schedule=schedule,
                loss_params=lp if with_lp else None,
                return_input_grads=with_xg)
            lpg = (jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, "pp"), ex["loss_param_grads"])
                if with_lp else {"head": jnp.zeros_like(lp["head"])})
            xg = (jax.lax.psum(ex["input_grads"], "pp")
                  if with_xg else jnp.zeros_like(xs))
            assert set(ex) == ({"loss_param_grads"} if with_lp else set()) | (
                {"input_grads"} if with_xg else set())
            return loss, g[None], lpg, xg

        loss, g, lpg, xg = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("pp"), P(), P(), P()),
            out_specs=(P(), P("pp"), P(), P())))(staged, x, tgt,
                                                 {"head": head})
        l_ref, (gw_ref, gh_ref, gx_ref) = ref()
        np.testing.assert_allclose(float(loss), float(l_ref), rtol=1e-5)
        if with_lp:
            np.testing.assert_allclose(
                np.asarray(lpg["head"]), np.asarray(gh_ref),
                atol=1e-5, rtol=1e-5)
        if with_xg:
            np.testing.assert_allclose(np.asarray(xg), np.asarray(gx_ref),
                                       atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(g).reshape(w_all.shape), np.asarray(gw_ref),
            atol=1e-5, rtol=1e-5)

    def test_unknown_schedule_raises(self):
        mesh = _mesh(2)
        w = jnp.zeros((2, 1, 4, 4))
        x = jnp.zeros((2, 1, 4))
        t = jnp.zeros((2, 1, 4))
        with pytest.raises(ValueError, match="schedule"):
            jax.shard_map(
                lambda wst, xs, ts: pipeline.pipeline_value_and_grad(
                    _stage_fn, wst[0], xs, ts, _loss_fn, axis_name="pp",
                    schedule="bogus"),
                mesh=mesh, in_specs=(P("pp"), P(), P()),
                out_specs=(P(), P("pp")),
            )(w, x, t)

    def test_1f1b_memory_independent_of_m(self):
        """The 1F1B claim, MEASURED: raising M (16 vs 4) must leave the
        1F1B temp footprint ~flat (in-flight state is bounded by 2(P-1)
        stage inputs), while GPipe's autodiff footprint grows with M.
        Uses XLA's compiled memory analysis at M=16, P=4."""
        p, layers, mb, d = 4, 8, 8, 64

        def compiled_temp_bytes(schedule, m):
            w_all = jnp.zeros((layers, d, d))
            x = jnp.zeros((m, mb, d))
            tgt = jnp.zeros((m, mb, d))
            staged = pipeline.stack_to_stages(w_all, p)
            mesh = _mesh(p)

            def inner(wst, xs, ts):
                loss, g = pipeline.pipeline_value_and_grad(
                    _stage_fn, wst[0], xs, ts, _loss_fn, axis_name="pp",
                    schedule=schedule)
                return loss, g[None]

            fn = jax.jit(jax.shard_map(
                inner, mesh=mesh, in_specs=(P("pp"), P(), P()),
                out_specs=(P(), P("pp"))))
            c = fn.lower(staged, x, tgt).compile()
            return c.memory_analysis().temp_size_in_bytes

        gpipe_4 = compiled_temp_bytes("gpipe", 4)
        gpipe_16 = compiled_temp_bytes("gpipe", 16)
        f1b_4 = compiled_temp_bytes("1f1b", 4)
        f1b_16 = compiled_temp_bytes("1f1b", 16)

        # GPipe: autodiff saves every tick's residuals -> grows with M.
        assert gpipe_16 > gpipe_4 * 2, (gpipe_4, gpipe_16)
        # 1F1B: in-flight state bounded by pipeline depth, not M.  Allow
        # slack for the (M-proportional) microbatch INPUT buffers that any
        # schedule carries.
        assert f1b_16 < f1b_4 * 2, (f1b_4, f1b_16)
        # And at the benchmark point (M=16, P=4) 1F1B must be the smaller
        # footprint.
        assert f1b_16 < gpipe_16, (f1b_16, gpipe_16)


class TestPipelinedTransformerAPI:
    def _setup(self, p=4):
        from horovod_tpu.models import transformer as T

        cfg = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64,
            max_seq=16, dtype=jnp.float32)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = T.synthetic_batch(1, cfg, batch=4)
        return T, cfg, params, batch

    def test_forward_matches(self):
        p = 4
        T, cfg, params, batch = self._setup(p)
        ref = T.forward(params, batch["tokens"], cfg)
        mesh = _mesh(p)

        out = jax.jit(jax.shard_map(
            lambda pr, tk: T.pipelined_forward(pr, tk, cfg),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        ))(params, batch["tokens"])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b", "interleaved"])
    @pytest.mark.slow
    def test_value_and_grad_exact(self, schedule):
        """The pipelined loss AND every parameter gradient — embedding,
        per-layer, final norm, head — must equal jax.grad(loss_fn), for
        ALL THREE schedules (interleaved runs v=2 virtual stages)."""
        p = 4
        T, cfg, params, batch = self._setup(p)
        l_ref, g_ref = jax.value_and_grad(
            lambda pr: T.loss_fn(pr, batch, cfg))(params)
        mesh = _mesh(p)

        l_pipe, g_pipe = jax.jit(jax.shard_map(
            lambda pr, b: T.pipelined_value_and_grad(
                pr, b, cfg, schedule=schedule),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        ))(params, batch)
        np.testing.assert_allclose(float(l_pipe), float(l_ref), atol=1e-5)
        _assert_grad_trees_match(g_pipe, g_ref)

    def _moe_setup(self, p=4):
        import dataclasses

        from horovod_tpu.models import transformer as T

        cfg = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64,
            max_seq=16, dtype=jnp.float32, n_experts=4,
            capacity_factor=4.0,  # dropless: exactness vs loss_fn holds
            moe_aux_coeff=0.02)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        batch = T.synthetic_batch(1, cfg, batch=4)
        return dataclasses, T, cfg, params, batch

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    @pytest.mark.slow
    def test_moe_aux_value_and_grad_exact_m1(self, schedule):
        """With ONE microbatch the pipelined dispatch group equals the
        full batch, so the aux-bearing pipelined loss and every gradient
        (router included — the leaf only the aux term can reach evenly)
        must equal jax.grad of the aux-bearing loss_fn."""
        p = 4
        dataclasses, T, cfg, params, batch = self._moe_setup(p)
        l_ref, g_ref = jax.value_and_grad(
            lambda pr: T.loss_fn(pr, batch, cfg))(params)
        mesh = _mesh(p)

        l_pipe, g_pipe = jax.jit(jax.shard_map(
            lambda pr, b: T.pipelined_value_and_grad(
                pr, b, cfg, schedule=schedule, n_microbatches=1),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        ))(params, batch)
        np.testing.assert_allclose(float(l_pipe), float(l_ref), atol=1e-5)
        _assert_grad_trees_match(g_pipe, g_ref)

    @pytest.mark.slow
    def test_moe_aux_schedules_agree_and_reach_router(self):
        """For M>1 the aux is per dispatch group (mean over groups): the
        two schedules must agree with each other exactly, and the aux
        term must actually move the router gradient vs coeff=0."""
        p = 4
        dataclasses, T, cfg, params, batch = self._moe_setup(p)
        mesh = _mesh(p)

        def run(cfg_, schedule):
            return jax.jit(jax.shard_map(
                lambda pr, b: T.pipelined_value_and_grad(
                    pr, b, cfg_, schedule=schedule, n_microbatches=4),
                mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            ))(params, batch)

        l_g, g_g = run(cfg, "gpipe")
        l_f, g_f = run(cfg, "1f1b")
        np.testing.assert_allclose(float(l_g), float(l_f), atol=1e-5)
        _assert_grad_trees_match(g_g, g_f)

        cfg0 = dataclasses.replace(cfg, moe_aux_coeff=0.0)
        _, g_0 = run(cfg0, "1f1b")
        diff = np.abs(np.asarray(g_f["layers"]["router"])
                      - np.asarray(g_0["layers"]["router"])).max()
        assert diff > 1e-7, "aux term must reach the router gradient"


def _run_composition_worker(mode: str):
    """Spawn tests/composition_worker.py in a SUBPROCESS: the XLA CPU
    runtime's collective rendezvous accumulates state across the several
    distinct multi-axis meshes a full-suite process builds and aborts
    (each composition passes standalone in its own process — a backend
    limitation, not a framework one).  The worker shares the ep
    shard/unshard helpers and gradient assertions with this module."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "PYTHONPATH": repo,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    }
    out = subprocess.run(
        [sys.executable,
         os.path.join(repo, "tests", "composition_worker.py"), mode],
        env=env, capture_output=True, text=True, timeout=500)
    assert out.returncode == 0, out.stdout + out.stderr
    assert f"COMPOSITION-{mode.upper()}-OK" in out.stdout, out.stdout


class TestPipelineCompositions:
    """1F1B composed with the other parallelism axes, each loss- and
    gradient-exact vs the unsharded single-device reference model (see
    composition_worker.py for the mesh arrangements)."""

    @pytest.mark.slow
    def test_1f1b_ring_attention_pp_x_sp_exact(self):
        """(pp, sp): ring K/V shards ppermute over sp within each
        pipeline stage while microbatch activations ppermute over pp."""
        _run_composition_worker("sp")

    @pytest.mark.slow
    def test_1f1b_switch_moe_pp_x_ep_exact(self):
        """(pp, ep): ep shards BOTH the batch (dp-style) and the experts
        — each device dispatches ITS tokens to resident experts via the
        all_to_all inside every stage."""
        _run_composition_worker("ep")

    @pytest.mark.slow
    def test_interleaved_ring_pp_x_sp_exact(self):
        """INTERLEAVED schedule (v=2 virtual stages) composed with ring
        attention over sp — the bubble-divided schedule is as composable
        as 1F1B."""
        _run_composition_worker("sp_interleaved")

    @pytest.mark.slow
    def test_1f1b_zigzag_ring_pp_x_sp_exact(self):
        """1F1B composed with the ZIGZAG (causal load-balanced) ring."""
        _run_composition_worker("sp_zigzag")

    @pytest.mark.slow
    def test_1f1b_ring_moe_pp_x_sp_x_ep_exact(self):
        """(pp, sp, ep): all three in one shard_map."""
        _run_composition_worker("triple")


class TestPipelineTransformerStage:
    def test_transformer_blocks_pipelined(self):
        """Pipeline the transformer's scanned layers: pp=4 stages of 2
        layers each must reproduce the plain forward."""
        import dataclasses

        from horovod_tpu.models import transformer as T

        cfg = T.TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64,
            max_seq=16, dtype=jnp.float32)
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
        ref = T.forward(params, tokens, cfg)

        p = 4
        mesh = _mesh(p)
        x_emb = params["embed"][tokens]  # (B, S, D) pre-layer activations
        mb = jnp.reshape(x_emb, (4, 1) + x_emb.shape[1:])  # M=4, mb=1

        def stage_fn(stage_layers, x):
            def body(h, lp):
                h2 = T._attention(T._rmsnorm(h, lp["ln1"]), lp, cfg)
                h = h + h2
                return h + T._dense_mlp(T._rmsnorm(h, lp["ln2"]), lp, cfg), None

            out, _ = jax.lax.scan(body, x, stage_layers)
            return out

        staged = pipeline.stack_to_stages(params["layers"], p)

        def inner(wst, xs):
            mine = jax.tree_util.tree_map(lambda l: l[0], wst)
            return pipeline.pipeline_apply(stage_fn, mine, xs,
                                           axis_name="pp")

        out = jax.jit(jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("pp"), P()),
            out_specs=P(),
        ))(staged, mb)
        out = jnp.reshape(out, x_emb.shape)
        out = T._rmsnorm(out, params["ln_f"])
        logits = jnp.einsum("bsd,dv->bsv", out, params["head"]).astype(
            jnp.float32)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
