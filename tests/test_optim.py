"""DistributedOptimizer / DistributedGradientTape / fusion tests
(reference: test_torch.py optimizer tests, test_tensorflow.py
DistributedGradientTape tests, backward_passes_per_step)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import spmd
from horovod_tpu.ops import fusion

N = 8


def _loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def _data(seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(N * 4, 3).astype(np.float32)
    w = np.array([[1.0], [-2.0], [0.5]], np.float32)
    y = x @ w + 0.1 * rng.randn(N * 4, 1).astype(np.float32)
    return x, y


def _params():
    return {
        "w": jnp.zeros((3, 1), jnp.float32),
        "b": jnp.zeros((1,), jnp.float32),
    }


class TestDistributedOptimizer:
    def test_matches_global_batch_sgd(self):
        """DP train step with DistributedOptimizer == single-worker step on
        the full batch (the defining correctness property of gradient
        averaging)."""
        x, y = _data()
        params = _params()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = spmd.make_train_step(_loss, opt, donate=False)
        opt_state = opt.init(params)
        p2, _, loss = step(params, opt_state, (x, y))

        # Single-process oracle on the full batch:
        g = jax.grad(_loss)(params, (x, y))
        expect = jax.tree_util.tree_map(lambda p, gg: p - 0.1 * gg, params, g)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(expect[k]), rtol=1e-4, atol=1e-5
            )
        assert np.isfinite(float(loss))

    def test_sum_op_scales(self):
        x, y = _data()
        params = _params()
        opt_avg = hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Average)
        opt_sum = hvd.DistributedOptimizer(optax.sgd(0.1 / N), op=hvd.Sum)
        s_avg = spmd.make_train_step(_loss, opt_avg, donate=False)
        s_sum = spmd.make_train_step(_loss, opt_sum, donate=False)
        pa, _, _ = s_avg(params, opt_avg.init(params), (x, y))
        ps, _, _ = s_sum(params, opt_sum.init(params), (x, y))
        np.testing.assert_allclose(
            np.asarray(pa["w"]), np.asarray(ps["w"]), rtol=1e-4, atol=1e-6
        )

    def test_training_converges(self):
        x, y = _data()
        params = _params()
        opt = hvd.DistributedOptimizer(optax.adam(0.05))
        step = spmd.make_train_step(_loss, opt)
        opt_state = opt.init(params)
        losses = []
        for _ in range(60):
            params, opt_state, loss = step(params, opt_state, (x, y))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.1
        np.testing.assert_allclose(np.asarray(params["w"]).ravel(), [1, -2, 0.5], atol=0.3)

    def test_adasum_op(self):
        x, y = _data()
        params = _params()
        opt = hvd.DistributedOptimizer(optax.sgd(0.05), op=hvd.Adasum)
        step = spmd.make_train_step(_loss, opt)
        opt_state = opt.init(params)
        for _ in range(40):
            params, opt_state, loss = step(params, opt_state, (x, y))
            # fetched every step, as a loop that logs its loss does: with
            # several steps of 8 virtual devices in flight XLA:CPU's pool
            # can run out of threads under load, a device of the next step
            # never starts and the rendezvous of its `ppermute` aborts the
            # process after 60 s (9 of 356 runs under a 14-16 fold load, at
            # the parent of PR 41 and since alike; 0 of 160 with the fetch)
            loss = float(loss)
        assert loss < 1.0


class TestDistributedAdasumOptimizer:
    """Delta-model Adasum (reference tensorflow/__init__.py:313-407,
    torch/__init__.py:219-407): the LOCAL optimizer update — not the
    gradient — is Adasum-combined.  Oracle: adasum_reduce_stack over the
    per-worker deltas."""

    def _worker_deltas(self, params, x, y, lr):
        """Per-worker sgd deltas for each of the N batch shards."""
        from horovod_tpu.ops import adasum as AD

        shard = len(x) // N
        deltas = []
        for i in range(N):
            b = (x[i * shard:(i + 1) * shard], y[i * shard:(i + 1) * shard])
            g = jax.grad(_loss)(params, b)
            deltas.append(jax.tree_util.tree_map(lambda gg: -lr * gg, g))
        return {
            k: AD.adasum_reduce_stack(
                jnp.stack([d[k] for d in deltas]))
            for k in params
        }

    def test_one_step_matches_pairwise_oracle(self):
        x, y = _data()
        params = _params()
        opt = hvd.DistributedAdasumOptimizer(optax.sgd(0.1))
        step = spmd.make_train_step(_loss, opt, donate=False)
        p2, _, _ = step(params, opt.init(params), (x, y))

        global_delta = self._worker_deltas(params, x, y, 0.1)
        for k in params:
            expect = params[k] + global_delta[k]
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(expect), rtol=1e-5, atol=1e-6)

    def test_adaptive_inner_optimizer(self):
        """The combined quantity must carry the inner optimizer's adaptive
        scaling (here: adam), not the raw gradient."""
        x, y = _data()
        params = _params()
        inner = optax.adam(0.05)
        opt = hvd.DistributedAdasumOptimizer(inner)
        step = spmd.make_train_step(_loss, opt, donate=False)
        p2, _, _ = step(params, opt.init(params), (x, y))

        from horovod_tpu.ops import adasum as AD

        shard = len(x) // N
        deltas = []
        for i in range(N):
            b = (x[i * shard:(i + 1) * shard], y[i * shard:(i + 1) * shard])
            g = jax.grad(_loss)(params, b)
            u, _ = inner.update(g, inner.init(params), params)
            deltas.append(u)
        for k in params:
            expect = params[k] + AD.adasum_reduce_stack(
                jnp.stack([d[k] for d in deltas]))
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(expect), rtol=1e-5, atol=1e-6)

    def test_identical_workers_halve_like_adasum(self):
        """All workers computing the SAME delta must produce that delta
        (Adasum's a==b case: coefficients sum to 1), not N× it."""
        x, y = _data()
        params = _params()
        # Replicate one shard to every worker so all grads are identical.
        xs = np.tile(x[:4], (N, 1))
        ys = np.tile(y[:4], (N, 1))
        opt = hvd.DistributedAdasumOptimizer(optax.sgd(0.1))
        step = spmd.make_train_step(_loss, opt, donate=False)
        p2, _, _ = step(params, opt.init(params), (xs, ys))
        g = jax.grad(_loss)(params, (xs[:4], ys[:4]))
        for k in params:
            expect = params[k] - 0.1 * g[k]
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(expect), rtol=1e-5, atol=1e-6)

    def test_backward_passes_per_step_drift_and_sync(self):
        """k=2: step 1 applies the local update only (workers drift);
        step 2 Adasum-combines the CUMULATIVE drift from start."""
        x, y = _data()
        params = _params()
        lr = 0.1
        opt = hvd.DistributedAdasumOptimizer(
            optax.sgd(lr), backward_passes_per_step=2)
        step = spmd.make_train_step(_loss, opt, donate=False)
        opt_state = opt.init(params)
        p1, opt_state, _ = step(params, opt_state, (x, y))
        p2, opt_state, _ = step(p1, opt_state, (x, y))

        # Oracle: simulate each worker's two local sgd steps from start.
        from horovod_tpu.ops import adasum as AD

        shard = len(x) // N
        deltas = []
        for i in range(N):
            b = (x[i * shard:(i + 1) * shard], y[i * shard:(i + 1) * shard])
            local = params
            for _ in range(2):
                g = jax.grad(_loss)(local, b)
                local = jax.tree_util.tree_map(
                    lambda p, gg: p - lr * gg, local, g)
            deltas.append(jax.tree_util.tree_map(
                lambda l, s: l - s, local, params))
        for k in params:
            expect = params[k] + AD.adasum_reduce_stack(
                jnp.stack([d[k] for d in deltas]))
            np.testing.assert_allclose(
                np.asarray(p2[k]), np.asarray(expect), rtol=1e-5, atol=1e-6)

    def test_bad_k(self):
        with pytest.raises(ValueError):
            hvd.DistributedAdasumOptimizer(
                optax.sgd(0.1), backward_passes_per_step=0)


class TestBackwardPassesPerStep:
    def test_accumulation(self):
        """k accumulation steps then one update == one update with the
        averaged gradient (torch/__init__.py:95-157 semantics)."""
        k = 4
        x, y = _data()
        params = _params()
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=k)
        step = spmd.make_train_step(_loss, opt, donate=False)
        opt_state = opt.init(params)
        p = params
        for i in range(k):
            p, opt_state, _ = step(p, opt_state, (x, y))
            if i < k - 1:
                # no update applied yet
                np.testing.assert_allclose(
                    np.asarray(p["w"]), np.asarray(params["w"])
                )
        g = jax.grad(_loss)(params, (x, y))
        expect = params["w"] - 0.1 * g["w"]
        np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(expect), rtol=1e-4, atol=1e-6)

    def test_no_average_aggregated(self):
        k = 2
        x, y = _data()
        params = _params()
        opt = hvd.DistributedOptimizer(
            optax.sgd(0.1),
            backward_passes_per_step=k,
            average_aggregated_gradients=False,
        )
        step = spmd.make_train_step(_loss, opt, donate=False)
        opt_state = opt.init(params)
        p = params
        for _ in range(k):
            p, opt_state, _ = step(p, opt_state, (x, y))
        g = jax.grad(_loss)(params, (x, y))
        expect = params["w"] - 0.1 * k * g["w"]  # sum of k identical grads
        np.testing.assert_allclose(np.asarray(p["w"]), np.asarray(expect), rtol=1e-4, atol=1e-6)

    def test_bad_k(self):
        with pytest.raises(ValueError):
            hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=0)


class TestDistributedGradientTape:
    def test_grads_averaged(self):
        x, y = _data()
        params = _params()

        def inner(xs, ys):
            tape = hvd.DistributedGradientTape(_loss)
            loss, grads = tape(params, (xs, ys))
            return grads["w"][None]

        out = jax.jit(
            spmd.shard(
                lambda xs, ys: inner(xs, ys),
                in_specs=(P(hvd.AXIS), P(hvd.AXIS)),
                out_specs=P(hvd.AXIS),
            )
        )(x, y)
        full = jax.grad(_loss)(params, (x, y))
        np.testing.assert_allclose(
            np.asarray(out[0]), np.asarray(full["w"]), rtol=1e-4, atol=1e-5
        )


class TestFusion:
    def test_buckets_respect_threshold_and_dtype(self):
        leaves = [np.ones(10, np.float32), np.ones(10, np.float32),
                  np.ones(10, np.int32), np.ones(1000, np.float32)]
        buckets = fusion.make_buckets(leaves, threshold=100)
        # int32 leaf must be in its own bucket; big leaf alone
        for b in buckets:
            dtypes = {np.asarray(leaves[i]).dtype for i in b}
            assert len(dtypes) == 1
        flat = sorted(i for b in buckets for i in b)
        assert flat == [0, 1, 2, 3]

    def test_fused_tree_matches_unfused(self):
        rng = np.random.RandomState(0)
        tree = {
            "a": rng.randn(N, 4).astype(np.float32),
            "b": rng.randn(N, 5).astype(np.float32),
            "c": rng.randn(N, 2, 3).astype(np.float32),
        }

        def inner(a, b, c):
            t = {"a": a[0], "b": b[0], "c": c[0]}
            out = fusion.fused_allreduce_tree(t, hvd.Sum, threshold=1 << 20)
            return jax.tree_util.tree_map(lambda l: l[None], out)

        out = jax.jit(
            spmd.shard(
                inner,
                in_specs=(P(hvd.AXIS),) * 3,
                out_specs={"a": P(hvd.AXIS), "b": P(hvd.AXIS), "c": P(hvd.AXIS)},
            )
        )(tree["a"], tree["b"], tree["c"])
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(out[k][0]), tree[k].sum(axis=0), rtol=1e-4, atol=1e-5
            )

    def test_tiny_threshold_many_buckets(self):
        leaves = [np.ones(100, np.float32) for _ in range(5)]
        buckets = fusion.make_buckets(leaves, threshold=1)
        assert len(buckets) == 5


class TestALeafAloneKeepsItsShape:
    """A bucket of ONE leaf — one the threshold leaves alone, or the only
    one of its dtype — goes into its ``psum`` in its own shape and comes
    back as it is; only a bucket of several leaves is raveled,
    concatenated and split (on a TPU a ravel of a tiled array is a
    relayout of the whole leaf: PERF.md section 6, PR 43;
    ``tests/test_tpu_aot.py`` holds the compiled program).  The numbers
    are the packed form's to the bit."""

    THRESHOLD = 1024    # bytes: 256 float32 elements fill a bucket

    # per device: {name: (shape, dtype)}
    CASES = {
        "one_big": {"w": ((16, 32), np.float32)},
        "big_and_small": {"a": ((3,), np.float32), "w": ((16, 32), np.float32),
                          "b": ((5,), np.float32), "c": ((2, 2), np.float32),
                          "v": ((4, 8, 8), np.float32)},
        "mixed_dtypes": {"a": ((3,), np.float32), "h": ((4, 6), jnp.bfloat16),
                         "w": ((16, 32), np.float32), "b": ((7,), np.float32),
                         "k": ((2, 3), np.float16), "j": ((5,), np.float16)},
        "scalar": {"s": ((), np.float32), "h": ((4, 4), jnp.bfloat16),
                   "g": ((), jnp.bfloat16)},
    }

    @classmethod
    def _tree(cls, case):
        """One draw a device: the leaves with a leading axis of ``N``."""
        rng = np.random.RandomState(sorted(cls.CASES).index(case))
        return {k: jnp.asarray(rng.randn(N, *shape), dtype)
                for k, (shape, dtype) in cls.CASES[case].items()}

    @classmethod
    def _reduce(cls, op):
        def inner(tree):
            out = fusion.fused_allreduce_tree(
                jax.tree_util.tree_map(lambda x: x[0], tree), op,
                threshold=cls.THRESHOLD)
            return jax.tree_util.tree_map(lambda x: x[None], out)

        return spmd.shard(inner, in_specs=(P(hvd.AXIS),),
                          out_specs=P(hvd.AXIS))

    @staticmethod
    def _psum_shapes(jaxpr):
        """The shape of every ``psum``'s operand, in program order."""
        return [tuple(v.aval.shape) for eqn in _equations(jaxpr)
                if "psum" in eqn.primitive.name for v in eqn.invars]

    @pytest.mark.parametrize("op", [hvd.Sum, hvd.Average])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_the_result_is_the_packed_forms_and_the_psum_has_the_leafs_shape(
            self, monkeypatch, case, op):
        from conftest import every_bucket_packed

        tree = self._tree(case)
        names = sorted(self.CASES[case])     # a dict flattens by key
        shapes = [self.CASES[case][k][0] for k in names]
        buckets = fusion.make_buckets(
            [tree[k][0] for k in names], self.THRESHOLD)
        alone = [shapes[b[0]] for b in buckets if len(b) == 1]
        packed = [(sum(int(np.prod(shapes[i])) for i in b),)
                  for b in buckets if len(b) > 1]
        assert alone, buckets    # every case has a leaf alone

        seen = self._psum_shapes(jax.make_jaxpr(self._reduce(op))(tree).jaxpr)
        assert sorted(seen) == sorted(alone + packed), (seen, buckets)
        out = jax.jit(self._reduce(op))(tree)

        every_bucket_packed(monkeypatch)
        before = self._psum_shapes(
            jax.make_jaxpr(self._reduce(op))(tree).jaxpr)
        assert sorted(before) == sorted(
            [(int(np.prod(s)),) for s in alone] + packed), before
        want = jax.jit(self._reduce(op))(tree)

        for k in names:
            assert out[k].dtype == want[k].dtype == tree[k].dtype
            assert out[k].shape == want[k].shape == tree[k].shape
            np.testing.assert_array_equal(
                np.asarray(out[k].astype(jnp.float32)),
                np.asarray(want[k].astype(jnp.float32)), err_msg=k)
            # every device holds the reduction of all N draws
            ref = np.asarray(tree[k].astype(jnp.float32)).sum(axis=0)
            if op == hvd.Average:
                ref = ref / N
            np.testing.assert_allclose(
                np.asarray(out[k][0].astype(jnp.float32)), ref,
                rtol=0.05, atol=0.05)


class TestSparseGradients:
    """Row-sparse embedding-gradient reduction — the IndexedSlices
    allgather analogue (reference tensorflow/__init__.py:74-89)."""

    def _sparse_grad(self, V=64, D=8, rows=(3, 17, 40)):
        g = np.zeros((V, D), np.float32)
        for r in rows:
            g[r] = np.random.RandomState(r).randn(D)
        return g

    def test_matches_dense_allreduce(self):
        from horovod_tpu.ops import sparse as SP

        g = self._sparse_grad()
        for op in (hvd.Sum, hvd.Average):
            dense = np.asarray(hvd.allreduce(g, op, name=f"sp.ref.{op}"))
            sparse = SP.sparse_allreduce(g, op, name=f"sp.t.{op}")
            np.testing.assert_allclose(sparse, dense, rtol=1e-6,
                                       err_msg=op)

    def test_wire_bytes_proportional_to_touched_rows(self):
        from horovod_tpu.ops import sparse as SP

        g = self._sparse_grad(V=1000, D=16, rows=(1, 2, 3))
        out, stats = SP.sparse_allreduce(g, hvd.Average, name="sp.stats",
                                         return_stats=True)
        assert stats["rows"] == 3 and stats["total_rows"] == 1000
        # 3 touched rows of 1000: sparse wire bytes ~ 0.3% of dense.
        assert stats["sparse_bytes"] < stats["dense_bytes"] / 100
        np.testing.assert_allclose(
            out, np.asarray(hvd.allreduce(g, hvd.Average, name="sp.s2")),
            rtol=1e-6)

    def test_all_zero_gradient(self):
        from horovod_tpu.ops import sparse as SP

        g = np.zeros((16, 4), np.float32)
        out = SP.sparse_allreduce(g, hvd.Sum, name="sp.zero")
        np.testing.assert_array_equal(out, g)

    def test_optimizer_sparse_keys_matches_dense_path(self):
        """DistributedOptimizer(sparse_keys=('embed',)) must produce the
        same updates as the dense path — only the wire mechanism
        changes."""
        grads = {
            "embed": jnp.asarray(self._sparse_grad()),
            "dense": {"w": jnp.ones((5, 5)), "b": jnp.ones((5,))},
        }
        params = jax.tree_util.tree_map(jnp.zeros_like, grads)

        def run(**kw):
            opt = hvd.DistributedOptimizer(optax.sgd(1.0), **kw)
            state = opt.init(params)
            up, _ = opt.update(
                jax.tree_util.tree_map(np.asarray, grads), state, params)
            return up

        up_sparse = run(sparse_keys=("embed",))
        up_dense = run()
        for path, a in jax.tree_util.tree_leaves_with_path(up_sparse):
            b = dict(jax.tree_util.tree_leaves_with_path(up_dense))[path]
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6,
                                       err_msg=jax.tree_util.keystr(path))

    def test_traced_leaves_fall_back_dense(self):
        """Inside jit the sparse route must not engage (static shapes):
        the same sparse_keys optimizer works compiled, via shard_map."""
        from horovod_tpu import optim

        g = {"embed": jnp.ones((8, 4)), "w": jnp.ones((3,))}

        def fn(g):
            return optim.distributed_gradients(
                g, hvd.Average, sparse_keys=("embed",))

        out = spmd.run(fn, g, in_specs=P(), out_specs=P())
        np.testing.assert_allclose(np.asarray(out["embed"]),
                                   np.ones((8, 4)), rtol=1e-6)

    def test_adasum_op_rejected(self):
        from horovod_tpu.ops import sparse as SP

        with pytest.raises(ValueError, match="Sum/Average"):
            SP.sparse_allreduce(np.ones((4, 2), np.float32), hvd.Adasum)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs under it (a
    ``shard_map``'s body, a ``cond``'s branches), in program order."""
    from conftest import _sub_jaxprs

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub)


def _primitives(jaxpr):
    """``[(primitive's name, number of operands)]`` of ``_equations``."""
    return [(eqn.primitive.name, len(eqn.invars))
            for eqn in _equations(jaxpr)]


class TestGradientsEnterTheReductionAsValues:
    """In traced code every gradient leaf passes through its OWN
    ``lax.optimization_barrier`` on its way into the reduction
    (``optim._as_values``): the compiler cannot put the optimizer into
    the epilogue of the matmul that makes the gradient, which is what
    XLA:TPU did in a world of one, where the reduction emits nothing
    (PERF.md section 6, PR 41; ``tests/test_tpu_aot.py`` holds the
    compiled programs).  The numbers are the parent's."""

    TREE = {"w": np.ones((3, 1), np.float32), "b": np.ones((1,), np.float32),
            "scale": np.ones((), np.float32)}

    @staticmethod
    def _parents_form(monkeypatch):
        from horovod_tpu import optim

        monkeypatch.setattr(optim, "_as_values", lambda grads: grads)

    @pytest.mark.parametrize("k", [1, 2])
    def test_one_barrier_a_leaf_before_the_first_collective(self, k):
        opt = hvd.DistributedOptimizer(optax.adamw(1e-2),
                                       backward_passes_per_step=k)
        state = opt.init(self.TREE)
        update = spmd.shard(
            lambda g, s, p: opt.update(g, s, p),
            in_specs=(P(), P(), P()), out_specs=(P(), P()))
        seen = _primitives(
            jax.make_jaxpr(update)(self.TREE, state, self.TREE).jaxpr)
        names = [name for name, _ in seen]
        barriers = [n for name, n in seen if name == "optimization_barrier"]
        # one a leaf and each over ONE array: no leaf waits for another's
        assert barriers == [1] * len(jax.tree_util.tree_leaves(self.TREE))
        first = next(i for i, name in enumerate(names) if "psum" in name)
        last = max(i for i, name in enumerate(names)
                   if name == "optimization_barrier")
        assert last < first, names
        if k > 1:   # inside the boundary step's branch, with the reduction
            assert names.index("cond") < names.index("optimization_barrier")

    def test_an_eager_call_holds_no_barrier(self, monkeypatch):
        calls = []
        barrier = jax.lax.optimization_barrier
        monkeypatch.setattr(jax.lax, "optimization_barrier",
                            lambda x: calls.append(x) or barrier(x))
        opt = hvd.DistributedOptimizer(optax.sgd(1.0))
        grads = self.TREE
        updates, _ = opt.update(grads, opt.init(self.TREE), self.TREE)
        assert calls == []
        for u, g in zip(jax.tree_util.tree_leaves(updates),
                        jax.tree_util.tree_leaves(grads)):
            np.testing.assert_allclose(np.asarray(u), -g, rtol=1e-6)
        # the same optimizer traced: the counter does see a barrier
        jax.make_jaxpr(spmd.shard(
            lambda g: opt.update(g, opt.init(self.TREE), self.TREE)[0],
            in_specs=P(), out_specs=P()))(self.TREE)
        assert len(calls) == len(grads)

    @pytest.mark.parametrize("op", [hvd.Average, hvd.Sum])
    @pytest.mark.parametrize("world", [1, 4])
    def test_three_steps_are_the_parents(self, monkeypatch, world, op):
        """Parameters and AdamW's state after three steps in a world of
        one and of four: the same mathematics in the same precision, so
        equal to float32's rounding (a moved fusion boundary may round
        differently; the CPU compiler's does not)."""
        from jax.sharding import Mesh

        mesh = Mesh(np.array(jax.devices()[:world]), (hvd.AXIS,))
        x, y = _data()

        def three_steps():
            opt = hvd.DistributedOptimizer(
                optax.adamw(0.05, weight_decay=0.1), op=op)
            step = spmd.make_train_step(_loss, opt, mesh=mesh, donate=False)
            params, state = _params(), opt.init(_params())
            for _ in range(3):   # one step in flight (test_adasum_op says why)
                params, state, loss = step(params, state, (x, y))
                loss.block_until_ready()
            return params, state

        got = three_steps()
        self._parents_form(monkeypatch)
        want = three_steps()
        assert float(jnp.abs(got[0]["w"]).sum()) > 0
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-6, atol=1e-9)

    @pytest.mark.parametrize("case", ["adasum", "fp16", "bf16",
                                      "sparse_keys", "unfused"])
    def test_the_other_reductions_give_what_they_gave(self, monkeypatch,
                                                      case):
        from horovod_tpu import optim

        op, kw = {
            "adasum": (hvd.Adasum, {}),
            "fp16": (hvd.Average, dict(compression=hvd.Compression.fp16)),
            "bf16": (hvd.Average, dict(compression=hvd.Compression.bf16)),
            "sparse_keys": (hvd.Average, dict(sparse_keys=("embed",))),
            "unfused": (hvd.Average, dict(fuse=False))}[case]
        rng = np.random.RandomState(3)
        grads = {"embed": rng.randn(N, 8, 4).astype(np.float32),
                 "w": rng.randn(N, 3).astype(np.float32)}

        def reduce():   # every worker its own gradients
            fn = spmd.shard(
                lambda g: jax.tree_util.tree_map(
                    lambda l: l[None], optim.distributed_gradients(
                        jax.tree_util.tree_map(lambda l: l[0], g), op,
                        **kw)),
                in_specs=P(hvd.AXIS), out_specs=P(hvd.AXIS))
            return jax.jit(fn)(grads)

        got = reduce()
        self._parents_form(monkeypatch)
        want = reduce()
        for k in grads:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=2e-6)
        if op == hvd.Average:
            tol = {"fp16": 2e-3, "bf16": 2e-2}.get(case, 1e-5)
            np.testing.assert_allclose(
                np.asarray(got["w"][0]), grads["w"].mean(axis=0),
                rtol=tol, atol=tol)
