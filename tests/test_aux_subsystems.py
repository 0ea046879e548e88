"""Auxiliary-subsystem tests: checkpoint/resume (orbax), HMAC secret,
NIC discovery handshake, TF/keras shim gating (roles of the reference's
test_timeline.py / secret usage / driver-task service tests)."""

import os

import jax
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import checkpoint
from horovod_tpu.runner import secret
from horovod_tpu.runner.rendezvous import KVClient, RendezvousServer


class TestCheckpoint:
    def _tree(self):
        return {
            "w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "b": np.ones(4, np.float32),
            "inner": {"step": np.asarray(7)},
        }

    def test_save_restore_roundtrip(self, hvd, tmp_path):
        tree = self._tree()
        checkpoint.save(str(tmp_path / "ck"), tree)
        out = checkpoint.restore(str(tmp_path / "ck"),
                                 jax.tree_util.tree_map(np.zeros_like, tree))
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_manager_retention_and_latest(self, hvd, tmp_path):
        mgr = checkpoint.CheckpointManager(str(tmp_path / "runs"),
                                           max_to_keep=2)
        assert mgr.latest_step() is None
        for s in (10, 20, 30):
            mgr.save(s, {"x": np.full(3, float(s))})
        assert mgr.all_steps() == [20, 30]  # 10 evicted
        step, tree = mgr.restore_latest({"x": np.zeros(3)})
        assert step == 30
        np.testing.assert_array_equal(tree["x"], np.full(3, 30.0))

    def test_restore_latest_empty(self, hvd, tmp_path):
        mgr = checkpoint.CheckpointManager(str(tmp_path / "empty"))
        step, tree = mgr.restore_latest({"x": np.ones(2)})
        assert step is None
        np.testing.assert_array_equal(tree["x"], np.ones(2))

    def test_async_save_roundtrip(self, hvd, tmp_path):
        """save_async returns immediately; wait() makes the write
        durable; the readback matches."""
        tree = self._tree()
        h = checkpoint.save_async(str(tmp_path / "ack"), tree)
        h.wait()
        out = checkpoint.restore(str(tmp_path / "ack"),
                                 jax.tree_util.tree_map(np.zeros_like, tree))
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        h.wait()  # idempotent

    def test_restore_latest_skips_corrupt_newest(self, hvd, tmp_path):
        """A truncated newest checkpoint falls back to the previous
        intact one instead of raising (the crash-mid-write resume
        story)."""
        mgr = checkpoint.CheckpointManager(str(tmp_path / "cruns"),
                                           max_to_keep=3)
        mgr.save(1, {"x": np.full(3, 1.0)})
        mgr.save(2, {"x": np.full(3, 2.0)})
        # truncate every file in the newest step dir (torn write)
        newest = mgr._step_dir(2)
        for root, _, files in os.walk(newest):
            for f in files:
                open(os.path.join(root, f), "wb").close()
        with pytest.warns(UserWarning, match="step 2.*unreadable"):
            step, tree = mgr.restore_latest({"x": np.zeros(3)})
        assert step == 1
        np.testing.assert_array_equal(tree["x"], np.full(3, 1.0))

    def test_saves_are_atomic_tmp_invisible(self, hvd, tmp_path):
        """A crash-abandoned step_N.tmp directory is never listed nor
        restored; a clean save commits via rename (no .tmp left)."""
        mgr = checkpoint.CheckpointManager(str(tmp_path / "atomic"))
        mgr.save(5, {"x": np.full(2, 5.0)})
        assert not any(n.endswith(".tmp")
                       for n in os.listdir(mgr.directory))
        # simulate a crash mid-save: a half-written tmp for step 6
        os.makedirs(mgr._step_dir(6) + ".tmp")
        assert mgr.all_steps() == [5]
        step, tree = mgr.restore_latest({"x": np.zeros(2)})
        assert step == 5
        np.testing.assert_array_equal(tree["x"], np.full(2, 5.0))
        # the next save sweeps the crash-abandoned tmp (no disk leak)
        mgr.save(7, {"x": np.full(2, 7.0)})
        assert not os.path.isdir(mgr._step_dir(6) + ".tmp")
        assert mgr.all_steps() == [5, 7]

    def test_manager_async_saves(self, hvd, tmp_path):
        """async_saves=True: saves overlap the 'training' between them
        (at most one in flight); restore paths wait before reading;
        retention still holds."""
        mgr = checkpoint.CheckpointManager(str(tmp_path / "aruns"),
                                           max_to_keep=2, async_saves=True)
        for s in (1, 2, 3):
            mgr.save(s, {"x": np.full(3, float(s))})
        step, tree = mgr.restore_latest({"x": np.zeros(3)})
        assert step == 3
        np.testing.assert_array_equal(tree["x"], np.full(3, 3.0))
        mgr.wait()
        assert mgr.all_steps() == [2, 3]


class TestSecret:
    def test_sign_verify_roundtrip(self, monkeypatch):
        monkeypatch.setenv(secret.ENV_KEY, secret.make_secret_key())
        payload = secret.sign(b"hello")
        assert payload != b"hello"
        assert secret.verify(payload) == b"hello"

    def test_tamper_rejected(self, monkeypatch):
        monkeypatch.setenv(secret.ENV_KEY, secret.make_secret_key())
        payload = bytearray(secret.sign(b"hello"))
        payload[-1] ^= 0xFF
        with pytest.raises(ValueError, match="HMAC"):
            secret.verify(bytes(payload))

    def test_disabled_without_key(self, monkeypatch):
        monkeypatch.delenv(secret.ENV_KEY, raising=False)
        assert secret.sign(b"x") == b"x"
        assert secret.verify(b"x") == b"x"

    def test_kv_signed_end_to_end(self, monkeypatch):
        key = secret.make_secret_key()
        monkeypatch.setenv(secret.ENV_KEY, key)
        server = RendezvousServer(0)  # picks up the env key
        port = server.start()
        try:
            kv = KVClient("127.0.0.1", port)
            kv.put("s", "k", b"payload")
            assert kv.get("s", "k") == b"payload"
            # unsigned writer (no key) is rejected AT THE SERVER (403), so
            # a stray process can neither inject state nor DoS readers
            monkeypatch.delenv(secret.ENV_KEY, raising=False)
            from urllib import error as urlerror

            with pytest.raises(urlerror.HTTPError) as ei:
                kv.put("s", "raw", b"unsigned")
            assert ei.value.code == 403
            # keyless reader of a signed value fails loudly, not garbage
            with pytest.raises(ValueError, match="no HOROVOD_SECRET_KEY"):
                kv.get("s", "k")
        finally:
            server.stop()


class TestDiscovery:
    def test_ring_discovery_localhost(self):
        from horovod_tpu.runner import discovery

        server = RendezvousServer(0)
        port = server.start()
        try:
            import threading

            size = 3
            threads = [
                threading.Thread(
                    target=discovery.run_task_discovery,
                    args=(KVClient("127.0.0.1", port), r, size),
                    kwargs={"timeout": 30},
                )
                for r in range(size)
            ]
            for t in threads:
                t.start()
            routable = discovery.discover(
                KVClient("127.0.0.1", port), size, timeout=30)
            for t in threads:
                t.join(timeout=30)
            assert sorted(routable) == [0, 1, 2]
            for addr in routable.values():
                assert addr  # a concrete address string
        finally:
            server.stop()

    def test_local_addresses_nonempty(self):
        from horovod_tpu.runner import discovery

        assert discovery.local_addresses()


tf = pytest.importorskip("tensorflow")


class TestTensorFlowShim:
    """Role of the reference's test_tensorflow.py op/tape/optimizer tests
    at single-worker scope (multi-rank covered by the launcher workers)."""

    def test_allreduce(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        # Sum is chip-weighted (one process speaks for local_size chips);
        # Average is the identity at one process.
        ls = hvd_tf.local_size()
        x = tf.constant([1.0, 2.0, 3.0])
        out = hvd_tf.allreduce(x, op=hvd_tf.Sum)
        np.testing.assert_allclose(out.numpy(), [ls * 1.0, ls * 2.0, ls * 3.0])
        out = hvd_tf.allreduce(x)  # default Average
        np.testing.assert_allclose(out.numpy(), [1.0, 2.0, 3.0])

    def test_allgather_broadcast(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        x = tf.constant([[1.0, 2.0]])
        assert hvd_tf.allgather(x).shape == (1, 2)
        np.testing.assert_allclose(
            hvd_tf.broadcast(x, 0).numpy(), x.numpy())

    def test_broadcast_variables(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        v = tf.Variable([5.0, 6.0])
        hvd_tf.broadcast_variables([v], 0)
        np.testing.assert_allclose(v.numpy(), [5.0, 6.0])

    def test_distributed_gradient_tape(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        w = tf.Variable([2.0, 3.0])
        with hvd_tf.DistributedGradientTape(tf.GradientTape()) as tape:
            loss = tf.reduce_sum(w * w)
        (g,) = tape.gradient(loss, [w])
        np.testing.assert_allclose(g.numpy(), [4.0, 6.0])

    def test_distributed_optimizer_trains(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(4,))])
        opt = hvd_tf.DistributedOptimizer(tf.keras.optimizers.SGD(0.05))
        x = tf.random.normal((64, 4), seed=0)
        y = tf.reduce_sum(x, axis=1, keepdims=True)
        losses = []
        for _ in range(20):
            with tf.GradientTape() as tape:
                loss = tf.reduce_mean((model(x) - y) ** 2)
            grads = tape.gradient(loss, model.trainable_variables)
            opt.apply_gradients(zip(grads, model.trainable_variables))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::5]


class TestTFCompression:
    def test_tape_fp16_compression_close_to_exact(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        w = tf.Variable([[1.0, -2.0], [0.5, 3.0]])
        with tf.GradientTape() as t0:
            loss = tf.reduce_sum(w * w)
        exact = t0.gradient(loss, [w])[0].numpy()

        with hvd_tf.DistributedGradientTape(
                tf.GradientTape(),
                compression=hvd_tf.Compression.fp16) as tape:
            loss = tf.reduce_sum(w * w)
        (g,) = tape.gradient(loss, [w])
        # fp16 wire round-trip: close, dtype restored to f32
        assert g.dtype == tf.float32
        np.testing.assert_allclose(g.numpy(), exact, rtol=1e-3)

    def test_backward_passes_per_step_aggregates(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        v = tf.Variable([0.0])
        opt = hvd_tf.DistributedOptimizer(
            tf.keras.optimizers.SGD(1.0), backward_passes_per_step=3)
        # two accumulation passes apply nothing...
        for g in ([1.0], [2.0]):
            opt.apply_gradients([(tf.constant(g), v)])
            np.testing.assert_allclose(v.numpy(), [0.0])
        # ...the third applies the mean of the window: (1+2+3)/3 = 2
        opt.apply_gradients([(tf.constant([3.0]), v)])
        np.testing.assert_allclose(v.numpy(), [-2.0])
        # next window starts fresh
        opt.apply_gradients([(tf.constant([6.0]), v)])
        np.testing.assert_allclose(v.numpy(), [-2.0])

    def test_optimizer_compression_trains(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(4,))])
        opt = hvd_tf.DistributedOptimizer(
            tf.keras.optimizers.SGD(0.05),
            compression=hvd_tf.Compression.bf16)
        x = tf.random.normal((64, 4), seed=0)
        y = tf.reduce_sum(x, axis=1, keepdims=True)
        losses = []
        for _ in range(20):
            with tf.GradientTape() as tape:
                loss = tf.reduce_mean((model(x) - y) ** 2)
            grads = tape.gradient(loss, model.trainable_variables)
            opt.apply_gradients(zip(grads, model.trainable_variables))
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::5]


class TestKerasShim:
    def test_callbacks_in_fit(self, hvd):
        import horovod_tpu.keras as hvd_keras

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(3,))])
        model.compile(optimizer=tf.keras.optimizers.SGD(0.05), loss="mse")
        x = np.random.randn(64, 3).astype(np.float32)
        y = x.sum(axis=1, keepdims=True)
        hist = model.fit(
            x, y, epochs=2, batch_size=16, verbose=0,
            callbacks=[
                hvd_keras.BroadcastGlobalVariablesCallback(0),
                hvd_keras.MetricAverageCallback(),
            ])
        assert len(hist.history["loss"]) == 2

    def test_lr_schedule_callback(self, hvd):
        import horovod_tpu.keras as hvd_keras

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(3,))])
        model.compile(optimizer=tf.keras.optimizers.SGD(0.1, momentum=0.9),
                      loss="mse")
        x = np.random.randn(32, 3).astype(np.float32)
        y = x.sum(axis=1, keepdims=True)
        cb = hvd_keras.LearningRateScheduleCallback(
            multiplier=lambda epoch: 0.5 ** epoch, staircase=True)
        hist = model.fit(x, y, epochs=3, batch_size=16, verbose=0,
                         callbacks=[cb])
        # base LR read from the optimizer; epoch e runs at 0.1 * 0.5^e
        lrs = hist.history["lr"]
        np.testing.assert_allclose(lrs, [0.1, 0.05, 0.025], rtol=1e-5)
        # momentum correction restored after the adjusting batch
        assert abs(float(model.optimizer.momentum) - 0.9) < 1e-6

    def test_lr_warmup_callback(self, hvd):
        import horovod_tpu.keras as hvd_keras

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(3,))])
        model.compile(optimizer=tf.keras.optimizers.SGD(0.1), loss="mse")
        x = np.random.randn(64, 3).astype(np.float32)
        y = x.sum(axis=1, keepdims=True)
        cb = hvd_keras.LearningRateWarmupCallback(
            warmup_epochs=2, steps_per_epoch=4, verbose=0)
        hist = model.fit(x, y, epochs=3, batch_size=16, verbose=0,
                         callbacks=[cb])
        # hvd.size() counts the 8 virtual chips: warmup ramps the LR from
        # base/8 toward base*1 at epoch warmup_epochs, then leaves it.
        lrs = hist.history["lr"]
        assert lrs[0] < lrs[1] <= lrs[2] * (1 + 1e-6), lrs
        assert abs(lrs[-1] - 0.1) / 0.1 < 0.25, lrs

    def test_load_model_rewraps(self, hvd, tmp_path):
        import horovod_tpu.keras as hvd_keras

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(2,))])
        model.compile(optimizer=tf.keras.optimizers.Adam(1e-3), loss="mse")
        path = str(tmp_path / "model.keras")
        model.save(path)
        loaded = hvd_keras.load_model(path)
        assert loaded.optimizer is not None


class TestTFBroadcastGlobalVariables:
    def test_graph_mode_points_to_callback(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        with tf.Graph().as_default():
            with pytest.raises(
                NotImplementedError,
                match="BroadcastGlobalVariablesCallback",
            ):
                hvd_tf.broadcast_global_variables(0)

    def test_eager_raises_with_pointer(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        with pytest.raises(ValueError, match="broadcast_variables"):
            hvd_tf.broadcast_global_variables(0)

    def test_broadcast_callback_in_fit(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        model = tf.keras.Sequential(
            [tf.keras.layers.Dense(1, input_shape=(3,))])
        model.compile(optimizer=tf.keras.optimizers.SGD(0.05), loss="mse")
        x = np.random.randn(32, 3).astype(np.float32)
        y = x.sum(axis=1, keepdims=True)
        cb = hvd_tf.BroadcastGlobalVariablesCallback(0)
        hist = model.fit(x, y, epochs=1, batch_size=16, verbose=0,
                         callbacks=[cb])
        assert cb._done
        assert len(hist.history["loss"]) == 1


class TestLogLevel:
    def test_env_configures_logger(self, monkeypatch):
        import logging

        from horovod_tpu import basics

        logger = logging.getLogger("horovod_tpu")
        old = logger.level
        try:
            monkeypatch.setenv("HOROVOD_LOG_LEVEL", "debug")
            basics._configure_logging()
            assert logger.level == logging.DEBUG
            monkeypatch.setenv("HOROVOD_LOG_LEVEL", "error")
            basics._configure_logging()
            assert logger.level == logging.ERROR
        finally:
            logger.setLevel(old)

    def test_native_logging_emits(self, tmp_path):
        """HOROVOD_LOG_LEVEL=info makes the native runtime log its init
        line (native/src/logging.h reads the same env the reference's
        logger did)."""
        import subprocess
        import sys

        code = (
            "import os\n"
            "os.environ['HOROVOD_LOG_LEVEL'] = 'info'\n"
            "os.environ.setdefault('HOROVOD_NUM_PROC', '1')\n"
            "from horovod_tpu import native\n"
            "rt = native.NativeRuntime()\n"
            "rt.init(0, 1, '127.0.0.1', 19393)\n"
            "rt.shutdown()\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120,
        )
        assert r.returncode == 0, r.stderr
        assert "[hvd_native rank 0 Info] init:" in r.stderr


class TestTFFunctionAllreduce:
    def test_allreduce_inside_tf_function(self, hvd):
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        @tf.function
        def reduced_sum(t):
            return hvd_tf.allreduce(t, op=hvd_tf.Sum, name="tf.fn.t")

        ls = hvd_tf.local_size()
        x = tf.constant([1.0, 2.0, 3.0])
        out = reduced_sum(x)
        np.testing.assert_allclose(out.numpy(), [ls * v for v in (1., 2., 3.)])
        # re-invocation reuses the same trace + collective name
        out2 = reduced_sum(tf.constant([4.0, 5.0, 6.0]))
        np.testing.assert_allclose(out2.numpy(), [ls * v for v in (4., 5., 6.)])

    def test_auto_name_from_symbolic_tensor(self, hvd):
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        @tf.function
        def fn(t):
            return hvd_tf.allreduce(t * 2.0, op=hvd_tf.Average)

        out = fn(tf.constant([2.0]))
        np.testing.assert_allclose(out.numpy(), [4.0])

    def test_gradient_through_function_allreduce(self, hvd):
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        # The reference DistributedGradientTape pattern (reduce GRADIENTS)
        # composing with tf.function compute.
        v = tf.Variable([1.0, 2.0])
        with tf.GradientTape() as tape:
            loss = tf.reduce_sum(v * v)
        grads = tape.gradient(loss, [v])
        reduced = hvd_tf.allreduce(grads[0], op=hvd_tf.Average)
        np.testing.assert_allclose(reduced.numpy(), [2.0, 4.0])

    def test_tape_flows_through_eager_allreduce(self, hvd):
        """hvd.allreduce INSIDE a taped loss must be differentiable
        (reference tensorflow/mpi_ops.py:110-121 _allreduce_grad): the
        custom gradient is an allreduce of the upstream gradient — the
        numpy bridge must not silently detach the tape."""
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        ls = hvd_tf.local_size()
        v = tf.Variable([1.0, 2.0])
        with tf.GradientTape() as tape:
            y = hvd_tf.allreduce(v * v, op=hvd_tf.Sum, name="tape.e")
            loss = tf.reduce_sum(y)
        (g,) = tape.gradient(loss, [v])
        # y = ls * v^2 (chip-weighted Sum) so dL/dv = ls * 2v — and the
        # backward allreduce(dy, Sum) = ls * dy delivers exactly that:
        # the chip-weighted Sum is its own VJP.
        np.testing.assert_allclose(g.numpy(), [ls * 2.0, ls * 4.0])

    def test_tape_flows_through_function_allreduce(self, hvd):
        """Same through tf.function: the py_function bridge carries the
        custom gradient."""
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        ls = hvd_tf.local_size()
        v = tf.Variable([3.0])

        @tf.function
        def loss_fn():
            y = hvd_tf.allreduce(v * v, op=hvd_tf.Average, name="tape.f")
            return tf.reduce_sum(y)

        with tf.GradientTape() as tape:
            loss = loss_fn()
        (g,) = tape.gradient(loss, [v])
        # Average is the identity at one process (for any chip count):
        # grad(Average) is Average — also the identity — so g = 2v
        # exactly.  A backward that leaked the chip-weighted Sum would
        # return ls * 2v and fail this on the 8-virtual-chip test mesh.
        np.testing.assert_allclose(g.numpy(), [6.0])
        assert ls > 1, "test mesh must have >1 chip to discriminate"

    def test_sparse_cotangent_through_allreduce(self, hvd):
        """A loss that GATHERS rows of the reduced tensor produces an
        IndexedSlices cotangent; the backward must densify it instead of
        handing a dtype=object array to the native runtime."""
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        v = tf.Variable([[1.0, 2.0], [3.0, 4.0]])
        with tf.GradientTape() as tape:
            y = hvd_tf.allreduce(v, op=hvd_tf.Average, name="tape.sp")
            loss = tf.reduce_sum(tf.gather(y, [0]))
        (g,) = tape.gradient(loss, [v])
        g = tf.convert_to_tensor(g)
        np.testing.assert_allclose(g.numpy(), [[1.0, 1.0], [0.0, 0.0]])

    def test_tape_flows_through_allgather_and_broadcast(self, hvd):
        """allgather/broadcast carry the reference's registered gradients
        (mpi_ops.py:143-166, 186-201): process-level sum of the
        cotangent, slice own rows / zero on non-root.  Unlike allreduce
        (whose forward is chip-weighted), these forwards are process-
        level, so the tape gradient must be finite-difference-correct —
        NO local_size factor."""
        tf = pytest.importorskip("tensorflow")
        import horovod_tpu.tensorflow as hvd_tf

        assert hvd_tf.local_size() > 1  # else this can't catch chip leaks
        v = tf.Variable([[1.0, 2.0]])
        with tf.GradientTape() as tape:
            y = hvd_tf.allgather(v, name="tape.ag")
            loss = tf.reduce_sum(y * 3.0)
        (g,) = tape.gradient(loss, [v])
        # d(3*sum(v))/dv == 3 exactly (allgather is the identity at one
        # process; a chip-weighted backward would return 3*local_size).
        np.testing.assert_allclose(g.numpy(), [[3.0, 3.0]])

        w = tf.Variable([5.0])
        with tf.GradientTape() as tape:
            y = hvd_tf.broadcast(w, 0, name="tape.bc")
            loss = tf.reduce_sum(y * 2.0)
        (g,) = tape.gradient(loss, [w])
        np.testing.assert_allclose(g.numpy(), [2.0])

        @tf.function
        def fn_loss():
            y = hvd_tf.allgather(v, name="tape.ag.fn")
            return tf.reduce_sum(y)

        with tf.GradientTape() as tape:
            loss = fn_loss()
        (g,) = tape.gradient(loss, [v])
        np.testing.assert_allclose(g.numpy(), [[1.0, 1.0]])


@pytest.mark.slow
class TestTFMultiProcess:
    def _spawn(self, tmp_path, scenario, nproc):
        import socket
        import sys

        from horovod_tpu.runner import launch
        from horovod_tpu.runner.hosts import HostSpec

        REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

        def free_port():
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                return s.getsockname()[1]

        out = tmp_path / "out"
        env = {
            "PATH": os.environ.get("PATH", ""),
            "REPO": REPO,
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_NUM_PROC": str(nproc),
            "HOROVOD_JAX_PORT": str(free_port()),
            "HOROVOD_NATIVE_PORT": str(free_port()),
        }
        args = [sys.executable, os.path.join(REPO, "tests", "tf_worker.py")]
        if scenario:
            args.append(scenario)
        rc = launch.launch_job(
            args,
            [HostSpec("localhost", 1)] * nproc,
            env=env,
            output_filename=str(out),
        )
        assert rc == 0, (out / "rank.0.stderr").read_text() + (
            out / f"rank.{nproc - 1}.stderr").read_text()
        for r in range(nproc):
            assert "TF-WORKER-OK" in (out / f"rank.{r}.stdout").read_text()

    def test_two_process_tf(self, tmp_path):
        self._spawn(tmp_path, None, 2)

    def test_tf_adasum_delta_two_process(self, tmp_path):
        """TF delta-model Adasum vs the pairwise oracle, 2 ranks
        (reference _DistributedAdasumOptimizer,
        tensorflow/__init__.py:313-407)."""
        self._spawn(tmp_path, "adasum", 2)


class TestTFAdasumDispatch:
    def test_factory_dispatch_and_single_process_identity(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        opt = hvd_tf.DistributedOptimizer(
            tf.keras.optimizers.SGD(0.1), op=hvd_tf.Adasum)
        assert getattr(opt, "_hvd_adasum", False), type(opt).__mro__
        # With one process the Adasum-combined delta IS the local delta,
        # so one step must equal the unwrapped optimizer's step.
        v = tf.Variable([1.0, 2.0])
        g = tf.constant([0.5, -1.0])
        opt.apply_gradients([(g, v)])
        np.testing.assert_allclose(v.numpy(), [0.95, 2.1], rtol=1e-6)


class TestSparseAllreduce:
    def test_indexed_slices_single_process(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        slices = tf.IndexedSlices(
            values=tf.ones([2, 3]), indices=tf.constant([0, 2], tf.int64),
            dense_shape=tf.constant([4, 3], tf.int64))
        red = hvd_tf.allreduce(slices, op=hvd_tf.Average)
        assert isinstance(red, tf.IndexedSlices)
        np.testing.assert_allclose(red.values.numpy(), np.ones((2, 3)))

    def test_adasum_sparse_raises(self, hvd):
        import horovod_tpu.tensorflow as hvd_tf

        slices = tf.IndexedSlices(
            values=tf.ones([1, 2]), indices=tf.constant([0], tf.int64))
        with pytest.raises(NotImplementedError):
            hvd_tf.allreduce(slices, op=hvd_tf.Adasum)


class TestEstimatorPlatformResolution:
    def test_explicit_platform_passthrough(self):
        from horovod_tpu.estimator.estimator import (
            EstimatorParams, resolve_platform)

        assert resolve_platform(EstimatorParams(jax_platform="cpu")) == "cpu"
        assert resolve_platform(EstimatorParams(jax_platform="tpu")) == "tpu"
        assert resolve_platform(EstimatorParams(jax_platform=None)) == ""

    def test_auto_falls_back_to_cpu_without_enough_tpus(self):
        from horovod_tpu.estimator.estimator import (
            EstimatorParams, resolve_platform)

        # Test session runs on the CPU backend: no TPUs visible -> cpu.
        assert resolve_platform(
            EstimatorParams(jax_platform="auto", num_proc=2)) == "cpu"
