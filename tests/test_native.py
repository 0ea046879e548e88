"""Tests for the native (C++) control-plane runtime.

Covers the subsystems the reference tests through its C++ core under
mpirun (SURVEY.md §4): negotiation/ordering, tensor fusion, the response
cache fast path, coordinator-detected mismatch errors, Join accounting,
the stall inspector, the timeline writer, and clean shutdown.  Single
process tests run against the session runtime (size=1 controller);
multi-process tests spawn two real processes through the launcher.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import eager_runtime, native
from horovod_tpu.runner import launch
from horovod_tpu.runner.hosts import HostSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "native_worker.py")


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class TestNativeBuild:
    def test_library_builds_and_loads(self):
        assert native.native_built(), native.build_error()

    def test_dtype_mapping(self):
        assert native.dtype_enum(np.dtype("float32")) == 7
        assert native.dtype_name(10) == "bfloat16"
        with pytest.raises(TypeError):
            native.dtype_enum("complex64")


class TestSingleProcessRuntime:
    """The session fixture starts the native runtime with size=1: the full
    enqueue -> negotiate -> fuse -> execute pipeline minus sockets."""

    def test_runtime_active(self, hvd):
        rt = eager_runtime.get()
        assert rt is not None, native.build_error()
        assert rt.cycles() > 0

    def test_sync_ops_through_native(self, hvd):
        rt = eager_runtime.get()
        before = rt.cycles()
        out = hvd.allreduce(np.arange(6, dtype=np.float32), hvd.Sum,
                            name="nat.t1")
        # Chip-weighted Sum: the submission stands for every local chip.
        np.testing.assert_allclose(
            out, hvd.local_size() * np.arange(6, dtype=np.float32))
        assert rt.cycles() > before

    def test_fused_async_group(self, hvd):
        hs = [
            hvd.allreduce_async(np.full((5,), float(i)), hvd.Sum,
                                name=f"nat.fuse.{i}")
            for i in range(4)
        ]
        for i, h in enumerate(hs):
            np.testing.assert_allclose(
                hvd.synchronize(h),
                np.full((5,), float(i * hvd.local_size())))

    def test_duplicate_name_rejected(self, hvd):
        h = hvd.allreduce_async(np.ones(3), hvd.Sum, name="nat.dup")
        with pytest.raises(eager_runtime.CollectiveError,
                           match="duplicate|already"):
            hvd.allreduce_async(np.ones(3), hvd.Sum, name="nat.dup")
        hvd.synchronize(h)

    def test_cache_populates_and_hits(self, hvd):
        rt = eager_runtime.get()
        entries_before = rt.cache_entries()
        for _ in range(4):
            hvd.allreduce(np.ones(2, np.float32), hvd.Sum, name="nat.cached")
        assert rt.cache_entries() > entries_before or rt.cache_hits() > 0

    def test_poll_eventually_true(self, hvd):
        h = hvd.allreduce_async(np.ones(4), hvd.Average, name="nat.poll")
        import time

        deadline = time.time() + 10
        while not hvd.poll(h):
            assert time.time() < deadline
            time.sleep(0.001)
        np.testing.assert_allclose(hvd.synchronize(h), np.ones(4))

    def test_barrier(self, hvd):
        hvd.barrier()  # size=1: completes via the BARRIER response path

    def test_mixed_dtypes_separate_buckets(self, hvd):
        a = hvd.allreduce_async(np.ones(3, np.float32), hvd.Sum, name="nat.f32")
        b = hvd.allreduce_async(np.ones(3, np.int32), hvd.Sum, name="nat.i32")
        ra, rb = hvd.synchronize(a), hvd.synchronize(b)
        assert ra.dtype == np.float32 and rb.dtype == np.int32


class TestResponseWire:
    def test_parse_roundtrip_via_executor(self, hvd):
        """The executor's parsed Response must faithfully carry names,
        shapes and scales — checked by a prescaled op end-to-end."""
        out = hvd.allreduce(np.full((2, 3), 2.0, np.float32), hvd.Sum,
                            name="nat.scaled", prescale_factor=0.5,
                            postscale_factor=4.0)
        np.testing.assert_allclose(
            out, np.full((2, 3), 4.0 * hvd.local_size()))


def _spawn_workers(tmp_path, scenario, extra_env=None, nproc=2):
    out = tmp_path / "out"
    env = {
        "PATH": os.environ.get("PATH", ""),
        "REPO": REPO,
        "JAX_PLATFORMS": "cpu",  # keep subprocesses off the TPU
        "HOROVOD_NUM_PROC": str(nproc),
        "HOROVOD_JAX_PORT": str(_free_port()),
        "HOROVOD_NATIVE_PORT": str(_free_port()),
        "HOROVOD_CYCLE_TIME": "1",
    }
    env.update(extra_env or {})
    rc = launch.launch_job(
        [sys.executable, WORKER, scenario],
        [HostSpec("localhost", 1)] * nproc,
        env=env,
        output_filename=str(out),
    )
    return rc, out


@pytest.mark.skipif(not native.native_built(), reason="native lib unavailable")
class TestMultiProcess:
    @pytest.mark.slow
    def test_two_process_full_protocol(self, tmp_path):
        rc, out = _spawn_workers(tmp_path, "full")
        r0 = (out / "rank.0.stdout").read_text()
        r1 = (out / "rank.1.stdout").read_text()
        assert rc == 0, (out / "rank.0.stderr").read_text() + (
            out / "rank.1.stderr").read_text()
        assert "NATIVE-WORKER-OK rank=0" in r0
        assert "NATIVE-WORKER-OK rank=1" in r1

    def test_worker_count_seam_two_chips_per_process(self, tmp_path):
        """2 processes x 2 virtual chips each: eager Sum/Average must be
        CHIP-level (weight per-process contributions by local_size,
        divide Average by size()) and match the in-graph collectives —
        the eager/in-graph worker-count seam."""
        rc, out = _spawn_workers(tmp_path, "localsize")
        assert rc == 0, (out / "rank.0.stderr").read_text() + (
            out / "rank.1.stderr").read_text()
        for r in (0, 1):
            assert "NATIVE-WORKER-OK" in (out / f"rank.{r}.stdout").read_text()

    @pytest.mark.slow
    def test_wrong_secret_key_rejected(self, tmp_path):
        """The control-plane sockets perform a mutual HMAC challenge keyed
        by the job's HOROVOD_SECRET_KEY (the trust model the rendezvous KV
        already uses — reference run/common/util/secret.py): a client with
        the wrong key must be refused, and must itself refuse the
        coordinator before trusting any negotiation state."""
        port = _free_port()
        script = (
            "import sys\n"
            "from horovod_tpu import native\n"
            "rt = native.NativeRuntime()\n"
            "rank = int(sys.argv[1])\n"
            "try:\n"
            f"    rt.init(rank, 2, '127.0.0.1', {port},"
            " connect_timeout_sec=15.0)\n"
            "except RuntimeError as e:\n"
            "    print(f'INIT-FAILED rank={rank}: {e}')\n"
            "    sys.exit(3)\n"
            "print(f'INIT-OK rank={rank}')\n"
            "rt.shutdown()\n"
        )
        env = {
            "PATH": os.environ.get("PATH", ""),
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
        }
        coord = subprocess.Popen(
            [sys.executable, "-c", script, "0"],
            env={**env, "HOROVOD_SECRET_KEY": "a" * 32},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        intruder = subprocess.run(
            [sys.executable, "-c", script, "1"],
            env={**env, "HOROVOD_SECRET_KEY": "b" * 32},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            timeout=120)
        # The wrong-key client detects the mismatch ITSELF (mutual auth)
        # and refuses to join.
        assert intruder.returncode == 3, intruder.stdout
        assert "HMAC challenge" in intruder.stdout, intruder.stdout
        # The coordinator never accepted it as rank 1: with nobody else
        # dialing in, bootstrap times out instead of proceeding with an
        # impostor.
        out, _ = coord.communicate(timeout=120)
        assert coord.returncode == 3, out
        assert "timed out waiting for" in out, out

    def test_same_secret_key_accepted(self, tmp_path):
        """Positive control for the HMAC handshake: both sides holding the
        job secret bootstrap normally (every launcher-spawned test also
        covers this — the launcher always exports HOROVOD_SECRET_KEY)."""
        port = _free_port()
        script = (
            "import sys\n"
            "from horovod_tpu import native\n"
            "rt = native.NativeRuntime()\n"
            "rank = int(sys.argv[1])\n"
            f"rt.init(rank, 2, '127.0.0.1', {port},"
            " connect_timeout_sec=60.0)\n"
            "print(f'INIT-OK rank={rank}')\n"
            "rt.shutdown()\n"
        )
        env = {
            "PATH": os.environ.get("PATH", ""),
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "HOROVOD_SECRET_KEY": "c" * 32,
        }
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", script, str(r)], env=env,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            for r in (0, 1)
        ]
        for p in procs:
            out, _ = p.communicate(timeout=120)
            assert p.returncode == 0, out
            assert "INIT-OK" in out, out

    def test_stall_inspector_warns(self, tmp_path):
        rc, out = _spawn_workers(
            tmp_path, "stall",
            extra_env={"HOROVOD_STALL_CHECK_TIME_SECONDS": "1"})
        assert rc == 0
        stderr0 = (out / "rank.0.stderr").read_text()
        assert "missing ranks [1]" in stderr0, stderr0
        assert "stalled.t" in stderr0


@pytest.mark.skipif(not native.native_built(), reason="native lib unavailable")
class TestTimelineNative:
    def test_timeline_json_written(self, tmp_path):
        """Run a small single-process job with HOROVOD_TIMELINE set and
        validate the chrome-tracing output (role of the reference's
        test_timeline.py)."""
        tl = tmp_path / "timeline.json"
        script = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import numpy as np, horovod_tpu as hvd\n"
            "hvd.init()\n"
            "for i in range(3):\n"
            "    hvd.allreduce(np.ones(4, np.float32), hvd.Sum, name='tl.t')\n"
            "hvd.shutdown()\n"
        )
        env = dict(os.environ)
        env.update({
            "HOROVOD_TIMELINE": str(tl),
            "HOROVOD_TIMELINE_MARK_CYCLES": "1",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": REPO,
        })
        subprocess.run([sys.executable, "-c", script], cwd=REPO, env=env,
                       check=True, timeout=180)
        events = json.loads(tl.read_text())
        names = {e.get("name") for e in events}
        assert "NEGOTIATE" in names and "EXECUTE" in names
        assert "CYCLE" in names
        # thread metadata labels the tensor lane
        assert any(e.get("ph") == "M" and
                   e.get("args", {}).get("name") == "tl.t" for e in events)
