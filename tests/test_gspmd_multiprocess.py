"""Multi-process execution of the COMPILED GSPMD path — the pod shape.

The reference's product is N processes training synchronously under a
launcher (``run/gloo_run.py``: one process per slot; SURVEY.md §4 runs
every test body that way).  These tests spawn real processes through the
same ``horovod_tpu.runner`` launcher and run the compiled
``make_gspmd_train_step`` over a GLOBAL mesh that spans them:

* 2 processes × 4 virtual CPU devices each == one 8-device dp4×tp2 mesh;
* batches are global arrays assembled from per-process input shards
  (``DataLoader`` global-array mode);
* checkpoints are written/restored collaboratively (multihost orbax);
* the 2-process run must produce BIT-IDENTICAL per-step losses and
  final parameter checksums to the single-process 8-device run of the
  exact same program — the "works in the sandbox" ⇔ "works on the pod"
  equivalence.
"""

import os
import re
import socket
import sys

import pytest

pytestmark = pytest.mark.slow  # tier-1 budget: see tests/DURATIONS.md

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "gspmd_worker.py")

from horovod_tpu.runner import launch  # noqa: E402
from horovod_tpu.runner.hosts import HostSpec  # noqa: E402

OK_RE = re.compile(
    r"GSPMD-WORKER-OK rank=(\d+) nproc=(\d+) "
    r"losses=(\S+) resume=(\S+) check=(\S+)"
)
RESUME_RE = re.compile(
    r"GSPMD-RESUME-OK rank=(\d+) nproc=(\d+) resume=(\S+)"
)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_job(tmp_path, tag, nproc, local_devices, restore_from=None):
    out = tmp_path / tag
    ckpt = tmp_path / f"ckpt-{tag}"
    env = {
        "PATH": os.environ.get("PATH", ""),
        "REPO": REPO,
        "JAX_PLATFORMS": "cpu",  # keep subprocesses off the TPU
        "HOROVOD_NUM_PROC": str(nproc),
        "HOROVOD_JAX_PORT": str(_free_port()),
        "HOROVOD_NATIVE_PORT": str(_free_port()),
        "GSPMD_LOCAL_DEVICES": str(local_devices),
        "GSPMD_CKPT_DIR": str(ckpt),
    }
    if restore_from is not None:
        env["GSPMD_RESTORE_FROM"] = str(restore_from)
    rc = launch.launch_job(
        [sys.executable, WORKER],
        [HostSpec("localhost", 1)] * nproc,
        env=env,
        output_filename=str(out),
    )
    stderr = "".join(
        (out / f"rank.{r}.stderr").read_text() for r in range(nproc)
        if (out / f"rank.{r}.stderr").exists()
    )
    assert rc == 0, stderr[-4000:]
    results = {}
    regex = RESUME_RE if restore_from is not None else OK_RE
    for r in range(nproc):
        text = (out / f"rank.{r}.stdout").read_text()
        m = regex.search(text)
        assert m, f"rank {r} produced no OK line:\n{text}\n{stderr[-2000:]}"
        if restore_from is not None:
            results[r] = dict(resume=m.group(3))
        else:
            results[r] = dict(
                losses=m.group(3), resume=m.group(4), check=m.group(5)
            )
    return results


class TestGspmdMultiProcess:
    def test_two_process_matches_single_process_bitwise(self, tmp_path):
        """The SAME compiled dp4×tp2 training program run as 2 processes
        × 4 devices and as 1 process × 8 devices must agree bit-for-bit
        on every step loss and on the final parameter checksum — plus
        each job internally proves multihost save→restore→resume
        replays its own losses exactly."""
        multi = _run_job(tmp_path, "np2", nproc=2, local_devices=4)
        single = _run_job(tmp_path, "np1", nproc=1, local_devices=8)

        # Both ranks of the 2-process job see identical replicated values.
        assert multi[0] == multi[1], (multi[0], multi[1])
        # Pod run ≡ sandbox run, bitwise.
        assert multi[0]["losses"] == single[0]["losses"], (
            multi[0]["losses"], single[0]["losses"])
        assert multi[0]["check"] == single[0]["check"], (
            multi[0]["check"], single[0]["check"])

        # Cross-topology resume: the checkpoint the 2-process job wrote
        # collaboratively restores into a DIFFERENT process layout (one
        # process, 8 devices) and continues bit-identically — pod
        # checkpoints are portable across deployment shapes (elastic
        # pod-resize resume).
        resumed = _run_job(tmp_path, "resume1", nproc=1, local_devices=8,
                           restore_from=tmp_path / "ckpt-np2")
        assert resumed[0]["resume"] == multi[0]["resume"], (
            resumed[0]["resume"], multi[0]["resume"])
