"""Elastic recovery end-to-end: kill one rank of a 3-process job, relaunch
with the 2 survivors, resume from the committed State.

Reference behavior bar (VERDICT r1 #9): ``gloo_run.py:162-259`` kill-all
on any-rank failure + the §5.3/5.4 recovery conventions (rank-0 commit,
restore-then-broadcast, re-init with surviving hosts).  Membership change
on TPU means a fresh mesh: the relaunch IS the recovery mechanism, and
:class:`horovod_tpu.elastic.State` guarantees the survivors resume from
one consistent (step, params) point.
"""

import json
import os
import sys

import pytest

from horovod_tpu import native
from horovod_tpu.runner import launch
from horovod_tpu.runner.discovery import FixedHostDiscovery
from horovod_tpu.runner.elastic_driver import ElasticDriver, ElasticJobError
from horovod_tpu.runner.hosts import HostSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")
DRIVER_WORKER = os.path.join(REPO, "tests", "elastic_driver_worker.py")
HANG_WORKER = os.path.join(REPO, "tests", "elastic_hang_worker.py")


def _free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _launch(tmp_path, phase, nproc, crash_rank=None):
    out = tmp_path / f"out.{phase}"
    results = tmp_path / f"results.{phase}"
    results.mkdir()
    env = {
        "PATH": os.environ.get("PATH", ""),
        "REPO": REPO,
        "JAX_PLATFORMS": "cpu",  # keep subprocesses off the TPU
        "HOROVOD_NUM_PROC": str(nproc),
        "HOROVOD_JAX_PORT": str(_free_port()),
        "HOROVOD_NATIVE_PORT": str(_free_port()),
        "HOROVOD_CYCLE_TIME": "1",
        "ELASTIC_CKPT": str(tmp_path / "state.ckpt"),
        "ELASTIC_RESULTS": str(results),
    }
    if crash_rank is not None:
        env["ELASTIC_CRASH_RANK"] = str(crash_rank)
    rc = launch.launch_job(
        [sys.executable, WORKER],
        [HostSpec("localhost", 1)] * nproc,
        env=env,
        output_filename=str(out),
    )
    return rc, out, results


@pytest.mark.skipif(not native.native_built(), reason="native lib unavailable")
class TestElasticRecovery:
    @pytest.mark.slow
    def test_crash_relaunch_resume(self, tmp_path):
        # Phase 1: 3 ranks, rank 2 dies at step 7 (after the step-5
        # commit).  The launcher must kill the survivors — nonzero exit,
        # no final results, but a checkpoint at step 5.
        rc, out, results = _launch(tmp_path, 1, nproc=3, crash_rank=2)
        assert rc != 0, "crash must fail the whole job (kill-all)"
        assert not list(results.iterdir()), "no rank may have finished"
        assert (tmp_path / "state.ckpt").exists()
        crash_log = (out / "rank.2.stdout").read_text()
        assert "ELASTIC-WORKER-CRASH rank=2 step=7" in crash_log

        # Phase 2: relaunch with the 2 survivors; they restore step 5 and
        # run to completion with consistent state.
        rc, out, results = _launch(tmp_path, 2, nproc=2)
        assert rc == 0, (out / "rank.0.stderr").read_text() + (
            out / "rank.1.stderr").read_text()
        finals = sorted(results.iterdir())
        assert len(finals) == 2
        records = [json.loads(p.read_text()) for p in finals]
        assert all(r["resumed_from"] == 5 for r in records), records
        assert all(r["step"] == 10 for r in records), records
        assert all(r["size"] == 2 for r in records), records
        # consistent state across the survivors
        assert records[0]["checksum"] == pytest.approx(
            records[1]["checksum"]), records

    def test_fresh_run_completes_without_checkpoint(self, tmp_path):
        rc, out, results = _launch(tmp_path, 1, nproc=2)
        assert rc == 0
        records = [json.loads(p.read_text()) for p in sorted(results.iterdir())]
        assert all(r["resumed_from"] is None for r in records)
        assert all(r["step"] == 10 for r in records)


class TestElasticDriverUnit:
    """Driver policy with a mocked executor: restart/blacklist/abort
    decisions without spawning processes."""

    HOSTS = [HostSpec("localhost-a", 1), HostSpec("localhost-b", 1),
             HostSpec("localhost-c", 1)]

    def _driver(self, executor, hosts=None, **kw):
        kw.setdefault("min_np", 2)
        kw.setdefault("backoff_initial", 0.0)
        return ElasticDriver(
            ["x"], FixedHostDiscovery(hosts or self.HOSTS),
            _executor=executor, _sleep=lambda s: None, **kw)

    def test_crash_blacklists_and_restarts(self):
        envs = []

        def executor(cmd, env=None, **kw):
            envs.append(dict(env))
            if int(env["HOROVOD_ELASTIC_EPOCH"]) == 0 and \
                    env["HOROVOD_RANK"] == "1":
                return 17
            return 0

        d = self._driver(executor)
        assert d.run() == 0
        assert d.epoch_sizes == [3, 2]
        assert d.blacklist.hosts() == ["localhost-b"]
        # survivors re-rendezvous with a fresh epoch and fresh ports
        e1 = [e for e in envs if e["HOROVOD_ELASTIC_EPOCH"] == "1"]
        assert len(e1) == 2
        assert {e["HOROVOD_RANK"] for e in e1} == {"0", "1"}
        assert all(e["HOROVOD_NUM_PROC"] == "2" for e in e1)
        e0 = [e for e in envs if e["HOROVOD_ELASTIC_EPOCH"] == "0"]
        assert e0[0]["HOROVOD_JAX_PORT"] != e1[0]["HOROVOD_JAX_PORT"]

    def test_restart_exit_code_is_not_blamed(self):
        def executor(cmd, env=None, **kw):
            if int(env["HOROVOD_ELASTIC_EPOCH"]) == 0:
                return 75  # EXIT_CODE_RESTART: requested, not a failure
            return 0

        d = self._driver(executor)
        assert d.run() == 0
        assert d.blacklist.hosts() == []  # nobody blacklisted
        assert d.epoch_sizes == [3, 3]

    def test_below_min_np_aborts_clearly(self):
        d = self._driver(lambda cmd, env=None, **kw: 17,
                         hosts=self.HOSTS[:2])
        with pytest.raises(ElasticJobError, match="below min_np"):
            d.run()

    def test_reset_limit_aborts(self):
        d = self._driver(lambda cmd, env=None, **kw: 75,
                         hosts=self.HOSTS[:1], min_np=1, reset_limit=2)
        with pytest.raises(ElasticJobError, match="reset_limit"):
            d.run()
        assert d.resets == 3

    def test_max_np_caps_world(self):
        sizes = []

        def executor(cmd, env=None, **kw):
            sizes.append(env["HOROVOD_NUM_PROC"])
            return 0

        d = self._driver(executor, max_np=2)
        assert d.run() == 0
        assert sizes == ["2", "2"]

    def test_blacklist_cooldown_readmits_host(self):
        clock = [0.0]
        d = self._driver(lambda cmd, env=None, **kw: 0)
        d.blacklist._clock = lambda: clock[0]
        d.blacklist._cooldown = 10.0
        d.blacklist.add("localhost-b")
        assert d.blacklist.hosts() == ["localhost-b"]
        assert len(d.blacklist.filter(self.HOSTS)) == 2
        clock[0] = 11.0
        assert d.blacklist.hosts() == []
        assert len(d.blacklist.filter(self.HOSTS)) == 3


class TestElasticDriverHeartbeat:
    @pytest.mark.slow
    def test_stale_heartbeat_triggers_restart(self, tmp_path):
        """A hung (not dead) rank stops heartbeating: the driver must
        stale-detect it over the rendezvous KV, terminate the epoch, and
        restart on the surviving hosts."""
        env = {
            "PATH": os.environ.get("PATH", ""),
            "REPO": REPO,
            "ELASTIC_HANG_RANK": "1",
            "HOROVOD_ELASTIC_HEARTBEAT": "0.2",
        }
        d = ElasticDriver(
            [sys.executable, HANG_WORKER],
            FixedHostDiscovery([HostSpec("localhost-a", 1),
                                HostSpec("localhost-b", 1),
                                HostSpec("localhost-c", 1)]),
            min_np=2, env=env,
            heartbeat_interval=0.2, heartbeat_timeout=1.5,
            shutdown_grace=1.0, backoff_initial=0.1,
            output_filename=str(tmp_path / "out"))
        assert d.run() == 0
        assert d.epoch_sizes == [3, 2]
        assert d.blacklist.hosts() == ["localhost-b"]


@pytest.mark.skipif(not native.native_built(), reason="native lib unavailable")
class TestElasticDriverFaultInjection:
    """The acceptance drill: 3 ranks, min_np=2, one rank dies mid-training
    after a commit — the driver re-rendezvouses and training resumes on
    the survivors from the last committed step."""

    def _drive(self, tmp_path, *, nhosts, crash_rank=None, **driver_kw):
        results = tmp_path / "results"
        results.mkdir(exist_ok=True)
        env = {
            "PATH": os.environ.get("PATH", ""),
            "REPO": REPO,
            "JAX_PLATFORMS": "cpu",  # keep subprocesses off the TPU
            "HOROVOD_CYCLE_TIME": "1",
            "ELASTIC_CKPT": str(tmp_path / "state.ckpt"),
            "ELASTIC_RESULTS": str(results),
        }
        if crash_rank is not None:
            env["ELASTIC_CRASH_RANK"] = str(crash_rank)
        hosts = [HostSpec(f"localhost-{c}", 1) for c in "abc"[:nhosts]]
        driver_kw.setdefault("min_np", 2)
        driver_kw.setdefault("backoff_initial", 0.1)
        driver_kw.setdefault("shutdown_grace", 20.0)
        d = ElasticDriver(
            [sys.executable, DRIVER_WORKER],
            FixedHostDiscovery(hosts), env=env,
            output_filename=str(tmp_path / "out"), **driver_kw)
        return d, results

    @pytest.mark.slow
    def test_crash_triggers_rerendezvous_and_resume(self, tmp_path):
        d, results = self._drive(tmp_path, nhosts=3, crash_rank=2)
        rc = d.run()
        assert rc == 0
        # one supervised restart: 3 ranks -> crash -> 2 survivors
        assert d.epoch_sizes == [3, 2]
        assert d.blacklist.hosts() == ["localhost-c"]

        finals = sorted(results.glob("final.e1.*.json"))
        assert len(finals) == 2, list(results.iterdir())
        records = [json.loads(p.read_text()) for p in finals]
        # resumed from the last committed step; no committed step lost
        assert all(r["resumed_from"] == 5 for r in records), records
        assert all(r["step"] == 10 for r in records), records
        assert all(r["size"] == 2 for r in records), records
        assert records[0]["checksum"] == pytest.approx(
            records[1]["checksum"]), records

        # step counter monotonic across the restart: epoch 1 replays
        # nothing before the committed step 5
        for r in (0, 1):
            steps = [int(s) for s in
                     (results / f"journal.e1.r{r}").read_text().split()]
            assert steps[0] == 6 and steps == sorted(steps), steps

    def test_below_min_np_aborts_not_hangs(self, tmp_path):
        d, _ = self._drive(tmp_path, nhosts=2, crash_rank=1)
        with pytest.raises(ElasticJobError, match="below min_np"):
            d.run()
