"""Launcher tests (reference: test/test_run.py — arg parsing, config-file
precedence, command construction with mocked exec; plus a REAL 2-process
local launch, which the reference only gets via CI's mpirun wrapper)."""

import os
import sys
import textwrap
import threading

import pytest

from horovod_tpu.runner import config_parser, launch, rendezvous
from horovod_tpu.runner.hosts import HostSpec, SlotInfo, allocate, parse_hosts
from horovod_tpu.runner.run import parse_args, _run


class TestHostParsing:
    def test_hosts_string(self):
        specs = parse_hosts("a:4,b:8")
        assert specs == [HostSpec("a", 4), HostSpec("b", 8)]

    def test_host_no_slots(self):
        assert parse_hosts("a,b") == [HostSpec("a", 1), HostSpec("b", 1)]

    def test_hostfile(self, tmp_path):
        f = tmp_path / "hosts"
        f.write_text("# comment\nnode1 slots=4\nnode2 slots=2\n\n")
        assert parse_hosts(hostfile=str(f)) == [
            HostSpec("node1", 4),
            HostSpec("node2", 2),
        ]

    def test_both_raises(self):
        with pytest.raises(ValueError):
            parse_hosts("a:1", "file")

    def test_default_localhost(self):
        assert parse_hosts() == [HostSpec("localhost", 0)]

    def test_allocate(self):
        slots = allocate([HostSpec("a", 4), HostSpec("b", 4)])
        assert slots[0].rank == 0 and slots[1].rank == 1
        assert all(s.size == 2 for s in slots)
        assert all(s.world_chips == 8 for s in slots)
        env = slots[1].to_env()
        assert env["HOROVOD_RANK"] == "1"
        assert env["HOROVOD_CROSS_SIZE"] == "2"
        assert env["HOROVOD_LOCAL_SIZE"] == "4"


class TestArgsAndConfig:
    def test_basic_parse(self):
        args = parse_args(["-np", "2", "-H", "h1:4,h2:4", "python", "train.py"])
        assert args.np == 2
        assert args.hosts == "h1:4,h2:4"
        assert args.command == ["python", "train.py"]

    def test_flag_groups(self):
        args = parse_args(
            [
                "--fusion-threshold-mb", "32",
                "--autotune",
                "--timeline-filename", "/tmp/t.json",
                "--no-stall-check",
                "--log-level", "DEBUG",
                "cmd",
            ]
        )
        env = config_parser.set_env_from_args({}, args)
        assert env["HOROVOD_FUSION_THRESHOLD"] == str(32 * 1024 * 1024)
        assert env["HOROVOD_AUTOTUNE"] == "1"
        assert env["HOROVOD_TIMELINE"] == "/tmp/t.json"
        assert env["HOROVOD_STALL_CHECK_DISABLE"] == "1"
        assert env["HOROVOD_LOG_LEVEL"] == "DEBUG"

    def test_config_file_and_cli_precedence(self, tmp_path):
        """CLI flags beat config-file values (test_run.py:176-233)."""
        cfg = tmp_path / "cfg.yaml"
        cfg.write_text(
            textwrap.dedent(
                """
                params:
                  fusion-threshold-mb: 16
                  cycle-time-ms: 3.5
                autotune:
                  enabled: true
                  warmup-samples: 5
                timeline:
                  filename: /tmp/from_config.json
                stall-check:
                  disable: false
                  warning-time-seconds: 120
                """
            )
        )
        args = parse_args(
            ["--fusion-threshold-mb", "64", "--config-file", str(cfg), "cmd"]
        )
        config_parser.apply_config_file(args, args.config_file)
        assert args.fusion_threshold_mb == 64.0  # CLI wins
        assert args.cycle_time_ms == 3.5  # config applies
        assert args.autotune is True
        assert args.autotune_warmup_samples == 5
        assert args.timeline_filename == "/tmp/from_config.json"
        assert args.stall_check_warning_time_seconds == 120

    def test_version(self, capsys):
        args = parse_args(["--version"])
        assert _run(args) == 0
        import horovod_tpu

        assert horovod_tpu.__version__ in capsys.readouterr().out

    def test_no_command(self):
        with pytest.raises(SystemExit):
            _run(parse_args(["-np", "1"]))


class TestSshPreflight:
    def test_local_hosts_skip_probe(self, monkeypatch):
        from horovod_tpu.runner import run as run_mod

        import subprocess

        def boom(*a, **k):
            raise AssertionError("must not probe local hosts")

        monkeypatch.setattr(subprocess, "run", boom)
        run_mod.check_hosts_ssh(["localhost", "127.0.0.1"])  # no raise

    def test_unreachable_host_fails_fast(self, monkeypatch, tmp_path):
        from horovod_tpu.runner import cache as cache_mod
        from horovod_tpu.runner import run as run_mod

        import subprocess

        monkeypatch.setattr(cache_mod, "DEFAULT_PATH",
                            str(tmp_path / "cache.json"))

        class R:
            returncode = 255

        calls = []

        def fake_run(cmd, **k):
            calls.append(cmd)
            return R()

        monkeypatch.setattr(subprocess, "run", fake_run)
        with pytest.raises(SystemExit, match="badhost"):
            run_mod.check_hosts_ssh(["badhost", "localhost"])
        assert len(calls) == 1  # only the remote host probed

    def test_success_cached(self, monkeypatch, tmp_path):
        from horovod_tpu.runner import cache as cache_mod
        from horovod_tpu.runner import run as run_mod

        import subprocess

        monkeypatch.setattr(cache_mod, "DEFAULT_PATH",
                            str(tmp_path / "cache.json"))

        class R:
            returncode = 0

        calls = []

        def fake_run(cmd, **k):
            calls.append(cmd)
            return R()

        monkeypatch.setattr(subprocess, "run", fake_run)
        run_mod.check_hosts_ssh(["far1", "far2"])
        assert len(calls) == 2
        run_mod.check_hosts_ssh(["far1", "far2"])  # cache hit: no probes
        assert len(calls) == 2
        run_mod.check_hosts_ssh(["far1"], use_cache=False)  # forced
        assert len(calls) == 3


class TestCache:
    def test_roundtrip_and_ttl(self, tmp_path):
        from horovod_tpu.runner.cache import Cache

        c = Cache(str(tmp_path / "c.json"), ttl_seconds=1000)
        assert c.get("k") is None
        c.put("k", {"a": 1})
        assert c.get("k") == {"a": 1}
        expired = Cache(str(tmp_path / "c.json"), ttl_seconds=0)
        assert expired.get("k") is None

    def test_corrupt_file_is_empty(self, tmp_path):
        from horovod_tpu.runner.cache import Cache

        p = tmp_path / "c.json"
        p.write_text("{not json")
        c = Cache(str(p))
        assert c.get("k") is None
        c.put("k", 1)  # must not raise
        assert c.get("k") == 1


class TestRendezvous:
    def test_kv_roundtrip(self):
        server = rendezvous.RendezvousServer()
        port = server.start()
        try:
            client = rendezvous.KVClient("127.0.0.1", port)
            assert client.get("scope", "k") is None
            client.put("scope", "k", b"value")
            assert client.get("scope", "k") == b"value"
            assert client.wait("scope", "k") == b"value"
            client.delete_scope("scope")
            assert client.get("scope", "k") is None
        finally:
            server.stop()

    def test_wait_timeout(self):
        server = rendezvous.RendezvousServer()
        port = server.start()
        try:
            client = rendezvous.KVClient("127.0.0.1", port)
            with pytest.raises(TimeoutError):
                client.wait("s", "missing", timeout=0.3)
        finally:
            server.stop()

    def test_concurrent_publish(self):
        server = rendezvous.RendezvousServer()
        port = server.start()
        try:
            client = rendezvous.KVClient("127.0.0.1", port)

            def pub(i):
                client.put("s", f"k{i}", str(i).encode())

            ts = [threading.Thread(target=pub, args=(i,)) for i in range(8)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            for i in range(8):
                assert client.get("s", f"k{i}") == str(i).encode()
        finally:
            server.stop()

    def test_scope_listing_and_server_side_access(self):
        """GET /scope/ lists keys (elastic heartbeat scanning), and the
        supervisor-side server helpers interoperate with signed client
        writes."""
        server = rendezvous.RendezvousServer()
        port = server.start()
        try:
            client = rendezvous.KVClient("127.0.0.1", port)
            client.put("hb", "r0", b"1.0")
            client.put("hb", "r1", b"2.0")
            assert client.keys("hb") == ["r0", "r1"]
            assert server.keys("hb") == ["r0", "r1"]
            assert server.get("hb", "r1") == b"2.0"
            server.put("hb", "r2", b"3.0")
            assert client.get("hb", "r2") == b"3.0"
            server.clear_scope("hb")
            assert client.keys("hb") == []
            assert client.get("hb", "r0") is None
        finally:
            server.stop()


class TestHostDiscovery:
    def test_fixed(self):
        from horovod_tpu.runner.discovery import FixedHostDiscovery

        specs = [HostSpec("a", 4), HostSpec("b", 4)]
        assert FixedHostDiscovery(specs).find_available_hosts() == specs

    def test_script(self, tmp_path):
        from horovod_tpu.runner.discovery import ScriptHostDiscovery

        script = tmp_path / "discover.sh"
        script.write_text("#!/bin/sh\n"
                          "echo 'node1:4'\n"
                          "echo '# stale entry'\n"
                          "echo 'node2'\n")
        script.chmod(0o755)
        specs = ScriptHostDiscovery(str(script)).find_available_hosts()
        assert specs == [HostSpec("node1", 4), HostSpec("node2", 1)]

    def test_failing_script_yields_empty(self, tmp_path):
        from horovod_tpu.runner.discovery import ScriptHostDiscovery

        assert ScriptHostDiscovery("exit 3").find_available_hosts() == []


class TestBlacklist:
    def test_cooldown_expiry(self):
        from horovod_tpu.runner.hosts import Blacklist

        clock = [0.0]
        b = Blacklist(cooldown=5.0, _clock=lambda: clock[0])
        b.add("bad")
        assert "bad" in b and b.hosts() == ["bad"]
        assert b.filter([HostSpec("bad", 1), HostSpec("ok", 1)]) == [
            HostSpec("ok", 1)]
        clock[0] = 5.1  # cooldown elapsed: host readmitted
        assert "bad" not in b and b.hosts() == []
        b.add("bad")
        assert b.failure_count("bad") == 2

    def test_forever(self):
        from horovod_tpu.runner.hosts import Blacklist

        b = Blacklist(cooldown=None)
        b.add("bad")
        assert "bad" in b


class TestLaunch:
    def test_command_construction_local(self):
        slot = SlotInfo("localhost", 0, 2, 4, 8)
        cmd, env, stdin = launch.build_command(
            slot, ["python", "t.py"], {"PATH": "/bin"}, "127.0.0.1", 5000
        )
        assert stdin is None
        assert cmd == ["python", "t.py"]
        assert env["HOROVOD_RANK"] == "0"
        assert env["HOROVOD_COORDINATOR_ADDR"] == "127.0.0.1"
        assert env["HOROVOD_COORDINATOR_PORT"] == "5000"
        assert env["HOROVOD_GLOO_RENDEZVOUS_PORT"] == "5000"

    def test_command_construction_ssh(self):
        slot = SlotInfo("remotehost", 1, 2, 4, 8)
        cmd, _, _ = launch.build_command(
            slot, ["python", "t.py"], {}, "10.0.0.1", 5000
        )
        assert cmd[0] == "ssh"
        assert "remotehost" in cmd
        remote = cmd[-1]
        assert "HOROVOD_RANK=1" in remote
        assert "python t.py" in remote

    def test_mocked_launch_all_ranks(self):
        """Reference-style mocked exec: assert each rank got the right env
        (test_run.py:259-352 pattern)."""
        calls = []

        def fake_exec(cmd, env=None, **kw):
            calls.append((cmd, env))
            return 0

        rc = launch.launch_job(
            ["python", "x.py"],
            [HostSpec("localhost", 4), HostSpec("localhost", 4)],
            env={},
            _executor=fake_exec,
        )
        assert rc == 0
        assert len(calls) == 2
        ranks = sorted(int(env["HOROVOD_RANK"]) for _, env in calls)
        assert ranks == [0, 1]

    def test_failure_propagates(self):
        def fake_exec(cmd, env=None, **kw):
            return 3 if env["HOROVOD_RANK"] == "1" else 0

        rc = launch.launch_job(
            ["x"],
            [HostSpec("localhost", 1)] * 2,
            env={},
            _executor=fake_exec,
        )
        assert rc == 3

    @pytest.mark.slow
    def test_real_two_process_launch(self, tmp_path):
        """Actually spawn 2 local processes that rendezvous through the KV
        server and verify each other's ranks — real end-to-end launch."""
        script = tmp_path / "worker.py"
        script.write_text(
            textwrap.dedent(
                """
                import os, sys
                sys.path.insert(0, os.environ["REPO"])
                from horovod_tpu.runner.rendezvous import KVClient
                rank = os.environ["HOROVOD_RANK"]
                size = int(os.environ["HOROVOD_SIZE"])
                c = KVClient(os.environ["HOROVOD_COORDINATOR_ADDR"],
                             int(os.environ["HOROVOD_COORDINATOR_PORT"]))
                c.put("test", f"rank{rank}", rank.encode())
                for r in range(size):
                    assert c.wait("test", f"rank{r}", timeout=30).decode() == str(r)
                print(f"rank {rank} ok")
                """
            )
        )
        out = tmp_path / "out"
        env = {
            "PATH": os.environ.get("PATH", ""),
            "REPO": os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            # keep the ranks off any chip
            "JAX_PLATFORMS": "cpu",
        }
        rc = launch.launch_job(
            [sys.executable, str(script)],
            [HostSpec("localhost", 1)] * 2,
            env=env,
            output_filename=str(out),
        )
        assert rc == 0
        assert "ok" in (out / "rank.0.stdout").read_text()
        assert "ok" in (out / "rank.1.stdout").read_text()


    @pytest.mark.slow
    def test_sigterm_kills_term_swallowing_ranks(self, tmp_path):
        """SIGTERM to the launcher must reap ranks that CATCH SIGTERM
        (JAX installs a preemption notifier that swallows it): the
        launcher has to stay alive through the watchers' TERM -> grace ->
        KILL escalation instead of dying after a token sleep."""
        import signal
        import subprocess
        import time

        script = tmp_path / "stubborn.py"
        script.write_text(
            "import signal, time\n"
            "signal.signal(signal.SIGTERM, lambda *a: None)\n"
            "print('ready', flush=True)\n"
            "time.sleep(600)\n"
        )
        driver = tmp_path / "driver.py"
        driver.write_text(textwrap.dedent(f"""
            import os, sys
            sys.path.insert(0, os.environ["REPO"])
            from horovod_tpu.runner import launch
            from horovod_tpu.runner.hosts import HostSpec
            launch.launch_job(
                [sys.executable, {str(script)!r}],
                [HostSpec("localhost", 1)] * 2,
                env={{"PATH": os.environ.get("PATH", ""),
                     "JAX_PLATFORMS": "cpu"}},
                output_filename={str(tmp_path / "out")!r})
        """))
        env = dict(os.environ)
        env["REPO"] = os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.Popen([sys.executable, str(driver)], env=env)
        # wait for both ranks to be up
        deadline = time.time() + 60
        outdir = tmp_path / "out"
        while time.time() < deadline:
            try:
                if all("ready" in (outdir / f"rank.{r}.stdout").read_text()
                       for r in (0, 1)):
                    break
            except OSError:
                pass
            time.sleep(0.3)
        else:
            proc.kill()
            raise AssertionError("ranks never came up")
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=30)
        # after the escalation window, no stubborn.py processes survive
        time.sleep(1.0)
        left = subprocess.run(
            ["pgrep", "-f", "stubborn.py"], capture_output=True
        ).stdout.decode().split()
        left = [p for p in left
                if subprocess.run(["ps", "-o", "comm=", "-p", p],
                                  capture_output=True
                                  ).stdout.decode().strip() == "python"]
        assert not left, f"orphaned rank processes: {left}"

    def test_ssh_secret_rides_stdin_not_argv(self):
        """The per-job HMAC key must never appear on a remote command line
        (visible via /proc/<pid>/cmdline to any local user)."""
        slot = SlotInfo("remotehost", 1, 2, 4, 8)
        cmd, _, stdin = launch.build_command(
            slot, ["python", "t.py"], {"HOROVOD_SECRET_KEY": "deadbeef"},
            "10.0.0.1", 5000
        )
        assert "deadbeef" not in " ".join(cmd)
        assert stdin == b"deadbeef\n"
        assert "read -r HOROVOD_SECRET_KEY" in cmd[-1]
