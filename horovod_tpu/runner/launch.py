"""Job launch: spawn one process per host with the rank/rendezvous env.

Reference: ``run/gloo_run.py`` (rank allocation → RendezvousServer → per
slot ssh/local spawn with HOROVOD_* env → output capture → kill-all on any
failure).  The mpirun path (``run/mpi_run.py``) has no TPU analogue: there
is no external runtime to delegate to, so this module IS the process
manager.
"""

from __future__ import annotations

import os
import shlex
import signal
import sys
import threading
from typing import Dict, List, Optional, Tuple

from horovod_tpu.runner import chips, safe_shell_exec
from horovod_tpu.runner.hosts import HostSpec, SlotInfo, allocate
from horovod_tpu.runner.rendezvous import RendezvousServer

SSH_COMMAND_PREFIX = "ssh -o PasswordAuthentication=no -o StrictHostKeyChecking=no"


def _is_local(hostname: str) -> bool:
    # "localhost-<suffix>" names are also local: distinct LOGICAL hosts on
    # one machine, used by elastic fault-injection drills and
    # single-machine simulation where host-level blacklisting must
    # distinguish the "hosts".  The dash is deliberate — a real cluster
    # host named e.g. "localhost2" must still go over ssh.
    return (hostname in ("localhost", "localhost.localdomain", "127.0.0.1",
                         os.uname().nodename)
            or hostname.startswith("localhost-"))


def build_command(
    slot: SlotInfo,
    command: List[str],
    env: Dict[str, str],
    coordinator_addr: str,
    coordinator_port: int,
    chip_env: Optional[Dict[str, str]] = None,
) -> (List[str], Dict[str, str], Optional[bytes]):
    """The env contract every rank receives (reference
    ``gloo_run.py:262-288``).  Returns (argv, env, stdin_bytes): for
    remote slots the per-job HMAC secret travels over the ssh channel's
    stdin, never on the command line where any local user could read it
    from /proc/<pid>/cmdline.  ``chip_env`` is a LOCAL rank's share of
    this host's TPU chips (:func:`chips.local_rank_envs`)."""
    slot_env = dict(env)
    slot_env.update(slot.to_env())
    slot_env["HOROVOD_COORDINATOR_ADDR"] = coordinator_addr
    slot_env["HOROVOD_COORDINATOR_PORT"] = str(coordinator_port)
    slot_env["HOROVOD_GLOO_RENDEZVOUS_ADDR"] = coordinator_addr  # compat name
    slot_env["HOROVOD_GLOO_RENDEZVOUS_PORT"] = str(coordinator_port)
    if _is_local(slot.hostname):
        # Local spawn: env travels through Popen(env=...), not argv — safe.
        slot_env.update(chip_env or {})
        return command, slot_env, None
    secret_val = slot_env.get("HOROVOD_SECRET_KEY")
    exports = " ".join(
        f"{k}={shlex.quote(v)}"
        for k, v in slot_env.items()
        if k != "HOROVOD_SECRET_KEY"
        and k.startswith(("HOROVOD_", "PYTHON", "PATH", "JAX_", "XLA_"))
    )
    cmd_str = " ".join(shlex.quote(c) for c in command)
    stdin_data = None
    if secret_val:
        remote = (
            f"cd {shlex.quote(os.getcwd())} > /dev/null 2>&1 ; "
            f"IFS= read -r HOROVOD_SECRET_KEY ; export HOROVOD_SECRET_KEY ; "
            f"env {exports} HOROVOD_SECRET_KEY=\"$HOROVOD_SECRET_KEY\" {cmd_str}"
        )
        stdin_data = (secret_val + "\n").encode()
    else:
        remote = (
            f"cd {shlex.quote(os.getcwd())} > /dev/null 2>&1 ; "
            f"env {exports} {cmd_str}"
        )
    return (shlex.split(SSH_COMMAND_PREFIX) + [slot.hostname, remote], env,
            stdin_data)


def spawn_ranks(
    command: List[str],
    slots: List[SlotInfo],
    env: Dict[str, str],
    coordinator_addr: str,
    coordinator_port: int,
    *,
    output_filename: Optional[str] = None,
    failure: Optional[threading.Event] = None,
    on_rank_exit=None,
    _executor=safe_shell_exec.execute,
) -> Tuple[List[threading.Thread], List[Optional[int]]]:
    """Start one supervised spawn thread per slot; returns the (started)
    threads and the shared exit-code list they fill in.

    The per-epoch core shared by :func:`launch_job` (single round,
    kill-all) and the ElasticDriver (round per rendezvous epoch,
    supervised restart).  ``failure`` set → every rank's process group is
    terminated (TERM → grace → KILL); ``on_rank_exit(index, slot, rc)``
    fires as each rank exits, from that rank's watcher thread."""
    exit_codes: List[Optional[int]] = [None] * len(slots)
    # Ranks that share THIS host each get a chip of their own — or the
    # launch is refused here, before any rank exists.
    local = [i for i, s in enumerate(slots) if _is_local(s.hostname)]
    chip_envs = dict(zip(local, chips.local_rank_envs(len(local), env)))

    def _run(i: int, slot: SlotInfo) -> None:
        # EVERY exit path must record an exit code: a None left behind
        # would wedge supervisors polling this list (the ElasticDriver's
        # epoch monitor) and read as success in launch_job's rollup.
        out = err = None
        try:
            try:
                cmd, slot_env, stdin_data = build_command(
                    slot, command, env, coordinator_addr, coordinator_port,
                    chip_envs.get(i))
                if output_filename:
                    os.makedirs(output_filename, exist_ok=True)
                    out = open(os.path.join(
                        output_filename, f"rank.{slot.rank}.stdout"), "w")
                    err = open(os.path.join(
                        output_filename, f"rank.{slot.rank}.stderr"), "w")
                prefix = (f"[{slot.rank}]<stdout>:"
                          if len(slots) > 1 else None)
                rc = _executor(
                    cmd,
                    env=slot_env,
                    stdout=out or sys.stdout,
                    stderr=err or sys.stderr,
                    prefix=prefix,
                    events=[failure] if failure is not None else [],
                    stdin_data=stdin_data,
                )
            except Exception:
                import traceback

                try:
                    traceback.print_exc(file=err or sys.stderr)
                except OSError:
                    pass
                rc = 1
        finally:
            for f in (out, err):
                if f:
                    try:
                        f.close()
                    except OSError:  # e.g. ENOSPC on the buffered flush
                        pass
            exit_codes[i] = rc
            if on_rank_exit is not None:
                on_rank_exit(i, slot, rc)

    threads = []
    for i, slot in enumerate(slots):
        t = threading.Thread(target=_run, args=(i, slot), daemon=True)
        t.start()
        threads.append(t)
    return threads, exit_codes


def launch_job(
    command: List[str],
    host_specs: List[HostSpec],
    *,
    env: Optional[Dict[str, str]] = None,
    output_filename: Optional[str] = None,
    coordinator_port: int = 0,
    _executor=safe_shell_exec.execute,
) -> int:
    """Launch ``command`` on every host; returns first nonzero exit code
    (and terminates all other ranks when any rank fails — the reference's
    any-failure-kills-all policy, ``gloo_run.py:162-259``).  For
    supervised restart instead of kill-all, see
    :mod:`horovod_tpu.runner.elastic_driver`."""
    env = dict(env if env is not None else os.environ)
    # Per-job HMAC secret so only this job's ranks can write rendezvous
    # state (reference run/common/util/secret.py usage in gloo_run).
    if "HOROVOD_SECRET_KEY" not in env:
        from horovod_tpu.runner import secret

        env["HOROVOD_SECRET_KEY"] = secret.make_secret_key()
    slots = allocate(host_specs)
    server = RendezvousServer(
        coordinator_port, secret_key=env["HOROVOD_SECRET_KEY"].encode())
    port = server.start()
    addr = os.environ.get("HOROVOD_HOSTNAME", "127.0.0.1")

    failure = threading.Event()
    # Pre-sized so the signal handler's "wait for the watchers" loop is
    # correct even if a signal lands before spawn_ranks rebinds it.
    exit_codes: List[Optional[int]] = [None] * len(slots)

    def _on_exit(i: int, slot: SlotInfo, rc: int) -> None:
        if rc != 0:
            failure.set()

    # Terminating the launcher must terminate every rank (the reference's
    # SIGTERM path, gloo_run.py:201): ranks run in their own sessions, so
    # without this a killed launcher orphans them mid-collective.
    prev_handlers = {}

    def _on_signal(signum, frame):
        import time

        failure.set()
        # Stay alive until the per-rank watchers finish their TERM ->
        # (grace) -> KILL escalation: ranks may swallow SIGTERM (JAX
        # installs a preemption notifier that catches it), so dying after
        # a token sleep would leave them orphaned mid-escalation.
        deadline = (time.time() + safe_shell_exec.GRACEFUL_TERMINATION_TIME_S
                    + 2.0)
        while time.time() < deadline and any(rc is None for rc in exit_codes):
            time.sleep(0.2)
        prev = prev_handlers.get(signum)
        if callable(prev):
            prev(signum, frame)
        elif prev == signal.SIG_DFL:
            signal.signal(signum, signal.SIG_DFL)
            os.kill(os.getpid(), signum)

    in_main = threading.current_thread() is threading.main_thread()
    if in_main:
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, _on_signal)
            except (ValueError, OSError):  # pragma: no cover
                pass

    try:
        threads, exit_codes = spawn_ranks(
            command, slots, env, addr, port,
            output_filename=output_filename, failure=failure,
            on_rank_exit=_on_exit, _executor=_executor)
        for t in threads:
            t.join()
    finally:
        server.stop()
        if in_main:
            for sig, prev in prev_handlers.items():
                try:
                    signal.signal(sig, prev)
                except (ValueError, OSError):  # pragma: no cover
                    pass
    bad = [rc for rc in exit_codes if rc]
    return bad[0] if bad else 0
