"""``horovodrun`` CLI for TPU jobs.

Reference: ``run/run.py:395-960`` — same flag groups (job size/hosts,
tuneable params, autotune, timeline, stall check, logging, config file with
CLI-override precedence), translated to the TPU launch model: one process
per host, JAX coordination service instead of mpirun/ssh-orted, chips
discovered from the TPU runtime.

Usage:
    horovodrun -np 2 -H host1:4,host2:4 python train.py
    horovodrun --config-file cfg.yaml python train.py
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from horovod_tpu.runner import config_parser
from horovod_tpu.runner.chips import ChipPartitionError
from horovod_tpu.runner.hosts import parse_hosts
from horovod_tpu.runner.launch import launch_job


class _RecordAction(argparse.Action):
    """Track explicitly-passed flags so config-file values don't override
    them (reference override-actions, ``run/run.py:337-393``)."""

    def __call__(self, parser, namespace, values, option_string=None):
        if not hasattr(namespace, "_explicit_args"):
            namespace._explicit_args = set()
        namespace._explicit_args.add(self.dest)
        setattr(
            namespace,
            self.dest,
            True if self.nargs == 0 and values in (None, []) else values,
        )


class _RecordStore(_RecordAction):
    pass


class _RecordTrue(_RecordAction):
    def __init__(self, *a, **kw):
        kw["nargs"] = 0
        super().__init__(*a, **kw)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    p = argparse.ArgumentParser(
        prog="horovodrun", description="Launch a horovod_tpu training job."
    )
    p.add_argument("-v", "--version", action="store_true", dest="version")
    p.add_argument("-cb", "--check-build", action="store_true",
                   dest="check_build",
                   help="show available frontends / control plane / data "
                        "plane and exit (reference --check-build)")
    p.add_argument("-np", "--num-proc", type=int, dest="np", default=None,
                   help="number of host processes (defaults to number of -H hosts)")
    group_hosts = p.add_mutually_exclusive_group()
    group_hosts.add_argument("-H", "--hosts", dest="hosts", default=None,
                             help="host1:chips,host2:chips")
    group_hosts.add_argument("--hostfile", dest="hostfile", default=None)
    p.add_argument("--output-filename", dest="output_filename", default=None,
                   help="per-rank stdout/stderr capture directory")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--config-file", dest="config_file", default=None)
    p.add_argument("--start-port", type=int, dest="start_port", default=0,
                   help="rendezvous port (0 = ephemeral)")
    p.add_argument("--disable-cache", action="store_true",
                   dest="disable_cache",
                   help="re-run pre-flight checks (ssh reachability) "
                        "instead of using cached results")

    tune = p.add_argument_group("tuneable parameter arguments")
    tune.add_argument("--fusion-threshold-mb", type=float, action=_RecordStore,
                      dest="fusion_threshold_mb", default=None)
    tune.add_argument("--cycle-time-ms", type=float, action=_RecordStore,
                      dest="cycle_time_ms", default=None)
    tune.add_argument("--cache-capacity", type=int, action=_RecordStore,
                      dest="cache_capacity", default=None)
    tune.add_argument("--hierarchical-allreduce", action=_RecordTrue,
                      dest="hierarchical_allreduce", default=None)
    tune.add_argument("--hierarchical-allgather", action=_RecordTrue,
                      dest="hierarchical_allgather", default=None)

    at = p.add_argument_group("autotune arguments")
    at.add_argument("--autotune", action=_RecordTrue, dest="autotune", default=False)
    at.add_argument("--autotune-log-file", action=_RecordStore,
                    dest="autotune_log_file", default=None)
    at.add_argument("--autotune-warmup-samples", type=int, action=_RecordStore,
                    dest="autotune_warmup_samples", default=None)
    at.add_argument("--autotune-steps-per-sample", type=int, action=_RecordStore,
                    dest="autotune_steps_per_sample", default=None)

    tl = p.add_argument_group("timeline arguments")
    tl.add_argument("--timeline-filename", action=_RecordStore,
                    dest="timeline_filename", default=None)
    tl.add_argument("--timeline-mark-cycles", action=_RecordTrue,
                    dest="timeline_mark_cycles", default=False)

    st = p.add_argument_group("stall check arguments")
    st.add_argument("--no-stall-check", action=_RecordTrue,
                    dest="no_stall_check", default=False)
    st.add_argument("--stall-check-warning-time-seconds", type=int,
                    action=_RecordStore,
                    dest="stall_check_warning_time_seconds", default=None)
    st.add_argument("--stall-check-shutdown-time-seconds", type=int,
                    action=_RecordStore,
                    dest="stall_check_shutdown_time_seconds", default=None)

    lg = p.add_argument_group("logging arguments")
    lg.add_argument("--log-level", action=_RecordStore, dest="log_level",
                    default=None,
                    choices=["TRACE", "DEBUG", "INFO", "WARNING", "ERROR", "FATAL"])

    el = p.add_argument_group(
        "elastic arguments",
        "supervised restart instead of kill-all: survive rank failure by "
        "re-rendezvousing over the remaining (non-blacklisted) hosts and "
        "resuming from the last committed elastic.State")
    el.add_argument("--min-np", type=int, dest="min_np", default=None,
                    help="minimum hosts to keep the job alive; enables "
                         "elastic mode")
    el.add_argument("--max-np", type=int, dest="max_np", default=None,
                    help="maximum hosts to use per rendezvous epoch")
    el.add_argument("--reset-limit", type=int, dest="reset_limit",
                    default=None,
                    help="abort after this many supervised restarts")
    el.add_argument("--blacklist-cooldown", type=float,
                    dest="blacklist_cooldown", default=600.0,
                    help="seconds a failed host stays blacklisted "
                         "(0 = forever)")
    el.add_argument("--host-discovery-script", dest="host_discovery_script",
                    default=None,
                    help="script printing one available host per line as "
                         "hostname[:slots]; polled before each epoch; "
                         "enables elastic mode")
    el.add_argument("--discovery-timeout", type=float,
                    dest="discovery_timeout", default=None,
                    help="seconds to keep polling discovery for min-np "
                         "hosts before aborting (default: 60 with a "
                         "discovery script — one transient script failure "
                         "must not kill the job — else 0)")
    el.add_argument("--metrics-port", type=int, dest="metrics_port",
                    default=None,
                    help="serve the fleet observability endpoints "
                         "(GET /metrics Prometheus + GET /fleet JSON, "
                         "aggregated across ranks) on this port "
                         "(0 = ephemeral; docs/observability.md)")
    el.add_argument("--straggler-threshold", type=float,
                    dest="straggler_threshold", default=2.0,
                    help="flag a rank as a straggler when its step time "
                         "exceeds this multiple of the fleet median "
                         "(report-only)")
    el.add_argument("--straggler-patience", type=int,
                    dest="straggler_patience", default=3,
                    help="consecutive slow step reports before flagging")

    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command to launch")
    args = p.parse_args(argv)
    if not hasattr(args, "_explicit_args"):
        args._explicit_args = set()
    return args


def check_hosts_ssh(hostnames, timeout: float = 15.0,
                    use_cache: bool = True) -> None:
    """Pre-flight: every remote host must accept a non-interactive ssh
    (reference ``_check_all_hosts_ssh_successful``, ``run/run.py:63-116``
    — run in parallel, fail fast naming the unreachable hosts).
    Successes are remembered in the launcher cache
    (:mod:`horovod_tpu.runner.cache`) so repeated launches skip the
    round-trips, like the reference's ``~/.horovod`` cache."""
    import concurrent.futures
    import shlex
    import subprocess

    from horovod_tpu.runner import cache as cache_mod
    from horovod_tpu.runner.launch import SSH_COMMAND_PREFIX, _is_local

    remote = sorted({h for h in hostnames if not _is_local(h)})
    c = cache_mod.Cache()
    if use_cache:
        remote = [h for h in remote if c.get(f"ssh.{h}") != "ok"]
    if not remote:
        return

    def probe(host):
        try:
            r = subprocess.run(
                shlex.split(SSH_COMMAND_PREFIX) + [host, "true"],
                capture_output=True, timeout=timeout)
            return host, r.returncode == 0
        except Exception:
            return host, False

    with concurrent.futures.ThreadPoolExecutor(len(remote)) as ex:
        results = list(ex.map(probe, remote))
    failed = [h for h, ok in results if not ok]
    if failed:
        raise SystemExit(
            "horovodrun: non-interactive ssh failed for host(s): "
            + ", ".join(failed)
            + " — ensure passwordless ssh (key-based) works to every host")
    if use_cache:
        for h, ok in results:
            if ok:
                c.put(f"ssh.{h}", "ok")


def check_build() -> int:
    """Print the capability matrix (reference ``horovodrun --check-build``,
    ``run/run.py:289-326`` — frameworks / controllers / tensor ops, with
    [X] marks).  Here the controller is always the native TCP star and
    the data plane is XLA; what varies is which frontends import and
    which XLA backends are visible."""
    import importlib.util

    import horovod_tpu

    def mark(ok):
        return "X" if ok else " "

    def importable(mod):
        try:
            return importlib.util.find_spec(mod) is not None
        except Exception:
            return False

    def xla_backend(name):
        try:
            import jax

            return len(jax.devices(name)) > 0
        except Exception:
            return False

    native_ok = True
    try:
        from horovod_tpu import native  # noqa: F401
    except Exception:
        native_ok = False

    print(f"""\
horovod_tpu v{horovod_tpu.__version__}:

Available Frontends:
    [X] JAX (native)
    [{mark(importable('tensorflow'))}] TensorFlow
    [{mark(importable('torch'))}] PyTorch
    [{mark(importable('tensorflow'))}] Keras
    [{mark(importable('mxnet'))}] MXNet

Available Control Planes:
    [{mark(native_ok)}] native TCP star (eager negotiation/fusion/cache)
    [X] compiled SPMD (no runtime controller needed under jit)

Available Data Planes (XLA backends visible from this process):
    [{mark(xla_backend('tpu'))}] TPU (ICI/DCN collectives)
    [{mark(xla_backend('cpu'))}] CPU

Cluster Integrations:
    [X] horovodrun / run_func launcher
    [{mark(importable('pyspark'))}] Spark""")
    return 0


def _run(args: argparse.Namespace) -> int:
    if args.version:
        import horovod_tpu

        print(horovod_tpu.__version__)
        return 0
    if args.check_build:
        return check_build()
    if not args.command:
        raise SystemExit("horovodrun: no command specified")
    config_parser.apply_config_file(args, args.config_file)
    host_specs = parse_hosts(args.hosts, args.hostfile)
    if args.np is not None:
        if args.hosts is None and args.hostfile is None:
            host_specs = [host_specs[0]] * 0 or [
                type(host_specs[0])("localhost", 0)
            ]
        if len(host_specs) not in (args.np, 1):
            raise SystemExit(
                f"horovodrun: -np {args.np} does not match {len(host_specs)} hosts"
            )
        if len(host_specs) == 1 and args.np > 1:
            host_specs = host_specs * args.np
    env = dict(os.environ)
    config_parser.set_env_from_args(env, args)
    check_hosts_ssh([h.hostname for h in host_specs],
                    use_cache=not args.disable_cache)
    elastic = (args.min_np is not None
               or args.host_discovery_script is not None)
    if elastic:
        from horovod_tpu.runner.discovery import (
            FixedHostDiscovery, ScriptHostDiscovery)
        from horovod_tpu.runner.elastic_driver import (
            ElasticJobError, run_elastic)

        if args.host_discovery_script:
            discovery = ScriptHostDiscovery(args.host_discovery_script)
            discovery_timeout = (args.discovery_timeout
                                 if args.discovery_timeout is not None
                                 else 60.0)
        else:
            discovery = FixedHostDiscovery(host_specs)
            discovery_timeout = args.discovery_timeout or 0.0
        if args.verbose:
            print(f"horovodrun: elastic launch "
                  f"(min_np={args.min_np or 1}, max_np={args.max_np})")
        try:
            return run_elastic(
                args.command,
                discovery=discovery,
                min_np=args.min_np or 1,
                max_np=args.max_np,
                env=env,
                reset_limit=args.reset_limit,
                blacklist_cooldown=args.blacklist_cooldown or None,
                discovery_timeout=discovery_timeout,
                output_filename=args.output_filename,
                coordinator_port=args.start_port,
                metrics_port=args.metrics_port,
                straggler_threshold=args.straggler_threshold,
                straggler_patience=args.straggler_patience,
            )
        except ElasticJobError as e:
            raise SystemExit(f"horovodrun: {e}")
    if args.verbose:
        print(f"horovodrun: launching on {len(host_specs)} host(s)")
    try:
        return launch_job(
            args.command,
            host_specs,
            env=env,
            output_filename=args.output_filename,
            coordinator_port=args.start_port,
        )
    except ChipPartitionError as e:
        raise SystemExit(f"horovodrun: {e}")


def run_commandline(argv: Optional[List[str]] = None) -> None:
    sys.exit(_run(parse_args(argv)))


if __name__ == "__main__":
    run_commandline()
