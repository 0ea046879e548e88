"""Programmatic in-process launch: ``run(fn, ...)`` executes ``fn`` on
every rank of a freshly launched job and returns each rank's result.

Reference: ``horovod.run.run()`` (``run/run.py:870-956``) and its
run-func plumbing (``run/run_task.py`` / ``run/task_fn.py``): the
launcher cloudpickles ``fn``, every rank fetches + executes it, and
results come back through the KV store.  Here the pickle and results
travel through a shared scratch directory (single host or shared fs) —
the transport the reference's KV server provided — while rank/rendezvous
env wiring reuses the standard launcher.
"""

from __future__ import annotations

import os
import pickle
import socket
import sys
import tempfile
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from horovod_tpu.runner import launch
from horovod_tpu.runner.hosts import HostSpec


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run(
    fn: Callable,
    args: tuple = (),
    kwargs: Optional[Dict] = None,
    *,
    num_proc: int = 2,
    hosts: Optional[List[HostSpec]] = None,
    env: Optional[Dict[str, str]] = None,
    use_jax_platform: str = "cpu",
    output_dir: Optional[str] = None,
) -> List[Any]:
    """Run ``fn(*args, **kwargs)`` on ``num_proc`` ranks; returns the list
    of per-rank return values (reference ``horovod.run.run`` contract).

    Each rank gets the full ``HOROVOD_*`` env from the launcher and is
    expected to call ``horovod_tpu.init()`` itself (typically via the
    frontend it uses) — exactly like a script started by ``horovodrun``.
    """
    if hosts is not None:
        total_slots = sum(h.slots for h in hosts)
        if total_slots != num_proc:
            raise ValueError(
                f"hosts provide {total_slots} slots but num_proc="
                f"{num_proc}; they must match")
    scratch = tempfile.mkdtemp(prefix="hvd_runfunc_")
    payload = os.path.join(scratch, "fn.pkl")
    # Pickle caller-module functions BY VALUE: the module that defines fn
    # (a script, a test file) is usually not importable inside a freshly
    # launched rank.  Package code (horovod_tpu.*) stays by-reference.
    registered = []

    def _collect(obj, depth=0):
        if depth > 4:
            return
        if isinstance(obj, dict):
            for v in obj.values():
                _collect(v, depth + 1)
            return
        if isinstance(obj, (list, tuple, set)):
            for v in obj:
                _collect(v, depth + 1)
            return
        mod_name = getattr(obj, "__module__", None)
        if (callable(obj) and mod_name and mod_name != "__main__"
                and not mod_name.startswith(("horovod_tpu", "builtins",
                                             "numpy", "torch", "jax",
                                             "optax"))):
            mod = sys.modules.get(mod_name)
            if mod is not None and mod not in registered:
                cloudpickle.register_pickle_by_value(mod)
                registered.append(mod)

    _collect((fn, args, kwargs or {}))
    try:
        with open(payload, "wb") as f:
            cloudpickle.dump((fn, args, kwargs or {}), f)
    finally:
        for mod in registered:
            cloudpickle.unregister_pickle_by_value(mod)

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # The ranks' environment is built from scratch, but what steers JAX
    # and XLA (the compile-cache directory, flags) travels with the job,
    # as it does to remote ranks (launch.build_command).
    run_env = {k: v for k, v in os.environ.items()
               if k.startswith(("JAX_", "XLA_"))}
    if use_jax_platform:
        # Also tells the launcher whether these ranks will open chips.
        run_env["JAX_PLATFORMS"] = use_jax_platform
    run_env.update({
        "PATH": os.environ.get("PATH", ""),
        "PYTHONPATH": repo + os.pathsep + os.environ.get("PYTHONPATH", ""),
        "HOROVOD_NUM_PROC": str(num_proc),
        "HOROVOD_JAX_PORT": str(_free_port()),
        "HOROVOD_NATIVE_PORT": str(_free_port()),
        "HVD_RUN_FUNC_PAYLOAD": payload,
        "HVD_RUN_FUNC_SCRATCH": scratch,
        "HVD_RUN_FUNC_PLATFORM": use_jax_platform,
    })
    run_env.update(env or {})

    try:
        rc = launch.launch_job(
            [sys.executable, "-m", "horovod_tpu.runner.run_task"],
            hosts or [HostSpec("localhost", 1)] * num_proc,
            env=run_env,
            output_filename=output_dir,
        )
        results: List[Any] = []
        errors: List[str] = []
        for r in range(num_proc):
            path = os.path.join(scratch, f"result.{r}.pkl")
            if not os.path.exists(path):
                errors.append(f"rank {r}: no result written (crashed?)")
                continue
            with open(path, "rb") as f:
                kind, value = pickle.load(f)
            if kind == "error":
                errors.append(f"rank {r} raised:\n{value}")
            else:
                results.append(value)
        if rc != 0 or errors:
            raise RuntimeError(
                "run(fn) failed"
                + (f" (exit code {rc})" if rc else "")
                + (f"; per-rank logs in {output_dir}" if output_dir else "")
                + ("\n" + "\n".join(errors) if errors else ""))
        return results
    finally:
        import shutil

        shutil.rmtree(scratch, ignore_errors=True)
