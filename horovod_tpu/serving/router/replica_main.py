"""One serving replica: a full engine + HTTP server, as a process.

This is what :class:`~horovod_tpu.serving.router.supervisor.
ReplicaSupervisor` spawns N of — the serving analogue of an elastic
training rank.  The model comes from either ``--params`` (a pickle
written by :func:`dump_model`, e.g. the LM ``examples/serve.py``
trains) or deterministic seeded init (``--seed`` + shape flags): every
replica built from the same seed/params serves byte-identical greedy
output, which is what makes router failover invisible to clients.

Lifecycle contract with the supervisor:

* SIGTERM / SIGINT → graceful drain (``ServingServer.stop``: /healthz
  goes 503, admitted requests finish within ``--drain-timeout``), then
  exit 0;
* the engine going terminally ``failed`` (restart budget exhausted,
  terminated) → drain whatever the teardown can still resolve and
  exit :data:`~horovod_tpu.serving.router.supervisor.
  EXIT_CODE_REPLICA_FAILED` so the exit watcher respawns without
  waiting for a registry poll;
* ``--journal PATH`` arms the engine's request journal as an
  append-only JSONL file (the supervisor passes a per-generation path
  from its ``journal_dir``): it survives SIGKILL, and the router reads
  it post-mortem to RESUME this replica's in-flight requests on a
  survivor (docs/serving.md "Front tier").  ``--no-resume`` restores
  the pre-journal fail-typed restart behavior;
* ``--fault site:kind[:skip[:delay]]`` threads a deterministic
  FaultInjector through the engine for chaos tests (a ``hang`` with a
  long delay and ``--tick-timeout 0`` wedges the replica for real —
  the stale-heartbeat eviction + supervisor-drain path).

Run one by hand:

    python -m horovod_tpu.serving.router.replica_main --port 8001 \\
        --seed 0 --warm 8
"""

from __future__ import annotations

import argparse
import os
import pickle
import signal
import sys
import threading


def dump_model(path: str, params, cfg) -> None:
    """Write a trained model where ``--params`` can load it: params as
    host numpy arrays plus the TransformerConfig fields (dtype by
    name, so the pickle is jax-version-proof)."""
    import dataclasses

    import jax
    import numpy as np

    cfg_dict = dataclasses.asdict(cfg)
    cfg_dict["dtype"] = np.dtype(cfg.dtype).name
    with open(path, "wb") as f:
        pickle.dump({
            "params": jax.tree_util.tree_map(np.asarray, params),
            "cfg": cfg_dict,
        }, f)


def load_model(path: str):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    with open(path, "rb") as f:
        blob = pickle.load(f)
    cfg_dict = dict(blob["cfg"])
    cfg_dict["dtype"] = getattr(jnp, cfg_dict["dtype"])
    return blob["params"], T.TransformerConfig(**cfg_dict)


def build_model(args):
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq=args.max_seq, dtype=jnp.float32,
        attention_impl="reference", n_kv_heads=args.kv_heads)
    return T.init_params(jax.random.PRNGKey(args.seed), cfg), cfg


def parse_setting(text: str):
    """``name=value`` -> (name, typed value), same typing ladder as the
    replay CLI's settings (int → float → bool/none → str)."""
    if "=" not in text:
        raise argparse.ArgumentTypeError(
            f"--set wants name=value, got {text!r}")
    name, raw = text.split("=", 1)
    name = name.strip()
    raw = raw.strip()
    for cast in (int, float):
        try:
            return name, cast(raw)
        except ValueError:
            pass
    low = raw.lower()
    if low in ("true", "false"):
        return name, low == "true"
    if low in ("none", "null"):
        return name, None
    return name, raw


def parse_fault(text: str):
    """``site:kind[:skip[:delay]]`` -> FaultSpec."""
    from horovod_tpu.serving.faults import FaultSpec

    parts = text.split(":")
    if len(parts) < 2:
        raise argparse.ArgumentTypeError(
            f"--fault wants site:kind[:skip[:delay]], got {text!r}")
    spec = {"site": parts[0], "kind": parts[1]}
    if len(parts) > 2:
        spec["skip"] = int(parts[2])
    if len(parts) > 3:
        spec["delay"] = float(parts[3])
    return FaultSpec(**spec)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="one supervised serving replica")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--params", default="",
                    help="pickle from dump_model() (overrides the "
                         "seeded-init shape flags)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=32)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=64)
    ap.add_argument("--max-seq", type=int, default=48)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard this replica's "
                         "engine over a tp-device GSPMD mesh (heads + "
                         "MLP hidden split, paged KV pool head-"
                         "sharded; docs/serving.md 'Tensor-parallel "
                         "replicas').  Needs tp visible devices — on "
                         "CPU hosts the forced-host-device flag is "
                         "armed automatically when absent")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-queue-depth", type=int, default=64)
    ap.add_argument("--max-prefills-per-tick", type=int, default=2)
    ap.add_argument("--tick-timeout", type=float, default=60.0,
                    help="engine watchdog budget (0 disables)")
    ap.add_argument("--request-timeout", type=float, default=120.0)
    ap.add_argument("--drain-timeout", type=float, default=10.0)
    ap.add_argument("--journal", default="",
                    help="request-journal JSONL path (survives SIGKILL; "
                         "the router reads it post-mortem to resume "
                         "this replica's in-flight requests elsewhere)")
    ap.add_argument("--spans", default="",
                    help="span-stream JSONL path (distributed tracing; "
                         "the process label is the filename stem, e.g. "
                         "r0g1.spans.jsonl -> r0g1).  Flushed per "
                         "record, so a SIGKILL leaves the started "
                         "spans for the router's /trace autopsy")
    ap.add_argument("--span-latency-threshold", type=float, default=1.0,
                    help="tail-sampling latency threshold in seconds: "
                         "requests slower than this keep full tick-"
                         "level span detail")
    ap.add_argument("--span-head-rate", type=float, default=0.0,
                    help="deterministic head-sampling rate [0,1] for "
                         "full span detail on otherwise-boring requests")
    ap.add_argument("--no-resume", action="store_true",
                    help="disable in-engine restart-resume (in-flight "
                         "requests fail typed on a supervised restart, "
                         "the pre-journal behavior)")
    ap.add_argument("--warm", type=int, action="append", default=[],
                    help="prompt lengths to pre-compile before "
                         "accepting traffic (repeatable)")
    ap.add_argument("--autotune", action="store_true",
                    help="install the online autotuner after warmup "
                         "(GET /tuning exposes its state; needs "
                         "--warm so a warmed knob space exists — "
                         "docs/serving.md 'Autotuning')")
    ap.add_argument("--fault", type=parse_fault, action="append",
                    default=[], metavar="SITE:KIND[:SKIP[:DELAY]]",
                    help="deterministic FaultInjector spec (chaos "
                         "tests; repeatable)")
    ap.add_argument("--config-gen", type=int, default=0,
                    help="config-generation label stamped into the "
                         "engine's /stats (fleet rollouts; never read "
                         "by the engine itself)")
    ap.add_argument("--set", type=parse_setting, action="append",
                    default=[], dest="settings", metavar="NAME=VALUE",
                    help="extra EngineConfig field override, typed "
                         "like the replay CLI's settings (repeatable; "
                         "how a rollout candidate carries knobs with "
                         "no dedicated flag)")
    args = ap.parse_args(argv)

    if args.tp > 1:
        # Devices must exist BEFORE the backend spins up.  The
        # supervisor already sets the flag in every tp replica's
        # spawn env (the reliable path); this covers bare
        # `python -m ... --tp N` runs on CPU hosts.
        from horovod_tpu.serving.sharding import ensure_devices

        ensure_devices(args.tp)

    from horovod_tpu import place_compile_cache, serving
    from horovod_tpu.serving.router.supervisor import (
        EXIT_CODE_REPLICA_FAILED,
    )

    # A respawned generation (or a sibling replica) finds the first
    # one's warmup compiles instead of paying them again.
    place_compile_cache()

    if args.spans:
        from horovod_tpu.obs import tracing as obs_tracing

        stem = os.path.basename(args.spans).split(".")[0]
        obs_tracing.start_spans(
            args.spans, proc=stem or f"pid{os.getpid()}",
            role="replica",
            sampling=obs_tracing.SpanSampling(
                latency_threshold_s=args.span_latency_threshold,
                head_rate=args.span_head_rate))

    if args.params:
        params, cfg = load_model(args.params)
    else:
        params, cfg = build_model(args)

    # Armed EMPTY here; the specs are added AFTER warmup so their
    # skips are post-warmup relative (below) — a spec present during
    # warmup could fire inside it and burn its budget (or wedge the
    # replica) before the listener even exists.
    inj = serving.FaultInjector() if args.fault else None
    cfg_kwargs = dict(
        n_slots=args.slots, max_len=cfg.max_seq,
        max_queue_depth=args.max_queue_depth,
        max_prefills_per_tick=args.max_prefills_per_tick,
        tick_timeout=args.tick_timeout,
        tp=args.tp,
        autotune=args.autotune,
        resume=not args.no_resume,
        journal_path=args.journal or None, faults=inj,
        config_generation=args.config_gen)
    # --set overrides land LAST so a rollout candidate can retarget any
    # EngineConfig field, dedicated flag or not.
    cfg_kwargs.update(dict(args.settings))
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**cfg_kwargs))
    if args.warm or args.autotune:
        # Pre-compile BEFORE the listener exists: the registry's first
        # successful poll means "routable", and a routable replica must
        # never pay XLA compilation inside a request (or a tight
        # watchdog budget).  --autotune without --warm still warms the
        # default length: the tuner installs at the END of warmup and
        # derives its compile-safe knob bounds from what it compiled.
        engine.warmup(sorted(set(args.warm)) or [1])
    if inj is not None:
        # --fault skips count from AFTER warmup (the post-warm
        # relative idiom from tests/test_chaos.py): how many probe
        # visits warmup itself spends is a pipeline internal no chaos
        # test should have to predict.
        for spec in args.fault:
            spec.skip += inj.visits(spec.site)
            inj.add(spec)

    stop_requested = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda signum, frame: stop_requested.set())

    srv = serving.ServingServer(
        engine, host=args.host, port=args.port,
        request_timeout=args.request_timeout).start()
    host, port = srv.address
    print(f"replica ready on {host}:{port} (slots={args.slots}, "
          f"tp={args.tp}, pid={os.getpid()})", flush=True)

    failed = False
    while not stop_requested.is_set():
        if engine.terminal:
            failed = True
            break
        stop_requested.wait(0.2)

    srv.stop(drain_timeout=args.drain_timeout)
    if args.spans:
        from horovod_tpu.obs import tracing as obs_tracing

        obs_tracing.stop_spans()
    print(f"replica on port {port} stopped "
          f"(engine state: {engine.health})", flush=True)
    return EXIT_CODE_REPLICA_FAILED if failed else 0


if __name__ == "__main__":
    sys.exit(main())
