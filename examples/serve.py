"""Serve a toy LM over HTTP with the continuous-batching engine.

Trains the same count-mod-32 LM as ``examples/generate.py`` for a few
steps, then stands up the full serving stack (docs/serving.md):
slot-based KV cache + FCFS scheduler + engine loop + stdlib-HTTP front
— and fires a burst of concurrent clients at it to show continuous
batching at work.  Runs on any backend, including JAX_PLATFORMS=cpu.

Run:  python examples/serve.py [--steps 30] [--port 8000] [--keep]
      python examples/serve.py --trace /tmp/serve_trace.json --chaos
      python examples/serve.py --replicas 3
      python examples/serve.py --tp 2              # one GSPMD-sharded engine
      python examples/serve.py --replicas 2 --tp 2 # router over tp-2 replicas

``--tp N`` shards the engine (or, with ``--replicas``, every replica's
engine) over an N-device GSPMD ``tp`` mesh — attention heads and the
MLP hidden dim split, the paged KV pool head-sharded — serving output
token-identical to tp=1 (docs/serving.md "Tensor-parallel replicas").
CPU demos force N host devices automatically.

``--replicas N`` (N > 1) stands up the REPLICATED front tier instead
(docs/serving.md "Front tier"): the trained params are pickled once,
a ReplicaSupervisor spawns N replica processes serving them, and a
router proxies /generate over the pool with join-shortest-queue +
failover.  The demo SIGKILLs one replica in the middle of the burst
and shows every request still completing (the router retries on a
surviving replica; the supervisor respawns the dead one).  SIGTERM /
Ctrl-C still drain gracefully.

With ``--keep`` the server stays up (curl it yourself):
    curl -s localhost:8000/generate -d '{"tokens": [3,4,5], "max_new_tokens": 8}'
    curl -s localhost:8000/stats
    curl -s localhost:8000/metrics          # Prometheus text exposition

``--trace PATH`` records ONE Perfetto/Chrome trace (open in
https://ui.perfetto.dev) interleaving the training steps, every serving
request's queue/prefill/decode spans (with trace ids), the engine
tick-phase spans, and instant events for XLA compiles — plus a
``PATH.jsonl`` structured request log.  ``--chaos`` injects one decode
fault after the demo burst so the trace also shows a supervised engine
restart (docs/observability.md).

Shutdown is GRACEFUL: SIGTERM (what Kubernetes / systemd send) and
Ctrl-C both trigger a drain — /healthz flips to 503 ``draining``, new
/generate calls are rejected with 503, in-flight requests run to
completion, then the server tears down (docs/serving.md "Operations").
"""

from __future__ import annotations

import argparse
import json
import signal
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import optax


def train_toy_lm(steps: int):
    """The counting LM from examples/generate.py: tokens[i+1] =
    tokens[i] + 1 (mod 32)."""
    from horovod_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=32, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq=64, dtype=jnp.float32, n_kv_heads=2)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    base = np.arange(64 * 8).reshape(8, 64) % 32
    batch = {"tokens": jnp.asarray(base, jnp.int32),
             "targets": jnp.asarray((base + 1) % 32, jnp.int32)}
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state):
        loss, grads = jax.value_and_grad(T.loss_fn)(params, batch, cfg)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    from horovod_tpu import obs

    loss = None
    for _ in range(steps):
        # Span + step-time histogram: with --trace the training steps
        # land on the same Perfetto time axis as the serving requests.
        with obs.training_step():
            params, opt_state, loss = step(params, opt_state)
    print(f"trained {steps} steps, loss {float(loss):.3f}")
    return params, cfg


def replicated_demo(args, params, cfg) -> None:
    """The front tier end to end: N replicas serving the SAME trained
    params behind the router, one SIGKILLed mid-burst — and every
    request still completes (docs/serving.md "Front tier")."""
    import os
    import signal as _signal
    import tempfile

    from horovod_tpu import obs
    from horovod_tpu.serving.router import (
        ReplicaRegistry,
        ReplicaSpec,
        ReplicaSupervisor,
        RouterServer,
    )
    from horovod_tpu.serving.router.replica_main import dump_model

    fd, params_path = tempfile.mkstemp(prefix="serve_lm_",
                                       suffix=".pkl")
    os.close(fd)
    dump_model(params_path, params, cfg)

    stop_requested = threading.Event()
    signal.signal(signal.SIGTERM,
                  lambda signum, frame: stop_requested.set())

    registry = ReplicaRegistry(poll_interval=0.2, heartbeat_stale=15.0)
    journal_dir = tempfile.mkdtemp(prefix="serve_journal_")
    # Span streams: every replica + the router append to span_dir, so
    # GET /trace/<id> can autopsy the SIGKILL'd request afterwards
    # (docs/observability.md "Distributed tracing").
    span_dir = args.spans or tempfile.mkdtemp(prefix="serve_spans_")
    obs.tracing.start_spans(
        os.path.join(span_dir, "router.spans.jsonl"),
        proc="router", role="router")
    sup = ReplicaSupervisor(
        ReplicaSpec(params_path=params_path, slots=args.slots,
                    tp=args.tp,
                    warm=[8], tick_timeout=30.0, drain_timeout=10.0),
        args.replicas, registry=registry, unhealthy_grace=3.0,
        journal_dir=journal_dir, span_dir=span_dir)
    rt = RouterServer(registry, port=args.port,
                      resume_lookup=sup.resume_lookup,
                      span_dir=span_dir)
    try:
        sup.start()
        rt.start()
        host, port = rt.address
        base = f"http://{host}:{port}"
        print(f"spawning {args.replicas} replicas "
              f"(pids {[h.pid for h in sup.replicas()]}) ...")
        if not sup.wait_ready(timeout=180):
            raise RuntimeError("replicas never became ready")
        print(f"router on {base}  ({args.replicas} replicas in rotation)")
        if args.tp > 1:
            print("replica meshes: " + ", ".join(
                f"{s.endpoint.rid}[{s.mesh}]"
                for s in registry.in_rotation()))

        # Twice the single-engine burst, through the router; replica
        # r0 is SIGKILLed once half the requests are in flight.
        n = 2 * args.clients
        rng = np.random.default_rng(0)
        out, errs = {}, {}
        started = threading.Semaphore(0)

        def client(i):
            start = int(rng.integers(0, 24))
            prompt = [(start + j) % 32 for j in range(2 + i % 3)]
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"tokens": prompt,
                                 "max_new_tokens": 6 + i % 4}).encode(),
                headers={"Content-Type": "application/json"})
            started.release()
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    out[i] = (prompt, json.loads(r.read()),
                              r.headers.get("X-Router-Replica"))
            except urllib.error.HTTPError as e:
                errs[i] = (e.code, json.loads(e.read()))
            except Exception as e:  # transport failure = a real DROP
                errs[i] = (None, {"type": repr(e)})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for _ in range(n // 2):
            started.acquire()
        victim = sup.handle(0)
        print(f"SIGKILL replica {victim.rid} (pid {victim.pid}) "
              f"mid-burst ...")
        os.kill(victim.pid, _signal.SIGKILL)
        for t in threads:
            t.join()

        by_rep = {}
        for i, (prompt, resp, rep) in sorted(out.items()):
            by_rep.setdefault(rep, []).append(i)
            print(f"client {i:2d}: {prompt} -> {resp['tokens']}  "
                  f"(via {rep}, {resp['finish_reason']})")
        for i, (code, resp) in sorted(errs.items()):
            print(f"client {i:2d}: HTTP {code} ({resp.get('type')})")
        stats = rt.stats()
        dropped = (n - len(out)
                   - sum(1 for c, _ in errs.values() if c is not None))
        print(f"{len(out) + len(errs)}/{n} requests resolved: "
              f"{len(out)} with tokens, "
              f"{len(errs) - dropped} typed errors, {dropped} dropped")
        print(f"per-replica: "
              f"{ {k: len(v) for k, v in by_rep.items()} }  "
              f"retries={stats['retries']:.0f} "
              f"failovers={stats['failovers']:.0f} "
              f"resumed={stats['resume_failovers']:.0f}")

        # The autopsy: pick a request that rode the failover (resumed
        # or multi-attempt) and print its cross-process span tree.
        from horovod_tpu.obs.trace_store import TraceStore

        autopsy_tid = None
        for i, (prompt, resp, rep) in sorted(out.items()):
            if resp.get("resumed"):
                autopsy_tid = resp.get("trace_id")
                break
        if autopsy_tid is None and out:
            autopsy_tid = next(iter(sorted(out.items())))[1][1] \
                .get("trace_id")
        if autopsy_tid:
            tree = TraceStore.from_dir(span_dir).ascii_tree(autopsy_tid)
            if tree:
                print(f"\nautopsy (GET {base}/trace/{autopsy_tid}):")
                print(tree)
            print(f"span streams: {span_dir}  (explore with "
                  f"python -m horovod_tpu.obs.trace --spans "
                  f"{span_dir} --list)")

        deadline = time.monotonic() + 60
        while (len(registry.in_rotation()) < args.replicas
               and time.monotonic() < deadline):
            time.sleep(0.2)
        print(f"supervisor respawned {victim.rid} -> "
              f"{sup.handle(0).rid}; "
              f"{len(registry.in_rotation())}/{args.replicas} back in "
              f"rotation (restarts: "
              f"{registry.metrics.replica_restarts.value:.0f})")

        if args.keep and not stop_requested.is_set():
            print("serving until SIGTERM / Ctrl-C ...")
            try:
                stop_requested.wait()
            except KeyboardInterrupt:
                pass
        print("draining front tier (replicas finish in-flight work) ...")
    finally:
        rt.stop()
        sup.stop(drain=True)
        obs.tracing.stop_spans()
        os.unlink(params_path)
    print("stopped")


def rollout_demo(args, params, cfg) -> None:
    """Zero-downtime fleet reconfiguration end to end (docs/serving.md
    "Fleet rollouts"): 3 replicas behind the router, a candidate
    config POSTed to the admin surface, the canary SIGKILLed mid-score
    — and the controller rolls the fleet back to the incumbent config
    on its own, with every in-flight request resolving."""
    import os
    import signal as _signal
    import tempfile

    from horovod_tpu.serving.router import (
        ReplicaRegistry,
        ReplicaSpec,
        ReplicaSupervisor,
        RolloutController,
        RouterServer,
    )
    from horovod_tpu.serving.router.replica_main import dump_model

    n = max(args.replicas, 3)
    fd, params_path = tempfile.mkstemp(prefix="serve_lm_",
                                       suffix=".pkl")
    os.close(fd)
    dump_model(params_path, params, cfg)
    registry = ReplicaRegistry(poll_interval=0.2, heartbeat_stale=15.0)
    journal_dir = tempfile.mkdtemp(prefix="serve_journal_")
    sup = ReplicaSupervisor(
        ReplicaSpec(params_path=params_path, slots=args.slots,
                    warm=[8], tick_timeout=30.0, drain_timeout=10.0),
        n, registry=registry, unhealthy_grace=3.0,
        journal_dir=journal_dir)
    # canary_windows is generous: the demo kills the canary before
    # scoring ever finishes, proving the crash-trip path.
    ctl = RolloutController(sup, canary_weight=0.3, canary_windows=60,
                            window_s=1.0, ready_timeout=240.0)
    rt = RouterServer(registry, port=args.port,
                      resume_lookup=sup.resume_lookup, rollout=ctl)
    stop_load = threading.Event()

    def load_loop(base):
        rng = np.random.default_rng(5)
        while not stop_load.is_set():
            prompt = [int(t) for t in rng.integers(0, 32, 3)]
            try:
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps({"tokens": prompt,
                                     "max_new_tokens": 8}).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=60).read()
            except Exception:
                pass
            time.sleep(0.1)

    def post(base, payload):
        req = urllib.request.Request(
            base + "/rollout", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())

    def fleet_gens():
        gens = {}
        for st in registry.statuses():
            try:
                with urllib.request.urlopen(
                        st.endpoint.base_url + "/stats",
                        timeout=2.0) as r:
                    gens[st.endpoint.rid] = json.loads(r.read()).get(
                        "config_generation")
            except Exception:
                pass
        return gens

    loader = None
    try:
        sup.start()
        rt.start()
        host, port = rt.address
        base = f"http://{host}:{port}"
        print(f"spawning {n} replicas ...")
        if not sup.wait_ready(timeout=240):
            raise RuntimeError("replicas never became ready")
        print(f"router on {base}  ({n} replicas in rotation, "
              f"config generations {fleet_gens()})")
        loader = threading.Thread(target=load_loop, args=(base,),
                                  daemon=True)
        loader.start()

        candidate = {"max_prefills_per_tick": 4}
        print(f"POST /rollout candidate={candidate}")
        status = post(base, {"candidate": candidate})
        print(f"  -> rollout started: gen {status['config_generation']}")

        killed = False
        last_state = None
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            st = ctl.status()
            if st["state"] != last_state:
                last_state = st["state"]
                print(f"  state: {last_state}"
                      + (f"  (trip: {st['trip_reason']})"
                         if st["trip_reason"] else ""))
            if st["state"] == "canary" and not killed:
                h = sup.handle(0)
                time.sleep(1.0)   # let a scoring window open
                print(f"  SIGKILL canary {h.rid} (pid {h.pid}) "
                      f"mid-score ...")
                os.kill(h.pid, _signal.SIGKILL)
                killed = True
            if not st["active"]:
                break
            time.sleep(0.1)
        final = ctl.status()
        print(f"rollout terminal state: {final['state']} "
              f"(trip: {final['trip_reason']})")
        snap = registry.metrics.snapshot()
        print(f"rollbacks={snap['rollout_rollbacks']:.0f} "
              f"promotions={snap['rollout_promotions']:.0f} "
              f"steps={snap['rollout_steps']:.0f}")
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            gens = fleet_gens()
            if len(gens) >= n and set(gens.values()) == {0}:
                break
            time.sleep(0.5)
        print(f"fleet converged back to the incumbent: {fleet_gens()}")
        print(f"rollout journal: "
              f"{os.path.join(journal_dir, 'rollout.journal.jsonl')}")
    finally:
        stop_load.set()
        if loader is not None:
            loader.join(5.0)
        rt.stop()
        sup.stop(drain=True)
        os.unlink(params_path)
    print("stopped")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30, help="train steps")
    ap.add_argument("--port", type=int, default=0,
                    help="HTTP port (0 = ephemeral)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--clients", type=int, default=6,
                    help="demo burst size")
    ap.add_argument("--keep", action="store_true",
                    help="keep serving after the demo burst")
    ap.add_argument("--trace", default="",
                    help="record a Perfetto/Chrome trace (training + "
                         "serving on one time axis) at this path, plus "
                         "a <path>.jsonl request log")
    ap.add_argument("--chaos", action="store_true",
                    help="inject one decode fault after the demo burst "
                         "so the trace shows a supervised engine restart")
    ap.add_argument("--replicas", type=int, default=1,
                    help="N > 1: serve through the replicated front "
                         "tier (router + supervisor) and SIGKILL one "
                         "replica mid-burst to demo zero-drop failover")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the engine "
                         "(each replica, with --replicas) over a "
                         "tp-device GSPMD mesh — heads + MLP hidden "
                         "split, paged KV pool head-sharded, output "
                         "token-identical to tp=1 (docs/serving.md "
                         "'Tensor-parallel replicas').  CPU demos get "
                         "forced host devices automatically")
    ap.add_argument("--autotune", action="store_true",
                    help="install the online autotuner and drive a "
                         "synthetic load until it converges, printing "
                         "each sampled knob setting and its objective "
                         "(docs/serving.md 'Autotuning')")
    ap.add_argument("--rollout", action="store_true",
                    help="fleet-rollout demo (docs/serving.md 'Fleet "
                         "rollouts'): 3+ replicas behind the router, a "
                         "candidate config POSTed to /rollout, the "
                         "canary SIGKILLed mid-score — the controller "
                         "rolls the whole fleet back to the incumbent "
                         "on its own (forces --replicas >= 3)")
    ap.add_argument("--spans", default="",
                    help="(with --replicas) span-stream directory for "
                         "distributed tracing — the killed request's "
                         "cross-process autopsy prints after the "
                         "burst and GET /trace/<id> serves it (a tmp "
                         "dir is used when omitted)")
    args = ap.parse_args()

    if args.tp > 1:
        # Devices must exist before the backend spins up (CPU hosts:
        # the forced-host-device flag; a real accelerator host already
        # exposes its topology).  jax has not run an op yet, so the
        # flag is still read at backend init.
        from horovod_tpu.serving.sharding import ensure_devices

        ensure_devices(args.tp)

    import horovod_tpu as hvd
    from horovod_tpu import obs, serving

    hvd.place_compile_cache()
    if args.replicas > 1 or args.rollout:
        # A chip belongs to one process, and here the REPLICAS own the
        # chips (the supervisor hands each its own).  This process only
        # trains the toy LM, routes and supervises: pinned to CPU before
        # any backend starts, or on a TPU host it would hold the chips
        # its children need.
        jax.config.update("jax_platforms", "cpu")
        print("front-tier parent pinned to CPU; replicas own the "
              "accelerators")
    hvd.init()
    if args.trace:
        obs.tracing.start(args.trace, jsonl_path=args.trace + ".jsonl")
    params, cfg = train_toy_lm(args.steps)

    if args.rollout:
        rollout_demo(args, params, cfg)
        if args.trace:
            obs.tracing.stop()
            print(f"trace written: {args.trace} (open in "
                  f"https://ui.perfetto.dev); request log: "
                  f"{args.trace}.jsonl")
        hvd.shutdown()
        return

    if args.replicas > 1:
        replicated_demo(args, params, cfg)
        if args.trace:
            obs.tracing.stop()
            print(f"trace written: {args.trace} (open in "
                  f"https://ui.perfetto.dev); request log: "
                  f"{args.trace}.jsonl")
        hvd.shutdown()
        return

    inj = serving.FaultInjector() if args.chaos else None
    engine = serving.InferenceEngine(
        params, cfg,
        serving.EngineConfig(n_slots=args.slots, max_len=cfg.max_seq,
                             restart_backoff=0.05, faults=inj,
                             tp=args.tp,
                             # turns token counters into achieved
                             # FLOP/s in /stats (docs/observability.md)
                             model_flops_per_token=obs.xprof
                             .transformer_flops_per_token(params)),
        detokenize=lambda t: f" {t}")
    if args.tp > 1:
        print(f"engine sharded over {engine.stats()['mesh']}")
    if args.autotune:
        # Warm FIRST (the tuner derives its compile-safe knob bounds
        # from what warmup compiled), then install with demo-friendly
        # pacing — short scoring windows so convergence is watchable.
        from horovod_tpu.tuning import OnlineTuner

        engine.warmup([2, 4])
        tuner = OnlineTuner.install(engine, window_ticks=8,
                                    bo_samples=5)
        print(f"autotuner installed: knobs "
              f"{sorted(tuner.space.defaults())}")
    # SIGTERM (k8s/systemd stop) -> graceful drain, same as Ctrl-C —
    # installed for the WHOLE serving lifetime, demo burst included:
    # the load balancer sees 503 on /healthz, admitted requests
    # finish, then the listener closes.
    stop_requested = threading.Event()
    signal.signal(signal.SIGTERM,
                  lambda signum, frame: stop_requested.set())
    srv = serving.ServingServer(engine, port=args.port).start()
    host, port = srv.address
    base = f"http://{host}:{port}"
    print(f"serving on {base}  (slots={args.slots})")

    # Demo burst: concurrent clients, different prompts and lengths —
    # the engine fuses them into one masked decode batch.
    rng = np.random.default_rng(0)
    def client(i, out):
        start = int(rng.integers(0, 24))
        prompt = [(start + j) % 32 for j in range(2 + i % 3)]
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": prompt,
                             "max_new_tokens": 6 + i % 4}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out[i] = (prompt, json.loads(r.read()))

    out = {}
    threads = [threading.Thread(target=client, args=(i, out))
               for i in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in sorted(out):
        prompt, resp = out[i]
        print(f"client {i}: {prompt} ->{resp['text']}  "
              f"(ttft {resp['ttft_ms']}ms, {resp['finish_reason']})")

    with urllib.request.urlopen(base + "/stats", timeout=10) as r:
        stats = json.loads(r.read())
    print(f"stats: {stats['requests_completed']} completed, "
          f"{stats['tokens_generated']} tokens, "
          f"decode compiles {stats['decode_compilations']}, "
          f"TTFT p50 {stats['ttft_seconds']['p50']}s")

    if args.autotune:
        # Drive waves of mixed traffic until the tuner pins (or a wave
        # cap), printing each scored sample as it lands — live
        # convergence, knob by knob.
        tuner = engine._tuner
        printed = 0
        for wave in range(200):
            if tuner.phase == "pinned":
                break
            waves = []
            for i in range(args.slots * 2):
                start = int(rng.integers(0, 24))
                prompt = [(start + j) % 32 for j in range(2 + i % 3)]
                req = urllib.request.Request(
                    base + "/generate",
                    data=json.dumps({"tokens": prompt,
                                     "max_new_tokens": 6}).encode(),
                    headers={"Content-Type": "application/json"})
                t = threading.Thread(
                    target=lambda r=req: urllib.request.urlopen(
                        r, timeout=120).read())
                t.start()
                waves.append(t)
            for t in waves:
                t.join()
            snap = tuner.snapshot()
            for entry in snap["trajectory"][printed:]:
                print(f"  sample {entry['sample']:>2} "
                      f"[{entry['phase']}] {entry['settings']} -> "
                      f"objective {entry['objective']:.3f}"
                      + ("  (SLO violation, rolled back)"
                         if entry["violated"] else ""))
            printed = len(snap["trajectory"])
        snap = tuner.snapshot()
        print(f"autotune: phase={snap['phase']} after "
              f"{snap['samples']} samples; best objective "
              f"{snap['best']['objective']} with "
              f"{snap['best']['settings']}; GET {base}/tuning "
              f"serves this snapshot")

    if args.chaos:
        # One injected decode fault: the probe request fails typed
        # (503 engine_failed, trace id intact), the engine restarts
        # with a fresh cache, and the trace gains an engine_restart
        # instant next to the request spans.
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="raise",
            skip=inj.visits("decode_tick") + 1))
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"tokens": [1, 2, 3],
                             "max_new_tokens": 8}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Trace-Id": "chaos-demo"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                code, resp = r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            code, resp = e.code, json.loads(e.read())
        print(f"chaos: injected decode fault -> HTTP {code} "
              f"({resp.get('type')}, trace {resp.get('trace_id')})")
        deadline = time.monotonic() + 30
        while engine.health != "healthy" and time.monotonic() < deadline:
            time.sleep(0.05)
        with urllib.request.urlopen(req, timeout=60) as r:
            resp = json.loads(r.read())
        print(f"chaos: recovered ->{resp['text']}  "
              f"(engine restarts: "
              f"{engine.metrics.engine_restarts.value})")

    if args.keep and not stop_requested.is_set():
        print("serving until SIGTERM / Ctrl-C ...")
        try:
            stop_requested.wait()
        except KeyboardInterrupt:
            pass
    print("draining (in-flight requests run to completion) ...")
    srv.stop(drain_timeout=30.0)
    print(f"stopped; final engine state: {engine.health}")
    if args.trace:
        obs.tracing.stop()
        print(f"trace written: {args.trace} (open in "
              f"https://ui.perfetto.dev); request log: "
              f"{args.trace}.jsonl")
    hvd.shutdown()


if __name__ == "__main__":
    main()
