"""Serving-path benchmark: prefill latency + autoregressive decode
throughput on the current chip.

The decode loop is ONE compiled ``lax.scan`` (``sample_decode``), so the
per-call dispatch cost amortizes over all steps; timing closes with a
value fetch of the final tokens.  GQA rows show the KV-cache bandwidth lever
(`n_kv_heads` shrinks the cache the decode step streams every token).

    python benchmarks/serving.py [--batches 1 8 32] [--steps 128]

``--engine`` instead drives the continuous-batching engine
(horovod_tpu/serving/) with a Poisson OPEN-LOOP arrival process —
requests arrive on their own clock, not when the server is ready, the
load shape a static-batch number can't see — and reports tok/s,
p50/p99 TTFT, and mean slot occupancy next to a static-batch decode
reference at B = n_slots, PLUS the EngineConfig.overlap A/B
(steady-state decode tok/s, pipelined vs synchronous, identical
workload), the page pool's kv_bytes_per_token and high-water mark in
the JSON line, and the pipeline phase metrics
(overlap_efficiency = device-wait share of the tick,
host_syncs_per_tick):

    python benchmarks/serving.py --engine [--slots 8] [--arrival-rate 4]

plus the sampled-vs-greedy throughput A/B (per-slot vectorized
sampling is data in the same executable; the ratio is the in-tick
sort/softmax/categorical cost) and, with ``--stream``, the SSE
streaming leg: client-observed TTFB p50/p99 (first token event on the
wire) against the non-streamed server-reported TTFT:

    python benchmarks/serving.py --engine --stream

``--router N`` drives the REPLICATED front tier (docs/serving.md
"Front tier"): a ReplicaSupervisor spawns N replica processes (each a
full engine + HTTP server, seeded identically), a router proxies the
same Poisson open-loop workload over them with join-shortest-queue,
and the JSON line reports aggregate tok/s, per-replica request counts
and mean occupancy, and the router's retry/failover counters:

    python benchmarks/serving.py --router 2 [--slots 8] [--arrival-rate 4]

``--chaos`` is the DURABILITY benchmark (docs/serving.md "Durable
in-flight requests"): the same open-loop workload with deterministic
engine crashes injected mid-decode and restart-resume on — the JSON
line reports resumed-vs-restarted counts, the wasted-token ratio
(tokens re-prefilled by resumes / tokens generated), and per-request
byte-identity against the no-fault greedy oracle:

    python benchmarks/serving.py --chaos [--slots 8]

``--tp N`` is the TENSOR-PARALLEL A/B (docs/serving.md
"Tensor-parallel replicas"): a tp=N GSPMD-sharded engine vs the tp=1
single-device engine on the identical mixed greedy/sampled workload —
steady-state decode tok/s both ways, ``tp_equal_output_tokens`` (the
full per-request sequences), and ``decode_recompiles: 0`` in the JSON
line, under the existing CPU smoke clamp (forced host devices stand in
for the ICI mesh):

    python benchmarks/serving.py --tp 2 [--slots 8]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run_engine_once(args, cfg, params, prompts, arrival, overlap):
    """One open-loop run against a fresh engine; returns the stats the
    A/B needs.  Warm covers every (prefill bucket, admission batch k)
    shape plus the decode tick, then metrics reset so the reported
    numbers describe serving latency, not JIT compile time."""
    from horovod_tpu import serving

    from horovod_tpu.obs import xprof

    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(
            n_slots=args.slots, max_len=cfg.max_seq,
            max_prefills_per_tick=args.max_prefills_per_tick,
            max_queue_depth=max(args.n_requests, 8), overlap=overlap,
            # achieved FLOP/s ride the snapshot in the JSON line
            model_flops_per_token=xprof.transformer_flops_per_token(
                params)))

    engine.warmup(sorted({engine._bucket(len(p)) for p in prompts}))
    warm_compiles = engine.decode_compilations
    engine.metrics = serving.ServingMetrics()

    engine.start()
    engine.stats()  # first token-rate sample for achieved FLOP/s
    occ, futs = [], []
    t0 = time.monotonic()
    for i in range(args.n_requests):
        now = time.monotonic() - t0
        if now < arrival[i]:
            time.sleep(arrival[i] - now)
        futs.append(engine.submit(prompts[i], max_new_tokens=args.steps))
        occ.append(engine.slots.occupancy)
    while not all(f.done() for f in futs):
        occ.append(engine.slots.occupancy)
        time.sleep(0.005)
    wall = time.monotonic() - t0
    engine.stop()

    # tokens_so_far never raises: with the fault-tolerance layer a
    # request can resolve with a typed error (engine restart) instead
    # of tokens — the benchmark reports that instead of crashing.
    toks = sum(len(f.tokens_so_far()) for f in futs)
    snap = engine.stats()  # superset of metrics.snapshot(): adds
    # state/heartbeat plus the achieved-FLOP/s window closed here
    # Overlap efficiency: the share of a tick's host-visible time the
    # device wait accounts for — 1.0 means every host cycle (emit,
    # retire, admission bookkeeping, dispatch) was hidden behind
    # device compute; the sync path's number is the ceiling the
    # pipeline is chasing.
    phases = [snap["tick_dispatch_seconds"]["mean"] or 0.0,
              snap["tick_device_wait_seconds"]["mean"] or 0.0,
              snap["tick_host_seconds"]["mean"] or 0.0]
    tick_wall = sum(phases)
    return {
        "engine": engine, "snap": snap, "toks": toks, "wall": wall,
        "tok_s": toks / wall if wall else 0.0,
        "occ": float(np.mean(occ)) if occ else 0.0,
        "overlap_efficiency":
            round(phases[1] / tick_wall, 4) if tick_wall else None,
        "host_syncs_per_tick": snap["host_syncs_per_tick"],
        "recompiles": engine.decode_compilations - warm_compiles,
    }


def _ab_decode(args, cfg, params):
    """The EngineConfig.overlap A/B: steady-state decode tok/s with
    the pipelined loop vs the synchronous baseline on the IDENTICAL
    workload (equal output tokens by construction).  Per-tick wall
    times are sampled at FULL slot occupancy and compared at the 25th
    percentile — on shared/noisy hosts a best-of-walls comparison
    measures scheduler luck, while a low per-tick percentile estimates
    the clean tick for both modes — with the two engines' reps
    interleaved so drift hits both equally."""
    from horovod_tpu import serving

    S = args.slots
    prompt = np.random.default_rng(1).integers(
        0, cfg.vocab_size, max(args.prompt_len // 2, 1)).tolist()
    engines = {}
    for name, ov in (("overlap", True), ("sync", False)):
        eng = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=S, max_len=cfg.max_seq,
                max_prefills_per_tick=args.max_prefills_per_tick,
                max_queue_depth=max(2 * S, 8), overlap=ov))
        eng.warmup([len(prompt)])
        engines[name] = (eng, [])

    toks = {}
    # Enough full-pool ticks per rep for a stable percentile — but
    # never more than a slot admits (prompt + steps - 1 <= max_seq),
    # or submit() rightly rejects the A/B workload as too long.
    steps = max(min(max(args.steps, 24), cfg.max_seq - len(prompt) + 1), 1)
    for _ in range(max(args.iters, 4)):
        for name, (eng, dts) in engines.items():
            futs = [eng.submit(prompt, max_new_tokens=steps)
                    for _ in range(S)]
            while not all(f.done() for f in futs):
                full = eng.slots.active_count == S
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                if full and eng.slots.active_count == S:
                    dts.append(dt)  # a pure steady-state decode step
            toks.setdefault(name, []).extend(
                f.tokens_so_far() for f in futs)

    # p25, not mean/median: host noise is one-sided (a preempted tick
    # is only ever SLOWER), so a low percentile estimates the clean
    # per-tick time for both modes and the ratio stays stable on
    # shared hosts.
    q = {name: float(np.percentile(dts, 25))
         for name, (_, dts) in engines.items()}
    return {
        "decode_tok_s_overlap": round(S / q["overlap"], 2),
        "decode_tok_s_sync": round(S / q["sync"], 2),
        "overlap_decode_speedup": round(q["sync"] / q["overlap"], 3),
        "equal_output_tokens": toks["overlap"] == toks["sync"],
        "ab_steps_sampled": {n: len(d) for n, (_, d) in engines.items()},
    }


def _ab_spec(args, T, cfg):
    """The EngineConfig.speculative A/B (docs/serving.md "Speculative
    decoding"): EFFECTIVE steady-state decode tok/s — tokens emitted
    per second of tick wall-clock, since a speculative tick emits
    1..K+1 tokens per slot — speculative vs the plain overlap pipeline
    on two workload shapes:

    * **repetitive** — a toy LM trained (briefly, here) on Markov-1
      cyclic sequences (next token a function of the current one,
      period 8) decoding cyclic prompts: continuations genuinely
      repeat, so the n-gram prompt-lookup draft agrees and acceptance
      approaches 1.  This is the shape speculation exists for.
    * **adversarial** — a RANDOM-INIT target decoding random prompts
      at the same completion length: its greedy streams are acyclic,
      so bigrams never recur, drafts never agree, and every
      steady-state tick pays the W-position verify for one token.
      The ratio here is the bounded overhead of losing.

    The target model is TRAINED (not the random-init params the other
    A/Bs share) because speculative throughput is a property of output
    predictability — a random model's stream gives the draft nothing
    to agree with, and the A/B would measure only overhead.  Both
    engines decode the identical workload; equal output sequences are
    asserted, not assumed.  With ``--spec-draft model`` the draft is a
    half-depth TransformerConfig sharing the tokenizer, trained on the
    same corpus (two-model config; the CPU smoke clamp sizes both)."""
    import optax

    from horovod_tpu import serving

    S = args.slots
    K = args.spec_k
    V = cfg.vocab_size
    period = 8
    rng = np.random.default_rng(5)

    def train(model_cfg, seed, steps=45):
        p = T.init_params(jax.random.PRNGKey(seed), model_cfg)
        opt = optax.adam(1e-2)
        ost = opt.init(p)

        def batch(n=32, s=48):
            block = rng.integers(0, V // period, n)
            phase = rng.integers(0, period, n)
            toks = (block[:, None] * period
                    + (phase[:, None] + np.arange(s)[None, :]) % period)
            nxt = (block[:, None] * period
                   + (phase[:, None] + 1 + np.arange(s)[None, :]) % period)
            return {"tokens": jnp.asarray(toks, jnp.int32),
                    "targets": jnp.asarray(nxt, jnp.int32)}

        @jax.jit
        def step(p, o, b):
            l, g = jax.value_and_grad(T.loss_fn)(p, b, model_cfg)
            u, o = opt.update(g, o, p)
            return optax.apply_updates(p, u), o, l

        for _ in range(steps):
            p, ost, loss = step(p, ost, batch())
        return p, float(loss)

    params, loss = train(cfg, seed=11)
    draft = (None, None)
    if args.spec_draft == "model":
        dcfg = dataclasses.replace(cfg, n_layers=max(1, cfg.n_layers // 2))
        dparams, _ = train(dcfg, seed=12)
        draft = (dparams, dcfg)

    def make(model_params, spec):
        eng = serving.InferenceEngine(
            model_params, cfg, serving.EngineConfig(
                n_slots=S, max_len=cfg.max_seq,
                max_prefills_per_tick=args.max_prefills_per_tick,
                max_queue_depth=max(4 * S, 16), speculative=spec,
                spec_k=K, spec_draft=args.spec_draft if spec else "auto"),
            draft_params=draft[0] if spec else None,
            draft_cfg=draft[1] if spec else None)
        eng.warmup([12])
        return eng

    def measure(engines, prompts, steps, reps):
        # Effective tok/s over FULL-OCCUPANCY ticks only (the
        # _ab_decode discipline): admission/drain ticks measure
        # scheduling, not the speculative multiplier, and on shared
        # hosts they dominate the noise.  Tokens and wall are summed
        # per tick because a speculative tick emits a variable count.
        # Rep 0 is WARM (unmeasured, both engines): it absorbs the
        # adaptive controller's first evaluation window — a one-time
        # adaptation cost, not the steady state the ratio describes —
        # plus any residual compile/cache warmth, symmetrically.
        stats = {n: [0, 0.0, []] for n in engines}
        for rep in range(reps + 1):
            for name, eng in engines.items():  # interleaved reps
                futs = [eng.submit(p, max_new_tokens=steps)
                        for p in prompts]
                while not all(f.done() for f in futs):
                    full = eng.slots.active_count == S
                    before = eng.metrics.tokens_generated.value
                    t0 = time.perf_counter()
                    eng.step()
                    dt = time.perf_counter() - t0
                    if full and rep:
                        stats[name][0] += (
                            eng.metrics.tokens_generated.value - before)
                        stats[name][1] += dt
                stats[name][2].extend(f.tokens_so_far() for f in futs)
        return {n: (v[0] / v[1] if v[1] else 0.0, v[2])
                for n, v in stats.items()}

    steps = max(min(args.steps * 2, cfg.max_seq - 13), 16)
    reps = max(args.iters, 3)
    engines = {"spec": make(params, True), "plain": make(params, False)}
    rep_prompts = [((b % (V // period)) * period
                    + (np.arange(12) % period)).tolist() for b in range(S)]
    rep = measure(engines, rep_prompts, steps, reps)
    spec_eng = engines["spec"]
    drafted = spec_eng.metrics.spec_drafted.value
    acc_rate = (spec_eng.metrics.spec_accepted.value / drafted
                if drafted else None)
    tpt = spec_eng.metrics.tokens_per_tick
    # Adversarial: a random-init target's greedy streams are acyclic —
    # the drafts have nothing to agree with at FULL completion length,
    # so this measures steady-state decode paying the verify for
    # nothing (the draft model, if any, is equally useless here: it
    # was trained on the cyclic corpus the random target ignores).
    rnd_params = T.init_params(jax.random.PRNGKey(13), cfg)
    adv_engines = {"spec": make(rnd_params, True),
                   "plain": make(rnd_params, False)}
    adv_prompts = [rng.integers(0, V, 12).tolist() for _ in range(S)]
    adv = measure(adv_engines, adv_prompts, steps, reps)
    adv_drafted = adv_engines["spec"].metrics.spec_drafted.value
    adv_acc = (adv_engines["spec"].metrics.spec_accepted.value
               / adv_drafted if adv_drafted else None)
    equal = (rep["spec"][1] == rep["plain"][1]
             and adv["spec"][1] == adv["plain"][1])
    # ASSERTED, not just recorded: a speedup over diverging output is
    # not a speedup, and an identity regression must fail the
    # benchmark loudly rather than ride a JSON field nobody reads.
    assert equal, "speculative output diverged from plain greedy"
    return {
        "spec_k": K,
        "spec_draft": args.spec_draft,
        "spec_train_loss": round(loss, 5),
        "spec_decode_tok_s_repetitive": round(rep["spec"][0], 2),
        "plain_decode_tok_s_repetitive": round(rep["plain"][0], 2),
        "spec_repetitive_speedup":
            round(rep["spec"][0] / rep["plain"][0], 3)
            if rep["plain"][0] else None,
        "spec_decode_tok_s_adversarial": round(adv["spec"][0], 2),
        "plain_decode_tok_s_adversarial": round(adv["plain"][0], 2),
        "spec_adversarial_ratio":
            round(adv["spec"][0] / adv["plain"][0], 3)
            if adv["plain"][0] else None,
        "spec_acceptance_rate":
            round(acc_rate, 4) if acc_rate is not None else None,
        "spec_acceptance_rate_adversarial":
            round(adv_acc, 4) if adv_acc is not None else None,
        "spec_tokens_per_tick_mean": tpt.mean(),
        "spec_tokens_per_tick_p50": tpt.percentile(0.50),
        "spec_tokens_per_tick_p95": tpt.percentile(0.95),
        "spec_equal_output_tokens": equal,
        "spec_decode_compilations": spec_eng.decode_compilations,
    }


def _tp_mode(args, T) -> None:
    """The ``--tp N`` A/B leg (docs/serving.md "Tensor-parallel
    replicas"): steady-state decode tok/s of a tp=N GSPMD-sharded
    engine vs the tp=1 single-device engine on the IDENTICAL workload
    — reps interleaved, per-tick walls compared at the p25 exactly
    like the overlap A/B — with the benchmark's live token-identity
    check (``tp_equal_output_tokens``: the full per-request SEQUENCES,
    greedy and sampled rows both) and the zero-recompile guard in the
    JSON line.  On a single CPU host the tp engine pays real psum/
    all-gather collectives between forced host devices for no real
    memory win, so the ratio is the COORDINATION OVERHEAD floor, not a
    speedup — the tp win on hardware is serving a model whose params +
    KV do not fit one chip at all."""
    import dataclasses as _dc

    from horovod_tpu import serving

    if len(jax.devices()) < args.tp:
        print(json.dumps({
            "benchmark": "serving_tp", "skipped": True,
            "reason": f"{len(jax.devices())} devices < tp={args.tp} "
                      f"(set XLA_FLAGS="
                      f"--xla_force_host_platform_device_count="
                      f"{args.tp} before backend init)"}))
        return

    dtype = jnp.float32 if jax.devices()[0].platform == "cpu" \
        else jnp.bfloat16
    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq=args.prompt_len + args.steps,
        n_kv_heads=args.kv_heads[-1] if args.kv_heads else 0,
        attention_impl="reference", dtype=dtype)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    S = args.slots
    prompt = np.random.default_rng(7).integers(
        0, cfg.vocab_size, max(args.prompt_len // 2, 1)).tolist()
    engines = {}
    warm_compiles = {}
    for name, tp in (("tp", args.tp), ("tp1", 1)):
        eng = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=S, max_len=cfg.max_seq, tp=tp,
                max_prefills_per_tick=args.max_prefills_per_tick,
                max_queue_depth=max(2 * S, 8)))
        eng.warmup([len(prompt)])
        warm_compiles[name] = eng.decode_compilations
        engines[name] = (eng, [])

    toks = {}
    steps = max(min(max(args.steps, 24),
                    cfg.max_seq - len(prompt) + 1), 1)
    for rep in range(max(args.iters, 4)):
        for name, (eng, dts) in engines.items():
            # Half the slots sampled: the A/B's identity check covers
            # the sampled rows' key schedule under the sharded tick.
            futs = [eng.submit(prompt, max_new_tokens=steps,
                               temperature=0.9 if i % 2 else 0.0,
                               seed=i)
                    for i in range(S)]
            while not all(f.done() for f in futs):
                full = eng.slots.active_count == S
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                if full and eng.slots.active_count == S:
                    dts.append(dt)
            toks.setdefault(name, []).extend(
                f.tokens_so_far() for f in futs)
    q = {name: float(np.percentile(dts, 25))
         for name, (_, dts) in engines.items()}
    recompiles = {name: eng.decode_compilations - warm_compiles[name]
                  for name, (eng, _) in engines.items()}
    result = {
        "benchmark": "serving_tp",
        "chip": jax.devices()[0].device_kind,
        "tp": args.tp,
        "mesh": engines["tp"][0].stats()["mesh"],
        "model": _dc.asdict(cfg) | {"dtype": jnp.dtype(dtype).name},
        "slots": S,
        "steps_per_request": steps,
        "decode_tok_s_tp": round(S / q["tp"], 2),
        "decode_tok_s_tp1": round(S / q["tp1"], 2),
        "tp_decode_ratio": round(q["tp1"] / q["tp"], 3),
        "tp_equal_output_tokens": toks["tp"] == toks["tp1"],
        "decode_recompiles": recompiles["tp"],
        "decode_recompiles_tp1": recompiles["tp1"],
        "ab_steps_sampled": {n: len(d)
                             for n, (_, d) in engines.items()},
    }
    print(json.dumps(result))


def _ab_tracing(args, cfg, params):
    """The tracing-overhead A/B (docs/observability.md): steady-state
    decode tok/s with request tracing ENABLED vs DISABLED, identical
    overlapped-pipeline workload, reps interleaved and compared at the
    per-tick p25 exactly like :func:`_ab_decode`.  The disabled run IS
    the instrumented engine with no tracer attached — the cost of the
    hooks themselves (one global read per site) — so
    ``tracing_overhead_ratio`` near 1.0 demonstrates the off-by-default
    path is free, and the enabled ratio is the price of a full trace
    (bounds guarded by the perf-marked test in tests/test_obs.py:
    <=2% disabled, <=5% enabled).

    Extended to the SPAN layer (ISSUE 12): a third leg runs with a
    :class:`~horovod_tpu.obs.tracing.SpanRecorder` active under the
    DEFAULT tail-sampling policy — steady-state clean traffic buffers
    tick tuples and then tail-DROPS them at retirement (start/finish
    records only hit the stream), which is the deployed configuration
    — reporting ``span_tracing_overhead_ratio`` plus the
    retained-vs-dropped trace counts."""
    import tempfile

    from horovod_tpu import serving
    from horovod_tpu.obs import tracing as obs_tracing

    S = args.slots
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, max(args.prompt_len // 2, 1)).tolist()

    tracer = obs_tracing.get()
    own_path = None
    if tracer is None:
        fd, own_path = tempfile.mkstemp(prefix="hvd_trace_ab_",
                                        suffix=".json")
        os.close(fd)
        tracer = obs_tracing.start(own_path)
    obs_tracing.deactivate()
    sfd, span_path = tempfile.mkstemp(prefix="hvd_span_ab_",
                                      suffix=".jsonl")
    os.close(sfd)
    prev_spans = None
    srec = None

    engines = {}
    try:
        # Inside the try so a constructor failure (unwritable tmp,
        # disk full) still restores the process's active recorder.
        prev_spans = obs_tracing.deactivate_spans()
        srec = obs_tracing.SpanRecorder(span_path, proc="bench",
                                        role="replica")
        for name in ("notracing", "tracing", "spans"):
            eng = serving.InferenceEngine(
                params, cfg, serving.EngineConfig(
                    n_slots=S, max_len=cfg.max_seq,
                    max_prefills_per_tick=args.max_prefills_per_tick,
                    max_queue_depth=max(2 * S, 8), overlap=True))
            eng.warmup([len(prompt)])
            engines[name] = (eng, [])

        steps = max(min(max(args.steps, 24),
                        cfg.max_seq - len(prompt) + 1), 1)
        for _ in range(max(args.iters, 4)):
            for name, (eng, dts) in engines.items():
                obs_tracing.activate(tracer if name == "tracing" else None)
                obs_tracing.activate_spans(srec if name == "spans"
                                           else None)
                futs = [eng.submit(prompt, max_new_tokens=steps)
                        for _ in range(S)]
                while not all(f.done() for f in futs):
                    full = eng.slots.active_count == S
                    t0 = time.perf_counter()
                    eng.step()
                    dt = time.perf_counter() - t0
                    if full and eng.slots.active_count == S:
                        dts.append(dt)
                obs_tracing.deactivate()
                obs_tracing.deactivate_spans()
    finally:
        obs_tracing.activate(tracer)
        obs_tracing.activate_spans(prev_spans)
        if srec is not None:
            srec.close()
        os.unlink(span_path)
        if own_path is not None:
            obs_tracing.stop()
            os.unlink(own_path)

    q = {name: float(np.percentile(dts, 25))
         for name, (_, dts) in engines.items()}
    return {
        "decode_tok_s_tracing": round(S / q["tracing"], 2),
        "decode_tok_s_notracing": round(S / q["notracing"], 2),
        "decode_tok_s_spans": round(S / q["spans"], 2),
        "tracing_overhead_ratio": round(q["tracing"] / q["notracing"], 4),
        "span_tracing_overhead_ratio": round(
            q["spans"] / q["notracing"], 4),
        "span_traces_retained": srec.n_retained,
        "span_traces_dropped": srec.n_dropped,
    }


def _ab_sampled(args, cfg, params):
    """Sampled-vs-greedy throughput A/B: per-slot sampling rides the
    SAME compiled tick as parameter columns, so the only cost is the
    in-tick sort/softmax/categorical — this measures it (same
    interleaved-rep p25 idiom as the overlap A/B), and asserts the
    zero-recompile property across the whole mix."""
    from horovod_tpu import serving

    S = args.slots
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, max(args.prompt_len // 2, 1)).tolist()
    eng = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(
            n_slots=S, max_len=cfg.max_seq,
            max_prefills_per_tick=args.max_prefills_per_tick,
            max_queue_depth=max(2 * S, 8)))
    eng.warmup([len(prompt)])
    base_compiles = eng.decode_compilations
    steps = max(min(max(args.steps, 24), cfg.max_seq - len(prompt) + 1), 1)
    dts = {"greedy": [], "sampled": []}
    for _ in range(max(args.iters, 4)):
        for name, kw in (("greedy", {}),
                         ("sampled", dict(temperature=1.0, top_k=16,
                                          top_p=0.9))):
            futs = [eng.submit(prompt, max_new_tokens=steps, seed=i,
                               **kw) for i in range(S)]
            while not all(f.done() for f in futs):
                full = eng.slots.active_count == S
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                if full and eng.slots.active_count == S:
                    dts[name].append(dt)
    q = {n: float(np.percentile(d, 25)) for n, d in dts.items()}
    return {
        "decode_tok_s_greedy": round(S / q["greedy"], 2),
        "decode_tok_s_sampled": round(S / q["sampled"], 2),
        "sampled_vs_greedy_ratio": round(q["greedy"] / q["sampled"], 3),
        "sampling_recompiles": eng.decode_compilations - base_compiles,
    }


def _ab_stream(args, cfg, params):
    """The streaming-transport leg (``--stream``): client-observed
    TTFB — request start to the FIRST SSE token event on the wire —
    p50/p99 against the non-streamed server-reported TTFT on the same
    closed-loop HTTP workload.  Streaming exists to close the gap
    between 'first token computed' and 'first byte a user sees'; this
    reports both ends of it."""
    import http.client

    from horovod_tpu import serving
    from horovod_tpu.serving import sse

    eng = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(
            n_slots=args.slots, max_len=cfg.max_seq,
            max_prefills_per_tick=args.max_prefills_per_tick,
            max_queue_depth=max(args.n_requests, 8)))
    prompt = np.random.default_rng(3).integers(
        0, cfg.vocab_size, max(args.prompt_len // 2, 1)).tolist()
    eng.warmup([len(prompt)])
    srv = serving.ServingServer(eng, port=0).start()
    host, port = srv.address
    steps = max(min(args.steps, cfg.max_seq - len(prompt) + 1), 1)
    n = max(min(args.n_requests, 16), 8)

    def post(body):
        c = http.client.HTTPConnection(host, port, timeout=60)
        c.request("POST", "/generate", body=json.dumps(body).encode())
        return c, c.getresponse()

    ttft_ms, ttfb_ms, toks = [], [], {}
    try:
        for i in range(n):
            c, r = post({"tokens": prompt, "max_new_tokens": steps,
                         "temperature": 1.0, "seed": i})
            resp = json.loads(r.read())
            c.close()
            ttft_ms.append(resp["ttft_ms"])
            toks.setdefault("plain", []).append(resp["tokens"])
        for i in range(n):
            t0 = time.perf_counter()
            c, r = post({"tokens": prompt, "max_new_tokens": steps,
                         "temperature": 1.0, "seed": i,
                         "stream": True})
            if r.status != 200:
                raise RuntimeError(
                    f"stream request {i} rejected: {r.status} "
                    f"{r.read()!r}")
            parser = sse.SSEParser()
            events = []
            while not any(k == "token" for k, _ in events):
                data = r.read1(256)
                if not data:  # error stream / EOF before any token
                    raise RuntimeError(
                        f"stream {i} ended without a token event: "
                        f"{events}")
                events.extend(parser.feed(data))
            ttfb_ms.append((time.perf_counter() - t0) * 1e3)
            while True:
                data = r.read1(4096)
                if not data:
                    break
                events.extend(parser.feed(data))
            c.close()
            toks.setdefault("stream", []).append(
                [p["token"] for k, p in events if k == "token"])
    finally:
        srv.stop(drain_timeout=10)
    snap = eng.metrics.streamed_ttfb.snapshot()
    return {
        "stream_ttfb_ms_p50": round(float(np.percentile(ttfb_ms, 50)), 3),
        "stream_ttfb_ms_p99": round(float(np.percentile(ttfb_ms, 99)), 3),
        "nonstream_ttft_ms_p50":
            round(float(np.percentile(ttft_ms, 50)), 3),
        "nonstream_ttft_ms_p99":
            round(float(np.percentile(ttft_ms, 99)), 3),
        # server-side first-event histogram (arrival -> wire)
        "stream_ttfb_server_mean_s": snap["mean"],
        "stream_equal_output_tokens": toks["plain"] == toks["stream"],
        "streamed_tokens": eng.metrics.streamed_tokens.value,
    }


def _router_mode(args, cfg) -> None:
    """Open-loop benchmark through the replicated front tier: N
    replica PROCESSES behind the join-shortest-queue router, the same
    Poisson arrivals as ``--engine`` — aggregate tok/s plus
    per-replica occupancy/request spread in the JSON line.  Replicas
    init from the same seed (replica_main), so the answers are
    byte-identical no matter which replica serves them."""
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    from horovod_tpu.serving.router import (
        ReplicaRegistry,
        ReplicaSpec,
        ReplicaSupervisor,
        RouterServer,
    )

    rng = np.random.default_rng(0)
    lengths = rng.integers(max(args.prompt_len // 2, 1),
                           args.prompt_len + 1, args.n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
               for n in lengths]
    arrival = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                        args.n_requests))

    spec = ReplicaSpec(
        seed=0, vocab=cfg.vocab_size, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
        max_seq=cfg.max_seq, n_kv_heads=cfg.n_kv_heads or 0,
        slots=args.slots,
        max_prefills_per_tick=args.max_prefills_per_tick,
        max_queue_depth=max(args.n_requests, 8),
        warm=(max(args.prompt_len // 2, 1), args.prompt_len))
    registry = ReplicaRegistry(poll_interval=0.2)
    sup = ReplicaSupervisor(spec, args.router, registry=registry)
    rt = RouterServer(registry, port=0)
    try:
        sup.start()
        rt.start()
        if not sup.wait_ready(timeout=600):
            raise RuntimeError("replicas never became ready")
        host, port = rt.address
        base = f"http://{host}:{port}"

        results = {}
        occ_samples: dict = {}
        done = threading.Event()

        def occ_sampler():
            while not done.is_set():
                for s in registry.statuses():
                    occ_samples.setdefault(s.endpoint.rid,
                                           []).append(s.occupancy)
                time.sleep(0.05)

        def client(i):
            req = urllib.request.Request(
                base + "/generate",
                data=_json.dumps({
                    "tokens": prompts[i],
                    "max_new_tokens": args.steps}).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=300) as r:
                    results[i] = (r.status, _json.loads(r.read()),
                                  r.headers.get("X-Router-Replica"))
            except urllib.error.HTTPError as e:
                results[i] = (e.code, _json.loads(e.read()), None)
            except Exception as e:
                # Transport-level failure: a DROPPED request.  It must
                # show in the accounting — the front tier's whole claim
                # is that this number stays 0.
                results[i] = (None, {"type": repr(e)}, None)

        sampler = threading.Thread(target=occ_sampler, daemon=True)
        sampler.start()
        threads = []
        t0 = time.monotonic()
        for i in range(args.n_requests):
            now = time.monotonic() - t0
            if now < arrival[i]:
                time.sleep(arrival[i] - now)
            th = threading.Thread(target=client, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        wall = time.monotonic() - t0
        done.set()
        sampler.join(1.0)

        toks = sum(len(r[1].get("tokens", []))
                   for r in results.values())
        per_replica_req: dict = {}
        for code, _, rid in results.values():
            if rid is not None:
                per_replica_req[rid] = per_replica_req.get(rid, 0) + 1
        stats = rt.stats()
        result = {
            "metric": f"router open-loop tok/s ({args.router} replicas "
                      f"x S={args.slots} slots, {args.arrival_rate}/s "
                      f"Poisson, {args.n_requests} reqs x "
                      f"{args.steps} toks)",
            "value": round(toks / wall, 2) if wall else 0.0,
            "unit": "tok/s",
            "replicas": args.router,
            "requests": args.n_requests,
            "completed_with_tokens": sum(
                1 for c, _, _ in results.values() if c == 200),
            "typed_errors": sum(
                1 for c, _, _ in results.values()
                if c is not None and c != 200),
            "dropped": args.n_requests - sum(
                1 for c, _, _ in results.values() if c is not None),
            "per_replica_requests": per_replica_req,
            "per_replica_occupancy": {
                rid: round(float(np.mean(v)), 3)
                for rid, v in sorted(occ_samples.items())},
            "router_retries": stats["retries"],
            "router_failovers": stats["failovers"],
            "router_replica_restarts": stats["replica_restarts"],
            "proxy_latency_p50_s":
                stats["proxy_latency_seconds"]["p50"],
            "chip": args.chip_kind,
            "registry": registry.metrics.registry.snapshot(),
        }
        print(f"router   {args.router} replicas {result['value']:9.1f} "
              f"tok/s aggregate | spread {per_replica_req} | "
              f"retries {stats['retries']:.0f}")
        print(json.dumps(result))
    finally:
        rt.stop()
        sup.stop(drain=False)


def _rollout_mode(args, cfg) -> None:
    """Zero-downtime reconfiguration benchmark (``--rollout``): the
    candidate config comes out of ``tuning.replay.tune()`` (offline BO
    over replay runs of a synthetic trace — the full tuned-settings
    path docs/serving.md's rollout runbook deploys), then a 3-replica
    fleet behind the router serves a continuous closed-loop load while
    that candidate is rolled out replica-by-replica through the canary
    gate to full promotion.  The JSON line reports the tuned candidate,
    the canary/incumbent scores, the per-step durations, the rollback
    count (the claim is 0) and the number of rollout-attributable 5xx
    responses (the claim is 0: capacity never drops below N-1 and
    drains run to completion)."""
    import json as _json
    import tempfile
    import threading
    import urllib.error
    import urllib.request

    from horovod_tpu import serving
    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.router import (
        ReplicaRegistry,
        ReplicaSpec,
        ReplicaSupervisor,
        RolloutController,
        RouterServer,
    )
    from horovod_tpu.tuning.replay import TraceRequest, tune, warm_lens

    n = args.router if args.router > 1 else 3
    rng = np.random.default_rng(0)
    lengths = rng.integers(max(args.prompt_len // 2, 1),
                           args.prompt_len + 1, 64)
    prompts = [rng.integers(0, cfg.vocab_size, int(m)).tolist()
               for m in lengths]

    # --- source the candidate from tuning.replay.tune() -------------
    # Offline BO over replay runs of a synthetic trace: one fresh
    # warmed engine per sample, constructor knobs in scope.  The
    # winner's ``settings`` dict is POSTed to /rollout verbatim — the
    # tuned-config deployment path end to end.
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    trace = [TraceRequest(
        id=i,
        prompt=tuple(int(t) for t in rng.integers(
            0, cfg.vocab_size,
            int(lengths[i % len(lengths)]))),
        max_new_tokens=args.steps) for i in range(8)]

    def build(settings):
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=args.slots, max_len=cfg.max_seq,
                tick_timeout=0.0, **settings))
        engine.warmup(warm_lens(trace, engine))
        return engine

    tuned = tune(build, trace,
                 bounds={"max_prefills_per_tick": (1, 4)},
                 samples=2, seed=0)
    candidate = dict(tuned["best"]["settings"])
    print(f"replay-tuned candidate: {candidate} "
          f"(score {tuned['best']['score']})")

    spec = ReplicaSpec(
        seed=0, vocab=cfg.vocab_size, d_model=cfg.d_model,
        n_heads=cfg.n_heads, n_layers=cfg.n_layers, d_ff=cfg.d_ff,
        max_seq=cfg.max_seq, n_kv_heads=cfg.n_kv_heads or 0,
        slots=args.slots,
        max_prefills_per_tick=args.max_prefills_per_tick,
        max_queue_depth=64,
        warm=(max(args.prompt_len // 2, 1), args.prompt_len))
    registry = ReplicaRegistry(poll_interval=0.2)
    journal_dir = tempfile.mkdtemp(prefix="bench_rollout_")
    sup = ReplicaSupervisor(spec, n, registry=registry,
                            journal_dir=journal_dir)
    ctl = RolloutController(sup, canary_weight=0.3, canary_windows=2,
                            window_s=0.5, ready_timeout=600.0)
    rt = RouterServer(registry, port=0, rollout=ctl)
    counts = {"200": 0, "5xx": 0, "other": 0, "dropped": 0}
    counts_lock = threading.Lock()
    stop = threading.Event()

    def loader(worker):
        lrng = np.random.default_rng(worker)
        while not stop.is_set():
            prompt = prompts[int(lrng.integers(0, len(prompts)))]
            req = urllib.request.Request(
                base + "/generate",
                data=_json.dumps({
                    "tokens": prompt,
                    "max_new_tokens": args.steps}).encode(),
                headers={"Content-Type": "application/json"})
            key = "dropped"
            try:
                with urllib.request.urlopen(req, timeout=300) as r:
                    key = "200" if r.status == 200 else "other"
                    r.read()
            except urllib.error.HTTPError as e:
                key = "5xx" if e.code >= 500 else "other"
                e.read()
            except Exception:
                pass
            with counts_lock:
                counts[key] += 1

    try:
        sup.start()
        rt.start()
        if not sup.wait_ready(timeout=600):
            raise RuntimeError("replicas never became ready")
        host, port = rt.address
        base = f"http://{host}:{port}"

        workers = [threading.Thread(target=loader, args=(w,),
                                    daemon=True) for w in range(4)]
        for th in workers:
            th.start()
        time.sleep(1.0)  # pre-rollout traffic baseline

        req = urllib.request.Request(
            base + "/rollout",
            data=_json.dumps({"candidate": candidate}).encode(),
            headers={"Content-Type": "application/json"})
        t0 = time.monotonic()
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 202, r.status
            r.read()
        if not ctl.wait(timeout=600):
            raise RuntimeError("rollout never reached a terminal state")
        wall = time.monotonic() - t0
        time.sleep(1.0)  # post-rollout traffic on the new config
        stop.set()
        for th in workers:
            th.join(300.0)

        status = ctl.status()
        gens = {}
        for st in registry.statuses():
            with urllib.request.urlopen(st.endpoint.base_url + "/stats",
                                        timeout=5.0) as r:
                gens[st.endpoint.rid] = _json.loads(r.read()).get(
                    "config_generation")
        snap = registry.metrics.snapshot()
        result = {
            "metric": f"fleet rollout wall-clock ({n} replicas x "
                      f"S={args.slots} slots, candidate {candidate}, "
                      f"continuous closed-loop load)",
            "value": round(wall, 2),
            "unit": "s",
            "replicas": n,
            "candidate": candidate,
            "tune_trajectory": tuned["trajectory"],
            "terminal_state": status["state"],
            "trip_reason": status["trip_reason"],
            "canary_score": status["canary_score"],
            "incumbent_score": status["incumbent_score"],
            "step_durations_s": status["step_durations_s"],
            "rollbacks": int(snap["rollout_rollbacks"]),
            "promotions": int(snap["rollout_promotions"]),
            "rollout_steps": int(snap["rollout_steps"]),
            "requests_200": counts["200"],
            "http_5xx": counts["5xx"],
            "dropped": counts["dropped"],
            "config_generations": gens,
            "chip": args.chip_kind,
        }
        print(f"rollout  {n} replicas promoted in {wall:6.1f}s | "
              f"canary {status['canary_score']} vs incumbent "
              f"{status['incumbent_score']} | "
              f"5xx {counts['5xx']} | rollbacks "
              f"{int(snap['rollout_rollbacks'])}")
        print(json.dumps(result))
    finally:
        stop.set()
        rt.stop()
        sup.stop(drain=False)


def _chaos_mode(args, T, cfg, params) -> None:
    """Durability benchmark (``--chaos``): the open-loop workload with
    deterministic engine crashes injected mid-decode, restart-resume
    ON (the default).  Reports resumed-vs-restarted counts, the
    wasted-token ratio (tokens re-prefilled by resumes / tokens
    generated), and per-request oracle identity — the honest price
    and proof of durability under faults."""
    from horovod_tpu import serving

    rng = np.random.default_rng(0)
    lengths = rng.integers(max(args.prompt_len // 2, 1),
                           args.prompt_len + 1, args.n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in lengths]
    arrival = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                        args.n_requests))

    inj = serving.FaultInjector(seed=0)
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(
            n_slots=args.slots, max_len=cfg.max_seq,
            max_prefills_per_tick=args.max_prefills_per_tick,
            max_queue_depth=max(args.n_requests, 8),
            max_restarts=1000, restart_backoff=0.01,
            restart_backoff_max=0.05, faults=inj))
    # Warm the prompt buckets AND the resume buckets (prompt + emitted
    # can reach prompt_len + steps): a resumed re-admission must not
    # pay XLA compilation mid-benchmark.
    cap = engine.slots.max_len - 2
    warm = sorted({min(n, cap) for p in prompts
                   for n in (len(p), len(p) + args.steps)})
    engine.warmup(warm)
    # One crash roughly every ``steps`` decode ticks, spread across the
    # run — each one forces a restart with in-flight requests to
    # resume.
    base = inj.visits("decode_tick")
    n_faults = 4
    for i in range(n_faults):
        inj.add(serving.FaultSpec(
            site="decode_tick", kind="raise",
            skip=base + 5 + i * max(args.steps, 8)))

    engine.start()
    futs = []
    t0 = time.monotonic()
    for i in range(args.n_requests):
        now = time.monotonic() - t0
        if now < arrival[i]:
            time.sleep(arrival[i] - now)
        futs.append(engine.submit(prompts[i], max_new_tokens=args.steps))
    while not all(f.done() for f in futs):
        time.sleep(0.005)
    wall = time.monotonic() - t0
    engine.stop()

    # Byte-identity against the no-fault greedy oracle, per request.
    ok = typed = mismatched = 0
    for p, f in zip(prompts, futs):
        try:
            out = f.result(timeout=0)
        except serving.ServingError:
            typed += 1
            continue
        ref = np.asarray(T.greedy_decode(
            params, jnp.asarray([p], jnp.int32), args.steps,
            cfg))[0].tolist()
        if out == ref:
            ok += 1
        else:
            mismatched += 1

    snap = engine.stats()
    toks = snap["tokens_generated"]
    wasted = snap["resume_wasted_tokens"]
    result = {
        "metric": f"chaos durability: wasted-token ratio under "
                  f"{n_faults} injected crashes "
                  f"(S={args.slots}, {args.n_requests} reqs x "
                  f"{args.steps} toks, restart-resume on)",
        "value": round(wasted / toks, 4) if toks else None,
        "unit": "re-prefilled/generated",
        "requests_resumed": snap["requests_resumed"],
        "engine_restarts": snap["engine_restarts"],
        "engine_failures": snap["engine_failures"],
        "requests_oracle_identical": ok,
        "requests_typed_error": typed,
        "requests_mismatched": mismatched,
        "resume_wasted_tokens": wasted,
        "tokens_generated": toks,
        "wall_s": round(wall, 3),
        "faults_fired": [list(f) for f in inj.fired],
        "journal_inflight": snap["journal_inflight"],
        "decode_compilations": snap["decode_compilations"],
        "chip": jax.devices()[0].device_kind,
    }
    print(f"chaos    {snap['requests_resumed']:.0f} resumed across "
          f"{snap['engine_restarts']:.0f} restarts | "
          f"{ok}/{len(futs)} oracle-identical ({typed} typed, "
          f"{mismatched} mismatched) | wasted-token ratio "
          f"{result['value']}")
    print(json.dumps(result))


def _slo_mode(args, T) -> None:
    """SLO-scheduling benchmark (``--slo``, docs/serving.md
    "Scheduling"): a scenario-diverse workload — bursty arrivals,
    BIMODAL prompt lengths (short interactive queries sharing the
    engine with a stream of long batch prompts), mixed priority
    classes — served twice over identical arrivals:

    * **slo**: chunked prefill (``prefill_chunk_tokens``) + priority
      classes + preemption — the PR 14 scheduler;
    * **fcfs**: whole-prompt prefill, every request one class — the
      historical engine.

    The JSON line reports per-class TTFT p50/p99 for both, the
    interactive-class p99 ratio (the acceptance criterion: >= 2x
    better under the long-prompt interference leg), total tok/s (must
    stay within 10%), preemption counts, per-request oracle identity
    for the SLO leg (chunked + preempted + resumed output must be
    token-identical), and ``decode_recompiles`` (must be 0 — chunk
    boundaries and priorities are data)."""
    from horovod_tpu import serving

    steps = min(args.steps, 16)
    long_len, chunk = 288, 32
    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq=long_len + 2 * steps + 32,
        n_kv_heads=args.kv_heads[-1] if args.kv_heads else 0,
        attention_impl="reference",
        dtype=jnp.float32 if jax.devices()[0].platform == "cpu"
        else jnp.bfloat16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    rng = np.random.default_rng(0)
    # Bimodal, bursty: two waves, each an interleaved mix of LONG
    # batch prompts and bursts of short interactive ones — the
    # interference leg: in FCFS order every short prompt behind a long
    # one waits out its whole prefill.
    work = []  # (arrival_s, prompt, priority)
    t = 0.0
    for wave in range(2):
        for j in range(2):  # long batch prompts lead the wave
            n = int(rng.integers(long_len - 48, long_len + 1))
            work.append((t, rng.integers(0, cfg.vocab_size, n).tolist(),
                         "batch"))
        for j in range(6):  # ... then a burst of interactive queries
            n = int(rng.integers(3, 13))
            work.append((t + 0.01 * (j + 1),
                         rng.integers(0, cfg.vocab_size, n).tolist(),
                         "interactive"))
        t += 0.25

    def run(slo: bool):
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=4, max_len=cfg.max_seq,
                max_prefills_per_tick=args.max_prefills_per_tick,
                max_queue_depth=64,
                prefill_chunk_tokens=chunk if slo else 0))
        warm_lens = sorted({len(p) for _, p, _ in work})
        engine.warmup([warm_lens[0], warm_lens[len(warm_lens) // 2],
                       warm_lens[-1]])
        warm_compiles = engine.decode_compilations
        engine.metrics = serving.ServingMetrics()
        engine.start()
        futs = []
        t0 = time.monotonic()
        for arrival, prompt, pri in work:
            now = time.monotonic() - t0
            if now < arrival:
                time.sleep(arrival - now)
            futs.append((pri, prompt, engine.submit(
                prompt, max_new_tokens=steps,
                priority=pri if slo else "interactive")))
        while not all(f.done() for _, _, f in futs):
            time.sleep(0.002)
        wall = time.monotonic() - t0
        engine.stop()
        snap = engine.stats()
        by_class = {"interactive": [], "batch": []}
        oracle_ok = oracle_bad = 0
        for pri, prompt, f in futs:
            if f.ttft is not None:
                by_class[pri].append(f.ttft)
            if slo:
                ref = np.asarray(T.greedy_decode(
                    params, jnp.asarray([prompt], jnp.int32), steps,
                    cfg))[0].tolist()
                if f.result(timeout=0) == ref:
                    oracle_ok += 1
                else:
                    oracle_bad += 1
        toks = sum(len(f.tokens_so_far()) for _, _, f in futs)
        out = {
            "tok_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "preemptions": snap["preemptions"],
            "decode_recompiles":
                engine.decode_compilations - warm_compiles,
        }
        for cls, vals in by_class.items():
            vals.sort()
            out[f"{cls}_ttft_p50_ms"] = round(
                vals[len(vals) // 2] * 1e3, 2) if vals else None
            out[f"{cls}_ttft_p99_ms"] = round(
                vals[min(len(vals) - 1,
                         int(len(vals) * 0.99))] * 1e3, 2) \
                if vals else None
        if slo:
            out["oracle_identical"] = oracle_ok
            out["oracle_mismatched"] = oracle_bad
        return out

    fcfs = run(slo=False)
    slo = run(slo=True)
    ratio = (fcfs["interactive_ttft_p99_ms"]
             / slo["interactive_ttft_p99_ms"]
             if slo["interactive_ttft_p99_ms"] else None)
    tput_ratio = (slo["tok_s"] / fcfs["tok_s"]
                  if fcfs["tok_s"] else None)
    result = {
        "metric": f"slo scheduling: interactive TTFT p99 improvement "
                  f"(chunk={chunk} prio+preempt vs FCFS whole-prefill; "
                  f"bimodal {long_len}-token batch stream + "
                  f"interactive bursts, S=4, {len(work)} reqs x "
                  f"{steps} toks)",
        "value": round(ratio, 2) if ratio else None,
        "unit": "x (fcfs_p99 / slo_p99; >= 2 is the acceptance bar)",
        "throughput_ratio": round(tput_ratio, 3) if tput_ratio else None,
        "prefill_chunk_tokens": chunk,
        "decode_recompiles": slo["decode_recompiles"],
        "slo": slo,
        "fcfs": fcfs,
        "chip": jax.devices()[0].device_kind,
    }
    print(f"slo      interactive TTFT p99 {slo['interactive_ttft_p99_ms']}ms "
          f"(chunked+prio) vs {fcfs['interactive_ttft_p99_ms']}ms (fcfs) "
          f"= {result['value']}x | tok/s {slo['tok_s']} vs "
          f"{fcfs['tok_s']} ({result['throughput_ratio']}x) | "
          f"{slo['preemptions']} preemptions, "
          f"{slo['decode_recompiles']} decode recompiles")
    print(json.dumps(result))


def _autotune_mode(args, T) -> None:
    """Autotuning A/B (``--autotune``, docs/serving.md "Autotuning"):
    TWO CONTRASTING workloads — a short-prompt interactive burst and a
    long-prompt batch stream (with an interactive trickle whose TTFT
    constraint the tuner must respect) — each served twice over
    identical arrivals:

    * **static**: the engine's config defaults, untouched;
    * **tuned**: the online tuner converges on a separate convergence
      drive drawn from the same workload distribution, PINS, and then
      the measured run replays the identical arrivals under the
      pinned knobs.

    The JSON line carries ``tuned_knobs``, ``tuning_samples``, the
    objective trajectory, per-class TTFT, throughput, and
    ``decode_recompiles`` (must be 0: every knob the online tuner may
    touch maps to an already-warmed executable shape)."""
    from horovod_tpu import serving
    from horovod_tpu.tuning import Objective, OnlineTuner

    steps = min(args.steps, 12)
    long_len, chunk = 160, 32
    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq=long_len + 2 * steps + 32,
        n_kv_heads=args.kv_heads[-1] if args.kv_heads else 0,
        attention_impl="reference",
        dtype=jnp.float32 if jax.devices()[0].platform == "cpu"
        else jnp.bfloat16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    def make_workload(name):
        rng = np.random.default_rng(1)
        work = []  # (arrival_s, prompt, priority)
        if name == "interactive_burst":
            # Bursty waves of short prompts: the tuner should favor
            # wide admission (high k) — prefill dominates.
            t = 0.0
            for wave in range(4):
                for j in range(6):
                    n = int(rng.integers(3, 13))
                    work.append((t + 0.004 * j,
                                 rng.integers(0, cfg.vocab_size,
                                              n).tolist(),
                                 "interactive"))
                t += 0.08
        else:  # long_batch
            # A stream of long batch prompts with an interactive
            # trickle riding along: throughput tuning must not buy
            # tokens by starving the trickle past its TTFT SLO.
            t = 0.0
            for wave in range(3):
                for j in range(3):
                    n = int(rng.integers(long_len - 32, long_len + 1))
                    work.append((t, rng.integers(0, cfg.vocab_size,
                                                 n).tolist(), "batch"))
                for j in range(2):
                    n = int(rng.integers(3, 13))
                    work.append((t + 0.02 * (j + 1),
                                 rng.integers(0, cfg.vocab_size,
                                              n).tolist(),
                                 "interactive"))
                t += 0.15
        return work

    slo = {"interactive": 0.5}

    def run(work, tuned: bool):
        engine = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=4, max_len=cfg.max_seq,
                max_prefills_per_tick=args.max_prefills_per_tick,
                max_queue_depth=64, prefill_chunk_tokens=chunk))
        lens = sorted({len(p) for _, p, _ in work})
        engine.warmup([lens[0], lens[len(lens) // 2], lens[-1]])
        warm_compiles = engine.decode_compilations
        tuning = None
        engine.start()
        if tuned:
            # Convergence drive: waves drawn from the same workload
            # distribution until the tuner pins (cap bounds the run).
            tuner = OnlineTuner.install(
                engine, window_ticks=8, bo_samples=6,
                objective=Objective(ttft_slo=slo))
            for wave in range(120):
                if tuner.phase == "pinned":
                    break
                futs = [engine.submit(p, max_new_tokens=steps,
                                      priority=pri)
                        for _, p, pri in work[:8]]
                while not all(f.done() for f in futs):
                    time.sleep(0.002)
            snap = tuner.snapshot()
            tuning = {
                "tuned_knobs": snap["best"]["settings"],
                "tuning_samples": snap["samples"],
                "converged": tuner.converged,
                "trajectory": [
                    {"sample": e["sample"], "phase": e["phase"],
                     "settings": e["settings"],
                     "objective": e["objective"],
                     "violated": e["violated"]}
                    for e in snap["trajectory"]],
            }
        # The measured leg: identical arrivals for both A/B sides;
        # fresh metrics so the tuner's convergence traffic (tuned leg)
        # does not pollute the measurement (the tuner's window resets
        # on the metrics swap).
        engine.metrics = serving.ServingMetrics()
        futs = []
        t0 = time.monotonic()
        for arrival, prompt, pri in work:
            now = time.monotonic() - t0
            if now < arrival:
                time.sleep(arrival - now)
            futs.append((pri, engine.submit(
                prompt, max_new_tokens=steps, priority=pri)))
        while not all(f.done() for _, f in futs):
            time.sleep(0.002)
        wall = time.monotonic() - t0
        engine.stop()
        by_class = {}
        for pri, f in futs:
            if f.ttft is not None:
                by_class.setdefault(pri, []).append(f.ttft)
        toks = sum(len(f.tokens_so_far()) for _, f in futs)
        out = {
            "tok_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "decode_recompiles":
                engine.decode_compilations - warm_compiles,
        }
        for cls, vals in sorted(by_class.items()):
            vals.sort()
            out[f"{cls}_ttft_p99_ms"] = round(
                vals[min(len(vals) - 1,
                         int(len(vals) * 0.99))] * 1e3, 2)
        if tuning is not None:
            out.update(tuning)
        return out

    result = {
        "metric": "autotuned vs static serving knobs (online tuner, "
                  f"pinned before measurement; S=4, chunk={chunk}, "
                  f"{steps} toks/req)",
        "unit": "tok_s ratio (tuned / static) per workload",
        "ttft_slo_ms": {k: v * 1e3 for k, v in slo.items()},
        "chip": jax.devices()[0].device_kind,
    }
    for name in ("interactive_burst", "long_batch"):
        work = make_workload(name)
        static = run(work, tuned=False)
        tuned = run(work, tuned=True)
        ratio = (tuned["tok_s"] / static["tok_s"]
                 if static["tok_s"] else None)
        slo_ms = slo["interactive"] * 1e3
        result[name] = {
            "ratio": round(ratio, 3) if ratio else None,
            "interactive_ttft_ok":
                tuned.get("interactive_ttft_p99_ms") is not None
                and tuned["interactive_ttft_p99_ms"] <= slo_ms,
            "static": static,
            "tuned": tuned,
        }
        print(f"autotune {name}: tok/s {tuned['tok_s']} (tuned, "
              f"{tuned['tuned_knobs']}) vs {static['tok_s']} (static) "
              f"= {result[name]['ratio']}x | interactive TTFT p99 "
              f"{tuned.get('interactive_ttft_p99_ms')}ms (SLO "
              f"{slo_ms:.0f}ms) | {tuned['tuning_samples']} samples, "
              f"{tuned['decode_recompiles']} decode recompiles")
    print(json.dumps(result))


def _engine_mode(args, T, cfg, params) -> None:
    """Open-loop continuous-batching benchmark: Poisson arrivals at
    ``--arrival-rate`` req/s with prompt lengths mixed over
    [prompt_len/2, prompt_len], against the engine's S-slot pool
    (overlapped pipeline — the production default), followed by the
    steady-state overlap-vs-sync decode A/B (:func:`_ab_decode`), the
    tracing-overhead A/B (:func:`_ab_tracing`), and the static-batch
    closed-loop ceiling.  With ``--trace`` the open-loop run records a
    Perfetto trace + JSONL request log, and the JSON line carries the
    trace file path; the line always carries the full metrics-registry
    snapshot so BENCH_r* runs double as observability fixtures."""
    rng = np.random.default_rng(0)
    lengths = rng.integers(max(args.prompt_len // 2, 1),
                           args.prompt_len + 1, args.n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in lengths]
    arrival = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                        args.n_requests))

    tracer = None
    if args.trace:
        from horovod_tpu.obs import tracing as obs_tracing

        tracer = obs_tracing.start(args.trace,
                                   jsonl_path=args.trace + ".jsonl")
    over = _run_engine_once(args, cfg, params, prompts, arrival,
                            overlap=True)
    if tracer is not None:
        from horovod_tpu.obs import tracing as obs_tracing

        obs_tracing.stop()
    ab = None if args.overlap_only else _ab_decode(args, cfg, params)
    tab = None if args.overlap_only else _ab_tracing(args, cfg, params)
    sab = None if args.overlap_only else _ab_spec(args, T, cfg)
    smab = None if args.overlap_only else _ab_sampled(args, cfg, params)
    stab = _ab_stream(args, cfg, params) if args.stream else None

    engine, snap = over["engine"], over["snap"]
    ttft = snap["ttft_seconds"]
    result = {
        "metric": f"continuous-batching open-loop tok/s "
                  f"(S={args.slots} slots, K={args.max_prefills_per_tick}, "
                  f"{args.arrival_rate}/s Poisson, "
                  f"{args.n_requests} reqs x {args.steps} toks, "
                  f"overlapped pipeline)",
        "value": round(over["tok_s"], 2),
        "unit": "tok/s",
        "ttft_p50_s": ttft["p50"],
        "ttft_p99_s": ttft["p99"],
        "ttft_mean_s": ttft["mean"],
        "mean_slot_occupancy": round(over["occ"], 3),
        "requests_completed": snap["requests_completed"],
        "engine_state": engine.health,
        "engine_restarts": snap["engine_restarts"],
        "decode_compilations": engine.decode_compilations,
        "decode_recompiles_after_warmup": over["recompiles"],
        "overlap_efficiency": over["overlap_efficiency"],
        "host_syncs": snap["host_syncs"],
        "host_syncs_per_tick": over["host_syncs_per_tick"],
        "tick_dispatch_mean_s": snap["tick_dispatch_seconds"]["mean"],
        "tick_device_wait_mean_s":
            snap["tick_device_wait_seconds"]["mean"],
        "tick_host_mean_s": snap["tick_host_seconds"]["mean"],
        "model_flops_per_token": snap["model_flops_per_token"],
        "achieved_flops_per_sec": snap["achieved_flops_per_sec"],
        # Tokens emitted per slot per tick (p50/p95 + mean): 1.0 on
        # this non-speculative open-loop run by construction — the
        # same axis the speculative A/B's multiplier reports on, so
        # the two compose with the PR 4 overlap ratio directly.
        "tokens_per_tick_mean": engine.metrics.tokens_per_tick.mean(),
        "tokens_per_tick_p50":
            engine.metrics.tokens_per_tick.percentile(0.50),
        "tokens_per_tick_p95":
            engine.metrics.tokens_per_tick.percentile(0.95),
        # Page-pool pressure for the open-loop run:
        # per-token cache cost, pool size, and the high-water mark that
        # sizes n_pages for this traffic shape.
        "paged": snap["paged"],
        "kv_bytes_per_token": snap["kv_bytes_per_token"],
        "kv_pages_total": snap["kv_pages_total"],
        "kv_pages_high_water": snap.get("kv_pages_high_water"),
        "kv_page_size": snap.get("page_size"),
        "chip": jax.devices()[0].device_kind,
        # The full registry snapshot rides the JSON line so BENCH_r*
        # artifacts carry the observability data (counters, gauges,
        # and histogram populations) for the run that produced them.
        "registry": engine.metrics.registry.snapshot(),
    }
    if args.trace:
        result["trace_file"] = args.trace
        result["trace_jsonl"] = args.trace + ".jsonl"
    if ab is not None:
        result.update(ab)
    if tab is not None:
        result.update(tab)
    if sab is not None:
        result.update(sab)
    if smab is not None:
        result.update(smab)
    if stab is not None:
        result.update(stab)

    # Static-batch reference at B = n_slots: the closed-loop ceiling the
    # engine is measured against (same cfg, full batch decoding in
    # lockstep with no admission dynamics).
    B = args.slots
    prompt = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, args.prompt_len)), jnp.int32)
    cache = T.init_cache(cfg, B, cfg.max_seq)
    logits, cache = jax.jit(
        lambda p, t, c: T.prefill(p, t, c, cfg))(params, prompt, cache)

    def decode_only(p, cache, logits):
        def gen(carry, _):
            cache, logits = carry
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits, cache = T.decode_step(p, tok, cache, cfg)
            return (cache, logits), tok

        _, toks = jax.lax.scan(gen, (cache, logits), None,
                               length=args.steps)
        return jnp.moveaxis(toks, 0, 1)

    dec = jax.jit(decode_only)
    np.asarray(dec(params, cache, logits))  # warm + sync
    best = float("inf")
    for _ in range(args.iters):
        t1 = time.perf_counter()
        np.asarray(dec(params, cache, logits))
        best = min(best, time.perf_counter() - t1)
    result["static_batch_decode_tok_s"] = round(B * args.steps / best, 2)
    result["vs_static_batch"] = round(
        result["value"] / result["static_batch_decode_tok_s"], 3)

    print(f"openloop S={args.slots} {result['value']:9.1f} tok/s | "
          f"TTFT p50 {ttft['p50']}s p99 {ttft['p99']}s | "
          f"occupancy {result['mean_slot_occupancy']:.2f} | "
          f"efficiency {result['overlap_efficiency']} | "
          f"syncs/tick {result['host_syncs_per_tick']}")
    if ab is not None:
        print(f"A/B      steady decode {ab['decode_tok_s_overlap']:9.1f} "
              f"tok/s overlapped vs {ab['decode_tok_s_sync']:9.1f} sync "
              f"-> {ab['overlap_decode_speedup']}x")
    if tab is not None:
        print(f"tracing  {tab['decode_tok_s_tracing']:9.1f} tok/s traced "
              f"vs {tab['decode_tok_s_notracing']:9.1f} untraced -> "
              f"{tab['tracing_overhead_ratio']}x per-tick")
        print(f"spans    {tab['decode_tok_s_spans']:9.1f} tok/s -> "
              f"{tab['span_tracing_overhead_ratio']}x per-tick "
              f"(tail sampling: {tab['span_traces_retained']} retained "
              f"/ {tab['span_traces_dropped']} dropped)")
    if sab is not None:
        print(f"spec     K={sab['spec_k']} ({sab['spec_draft']}) "
              f"repetitive {sab['spec_decode_tok_s_repetitive']:9.1f} "
              f"vs {sab['plain_decode_tok_s_repetitive']:9.1f} tok/s -> "
              f"{sab['spec_repetitive_speedup']}x (acceptance "
              f"{sab['spec_acceptance_rate']}, "
              f"{sab['spec_tokens_per_tick_mean']:.2f} tok/tick) | "
              f"adversarial {sab['spec_adversarial_ratio']}x")
    if smab is not None:
        print(f"sampled  {smab['decode_tok_s_sampled']:9.1f} tok/s vs "
              f"{smab['decode_tok_s_greedy']:9.1f} greedy -> "
              f"{smab['sampled_vs_greedy_ratio']}x "
              f"({smab['sampling_recompiles']} recompiles)")
    if stab is not None:
        print(f"stream   TTFB p50 {stab['stream_ttfb_ms_p50']}ms "
              f"p99 {stab['stream_ttfb_ms_p99']}ms vs non-stream TTFT "
              f"p50 {stab['nonstream_ttft_ms_p50']}ms "
              f"p99 {stab['nonstream_ttft_ms_p99']}ms")
    print(f"static   B={B} {result['static_batch_decode_tok_s']:9.1f} "
          f"tok/s (closed-loop ceiling)")
    print(json.dumps(result))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--d-ff", type=int, default=4096)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--prompt-len", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=128)
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 8, 32])
    ap.add_argument("--kv-heads", type=int, nargs="+", default=[0, 4])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--engine", action="store_true",
                    help="continuous-batching open-loop benchmark "
                         "(horovod_tpu/serving/) instead of the "
                         "static-batch sweep")
    ap.add_argument("--router", type=int, default=0, metavar="N",
                    help="open-loop benchmark through the replicated "
                         "front tier: N replica processes behind the "
                         "join-shortest-queue router "
                         "(docs/serving.md 'Front tier')")
    ap.add_argument("--rollout", action="store_true",
                    help="zero-downtime reconfiguration benchmark: a "
                         "3-replica fleet (or --router N) serves a "
                         "continuous load while a candidate config is "
                         "canaried and promoted replica-by-replica; "
                         "reports canary/incumbent scores, per-step "
                         "durations, rollback count (claim: 0) and "
                         "rollout-attributable 5xx (claim: 0) "
                         "(docs/serving.md 'Fleet rollouts')")
    ap.add_argument("--chaos", action="store_true",
                    help="durability benchmark: the open-loop workload "
                         "with deterministic engine crashes injected "
                         "mid-decode (restart-resume on); reports "
                         "resumed-vs-restarted counts, wasted-token "
                         "ratio, and per-request oracle identity")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-scheduling benchmark: bursty bimodal "
                         "mixed-class workload served with chunked "
                         "prefill + priorities + preemption vs the "
                         "FCFS whole-prefill baseline; reports "
                         "per-class TTFT p50/p99, the interactive p99 "
                         "ratio, throughput, and oracle identity")
    ap.add_argument("--autotune", action="store_true",
                    help="autotuning A/B: tuned-then-pinned online "
                         "knobs vs static defaults on two contrasting "
                         "workloads (short-prompt interactive burst, "
                         "long-prompt batch stream); reports tuned "
                         "knobs, objective trajectory, per-class "
                         "TTFT, and the zero-recompile guard")
    ap.add_argument("--slots", type=int, default=8,
                    help="engine mode: cache slots S")
    ap.add_argument("--max-prefills-per-tick", type=int, default=2,
                    help="engine mode: prefill/decode interleave K")
    ap.add_argument("--arrival-rate", type=float, default=4.0,
                    help="engine mode: Poisson arrivals per second")
    ap.add_argument("--n-requests", type=int, default=32)
    ap.add_argument("--tp", type=int, default=0, metavar="N",
                    help="tensor-parallel A/B: a tp=N GSPMD-sharded "
                         "engine vs the tp=1 single-device engine on "
                         "the identical workload — steady-state "
                         "decode tok/s, full-sequence token-identity "
                         "check, zero-recompile guard (docs/serving.md "
                         "'Tensor-parallel replicas').  CPU hosts get "
                         "N forced host devices automatically")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="speculative A/B: draft tokens per tick "
                         "(verify window is K+1 wide)")
    ap.add_argument("--spec-draft", default="ngram",
                    choices=["ngram", "model"],
                    help="speculative A/B draft source: n-gram "
                         "prompt lookup (no second model) or a "
                         "half-depth trained draft model")
    ap.add_argument("--overlap-only", action="store_true",
                    help="engine mode: skip the synchronous-baseline "
                         "run (no overlap A/B, no tracing A/B)")
    ap.add_argument("--stream", action="store_true",
                    help="engine mode: add the SSE streaming leg — "
                         "client-observed TTFB p50/p99 (first token "
                         "event on the wire) vs non-streamed TTFT on "
                         "the same closed-loop HTTP workload")
    ap.add_argument("--trace", default="",
                    help="engine mode: record the open-loop run as a "
                         "Perfetto/Chrome trace at this path (plus "
                         "<path>.jsonl request log) and report the "
                         "path in the JSON line")
    args = ap.parse_args()

    if args.tp > 1:
        # Devices must exist before the backend spins up; harmless
        # when the flag (or a real accelerator topology) is already
        # there.  This runs before the first jax.devices() call below.
        from horovod_tpu.serving.sharding import ensure_devices

        ensure_devices(args.tp)

    from horovod_tpu.models import transformer as T

    dtype = jnp.bfloat16
    if args.router or args.rollout:
        # This process only routes and supervises; the replica
        # processes it spawns own the chips (one process per chip), so
        # it must never open one itself.
        from horovod_tpu.runner import chips

        on_cpu = not chips.usable_chips(os.environ)
        jax.config.update("jax_platforms", "cpu")
        kind = "cpu" if on_cpu else "tpu (replica-owned)"
    else:
        on_cpu = jax.devices()[0].platform == "cpu"
        kind = jax.devices()[0].device_kind
    args.chip_kind = kind
    if on_cpu:
        # A TPU-sized run can't finish on CPU inside the harness budget
        # — clamp to a smoke configuration (disclosed on stderr).  float32,
        # not bf16: CPU emulates bf16 matmuls several-fold slower, and
        # the smoke config should measure the serving path, not the
        # emulation.
        dtype = jnp.float32
        smoke = {"d_model": 128, "n_layers": 2, "n_heads": 4, "d_ff": 256,
                 "vocab": 512, "prompt_len": 32, "steps": 16,
                 "n_requests": 16}
        clamped = {k: v for k, v in smoke.items() if getattr(args, k) > v}
        for k, v in clamped.items():
            setattr(args, k, v)
        args.batches = [b for b in args.batches if b <= 8] or [1]
        if (args.engine or args.router or args.chaos) \
                and args.arrival_rate < 64.0:
            # Saturate arrivals on the smoke config: at TPU-shaped
            # arrival rates the CPU run is dominated by waiting for the
            # Poisson clock and the overlap A/B would measure sleep().
            clamped["arrival_rate"] = args.arrival_rate = 64.0
        if clamped:
            print(f"running on CPU; clamped {clamped} to a smoke "
                  "configuration", file=sys.stderr)

    print(f"chip={kind} d{args.d_model} L{args.n_layers} "
          f"h{args.n_heads} d_ff{args.d_ff} vocab{args.vocab} "
          f"{jnp.dtype(dtype).name}")

    if args.tp:
        _tp_mode(args, T)
        return

    if args.slo:
        _slo_mode(args, T)
        return

    if args.autotune:
        _autotune_mode(args, T)
        return

    if args.router or args.rollout:
        kv = args.kv_heads[-1] if args.kv_heads else 0
        cfg = T.TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
            max_seq=args.prompt_len + args.steps,
            n_kv_heads=kv, attention_impl="reference", dtype=dtype,
        )
        if args.rollout:
            _rollout_mode(args, cfg)
        else:
            _router_mode(args, cfg)
        return

    if args.engine or args.chaos:
        kv = args.kv_heads[-1] if args.kv_heads else 0
        cfg = T.TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
            max_seq=args.prompt_len + args.steps,
            n_kv_heads=kv, attention_impl="reference", dtype=dtype,
        )
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        if args.chaos:
            _chaos_mode(args, T, cfg, params)
        else:
            _engine_mode(args, T, cfg, params)
        return

    for kv in args.kv_heads:
        cfg = T.TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model,
            n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
            max_seq=args.prompt_len + args.steps,
            n_kv_heads=kv, attention_impl="reference", dtype=dtype,
        )
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        kv_tag = f"kv{kv or args.n_heads}"

        for B in args.batches:
            prompt = jax.random.randint(
                jax.random.PRNGKey(1), (B, args.prompt_len), 0,
                cfg.vocab_size, jnp.int32)

            # ---- prefill latency --------------------------------------
            pre = jax.jit(lambda p, t: T.prefill(
                p, t, T.init_cache(cfg, B, cfg.max_seq), cfg))
            logits, cache = pre(params, prompt)
            float(jnp.sum(logits))  # warm + sync
            best_pre = float("inf")
            for _ in range(args.iters):
                t0 = time.perf_counter()
                logits, cache = pre(params, prompt)
                float(jnp.sum(logits))
                best_pre = min(best_pre, time.perf_counter() - t0)

            # ---- decode throughput (one scanned call) -----------------
            # Time the decode scan DIRECTLY from a prefilled cache: the
            # old best-of-N(total) - best-of-N(prefill) subtraction can
            # go small or negative under chip variance and overstate
            # tok/s (ADVICE r5).
            def decode_only(p, cache, logits):
                def gen(carry, _):
                    cache, logits = carry
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    logits, cache = T.decode_step(p, tok, cache, cfg)
                    return (cache, logits), tok

                _, toks = jax.lax.scan(
                    gen, (cache, logits), None, length=args.steps)
                return jnp.moveaxis(toks, 0, 1)

            dec = jax.jit(decode_only)
            np.asarray(dec(params, cache, logits))  # warm + sync
            best_dec = float("inf")
            for _ in range(args.iters):
                t0 = time.perf_counter()
                toks = dec(params, cache, logits)
                np.asarray(toks)
                best_dec = min(best_dec, time.perf_counter() - t0)

            # Raw combined prefill+decode (the end-to-end serving call),
            # reported alongside so the decomposition is auditable.
            e2e = jax.jit(lambda p, t: T.sample_decode(
                p, t, args.steps, cfg, rng=jax.random.PRNGKey(2),
                temperature=0.0))
            np.asarray(e2e(params, prompt))  # warm + sync
            best_e2e = float("inf")
            for _ in range(args.iters):
                t0 = time.perf_counter()
                np.asarray(e2e(params, prompt))
                best_e2e = min(best_e2e, time.perf_counter() - t0)

            tps = B * args.steps / best_dec
            per_tok_ms = best_dec / args.steps * 1e3
            print(f"{kv_tag} B={B:<3} prefill({args.prompt_len}) "
                  f"{best_pre * 1e3:7.1f}ms | decode {tps:8.0f} tok/s "
                  f"({per_tok_ms:.2f} ms/token-step) | "
                  f"combined {best_e2e * 1e3:7.1f}ms")


if __name__ == "__main__":
    main()
