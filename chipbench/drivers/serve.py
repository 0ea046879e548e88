"""A served model: ``serving.InferenceEngine`` behind
``serving.ServingServer``, loaded over HTTP by ``client.LoadClient``.

Set-up (counted in ``setup_s``): weights on the device from the seed in one
jitted call; the engine; a warm-up of exactly the shapes the cell's
traffic can reach; the standing population.  The window opens when every
standing request has its first token.  After the window closes the
streams are hung up, the engine is freed, and the reference decides
``correct`` on a seeded sample of the requests that finished."""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import time
import urllib.request

import numpy as np

from chipbench import costs, harness, peaks, reference, weights, xplane
from chipbench.client import LoadClient
from chipbench.harness import say


def _pow2_at_least(n: int, floor: int) -> int:
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


def warm_lengths(plan: dict, eng: dict) -> tuple:
    """The prompt lengths that reach every executable this plan can: one
    per whole-prompt bucket (powers of two from ``min_prefill_bucket``,
    prompts up to the chunk budget), and the longest chunked prompt (its
    chunks walk every landed-page bucket below it)."""
    chunk = eng.get("prefill_chunk_tokens", 0)
    lens = [len(r["tokens"]) for r in plan["standing"] + plan["arrivals"]]
    lens += [len(r["tokens"]) for c in plan["chains"].values() for r in c]
    short = sorted({_pow2_at_least(n, eng.get("min_prefill_bucket", 8))
                    for n in lens if not chunk or n <= chunk})
    long_ = max((n for n in lens if chunk and n > chunk), default=None)
    return [min(b, eng["max_len"] - 3) for b in short], long_


def warm(engine, plan: dict, eng: dict) -> None:
    """Drive the engine synchronously through its public ``submit`` /
    ``step`` until every shape the cell reaches is compiled: each
    whole-prompt bucket at every admission width, the chunked path, the
    decode tick and the greedy first-token picker.  (``warmup()`` also
    sweeps the sampled picker and every width for chunked prompts, which
    this traffic never reaches.)"""
    short, long_ = warm_lengths(plan, eng)
    kmax = min(eng.get("max_prefills_per_tick", 2), eng["n_slots"])
    groups = [[[0] * n] * k for n in short for k in range(1, kmax + 1)]
    if long_ is not None:
        groups.append([[0] * long_])
    for group in groups:
        futs = [engine.submit(p, max_new_tokens=2) for p in group]
        while not all(f.done() for f in futs):
            engine.step()


def _get_stats(base: str) -> dict:
    with urllib.request.urlopen(base + "/stats", timeout=30) as r:
        return json.loads(r.read())


def _wait(pred, timeout: float, poll: float = 0.01) -> bool:
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if pred():
            return True
        time.sleep(poll)
    return pred()


def distinct_ticks(times: list, tol_s: float) -> int:
    """Token arrivals that fall within ``tol_s`` of each other came from
    one tick: count the clusters."""
    n, last = 0, None
    for t in sorted(times):
        if last is None or t - last > tol_s:
            n += 1
        last = t
    return n


def observe(recs: list, t_open: float, t_close: float) -> dict:
    """Client-side series of one window (pure arithmetic on the records,
    so the tests can feed it by hand)."""
    due = [r for r in recs if r["counts_ttft"] and r["due"] is not None
           and t_open <= r["due"] < t_close]
    firsts = [r for r in due if r["token_t"]]
    gaps, gap_end = [], []
    work = 0.0
    for r in recs:
        tt = r["token_t"]
        for a, b in zip(tt, tt[1:]):
            if t_open <= b < t_close:
                gaps.append((b - a) * 1e3)
                gap_end.append(b)
        work += sum(1 for t in tt if t_open <= t < t_close)
        if tt and r["sent"] is not None and tt[0] > r["sent"]:
            # a prompt's tokens are work done between its sending and its
            # first token; the window gets the part of that span it covers
            # (all at the first token would move the rate by 2 % a prompt)
            inside = min(tt[0], t_close) - max(r["sent"], t_open)
            work += r["prompt_len"] * max(inside, 0.0) / (tt[0] - r["sent"])
    obs = {
        "window_s": t_close - t_open,
        "n_due": len(due), "n_first": len(firsts),
        "ttft_ms": [(r["token_t"][0] - r["due"]) * 1e3 for r in firsts],
        "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in due
                    if r["sent"] is not None],
        "gaps_ms": gaps, "work_tokens": work,
    }
    arrivals = sorted(t for r in recs for t in r["token_t"]
                      if t_open <= t < t_close)
    if len(arrivals) > 1:
        i = int(np.argmax(np.diff(arrivals)))
        obs["longest_silence"] = (arrivals[i + 1] - arrivals[i],
                                  arrivals[i] - t_open)
    if gaps:
        tol = 0.25e-3 * float(np.median(gaps))  # a quarter of a usual tick
        obs["client_ticks"] = distinct_ticks(gap_end, tol)
        for q in (90, 95):
            cut = float(np.percentile(gaps, q))
            obs[f"ticks_beyond_p{q}"] = distinct_ticks(
                [t for g, t in zip(gaps, gap_end) if g > cut], tol)
    over, per_k = [], []
    for r in firsts:
        b = r["breakdown"]
        if not b or b.get("queue_wait_s") is None \
                or b.get("prefill_s") is None:
            continue
        ttft = (r["token_t"][0] - r["due"]) * 1e3
        over.append(ttft - 1e3 * (b["queue_wait_s"] + b["prefill_s"]))
        per_k.append(1e3 * b["prefill_s"] / (r["prompt_len"] / 1e3))
    obs["queue_wait_ms"] = [1e3 * r["breakdown"]["queue_wait_s"]
                            for r in firsts if r["breakdown"]
                            and r["breakdown"].get("queue_wait_s") is not None]
    obs["http_overhead_ms"], obs["prefill_ms_per_ktok"] = over, per_k
    return obs


def pick_sample(recs: list, seed: int, n: int, max_len: int) -> list:
    """A seeded sample of the requests that finished, the one with the
    most served tokens always in it."""
    done = [r for r in recs if r["tokens"] and r["error"] is None
            and r["finish"] != "cancelled"
            and r["prompt_len"] + len(r["tokens"]) <= max_len]
    if not done:
        return []
    done.sort(key=lambda r: r["id"])
    longest = max(done, key=lambda r: len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 7])
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in take]


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 pad_to: int, dtype) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; with ``control`` also the same
    number for the tokens the lower-precision model puts first."""
    n = len(sample)
    toks = np.zeros((n, pad_to), np.int32)
    plens, nserved = [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        plens.append(r["prompt_len"])
        nserved.append(len(r["tokens"]))
    q_block = next(b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                   if pad_to % b == 0)
    logits, served, valid = reference.served_logits(
        seed, dims, dtype, toks, plens, nserved, q_block=q_block)
    gap, margin = reference.gaps_from_logits(logits, served, valid)
    out = {"positions": int(valid.sum()),
           "widest_gap": float(np.nanmax(gap)),
           "exact_share": float(np.nanmean(np.where(valid, gap == 0, np.nan)))}
    if control:
        mode = dims["check"]["control_mode"]
        low, _, _ = reference.served_logits(
            seed, dims, dtype, toks, plens, nserved, mode=mode,
            q_block=q_block)
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out["control_widest_gap"] = float(np.nanmax(cgap))
        out["control_mode"] = mode
    return out


def build_cfg(dims: dict):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"],
        n_layers=dims["num_hidden_layers"], d_ff=dims["intermediate_size"],
        max_seq=dims["engine"]["max_len"], rope_theta=dims["rope_theta"],
        dtype=jnp.dtype(dims["torch_dtype"]),
        attention_impl=dims["attention_impl"])


def drive(srv, plan: dict, seconds: float, traffic: dict, *, t0: float,
          marks: dict, trace_cell=None, watch=None) -> dict:
    """One window against a started server: bring the standing population
    up, open the window, offer the plan's load for ``seconds``, wait the
    grace for first tokens, hang up.  Returns the client (its records),
    the window's ends and the program's /stats at both."""
    host, port = srv.address
    base = f"http://{host}:{port}"
    client = LoadClient(host, port, plan["chains"])
    client.start()
    try:
        for r in plan["standing"]:
            client.submit(r)
        standing_ids = [r["id"] for r in plan["standing"]]

        def standing_up():
            return all(client.records[i]["token_t"]
                       or client.records[i]["done_t"] is not None
                       for i in standing_ids)

        if not _wait(standing_up, float(traffic.get("standing_wait_s", 240))):
            raise RuntimeError("the standing population never came up")
        marks["standing"] = time.monotonic() - t0
        t_open = time.monotonic() + 0.25
        for r in plan["arrivals"]:
            client.submit(r, t_open + r["due_s"])
        time.sleep(max(0.0, t_open - time.monotonic()))
        stats0 = _get_stats(base)
        if watch is not None:
            watch.arm(True)
        t_close = t_open + seconds
        tr_obs = {}
        if trace_cell is not None:
            tr_obs = _trace_part(base, traffic, t_open, seconds, trace_cell)
        time.sleep(max(0.0, t_close - time.monotonic()))
        stats1 = _get_stats(base)
        if watch is not None:
            watch.arm(False)
        client.close_chains()

        def firsts_in():
            return all(r["token_t"] or r["done_t"] is not None
                       for r in list(client.records.values())
                       if r["counts_ttft"] and r["due"] is not None
                       and r["due"] < t_close)

        _wait(firsts_in, float(traffic.get("first_token_grace_s", 5)))
    finally:
        client.stop()
    return {"client": client, "t_open": t_open, "t_close": t_close,
            "stats0": stats0, "stats1": stats1, "trace": tr_obs}


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        control: bool, t0: float, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving

    marks = {"import": time.monotonic() - t0}
    dims, traffic = cell["dims"], cell["traffic_params"]
    eng = dims["engine"]
    say(f"compile cache: {harness.place_caches()}")
    dtype = jnp.dtype(dims["torch_dtype"])
    params = jax.block_until_ready(weights.make_params(seed, dims, dtype))
    marks["weights"] = time.monotonic() - t0
    engine = serving.InferenceEngine(
        params, build_cfg(dims), serving.EngineConfig(**eng))
    gen = importlib.import_module(
        f"chipbench.generators.{traffic['generator']}")
    plan = gen.plan(traffic, seconds, seed,
                    {"vocab_size": dims["vocab_size"],
                     "max_len": eng["max_len"]})
    warm(engine, plan, eng)
    marks["warm"] = time.monotonic() - t0
    watch = harness.WindowWatch().install()
    srv = serving.ServingServer(
        engine, port=0, request_timeout=float(
            traffic.get("request_timeout_s", 600))).start()
    try:
        win = drive(srv, plan, seconds, traffic, t0=t0, marks=marks,
                    trace_cell=cell if trace else None, watch=watch)
    finally:
        peak = harness.memory_peak_bytes()
        final = engine.stats()
        srv.stop(drain_timeout=20.0)
    client, t_open, t_close = win["client"], win["t_open"], win["t_close"]
    stats0, stats1, tr_obs = win["stats0"], win["stats1"], win["trace"]
    setup_s = t_open - t0
    recs = list(client.records.values())
    obs = observe(recs, t_open, t_close)
    obs.update(stats0=stats0, stats1=stats1, dims=dims,
               peaks=peaks.peaks_for(device["kind"])
               if device["platform"] == "tpu" else None, **tr_obs)
    if trace and tr_obs.get("trace_t0") is not None:
        a, b = tr_obs["trace_t0"], tr_obs["trace_t1"]
        ctx = [r["prompt_len"] + j + 1 for r in recs
               for j, t in enumerate(r["token_t"]) if j > 0 and a <= t < b]
        kvb = jnp.dtype(final.get("kv_dtype", dims["torch_dtype"])).itemsize
        obs["paged_need_bytes"] = costs.paged_decode_bytes(
            dims, ctx, kv_bytes=kvb,
            scale_bytes=4 if final.get("kv_dtype") == "int8" else 0)
        obs["trace_decode_tokens"] = len(ctx)
    compiled = (final["decode_compilations"] + final["prefill_compilations"]
                - stats0["decode_compilations"]
                - stats0["prefill_compilations"])
    due = [r for r in recs if r["counts_ttft"] and r["due"] is not None
           and t_open <= r["due"] < t_close]
    failed = sum(1 for r in due if not r["token_t"] or r["error"])
    marks["window_open"] = setup_s
    n_finished = sum(r["tokens"] is not None for r in recs)
    say("set-up breakdown (s since process start): " + json.dumps(
        {k: round(v, 2) for k, v in marks.items()}))
    say(f"samples: requests due {obs['n_due']}, first tokens "
        f"{obs['n_first']}, token gaps {len(obs['gaps_ms'])}, distinct "
        f"ticks {obs.get('client_ticks')} (engine counted "
        f"{stats1['decode_ticks'] - stats0['decode_ticks']}), distinct ticks "
        f"beyond itl p90 {obs.get('ticks_beyond_p90')} and beyond p95 "
        f"{obs.get('ticks_beyond_p95')}, standing "
        f"{len(plan['standing'])}, finished {n_finished}, work tokens {obs['work_tokens']:.0f}, generator late p99 "
        f"{np.percentile(obs['late_ms'], 99) if obs['late_ms'] else 0:.2f} ms")
    if obs["gaps_ms"]:
        say("token-gap ladder (ms): " + ", ".join(
            f"p{q} {_p(obs['gaps_ms'], q):.2f}"
            for q in (50, 75, 80, 85, 90, 95, 99))
            + "; ttft ladder (ms): " + ", ".join(
            f"p{q} {_p(obs['ttft_ms'], q):.1f}" for q in (50, 70, 90)))
    if "longest_silence" in obs:
        say(f"longest silence between any two token arrivals "
            f"{obs['longest_silence'][0] * 1e3:.0f} ms, "
            f"{obs['longest_silence'][1]:.1f} s into the window; "
            + watch.line())
    half = t_open + seconds / 2
    h1, h2 = observe(recs, t_open, half), observe(recs, half, t_close)
    say("steadiness (first half | second half): gaps p50 "
        f"{_p(h1['gaps_ms'], 50):.1f} | {_p(h2['gaps_ms'], 50):.1f} ms, "
        f"work tokens/s {h1['work_tokens'] / (seconds / 2):.0f} | "
        f"{h2['work_tokens'] / (seconds / 2):.0f}, slots active at open "
        f"{stats0['slots_active']} at close {stats1['slots_active']}, queue "
        f"depth at close {stats1['queue_depth']}")
    say(f"engine: paged_kernel_engaged {final.get('paged_kernel_engaged')} "
        f"kv_dtype {final.get('kv_dtype')} pages high water "
        f"{final.get('kv_pages_high_water')} of {eng['n_pages']} restarts "
        f"{final['engine_restarts']} compilations inside the window "
        f"{compiled}; peak HBM {peak} bytes")
    compiled += len(watch.compiles)
    if compiled or final["engine_restarts"]:
        raise RuntimeError(
            f"{compiled} compilation(s) and {final['engine_restarts']} engine "
            "restart(s) inside the measured window: the warm-up missed a "
            "shape or the engine failed; the run measures nothing")
    if device["platform"] == "tpu" and not final.get("paged_kernel_engaged"):
        raise RuntimeError("the fused paged kernel is not in the tick")
    sample = pick_sample(recs, seed, int(dims["check"]["sample"]),
                         eng["max_len"])
    del engine, params, srv
    gc.collect()
    t_chk = time.monotonic()
    correct, limit = False, float(dims["check"]["served_gap_limit"])
    if sample:
        chk = check_served(sample, seed, dims, control=control,
                           pad_to=eng["max_len"], dtype=dtype)
        correct = chk["widest_gap"] <= limit
        say(f"correct: widest gap of a served token's logit below the "
            f"reference's best {chk['widest_gap']:.6f} (limit {limit}) over "
            f"{chk['positions']} served tokens of {len(sample)} requests, "
            f"{chk['exact_share']:.3f} of them the reference's own pick"
            + (f"; CONTROL {chk['control_mode']} widest gap "
               f"{chk['control_widest_gap']:.6f}" if control else "")
            + f"; reference took {time.monotonic() - t_chk:.1f} s")
    else:
        say("correct: no request finished, nothing to compare -> false")
    return {"obs": obs, "setup_s": setup_s, "correct": correct,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": peak}


def _p(values, q):
    return float(np.percentile(values, q)) if values else float("nan")


def _trace_part(base: str, traffic: dict, t_open: float, seconds: float,
                cell: dict) -> dict:
    """Trace a few seconds in the middle of the window."""
    import jax

    span = min(float(traffic.get("trace_seconds", 4.0)), seconds / 2)
    start = t_open + (seconds - span) / 2
    time.sleep(max(0.0, start - time.monotonic()))
    out_dir = os.path.join(harness.ROOT, ".chipbench_work", "trace",
                           cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    sa = _get_stats(base)
    a = time.monotonic()
    time.sleep(span)
    b = time.monotonic()
    sb = _get_stats(base)
    jax.profiler.stop_trace()   # collection ends here; processing is long
    summary = xplane.summarise(xplane.load(xplane.find_xplane(out_dir)))
    return {"trace": summary, "trace_t0": a, "trace_t1": b,
            "trace_ticks": sb["decode_ticks"] - sa["decode_ticks"]}
