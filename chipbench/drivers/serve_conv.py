"""A served model of gated short convolutions between attention layers
(``kind: serve_conv``): a conv layer keeps no keys or values but a
per-slot STATE, its last ``conv_L_cache - 1`` gated inputs, beside the
page pool; attention heads of 64 stored two to a 128-lane row; a
leading dense stack together with a layer pattern; a sigmoid router
that chooses under an expert bias; a head tied to the embedding.
``drivers/serve.py``'s run — the same engine, server, load client,
warm-up, window and sample — wired to this model's configuration,
seeded weights (``weights_conv``), reference (``reference_conv``) and
costs (``costs_conv``).

Only what names the model is restated here (``build_cfg``,
``check_served``, the costs and the counters' line in ``run``); the
rest is ``serve.py``'s and ``serve_patterned.py``'s own functions,
imported.  What ``run`` adds to the observations: the paged decode's
needed bytes over the traced window (the attention layers alone: a conv
layer reads no cache) and the expert layer's needed FLOPs and bytes
from the program's own counters.

``--control 1`` runs TWO controls on the same sample
(``check.control_modes``): the reference with fp8 operands, and the
reference with every conv layer's past taps zeroed — a program that
lost a request's state at a chunk or tick boundary would serve the
second's tokens, so the limit has to lie under both."""

from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

from chipbench import (costs_conv, harness, peaks, reference,
                       reference_conv, weights_conv)
from chipbench.drivers.serve import (_p, drive, observe, pick_sample,
                                     warm)
from chipbench.drivers.serve_patterned import _grown
from chipbench.harness import say
from chipbench.readers import stats_diff

_KINDS = {"conv": "conv", "full_attention": "full"}


def build_cfg(dims: dict):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    types = [_KINDS[k] for k in dims["layer_types"]]
    nd = dims["num_dense_layers"]
    if len(types) != dims["num_hidden_layers"] or dims["conv_bias"] \
            or dims["rope_parameters"]["rope_type"] != "default":
        raise ValueError("layer_types must name every layer; a conv bias "
                         "and a scaled rope are not this model's")
    # the pattern runs from layer 0 through the dense layers and the
    # rest alike; the rest is a whole number of its periods
    period = next(n for n in range(1, len(types) + 1)
                  if (len(types) - nd) % n == 0
                  and all(types[l] == types[l % n]
                          for l in range(len(types))))
    # a program that cannot state this configuration (no conv layer, no
    # tied head) fails HERE, with a TypeError, before any weight
    return T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"],
        d_head=weights_conv.head_dim(dims),
        n_layers=dims["num_hidden_layers"], n_dense_layers=nd,
        d_ff=dims["intermediate_size"],
        d_expert=dims["moe_intermediate_size"],
        n_experts=dims["num_experts"],
        n_experts_per_tok=dims["num_experts_per_tok"],
        norm_topk_prob=dims["norm_topk_prob"],
        norm_topk_eps=reference_conv.NORM_TOPK_EPS, moe_impl="dropless",
        moe_score="sigmoid", moe_score_bias=dims["use_expert_bias"],
        routed_scaling_factor=float(dims["routed_scaling_factor"]),
        qk_norm=True, norm_eps=dims["norm_eps"],
        layer_pattern=tuple(types[:period]),
        conv_kernel=dims["conv_L_cache"],
        tie_embeddings=dims["tie_word_embeddings"], kv_lane_dense=True,
        rope_theta=float(dims["rope_parameters"]["rope_theta"]),
        max_seq=dims["engine"]["max_len"],
        dtype=jnp.dtype(dims["torch_dtype"]),
        attention_impl=dims["attention_impl"])


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 dtype) -> dict:
    """``serve_patterned.check_served`` against this model's reference
    (the MEAN gap by which a served token's logit lies below the
    reference's best is judged: a router's top-k is a discontinuity);
    with ``control`` every mode of ``check.control_modes`` on the same
    sample (``fp8``: operands rounded; ``zero_taps``: the state lost)."""
    n, width = len(sample), dims["engine"]["max_len"]
    toks = np.zeros((n, width), np.int32)
    plens, nserved = [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        plens.append(r["prompt_len"])
        nserved.append(len(r["tokens"]))
    logits, served, valid = reference_conv.served_logits(
        seed, dims, dtype, toks, plens, nserved)
    gap, _ = reference.gaps_from_logits(logits, served, valid)

    def spread(g, prefix=""):
        g = g[valid]
        return {prefix + "mean_gap": float(g.mean()),
                prefix + "widest_gap": float(g.max()),
                prefix + "p99_gap": float(np.percentile(g, 99)),
                prefix + "exact_share": float(np.mean(g == 0))}

    out = {"positions": int(valid.sum()), **spread(gap)}
    for mode in dims["check"]["control_modes"] if control else ():
        kw = {"zero_taps": True} if mode == "zero_taps" else {"mode": mode}
        low, _, _ = reference_conv.served_logits(
            seed, dims, dtype, toks, plens, nserved, **kw)
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out.update(spread(cgap, mode + "_"))
    return out


def _engine_thread(s0: dict, s1: dict) -> str:
    """The engine thread's time over the window by phase, ms an
    iteration of its loop on two clocks (``/stats`` ``phase_*_seconds``
    beside ``phase_*_cpu_seconds``; `benchmarks/engine_clocks.py` prints
    the whole table): an UNTRACED run's own word on whether the host or
    the device sets the pace, and on what a slower run lost its time
    to.  A program without the keys (before PR 36) gets a line that says
    so."""
    try:
        from horovod_tpu.serving.metrics import PHASES, phase_key
    except ImportError:
        return "engine thread: this program keeps no phase clocks"

    def grown(key):
        a, b = s0.get(key), s1.get(key)
        if a is None or b is None:
            return 0.0
        return b["sum"] - a["sum"] if isinstance(b, dict) else float(b - a)

    iters = max(1, s1["engine_loop_seconds"]["count"]
                - s0["engine_loop_seconds"]["count"])
    rows = [("loop", "engine_loop_seconds", "engine_loop_cpu_seconds")] + [
        (n, phase_key(n), phase_key(n, cpu=True)) for n in PHASES]
    rows.sort(key=lambda r: -grown(r[1]))
    return (f"engine thread, ms an iteration (wall/cpu) over {iters}: "
            + ", ".join(f"{n} {1e3 * grown(w) / iters:.2f}/"
                        f"{1e3 * grown(c) / iters:.2f}"
                        for n, w, c in rows if grown(w) > 1e-3 * iters * 0.05))


def _silences(recs: list, t_open: float, t_close: float, n: int = 6) -> str:
    """The window's longest silences between two token arrivals (every
    stream falls silent when the engine's step is long), each with how
    far the nearest request's sending and the nearest request's end lie
    from its start: whether a long step goes with the turnover of a
    request or comes out of nowhere (a stall inside a tick's fetch)."""
    t = np.asarray(sorted(x for r in recs for x in r["token_t"]
                          if t_open <= x < t_close))
    if len(t) < 2:
        return "silences: too few tokens"
    sent = np.asarray(sorted(r["sent"] for r in recs
                             if r["sent"] is not None) or [np.inf])
    done = np.asarray(sorted(r["done_t"] for r in recs
                             if r.get("done_t") is not None) or [np.inf])
    gaps = np.diff(t)
    out = []
    for i in np.argsort(-gaps)[:n]:
        a = t[i]
        out.append(f"{gaps[i] * 1e3:.0f} ms at {a - t_open:.2f} s (nearest "
                   f"send {np.min(np.abs(sent - a)) * 1e3:.0f} ms, nearest "
                   f"end {np.min(np.abs(done - a)) * 1e3:.0f} ms away)")
    return "longest silences: " + "; ".join(out)


def _memory() -> str:
    """Bytes in use and the peak so far on the chip, in GB (which of the
    weights' making, the warm-up and the window set the run's peak)."""
    import jax

    st = jax.local_devices()[0].memory_stats() or {}
    return (f"in use {st.get('bytes_in_use', 0) / 1e9:.3f} GB, peak "
            f"{st.get('peak_bytes_in_use', 0) / 1e9:.3f} GB")


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        control: bool, t0: float, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving

    marks = {"import": time.monotonic() - t0}
    dims, traffic = cell["dims"], cell["traffic_params"]
    eng = dims["engine"]
    cfg = build_cfg(dims)
    say(f"compile cache: {harness.place_caches()}")
    dtype = jnp.dtype(dims["torch_dtype"])
    params = jax.block_until_ready(
        weights_conv.make_params(seed, dims, dtype))
    marks["weights"] = time.monotonic() - t0
    say(f"device memory once the weights are made: {_memory()}")
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**eng))
    gen = importlib.import_module(
        f"chipbench.generators.{traffic['generator']}")
    plan = gen.plan(traffic, seconds, seed,
                    {"vocab_size": dims["vocab_size"],
                     "max_len": eng["max_len"]})
    warm(engine, plan, eng)
    marks["warm"] = time.monotonic() - t0
    say(f"device memory once every shape is warm: {_memory()}")
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
    ticks = _grown(stats0, stats1, "decode_ticks")
    if trace and tr_obs.get("trace_t0") is not None and ticks:
        a, b = tr_obs["trace_t0"], tr_obs["trace_t1"]
        ctx = [r["prompt_len"] + j + 1 for r in recs
               for j, t in enumerate(r["token_t"]) if j > 0 and a <= t < b]
        obs["paged_need_bytes"] = costs_conv.paged_decode_bytes(
            dims, ctx, kv_bytes=jnp.dtype(
                final.get("kv_dtype", dims["torch_dtype"])).itemsize)
        obs["trace_decode_tokens"] = len(ctx)
        share = tr_obs["trace_ticks"] / ticks
        rows = share * _grown(stats0, stats1, "moe_rows_total")
        touched = share * _grown(stats0, stats1,
                                 "moe_experts_touched_total")
        obs["moe_need_bytes"] = costs_conv.moe_expert_bytes(
            dims, touched, rows, weight_bytes=dtype.itemsize)
        obs["moe_need_flops"] = costs_conv.moe_expert_flops(dims, rows)
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
        f"ticks {obs.get('client_ticks')} (engine counted {ticks:.0f}), "
        f"standing {len(plan['standing'])}, finished {n_finished}, work "
        f"tokens {obs['work_tokens']:.0f}, generator late p99 "
        f"{np.percentile(obs['late_ms'], 99) if obs['late_ms'] else 0:.2f}"
        f" ms")
    if obs["gaps_ms"]:
        say("token-gap ladder (ms): " + ", ".join(
            f"p{q} {_p(obs['gaps_ms'], q):.2f}"
            for q in (50, 75, 90, 95, 99))
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
    # what a run's work turns on in a closed loop of ~68 requests a
    # window (a prompt is ~0.75 % of it): the steps by kind, and which
    # requests were sent near the window's close
    kinds = {k: (_grown(stats0, stats1, f"decode_ticks_{k}"), stats_diff.read(
        obs, {"num": [f"engine_step_seconds_{k}"], "scale": 1000.0,
              "den": [f"decode_ticks_{k}"]})) for k in ("plain", "chunk")}
    say("engine steps: " + ", ".join(
        f"{k} {n:.0f} of {ms or 0:.2f} ms" for k, (n, ms) in kinds.items())
        + "; requests sent in the window's last 2 s at "
        + str(sorted(round(r["due"] - t_open, 2) for r in due
                     if r["due"] >= t_close - 2.0)) + " s, first 2 s at "
        + str(sorted(round(r["due"] - t_open, 2) for r in due
                     if r["due"] < t_open + 2.0)) + " s")
    say(_engine_thread(stats0, stats1))
    say(_silences(recs, t_open, t_close))
    if ticks:
        n_moe = dims["num_hidden_layers"] - dims["num_dense_layers"]
        say(f"experts: rows a tick "
            f"{_grown(stats0, stats1, 'moe_rows_total') / ticks:.1f} over "
            f"{n_moe} layers, experts touched a tick and layer "
            f"{_grown(stats0, stats1, 'moe_experts_touched_total') / ticks / n_moe:.1f}"
            f" of {dims['num_experts']}; conv state "
            f"{final.get('conv_state_bytes_per_slot')} B a slot, "
            f"{final.get('conv_state_slots_live')} slots live; KV "
            f"{final.get('kv_bytes_per_token')} B a token")
    say(f"engine: paged_kernel_engaged {final.get('paged_kernel_engaged')} "
        f"kv_dtype {final.get('kv_dtype')} pages high water "
        f"{final.get('kv_pages_high_water')} of {eng['n_pages']} restarts "
        f"{final['engine_restarts']} preemptions "
        f"{final.get('preemptions')} compilations inside the window "
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
    correct = False
    limit = float(dims["check"]["served_mean_gap_limit"])
    if sample:
        chk = check_served(sample, seed, dims, control=control, dtype=dtype)
        correct = chk["mean_gap"] <= limit
        say(f"correct: mean gap of a served token's logit below the "
            f"reference's best {chk['mean_gap']:.6f} (limit {limit}) over "
            f"{chk['positions']} served tokens of {len(sample)} requests "
            f"(widest {chk['widest_gap']:.4f}, p99 {chk['p99_gap']:.4f}, "
            f"{chk['exact_share']:.3f} of them the reference's own pick)"
            + "".join(
                f"; CONTROL {m} mean gap {chk[m + '_mean_gap']:.6f} (widest "
                f"{chk[m + '_widest_gap']:.4f}, p99 {chk[m + '_p99_gap']:.4f}"
                f", own pick {chk[m + '_exact_share']:.3f})"
                for m in (dims["check"]["control_modes"] if control else ()))
            + f"; reference took {time.monotonic() - t_chk:.1f} s")
    else:
        say("correct: no request finished, nothing to compare -> false")
    return {"obs": obs, "setup_s": setup_s, "correct": correct,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": peak}
