"""A served model with more than one kind of layer and sparse experts
(``kind: serve_patterned``): ``drivers/serve.py``'s run — the same
engine, server, load client, warm-up, window and sample — wired to this
model's configuration, seeded weights (``weights_patterned``), reference
(``reference_patterned``) and costs (``costs_patterned``).

Only what names the dense model is restated here (``build_cfg``,
``check_served``, ``run``); the rest is ``serve.py``'s own functions,
imported.  What ``run`` adds to the observations: the expert layer's
needed FLOPs and bytes over the traced window from the program's own
counters (``moe_rows_total``, ``moe_experts_touched_total``), and the
paged decode's needed bytes with the window layers counted at their
window."""

from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

from chipbench import (costs_patterned, harness, peaks, reference,
                       reference_patterned, weights_patterned)
from chipbench.drivers.serve import (_p, drive, observe, pick_sample,
                                     warm)
from chipbench.harness import say


def build_cfg(dims: dict):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    types = [kinds[k] for k in dims["layer_types"]]
    if len(types) != dims["num_hidden_layers"] or set(
            dims["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("layer_types must name every layer, and every "
                         "layer's MLP must be sparse")
    period = next(n for n in range(1, len(types) + 1)
                  if len(types) % n == 0
                  and types == types[:n] * (len(types) // n))
    rope = dims["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or slide["rope_type"] != "default":
        raise ValueError("expected YaRN on the full layers and the plain "
                         "rope on the sliding ones")
    return T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"], d_head=dims["head_dim"],
        n_layers=dims["num_hidden_layers"],
        d_ff=dims["moe_intermediate_size"],
        d_expert=dims["moe_intermediate_size"],
        n_experts=dims["num_experts"],
        n_experts_per_tok=dims["num_experts_per_tok"],
        norm_topk_prob=dims["norm_topk_prob"], moe_impl="dropless",
        qk_norm=True, norm_eps=dims["rms_norm_eps"],
        layer_pattern=tuple(types[:period]), window=dims["sliding_window"],
        rope_theta=float(full["rope_theta"]),
        rope_theta_sliding=float(slide["rope_theta"]),
        rope_yarn=(float(full["factor"]),
                   float(full["original_max_position_embeddings"]),
                   float(full["beta_fast"]), float(full["beta_slow"]),
                   float(full["attention_factor"])),
        max_seq=dims["engine"]["max_len"],
        dtype=jnp.dtype(dims["torch_dtype"]),
        attention_impl=dims["attention_impl"])


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 pad_to: int, dtype) -> dict:
    """``serve.check_served`` against this model's reference: the gap by
    which a served token's logit lies below the reference's best, over
    the sample — its MEAN is what is judged, its widest and its 99th
    percentile are printed beside it; with ``control`` also those
    numbers for the tokens the lower-precision model puts first.

    Why the mean: a router's top-k is a discontinuity.  Where two
    experts' scores tie within bfloat16's rounding, the program and the
    float32 reference pick different experts and one token's logits
    move by tenths — in ~9 % of positions at these widths, and as much
    when the REFERENCE itself is computed in bfloat16.  So the widest
    gap of a sound run (0.45-0.65) does not separate it from the fp8
    control's (0.69-0.84), while the mean over thousands of tokens does
    by a factor of seven, request by request (PERF.md, section 2)."""
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
    logits, served, valid = reference_patterned.served_logits(
        seed, dims, dtype, toks, plens, nserved, q_block=q_block)
    gap, _ = reference.gaps_from_logits(logits, served, valid)
    def spread(g, prefix=""):
        g = g[valid]
        return {prefix + "mean_gap": float(g.mean()),
                prefix + "widest_gap": float(g.max()),
                prefix + "p99_gap": float(np.percentile(g, 99)),
                prefix + "exact_share": float(np.mean(g == 0))}

    out = {"positions": int(valid.sum()), **spread(gap)}
    if control:
        mode = dims["check"]["control_mode"]
        low, _, _ = reference_patterned.served_logits(
            seed, dims, dtype, toks, plens, nserved, mode=mode,
            q_block=q_block)
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out.update(spread(cgap, "control_"), control_mode=mode)
    return out


def _grown(s0: dict, s1: dict, key: str) -> float:
    return float(s1.get(key) or 0) - float(s0.get(key) or 0)


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        control: bool, t0: float, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving

    marks = {"import": time.monotonic() - t0}
    dims, traffic = cell["dims"], cell["traffic_params"]
    eng = dims["engine"]
    # first of all: a program that cannot state this configuration (no
    # layer pattern, no top-k experts) fails HERE, before any weight
    cfg = build_cfg(dims)
    say(f"compile cache: {harness.place_caches()}")
    dtype = jnp.dtype(dims["torch_dtype"])
    params = jax.block_until_ready(
        weights_patterned.make_params(seed, dims, dtype))
    marks["weights"] = time.monotonic() - t0
    engine = serving.InferenceEngine(
        params, cfg, serving.EngineConfig(**eng))
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
    ticks = _grown(stats0, stats1, "decode_ticks")
    if trace and tr_obs.get("trace_t0") is not None and ticks:
        a, b = tr_obs["trace_t0"], tr_obs["trace_t1"]
        ctx = [r["prompt_len"] + j + 1 for r in recs
               for j, t in enumerate(r["token_t"]) if j > 0 and a <= t < b]
        obs["paged_need_bytes"] = costs_patterned.windowed_decode_bytes(
            dims, ctx, kv_bytes=jnp.dtype(
                final.get("kv_dtype", dims["torch_dtype"])).itemsize)
        obs["trace_decode_tokens"] = len(ctx)
        # the expert layer's need over the traced ticks: the program's
        # counts of the window (rows, experts touched: a tick's mean),
        # times the ticks the trace held
        share = tr_obs["trace_ticks"] / ticks
        rows = share * _grown(stats0, stats1, "moe_rows_total")
        touched = share * _grown(stats0, stats1,
                                 "moe_experts_touched_total")
        obs["moe_need_bytes"] = costs_patterned.moe_expert_bytes(
            dims, touched, rows, weight_bytes=dtype.itemsize)
        obs["moe_need_flops"] = costs_patterned.moe_expert_flops(dims, rows)
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
    if ticks:
        rows = _grown(stats0, stats1, "moe_rows_total")
        say(f"experts: rows a tick {rows / ticks:.1f} over "
            f"{dims['num_hidden_layers']} layers, experts touched a tick "
            f"and layer "
            f"{_grown(stats0, stats1, 'moe_experts_touched_total') / ticks / dims['num_hidden_layers']:.1f}"
            f" of {dims['num_experts']}; window pages a slot at most "
            f"{final.get('kv_window_pages_per_slot_max')} (bound "
            f"{final.get('kv_window_pages_per_slot_bound')})")
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
    correct = False
    limit = float(dims["check"]["served_mean_gap_limit"])
    if sample:
        chk = check_served(sample, seed, dims, control=control,
                           pad_to=eng["max_len"], dtype=dtype)
        correct = chk["mean_gap"] <= limit
        say(f"correct: mean gap of a served token's logit below the "
            f"reference's best {chk['mean_gap']:.6f} (limit {limit}) over "
            f"{chk['positions']} served tokens of {len(sample)} requests "
            f"(widest {chk['widest_gap']:.4f}, p99 {chk['p99_gap']:.4f}, "
            f"{chk['exact_share']:.3f} of them the reference's own pick)"
            + (f"; CONTROL {chk['control_mode']} mean gap "
               f"{chk['control_mean_gap']:.6f} (widest "
               f"{chk['control_widest_gap']:.4f}, p99 "
               f"{chk['control_p99_gap']:.4f}, own pick "
               f"{chk['control_exact_share']:.3f})" if control else "")
            + f"; reference took {time.monotonic() - t_chk:.1f} s")
    else:
        say("correct: no request finished, nothing to compare -> false")
    return {"obs": obs, "setup_s": setup_s, "correct": correct,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": peak}
