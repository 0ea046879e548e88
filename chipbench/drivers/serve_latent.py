"""A served model with latent attention, a leading dense layer and ONE
CHIP'S SHARE of its routed experts (``kind: serve_latent``):
``drivers/serve.py``'s run — the same engine, server, load client,
warm-up, window and sample — wired to this model's configuration,
seeded weights (``weights_latent``), reference (``reference_latent``)
and costs (``costs_latent``).

Only what names the model is restated here (``build_cfg``,
``check_served``, the costs in ``run``); the rest is ``serve.py``'s and
``serve_patterned.py``'s own functions, imported.  What ``run`` adds to
the observations: the absorbed decode kernel's needed bytes and FLOPs
over the traced window (from the contexts of the tokens that arrived in
it), and the held experts' from the program's own counters
(``moe_rows_total``, ``moe_experts_touched_total``)."""

from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

from chipbench import (costs_latent, harness, peaks, reference,
                       reference_latent, weights_latent)
from chipbench.drivers.serve import (_p, drive, observe, pick_sample,
                                     warm)
from chipbench.drivers.serve_patterned import _grown
from chipbench.harness import say


def build_cfg(dims: dict):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    rs = dims["rope_scaling"]
    if rs["type"] != "yarn" or dims["topk_method"] != "none" \
            or dims["moe_layer_freq"] != 1:
        raise ValueError("expected YaRN, topk_method 'none' (no score "
                         "correction) and an expert FFN in every layer "
                         "after the leading dense ones")

    def mscale(m):  # the published yarn_get_mscale
        f = float(rs["factor"])
        return 0.1 * m * np.log(f) + 1.0 if f > 1 else 1.0

    return T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_layers=dims["num_hidden_layers"],
        n_dense_layers=dims["first_k_dense_replace"],
        d_ff=dims["intermediate_size"],
        d_expert=dims["moe_intermediate_size"],
        q_lora_rank=dims["q_lora_rank"], kv_lora_rank=dims["kv_lora_rank"],
        qk_nope_head_dim=dims["qk_nope_head_dim"],
        qk_rope_head_dim=dims["qk_rope_head_dim"],
        v_head_dim=dims["v_head_dim"],
        n_experts=dims["router_outputs"],
        n_experts_held=dims["n_routed_experts"],
        expert_offset=dims["expert_offset"],
        n_experts_per_tok=dims["num_experts_per_tok"],
        n_shared_experts=dims["n_shared_experts"],
        norm_topk_prob=dims["norm_topk_prob"],
        moe_score=dims["scoring_func"],
        routed_scaling_factor=float(dims["routed_scaling_factor"]),
        n_group=dims["n_group"], topk_group=dims["topk_group"],
        moe_impl="dropless", norm_eps=dims["rms_norm_eps"],
        rope_theta=float(dims["rope_theta"]),
        rope_yarn=(float(rs["factor"]),
                   float(rs["original_max_position_embeddings"]),
                   float(rs["beta_fast"]), float(rs["beta_slow"]),
                   float(mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])),
                   float(rs["mscale_all_dim"])),
        max_seq=dims["engine"]["max_len"],
        dtype=jnp.dtype(dims["torch_dtype"]),
        attention_impl=dims["attention_impl"])


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 dtype) -> dict:
    """``serve_patterned.check_served`` against this model's reference:
    the MEAN gap by which a served token's logit lies below the
    reference's best is what is judged (a router's top-8 ties flip
    under bfloat16 here as there); its widest and its 99th percentile
    are printed beside it.  The reference runs each sequence at its
    own length (``reference_latent.served_logits``), not padded to the
    engine's ``max_len``."""
    n = len(sample)
    longest = max(r["prompt_len"] + len(r["tokens"]) for r in sample)
    toks = np.zeros((n, -(-longest // 2048) * 2048), np.int32)
    plens, nserved = [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        plens.append(r["prompt_len"])
        nserved.append(len(r["tokens"]))
    logits, served, valid = reference_latent.served_logits(
        seed, dims, dtype, toks, plens, nserved)
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
        low, _, _ = reference_latent.served_logits(
            seed, dims, dtype, toks, plens, nserved, mode=mode)
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out.update(spread(cgap, "control_"), control_mode=mode)
    return out


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        control: bool, t0: float, device: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving

    marks = {"import": time.monotonic() - t0}
    dims, traffic = cell["dims"], cell["traffic_params"]
    eng = dims["engine"]
    # first of all: a program that cannot state this configuration (no
    # latent attention, no share of the experts) fails HERE, before any
    # weight
    cfg = build_cfg(dims)
    say(f"compile cache: {harness.place_caches()}")
    dtype = jnp.dtype(dims["torch_dtype"])
    params = jax.block_until_ready(
        weights_latent.make_params(seed, dims, dtype))
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
        kvb = jnp.dtype(final.get("kv_dtype", dims["torch_dtype"])).itemsize
        obs["mla_need_bytes"] = costs_latent.mla_decode_bytes(
            dims, ctx, kv_bytes=kvb)
        obs["mla_need_flops"] = costs_latent.mla_decode_flops(dims, ctx)
        obs["trace_decode_tokens"] = len(ctx)
        # the held experts' need over the traced ticks: the program's
        # counts of the window (a tick's mean) times the ticks traced
        share = tr_obs["trace_ticks"] / ticks
        rows = share * _grown(stats0, stats1, "moe_rows_total")
        touched = share * _grown(stats0, stats1,
                                 "moe_experts_touched_total")
        obs["moe_need_bytes"] = costs_latent.held_expert_bytes(
            dims, touched, rows, weight_bytes=dtype.itemsize)
        obs["moe_need_flops"] = costs_latent.held_expert_flops(dims, rows)
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
        away = _grown(stats0, stats1, "moe_rows_routed_away_total")
        n_exp = dims["num_hidden_layers"] - dims["first_k_dense_replace"]
        say(f"experts: rows a tick HERE {rows / ticks:.1f} over {n_exp} "
            f"expert layers, routed away {away / ticks:.1f} (here "
            f"{100 * rows / max(rows + away, 1):.2f} %, uniform routing "
            f"would give {100 * costs_latent.rows_here_share(dims):.2f} %), "
            f"experts touched a tick and layer "
            f"{_grown(stats0, stats1, 'moe_experts_touched_total') / ticks / n_exp:.1f}"
            f" of the {dims['n_routed_experts']} held; latent bytes a "
            f"token {final.get('kv_latent_bytes_per_token')}")
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
        chk = check_served(sample, seed, dims, control=control, dtype=dtype)
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
