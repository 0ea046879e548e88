"""A served model with learned SPARSE attention over a latent cache (a
lightning indexer with its own key cache, each query attending its
``index_topk`` best-scored tokens), a router that chooses under a
score-correction bias, a leading dense layer and ONE CHIP'S SHARE of
its routed experts (``kind: serve_sparse``): ``drivers/serve.py``'s run
— the same engine, server, load client, warm-up, window and sample —
wired to this model's configuration, seeded weights
(``weights_sparse``), reference (``reference_sparse``) and costs
(``costs_sparse``).

Only what names the model is restated here (``build_cfg``,
``pick_sample``, ``check_served``, the costs and the counters' line in
``run``); the rest
is ``serve.py``'s, ``serve_patterned.py``'s and ``serve_latent.py``'s
own functions, imported.  What ``run`` adds to the observations: the
index walk's and the selected attend's needed bytes and FLOPs over the
traced window, from the program's own counters
(``dsa_scored_tokens_total``, ``dsa_selected_tokens_total``), and the
held experts' as ``serve_latent`` counts them.

``--control 1`` runs TWO controls on the same sample
(``check.control_modes``): the reference with fp8 operands, and the
reference with selection OFF (dense attention) — a program that ignored
its indexer would serve the second's tokens, so the limit has to lie
under both."""

from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

from chipbench import (costs_sparse, harness, peaks, reference,
                       reference_sparse, weights_sparse)
from chipbench.drivers import serve_latent
from chipbench.drivers.serve import _p, drive, observe, warm
from chipbench.drivers.serve import pick_sample as _seeded_order
from chipbench.drivers.serve_patterned import _grown
from chipbench.harness import say


def build_cfg(dims: dict):
    """``serve_latent.build_cfg``'s configuration (which checks YaRN and
    the expert layers' place) with the indexer's three sizes and the
    router's bias; ``topk_method`` must be the published
    ``noaux_tc``."""
    import dataclasses

    if dims["topk_method"] != "noaux_tc":
        raise ValueError("expected topk_method 'noaux_tc' (a "
                         "score-correction bias on the router's choice)")
    cfg = serve_latent.build_cfg(dict(dims, topk_method="none"))
    # a program that cannot state the indexer fails HERE, with a
    # TypeError, before any weight
    return dataclasses.replace(
        cfg, moe_score_bias=True, index_n_heads=dims["index_n_heads"],
        index_head_dim=dims["index_head_dim"],
        index_topk=dims["index_topk"])


def _width(max_len: int) -> int:
    """The ONE width every sequence is laid in for the reference (so it
    compiles once, whatever the lengths): the engine's ``max_len``, in
    whole bands."""
    band = reference_sparse.BAND
    return -(-max_len // band) * band if max_len > band else max_len


def pick_sample(recs: list, seed: int, dims: dict) -> list:
    """``serve.pick_sample``'s seeded order of the finished requests
    (the one with the most served tokens first), up to ``check.sample``
    of them, passing over a request whose (query, key) pairs would take
    the reference beyond ``check.reference_pairs``: its time grows with
    them (35.2 s for 7.13e8 pairs a layer, 38.3 s for 7.47e8: my chip
    runs, PR 32), and a run has to end inside the driver's limit
    whatever the seed draws.  The first is always taken."""
    chk, max_len = dims["check"], dims["engine"]["max_len"]
    out, spent = [], 0
    for r in _seeded_order(recs, seed, len(recs) + 1, max_len):
        cost = reference_sparse.pairs(r["prompt_len"] + len(r["tokens"]),
                                      _width(max_len))
        if out and spent + cost > chk["reference_pairs"]:
            continue
        out.append(r)
        spent += cost
        if len(out) == int(chk["sample"]):
            break
    return out


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 dtype) -> dict:
    """``serve_latent.check_served`` against this model's reference;
    with ``control`` every mode of ``check.control_modes`` on the same
    sample (``fp8``: operands rounded; ``dense``: selection off)."""
    n = len(sample)
    toks = np.zeros((n, _width(dims["engine"]["max_len"])), np.int32)
    plens, nserved = [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        plens.append(r["prompt_len"])
        nserved.append(len(r["tokens"]))
    logits, served, valid = reference_sparse.served_logits(
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
        kw = {"select": False} if mode == "dense" else {"mode": mode}
        low, _, _ = reference_sparse.served_logits(
            seed, dims, dtype, toks, plens, nserved, **kw)
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out.update(spread(cgap, mode + "_"))
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
        weights_sparse.make_params(seed, dims, dtype))
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
        obs["trace_decode_tokens"] = len(ctx)
        # every need over the traced ticks: the program's counts of the
        # window (a tick's mean) times the ticks traced
        share = tr_obs["trace_ticks"] / ticks
        scored = share * _grown(stats0, stats1, "dsa_scored_tokens_total")
        picked = share * _grown(stats0, stats1, "dsa_selected_tokens_total")
        obs["dsa_score_need_bytes"] = costs_sparse.index_score_bytes(
            dims, scored, kv_bytes=kvb)
        obs["dsa_score_need_flops"] = costs_sparse.index_score_flops(
            dims, scored)
        obs["dsa_attend_need_bytes"] = costs_sparse.selected_attend_bytes(
            dims, picked, kv_bytes=kvb)
        obs["dsa_attend_need_flops"] = costs_sparse.selected_attend_flops(
            dims, picked)
        rows = share * _grown(stats0, stats1, "moe_rows_total")
        touched = share * _grown(stats0, stats1,
                                 "moe_experts_touched_total")
        obs["moe_need_bytes"] = costs_sparse.held_expert_bytes(
            dims, touched, rows, weight_bytes=dtype.itemsize)
        obs["moe_need_flops"] = costs_sparse.held_expert_flops(dims, rows)
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
            f"would give {100 * costs_sparse.rows_here_share(dims):.2f} %), "
            f"experts touched a tick and layer "
            f"{_grown(stats0, stats1, 'moe_experts_touched_total') / ticks / n_exp:.1f}"
            f" of the {dims['n_routed_experts']} held; latent bytes a "
            f"token {final.get('kv_latent_bytes_per_token')}, index bytes "
            f"{final.get('kv_index_bytes_per_token')}")
        scored = _grown(stats0, stats1, "dsa_scored_tokens_total")
        say(f"sparse attention: a tick scores {scored / ticks:.0f} live "
            f"tokens a layer (its walk fetches "
            f"{_grown(stats0, stats1, 'dsa_walked_tokens_total') / ticks:.0f}"
            f") and attends "
            f"{_grown(stats0, stats1, 'dsa_selected_tokens_total') / ticks:.0f}"
            f" selected rows "
            f"({100 * _grown(stats0, stats1, 'dsa_selected_tokens_total') / max(scored, 1):.2f}"
            f" %); slot-ticks at a context <= index_topk "
            f"{_grown(stats0, stats1, 'dsa_full_rows_total'):.0f}")
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
    sample = pick_sample(recs, seed, dims)
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
                f"; CONTROL {m} mean gap {chk[m + '_mean_gap']:.6f} "
                f"(widest {chk[m + '_widest_gap']:.4f}, p99 "
                f"{chk[m + '_p99_gap']:.4f}, own pick "
                f"{chk[m + '_exact_share']:.3f})"
                for m in (dims["check"]["control_modes"] if control else ()))
            + f"; reference took {time.monotonic() - t_chk:.1f} s for "
            + f"""{sum(reference_sparse.pairs(
                r['prompt_len'] + len(r['tokens']),
                _width(eng['max_len'])) for r in sample)} pairs a layer """
            + f"(budget {dims['check']['reference_pairs']})")
    else:
        say("correct: no request finished, nothing to compare -> false")
    return {"obs": obs, "setup_s": setup_s, "correct": correct,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": peak}
