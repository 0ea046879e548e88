"""A served model of LINEAR-ATTENTION layers between BLOCK-SPARSE
attention layers (``kind: serve_linear_sparse``): beside the pages of its
few attention layers a slot keeps, a lightning layer, a float32 MATRIX
state (heads x head x head: 2 MiB), and a page keeps a COMPRESSED key a KV
head whose scores choose the blocks a query attends; the published muP
scales on every stream.  ``drivers/serve.py``'s run — the same engine,
server, load client, warm-up, window and sample — wired to this model's
configuration, seeded weights (``weights_linear_sparse``), reference
(``reference_linear_sparse``) and costs (``costs_linear_sparse``).

Only what names the model is restated here (``build_cfg``,
``check_served``, the costs and the counters' line in ``run``); the rest
is the older drivers' own functions, imported.  What ``run`` adds to the
observations, each at the traced ticks' share of the window's counters:
what the recurrence's tick needed (``lin_updated_slots_total``:
``lin_update_need_bytes`` — the body and its scope are the state-space
mixer's, the need is this model's own), and what the block-sparse tick
needed (``bsa_scored_rows_total``, ``bsa_attended_tokens_total``).

``--control 1`` runs THREE controls on the same sample
(``check.control_modes``): the reference with fp8 operands, the reference
with every lightning layer's state zeroed at each chunk boundary of the
prompt and at each tick (``lost_state``), and the reference with the
selection ignored (``dense``) — each judged by the comparison that decides
``correct`` (``judged``), which has to refuse all three: such a run is
``correct`` only if the served tokens pass and no control does."""

from __future__ import annotations

import gc
import importlib
import json
import time

import numpy as np

from chipbench import (costs_linear_sparse, harness, peaks, reference,
                       reference_linear_sparse)
from chipbench import weights_linear_sparse
from chipbench.drivers.serve import (_p, drive, observe, pick_sample,
                                     warm)
from chipbench.drivers.serve_conv import _engine_thread, _memory, _silences
from chipbench.drivers.serve_patterned import _grown
from chipbench.harness import say
from chipbench.readers import stats_diff

_CONTROLS = {"fp8": {"mode": "fp8"}, "lost_state": {"lose_state": True},
             "dense": {"select": False}}


def build_cfg(dims: dict):
    import jax.numpy as jnp

    from horovod_tpu.models import transformer as T

    if not (dims["use_output_gate"] and dims["use_output_norm"]
            and dims["qk_norm"] and dims["lightning_use_rope"]
            and dims["attn_use_output_gate"]) or dims["attn_use_rope"] \
            or dims["tie_word_embeddings"] \
            or dims["attention_bias"] \
            or dims["lightning_nh"] != dims["num_attention_heads"] \
            or dims["lightning_nkv"] != dims["lightning_nh"] \
            or dims["lightning_head_dim"] != dims["head_dim"]:
        raise ValueError("this model's lightning layers are as many heads "
                         "as its attention's, a key and value head each, "
                         "normed, roped and gated; its attention layers "
                         "gated and without a rope; its head untied")
    sc = dims["assumed"]["sparse_config"]
    r = weights_linear_sparse.residual_scale(dims)
    # a program that cannot state this configuration (no linear or
    # block-sparse layer) fails HERE, with a TypeError, before any weight
    return T.TransformerConfig(
        vocab_size=dims["vocab_size"], d_model=dims["hidden_size"],
        n_heads=dims["num_attention_heads"],
        n_kv_heads=dims["num_key_value_heads"], d_head=dims["head_dim"],
        n_layers=dims["num_hidden_layers"], d_ff=dims["intermediate_size"],
        norm_eps=dims["rms_norm_eps"], rope_theta=float(dims["rope_theta"]),
        layer_pattern=tuple(
            kind for _, kind in weights_linear_sparse.layers_run(dims)),
        qk_norm=dims["qk_norm"],
        bsa_kernel=sc["kernel_size"], bsa_stride=sc["kernel_stride"],
        bsa_block=sc["block_size"], bsa_topk=sc["topk"],
        bsa_window=sc["window_size"], bsa_init_blocks=sc["init_blocks"],
        bsa_dense_len=sc["dense_len"],
        embed_multiplier=float(dims["scale_emb"]),
        head_multiplier=weights_linear_sparse.logit_scale(dims),
        attn_out_multiplier=r, mlp_multipliers=(1.0, r),
        max_seq=dims["engine"]["max_len"],
        dtype=jnp.dtype(dims["torch_dtype"]),
        attention_impl=dims["attention_impl"])


def check_served(sample: list, seed: int, dims: dict, *, control: bool,
                 dtype) -> dict:
    """The gap by which a served token's logit lies below the
    reference's best, over the sample — mean (the one judged: a
    selection that flips between bfloat16 and float32 scores moves single
    tokens far, so the WIDEST does not separate; PERF.md section 2),
    widest, p99 — and with ``control`` the same for the tokens each mode
    of ``check.control_modes`` puts first (``fp8``: operands rounded;
    ``lost_state``: every lightning state zeroed at every boundary;
    ``dense``: the selection ignored)."""
    n, width = len(sample), dims["engine"]["max_len"]
    toks = np.zeros((n, width), np.int32)
    plens, nserved = [], []
    for i, r in enumerate(sample):
        seq = list(r["prompt"]) + list(r["tokens"])
        toks[i, :len(seq)] = seq
        plens.append(r["prompt_len"])
        nserved.append(len(r["tokens"]))
    logits, served, valid = reference_linear_sparse.served_logits(
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
        low, _, _ = reference_linear_sparse.served_logits(
            seed, dims, dtype, toks, plens, nserved, **_CONTROLS[mode])
        cgap, _ = reference.gaps_from_logits(logits, low.argmax(-1), valid)
        out.update(spread(cgap, mode + "_"))
    return out


def judged(chk: dict, dims: dict, prefix: str = "") -> bool:
    """THE comparison that decides ``correct``: of the served tokens
    (``prefix`` ""), and with ``--control 1`` of each control's
    (``"fp8_"``, ..), which it has to refuse — a run under ``--control
    1`` is ``correct`` only if the served tokens pass AND no control
    does."""
    return chk[prefix + "mean_gap"] <= float(
        dims["check"]["served_mean_gap_limit"])


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
        weights_linear_sparse.make_params(seed, dims, dtype))
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
        obs["trace_decode_tokens"] = len(ctx)
        share = tr_obs["trace_ticks"] / ticks

        def traced(counter):
            return share * _grown(stats0, stats1, counter)

        obs["lin_update_need_bytes"] = costs_linear_sparse.lin_update_bytes(
            dims, traced("lin_updated_slots_total"))
        obs["bsa_score_need_bytes"] = costs_linear_sparse.bsa_score_bytes(
            dims, traced("bsa_scored_rows_total"))
        obs["bsa_attend_need_bytes"] = costs_linear_sparse.bsa_attend_bytes(
            dims, traced("bsa_attended_tokens_total"))
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
        say(f"linear attention: rows updated a tick "
            f"{_grown(stats0, stats1, 'lin_updated_slots_total') / ticks:.1f}"
            f" (slots x layers; {eng['n_slots']} slots), tokens x layers "
            f"scanned {_grown(stats0, stats1, 'lin_scanned_tokens_total'):.0f}"
            f"; state {final.get('lin_state_bytes_per_slot')} B a slot; "
            f"block-sparse attention a tick: compressed rows scored "
            f"{_grown(stats0, stats1, 'bsa_scored_rows_total') / ticks:.0f}, "
            f"tokens attended "
            f"{_grown(stats0, stats1, 'bsa_attended_tokens_total') / ticks:.0f}"
            f" of "
            f"{_grown(stats0, stats1, 'bsa_live_tokens_total') / ticks:.0f} "
            f"live (x layers); KV {final.get('kv_bytes_per_token')} B a "
            f"token, compressed keys "
            f"{final.get('kv_compressed_bytes_per_page')} B a page")
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
    if sample:
        chk = check_served(sample, seed, dims, control=control, dtype=dtype)
        correct = judged(chk, dims)
        say(f"correct: mean gap of a served token's logit below the "
            f"reference's best {chk['mean_gap']:.6f} (limit "
            f"{dims['check']['served_mean_gap_limit']}) over "
            f"{chk['positions']} served tokens of {len(sample)} requests "
            f"(widest {chk['widest_gap']:.4f}, p99 {chk['p99_gap']:.4f}, "
            f"{chk['exact_share']:.3f} of them the reference's own pick) -> "
            f"{correct}; reference took {time.monotonic() - t_chk:.1f} s")
        for m in dims["check"]["control_modes"] if control else ():
            passes = judged(chk, dims, m + "_")
            say(f"CONTROL {m}: mean gap {chk[m + '_mean_gap']:.6f} (widest "
                f"{chk[m + '_widest_gap']:.4f}, p99 {chk[m + '_p99_gap']:.4f}"
                f", own pick {chk[m + '_exact_share']:.3f}) -> correct "
                f"{passes}" + (": THE LIMIT DOES NOT HOLD THIS CONTROL"
                               if passes else ""))
            correct = correct and not passes
    else:
        say("correct: no request finished, nothing to compare -> false")
    return {"obs": obs, "setup_s": setup_s, "correct": correct,
            "attempted": len(due), "failed": failed,
            "memory_peak_bytes": peak}
