"""Closed loop: ``clients`` callers, each sends its next request when its
last one completes.  There is no rate: the system sets the pace.

Lengths are ONE stratified list (prompt and output paired by a fixed,
seedless shuffle) and every walk through it is a fixed permutation of that
same list.  In a closed loop the ORDER decides which requests fall inside
the window, so a seeded order changes how much work a run holds (six
seeds spread `serve_tokens_per_s` by 9 %, my chip runs, PR 23); the seed
therefore only decides which client gets which walk — the clients are
alike, so the offered work is the same in every run — and draws the token
ids.  Each client's first request is
its standing one, sent during set-up part-way through its output (context
grown by what it has produced, a residual budget left), so the window
opens with every client in flight and finishing at staggered times."""

from __future__ import annotations

import numpy as np

from chipbench.generators.lengths import fixed_shuffle, stratified


def plan(traffic: dict, seconds: float, seed: int, limits: dict) -> dict:
    rng = np.random.default_rng(seed)
    vocab, max_len = limits["vocab_size"], limits["max_len"]
    c, k = int(traffic["clients"]), int(traffic["strata"])
    prompts = stratified(traffic["prompt"], k)
    outputs = fixed_shuffle(stratified(traffic["output"], k), 1)
    fracs = fixed_shuffle([(j + 0.5) / c for j in range(c)], 2)
    first = fixed_shuffle(list(range(k)), 3)[:c]
    per_client = int(traffic["requests_per_client"])
    standing, chains = [], {}
    deal = rng.permutation(c)        # which client gets which fixed walk
    for j in range(c):
        client = f"c{int(deal[j])}"
        i = first[j]
        done = int(outputs[i] * (1.0 - fracs[j]))
        left = max(1, outputs[i] - done)
        p = min(prompts[i] + done, max_len - left - 1)
        standing.append({
            "id": f"{client}.0", "client": client, "due_s": None,
            "tokens": rng.integers(0, vocab, p).tolist(),
            "max_new_tokens": int(left), "stream": bool(traffic["stream"]),
            "counts_ttft": False})
        walk = fixed_shuffle(list(range(k)), 10 + j)[:per_client]
        chains[client] = [{
            "id": f"{client}.{n + 1}", "client": client, "due_s": None,
            "tokens": rng.integers(
                0, vocab, min(prompts[i], max_len - outputs[i] - 1)).tolist(),
            "max_new_tokens": int(outputs[i]),
            "stream": bool(traffic["stream"]), "counts_ttft": True}
            for n, i in enumerate(walk)]
    return {"standing": standing, "arrivals": [], "chains": chains}
