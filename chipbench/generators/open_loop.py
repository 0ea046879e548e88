"""Open-loop arrivals at a FIXED rate: a Poisson process conditioned on
its count.

Every run of a cell offers the same load: the number of requests due in
the window is ``round(rate * seconds)``; their arrival times are sorted
uniforms over the window (which is what a Poisson process looks like given
its count); prompt and output lengths are the stratified quantiles of the
stated distributions, paired by a fixed (seedless) shuffle.  The seed only
permutes which request arrives when, and draws the token ids.

The window opens in steady state: ``standing`` requests — rate x mean life
of them (Little's law), drawn length-biased from the same strata, each
part-way through its output (context grown by what it has already
produced, a residual budget left) — are admitted during set-up.  They
count for inter-token gaps and tokens, not for time to first token."""

from __future__ import annotations

import numpy as np

from chipbench.generators.lengths import fixed_shuffle, stratified


def plan(traffic: dict, seconds: float, seed: int, limits: dict) -> dict:
    rng = np.random.default_rng(seed)
    vocab, max_len = limits["vocab_size"], limits["max_len"]
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    prompts = stratified(traffic["prompt"], n)
    outputs = fixed_shuffle(stratified(traffic["output"], n), 1)
    due = np.sort(rng.uniform(0.0, seconds, n))
    order = rng.permutation(n)
    arrivals = []
    for slot, i in enumerate(order):
        p, o = prompts[i], outputs[i]
        p = min(p, max_len - o - 1)
        arrivals.append({
            "id": f"w{slot}", "client": f"w{slot}", "due_s": float(due[slot]),
            "tokens": rng.integers(0, vocab, p).tolist(),
            "max_new_tokens": int(o), "stream": bool(traffic["stream"]),
            "counts_ttft": True})
    # The standing population: fixed from the traffic file alone.
    m = int(round(rate * float(traffic["mean_life_s"])))
    strata = int(traffic.get("standing_strata", 64))
    sp = stratified(traffic["prompt"], strata)
    so = fixed_shuffle(stratified(traffic["output"], strata), 1)
    # length-biased pick: a request is in flight in proportion to its life,
    # which is about its output length
    cum = np.cumsum(so, dtype=float) / float(sum(so))
    standing = []
    fracs = fixed_shuffle([(j + 0.5) / max(m, 1) for j in range(m)], 2)
    for j in range(m):
        i = int(np.searchsorted(cum, (j + 0.5) / m))
        done = int(so[i] * (1.0 - fracs[j]))  # already produced
        left = max(1, so[i] - done)
        p = min(sp[i] + done, max_len - left - 1)
        standing.append({
            "id": f"s{j}", "client": f"s{j}", "due_s": None,
            "tokens": rng.integers(0, vocab, p).tolist(),
            "max_new_tokens": int(left), "stream": bool(traffic["stream"]),
            "counts_ttft": False})
    return {"standing": standing, "arrivals": arrivals, "chains": {}}
