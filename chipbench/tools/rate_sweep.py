"""Find the highest rate a served cell sustains: ONE engine, one window per
offered rate, the table written to ``chiprun_out/<traffic>_rate_sweep.md``.

``python3 chipbench/tools/rate_sweep.py --workload m7b-serve-chat --rates
0.7,0.85,1.0,1.15,1.3 --seconds 36``.  A rate is sustained when (a) every
request due in the window got its first token within the grace, (b) the
queue is empty at the window's end and (c) time to first token in the
second half of the window is no worse than in the first (a growing queue
shows there first).  The traffic file takes 0.8 x the highest such rate;
README.md says how a later benchmark PR repeats this."""

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import harness, weights  # noqa: E402
from chipbench.drivers import serve  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=36.0)
    ap.add_argument("--seed", type=int, default=23)
    args = ap.parse_args()
    t0 = time.monotonic()
    cell = harness.load_cell(args.workload)
    device = harness.require_chips(cell["chips"])
    import jax.numpy as jnp

    from horovod_tpu import serving

    harness.place_caches()
    dims, eng = cell["dims"], cell["dims"]["engine"]
    params = weights.make_params(args.seed, dims, jnp.dtype(dims["torch_dtype"]))
    engine = serving.InferenceEngine(
        params, serve.build_cfg(dims), serving.EngineConfig(**eng))
    gen = importlib.import_module(
        f"chipbench.generators.{cell['traffic_params']['generator']}")
    limits = {"vocab_size": dims["vocab_size"], "max_len": eng["max_len"]}
    rates = [float(r) for r in args.rates.split(",")]
    plans = []
    for i, rate in enumerate(rates):
        traffic = dict(cell["traffic_params"], rate_per_s=rate)
        plans.append((traffic, gen.plan(traffic, args.seconds,
                                        args.seed + i, limits)))
    serve.warm(engine, plans[-1][1], eng)
    srv = serving.ServingServer(engine, port=0, request_timeout=600).start()
    rows = []
    try:
        for rate, (traffic, plan) in zip(rates, plans):
            win = serve.drive(srv, plan, args.seconds, traffic, t0=t0,
                              marks={})
            recs = list(win["client"].records.values())
            a, b = win["t_open"], win["t_close"]
            obs = serve.observe(recs, a, b)
            h1 = serve.observe(recs, a, (a + b) / 2)
            h2 = serve.observe(recs, (a + b) / 2, b)
            s0, s1 = win["stats0"], win["stats1"]
            ticks = s1["decode_ticks"] - s0["decode_ticks"]
            rows.append({
                "rate": rate, "due": obs["n_due"],
                "first_in_grace": obs["n_first"],
                "queue_at_end": s1["queue_depth"],
                "slots_open": s0["slots_active"],
                "slots_end": s1["slots_active"],
                "ttft_p50": serve._p(obs["ttft_ms"], 50),
                "ttft_p90": serve._p(obs["ttft_ms"], 90),
                "ttft_p50_h1": serve._p(h1["ttft_ms"], 50),
                "ttft_p50_h2": serve._p(h2["ttft_ms"], 50),
                "itl_p50": serve._p(obs["gaps_ms"], 50),
                "itl_p95": serve._p(obs["gaps_ms"], 95),
                "tick_ms": 1e3 * args.seconds / max(ticks, 1),
                "tokens_s": obs["work_tokens"] / obs["window_s"]})
            print(json.dumps(rows[-1]), flush=True)
            serve._wait(lambda: engine.stats()["slots_active"] == 0
                        and engine.stats()["queue_depth"] == 0, 60, 0.1)
    finally:
        srv.stop(drain_timeout=20.0)
    os.makedirs(os.path.join(harness.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(harness.ROOT, "chiprun_out",
                        f"{cell['traffic']}_rate_sweep.md")
    cols = list(rows[0])
    with open(path, "w") as f:
        f.write(f"# Rate sweep, {cell['name']}, {device['kind']} x"
                f"{device['count']}, window {args.seconds} s, one engine\n\n")
        f.write("| " + " | ".join(cols) + " |\n")
        f.write("|" + " --- |" * len(cols) + "\n")
        for r in rows:
            f.write("| " + " | ".join(
                f"{r[c]:.1f}" if isinstance(r[c], float) and c != "rate"
                else str(r[c]) for c in cols) + " |\n")
    print(open(path).read())
    return 0


if __name__ == "__main__":
    sys.exit(main())
