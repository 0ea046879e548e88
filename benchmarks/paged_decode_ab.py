"""A/B the fused Pallas paged-decode kernel against the unfused
gather/dequant/attend path, PAIRWISE in one process.

Two engines over the same params and the same paged pool geometry —
``paged_kernel=True`` vs ``paged_kernel=False`` — run the identical
workload with reps interleaved (chip-state variance dominates
cross-process comparisons; see moe_dispatch_ab.py), timed at the
full-pool per-tick p25 like benchmarks/serving.py ``_ab_decode``.  The
output sequences are compared token-for-token: the fused kernel is only
a win if it is also EXACT (the A/B oracle contract from
tests/test_paged.py).

Bytes-moved column (analytic, from the pool geometry — both paths walk
the full table-capacity row of ``MP = ceil(max_len / page_size)``
pages per slot per layer):

* fused: each referenced K/V page is streamed into VMEM once at its
  STORED dtype (int8 pages bring their f32 per-vector scales along);
  dequant happens in-register, nothing round-trips through HBM.
* unfused: the gather materializes an HBM copy of the full logical
  window at stored dtype (pool read + copy write + copy read), and a
  quantized pool additionally materializes the dequantized copy at the
  compute dtype (write + read by the attend einsum).

So per layer, per K-or-V tensor, with ``E = S*Hkv*MP*ps*Dh`` elements:
``fused = E*stored [+ scales]`` and ``unfused = 3*E*stored [+ scales]
[+ 2*E*compute if quantized]``.  The ratio is the bandwidth headroom
the fusion buys; the measured tick latency says how much of it the
backend realizes (on the CPU interpreter the fused path is SLOWER —
the interpreter exists for correctness, the ratio column is the TPU
story).

Run (CPU smoke — tiny shapes, emits one JSON line):

    JAX_PLATFORMS=cpu python benchmarks/paged_decode_ab.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--kv-dtype", default=None,
                    choices=[None, "bf16", "int8"],
                    help="pool storage dtype (None = compute dtype); "
                         "int8 exercises the in-load dequant")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from horovod_tpu import serving
    from horovod_tpu.models import transformer as T
    from horovod_tpu.serving.cache import resolve_kv_dtype

    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model,
        n_heads=args.n_heads, n_layers=args.n_layers, d_ff=args.d_ff,
        max_seq=args.max_seq, n_kv_heads=args.kv_heads,
        dtype=jnp.float32 if jax.devices()[0].platform == "cpu"
        else jnp.bfloat16,
        attention_impl="reference",
    )
    params = T.init_params(jax.random.PRNGKey(0), cfg)

    S = args.slots
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, args.prompt_len).tolist()
    steps = max(min(args.steps, cfg.max_seq - len(prompt)), 1)

    engines = {}
    for name, fused in (("fused", True), ("unfused", False)):
        eng = serving.InferenceEngine(
            params, cfg, serving.EngineConfig(
                n_slots=S, max_len=cfg.max_seq,
                page_size=args.page_size, kv_dtype=args.kv_dtype,
                max_queue_depth=max(2 * S, 8),
                paged_kernel=fused))
        eng.warmup([len(prompt)])
        engines[name] = (eng, [])
    assert engines["fused"][0].stats()["paged_kernel_engaged"]

    toks = {}
    for _ in range(max(args.iters, 2)):
        for name, (eng, dts) in engines.items():
            futs = [eng.submit(prompt, max_new_tokens=steps)
                    for _ in range(S)]
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
    zero_recompiles = all(
        eng.stats()["decode_compilations"] == 1
        for eng, _ in engines.values())

    # -- analytic bytes moved per decode tick (attention stage) ----------
    ps = args.page_size
    mp = -(-cfg.max_seq // ps)                   # table row width
    hkv = cfg.n_kv_heads or cfg.n_heads
    dh = cfg.d_model // cfg.n_heads
    elems = S * hkv * mp * ps * dh               # one K or V tensor
    stored = jnp.dtype(resolve_kv_dtype(cfg, args.kv_dtype)[0]).itemsize
    compute = jnp.dtype(cfg.dtype).itemsize
    quantized = args.kv_dtype == "int8"
    scales = (S * hkv * mp * ps) * 4 if quantized else 0
    fused_b = cfg.n_layers * 2 * (elems * stored + scales)
    unfused_b = cfg.n_layers * 2 * (
        3 * elems * stored + scales
        + (2 * elems * compute if quantized else 0))

    print(json.dumps({
        "platform": jax.devices()[0].platform,
        "kv_dtype": args.kv_dtype or "compute",
        "tick_s_fused_p25": round(q["fused"], 6),
        "tick_s_unfused_p25": round(q["unfused"], 6),
        "fused_tick_speedup": round(q["unfused"] / q["fused"], 3),
        "attn_bytes_per_tick_fused": fused_b,
        "attn_bytes_per_tick_unfused": unfused_b,
        "attn_bytes_ratio": round(unfused_b / fused_b, 3),
        "equal_output_tokens": toks["fused"] == toks["unfused"],
        "zero_decode_recompiles": zero_recompiles,
    }))


if __name__ == "__main__":
    main()
