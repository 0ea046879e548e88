"""Transformer-LM training benchmark on the real chip: tokens/sec + MFU,
with the attention implementation as the variable — XLA softmax attention
vs the Pallas flash kernel (``horovod_tpu/ops/attention.py``).

The reference has no LM benchmark (its headline is ResNet/Inception
throughput, ``docs/benchmarks.rst``); this measures the framework's
long-context extension the same way ``bench.py`` measures the DP path:
synthetic data on device, warmup, median over timed iterations, MFU from
XLA's cost analysis of the compiled step.

Run:  python benchmarks/transformer.py [--seq 2048] [--attention flash]
Prints one JSON line per configuration.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

# Runnable as `python benchmarks/transformer.py` without PYTHONPATH
# (same shim as benchmarks/serving.py).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--d-ff", type=int, default=4096)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--attention", default="flash",
                    choices=["reference", "flash", "ring", "ring_reference"])
    ap.add_argument("--sp", type=int, default=0,
                    help="ring attention: sequence-parallel axis size "
                         "(0 = all chips). sp=1 measures the ring "
                         "plumbing + flash-chunk path against plain "
                         "flash on identical shapes.")
    ap.add_argument("--remat", action="store_true",
                    help="rematerialize layers in backward (jax.checkpoint)")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots"],
                    help="full: recompute everything; dots: keep matmul "
                         "outputs, recompute elementwise only")
    ap.add_argument("--n-experts", type=int, default=0,
                    help="MoE experts per layer (0 = dense MLP)")
    ap.add_argument("--moe-impl", default="switch",
                    choices=["switch", "dense", "dropless"],
                    help="MoE dispatch: sparse capacity-factor token "
                         "dispatch (each token computes ONE expert), "
                         "the dense all-experts oracle, or grouped "
                         "ragged matmuls (dropless, serving path)")
    ap.add_argument("--moe-dispatch", default="sort",
                    choices=["sort", "cumsum"],
                    help="switch dispatch mechanism (sort = argsort + "
                         "gathers; cumsum = one-hot running-position "
                         "oracle)")
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention: K/V heads "
                         "(0 = n_heads); the ring rotates shards this "
                         "many heads wide")
    ap.add_argument("--num-iters", type=int, default=5)
    ap.add_argument("--steps-per-iter", type=int, default=5)
    args = ap.parse_args()

    import horovod_tpu as hvd
    from horovod_tpu import spmd
    from horovod_tpu.models import transformer as T
    from jax.sharding import NamedSharding, PartitionSpec as P

    hvd.place_compile_cache()
    hvd.init()

    cfg = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=args.seq,
        attention_impl=args.attention, remat=args.remat,
        remat_policy=args.remat_policy,
        n_experts=args.n_experts,
        moe_impl=args.moe_impl,
        moe_dispatch=args.moe_dispatch,
        capacity_factor=args.capacity_factor,
        n_kv_heads=args.kv_heads,
    )
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    if args.attention.startswith("ring"):
        # Ring runs under a (dp, sp) shard_map; gradients are pmean'd
        # over both axes in the step, so the inner optimizer is plain.
        opt = optax.adamw(3e-4)
    else:
        opt = hvd.DistributedOptimizer(optax.adamw(3e-4))
    opt_state = opt.init(params)

    n = hvd.size()
    ring = args.attention.startswith("ring")
    if ring:
        # Sequence-parallel: the sp axis must be BOUND (shard_map) so K/V
        # shards can ppermute around the ring through the flash kernels.
        # Gradients are pmean'd explicitly (the optimizer is plain optax).
        from horovod_tpu.parallel.meshes import MeshSpec, make_mesh

        sp = args.sp or n
        dp = n // sp
        mesh = make_mesh(MeshSpec(dp=dp, sp=sp))
        data_axes = ("dp", "sp")
        batch_spec = P("dp", "sp")
        rows = args.batch_size * dp
    else:
        mesh = hvd.mesh()
        data_axes = (hvd.AXIS,)
        batch_spec = P(hvd.AXIS)
        rows = args.batch_size * n

    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(p, batch, cfg))(params)
        if ring:
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, data_axes), grads)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, data_axes))

    step = jax.jit(spmd.shard(
        _step, in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P()), mesh=mesh), donate_argnums=(0, 1))
    # Targets are the FULL-sequence next-token shift, computed before
    # sharding: a per-shard roll inside the step would wrap around each
    # sp chunk, silently training a different objective on the ring path.
    tok_host = np.random.randint(0, args.vocab, (rows, args.seq))
    tokens = {
        "tokens": jax.device_put(
            jnp.asarray(tok_host, jnp.int32),
            NamedSharding(mesh, batch_spec)),
        "targets": jax.device_put(
            jnp.asarray(np.roll(tok_host, -1, axis=1), jnp.int32),
            NamedSharding(mesh, batch_spec)),
    }

    from horovod_tpu.obs import xprof

    step = step.lower(params, opt_state, tokens).compile()
    # Peak-HBM and the chip-peak table come from obs.xprof (the
    # library-ized form of bench.py's cost_analysis trick); the MFU
    # numerator stays ANALYTIC on purpose — XLA's cost analysis counts
    # a lax.scan body ONCE, so it undercounts the per-layer work
    # n_layers-fold here: 6 x matmul-params x tokens for the dense path
    # + causal attention scores, fwd+bwd.
    report = xprof.introspect(step, fn="transformer_train_step")
    n_matmul = xprof.matmul_param_count(params)
    moe_removed = 0
    if args.n_experts > 1:
        # MODEL FLOPs for top-1 MoE: each token's MLP runs ONE expert, so
        # the expert stacks contribute 1/E of their parameter count (the
        # PaLM useful-work convention; reported as "mfu").  Dense dispatch
        # EXECUTES all E experts — that hardware utilization is reported
        # separately as "mfu_executed" (the r3 table's ¹ convention).
        expert_params = sum(
            int(np.prod(params["layers"][k].shape))
            for k in ("w_gate", "w_up", "w_down"))
        moe_removed = expert_params * (args.n_experts - 1) // args.n_experts
        n_matmul -= moe_removed
    # Per-chip FLOPs: global batch rows / n chips (for ring, the sequence
    # is sharded too, so per-chip work is global work / n either way).
    B = rows / n
    S = args.seq
    dense_flops = 6 * n_matmul * B * S
    attn_flops = 6 * args.n_layers * B * S * S * args.d_model  # causal
    # MFU convention (PaLM appendix B): model FLOPs only — remat's
    # recompute is NOT counted, so --remat runs report the honest
    # utilization of useful work.
    step_flops = float(dense_flops + attn_flops)

    kind = jax.devices()[0].device_kind
    peak = xprof.chip_peak_flops()
    # Arm the live training_mfu gauge; one measured unit below is an
    # iteration of steps_per_iter steps closed by a sync.
    xprof.set_training_cost(
        step_flops * args.steps_per_iter if step_flops else None, peak)

    def _sync(x):
        return float(np.asarray(jax.device_get(x)))

    for _ in range(2):  # warmup
        for _ in range(args.steps_per_iter):
            params, opt_state, loss = step(params, opt_state, tokens)
    _sync(loss)

    from horovod_tpu import obs

    times = []
    for _ in range(args.num_iters):
        t0 = time.perf_counter()
        with obs.training_step("transformer_bench_iter"):
            for _ in range(args.steps_per_iter):
                params, opt_state, loss = step(params, opt_state, tokens)
            _sync(loss)
        times.append((time.perf_counter() - t0) / args.steps_per_iter)

    med = float(np.median(times))
    tokens_per_step = rows * args.seq / n  # per chip
    result = {
        "metric": (f"TransformerLM d{args.d_model} L{args.n_layers} "
                   f"seq{args.seq}"
                   + (f" moe{args.n_experts}-{args.moe_impl}"
                      + (f"-{args.moe_dispatch}"
                         if args.moe_impl == "switch" else "")
                      + f"-cf{args.capacity_factor:g}"
                      if args.n_experts > 1 else "")
                   + f" {args.attention}-attention train "
                   f"throughput per chip"),
        "value": round(tokens_per_step / med, 1),
        "unit": "tokens/sec/chip",
        "median_step_s": round(med, 5),
        "mfu": (round(step_flops / med / peak, 4) if peak and step_flops
                else None),
        "tflops_per_sec": (round(step_flops / med / 1e12, 1)
                           if step_flops else None),
        "hbm_peak_bytes": report.peak_hbm_bytes,
        "chip": kind,
    }
    if args.n_experts > 1 and args.moe_impl == "dense" and peak:
        # Dense dispatch actually executes every expert: report that
        # hardware utilization alongside the model MFU (r3's convention,
        # kept reproducible).
        executed = step_flops + 6 * moe_removed * B * S
        result["mfu_executed"] = round(executed / med / peak, 4)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
