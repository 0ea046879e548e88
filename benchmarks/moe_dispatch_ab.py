"""A/B the MoE dispatch mechanisms on the current chip, PAIRWISE in one
process.

Run-to-run variance between processes can exceed the effect measured,
so comparisons interleave inside one process.  An E=8 model with f32 AdamW state is ~5 GB, so only two live
at once: each comparison is a PAIR round-robined for several rounds
(minimum kept), with the sort-dispatch candidate appearing in every pair
as the common reference.

Shapes default to the docs/benchmarks.md E-sweep row (d1024 L8 seq2048
b4 d_ff2048, flash + remat(dots)) so rows are directly comparable.

Run:  python benchmarks/moe_dispatch_ab.py [--es 2 8]
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--d-ff", type=int, default=2048)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--capacity-factor", type=float, default=1.25)
    ap.add_argument("--es", type=int, nargs="+", default=[2, 8])
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--steps-per-round", type=int, default=3)
    ap.add_argument("--pairs", nargs="+",
                    default=["cumsum", "dense-dispatch", "dense-mlp"],
                    help="which comparisons to run against switch-sort "
                         "(each pair compiles two full models; select a "
                         "subset to fit a time budget)")
    ap.add_argument("--prefill", action="store_true",
                    help="instead of training steps, A/B the PREFILL "
                         "pass (dropless grouped-matmul dispatch vs the "
                         "dense every-expert oracle) at each E")
    args = ap.parse_args()

    from horovod_tpu.models import transformer as T

    base = T.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=args.seq,
        attention_impl="flash", capacity_factor=args.capacity_factor,
        remat=True, remat_policy="dots",
    )
    batch = T.synthetic_batch(0, base, batch=args.batch_size, seq=args.seq)
    opt = optax.adamw(3e-4)
    tokens = args.batch_size * args.seq

    def build(cfg):
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, g = jax.value_and_grad(
                lambda p: T.loss_fn(p, batch, cfg))(params)
            up, opt_state = opt.update(g, opt_state, params)
            return optax.apply_updates(params, up), opt_state, loss

        params, opt_state, loss = step(params, opt_state)  # compile+warm
        float(loss)
        return [step, params, opt_state]

    def ab(named_cfgs):
        """Round-robin the pair; returns {name: best_sec_per_step}."""
        slots = {name: build(cfg) for name, cfg in named_cfgs}
        best = {name: float("inf") for name, _ in named_cfgs}
        for _ in range(args.rounds):
            for name, slot in slots.items():
                step, params, opt_state = slot
                t0 = time.perf_counter()
                for _ in range(args.steps_per_round):
                    params, opt_state, loss = step(params, opt_state)
                float(loss)  # value fetch closes the timing loop
                best[name] = min(
                    best[name],
                    (time.perf_counter() - t0) / args.steps_per_round)
                slot[1], slot[2] = params, opt_state
        del slots
        gc.collect()
        return best

    kind = jax.devices()[0].device_kind
    print(f"chip={kind} d{args.d_model} L{args.n_layers} seq{args.seq} "
          f"b{args.batch_size} d_ff{args.d_ff} cf{args.capacity_factor:g} "
          f"remat=dots flash")

    if args.prefill:
        # A/B the serving prefill: dropless vs dense dispatch, one
        # params set, two jitted prefill fns interleaved.
        for E in args.es:
            cfg = dataclasses.replace(base, n_experts=E, remat=False,
                                      attention_impl="reference")
            params = T.init_params(jax.random.PRNGKey(0), cfg)
            prompt = batch["tokens"]
            fns = {}
            for impl in ("dropless", "dense"):
                fns[impl] = jax.jit(lambda p, t, impl=impl: T.prefill(
                    p, t, T.init_cache(cfg, t.shape[0], args.seq), cfg,
                    moe_impl=impl)[0])
                float(jnp.sum(fns[impl](params, prompt)))  # compile
            best = {k: float("inf") for k in fns}
            for _ in range(args.rounds):
                for impl, fn in fns.items():
                    t0 = time.perf_counter()
                    for _ in range(args.steps_per_round):
                        out = fn(params, prompt)
                    float(jnp.sum(out))
                    best[impl] = min(
                        best[impl],
                        (time.perf_counter() - t0) / args.steps_per_round)
            print(f"E={E} prefill: dropless {best['dropless'] * 1e3:.1f}ms"
                  f" | dense {best['dense'] * 1e3:.1f}ms | dropless = "
                  f"{best['dense'] / best['dropless']:.2f}x faster")
        return

    for E in args.es:
        moe = dataclasses.replace(base, n_experts=E)
        sort_cfg = dataclasses.replace(moe, moe_dispatch="sort")
        all_pairs = {
            "cumsum": (("switch-cumsum",
                        dataclasses.replace(moe, moe_dispatch="cumsum")),
                       ("switch-sort", sort_cfg)),
            "dense-dispatch": (("dense-dispatch",
                                dataclasses.replace(moe, moe_impl="dense")),
                               ("switch-sort", sort_cfg)),
            "dense-mlp": (("dense-mlp", base), ("switch-sort", sort_cfg)),
        }
        for key in args.pairs:
            pair = all_pairs[key]
            best = ab(pair)
            names = list(best)
            a, b = names[0], names[1]
            print(f"E={E}  {a:<15} {tokens / best[a]:>8.0f} tok/s | "
                  f"{b:<12} {tokens / best[b]:>8.0f} tok/s | "
                  f"{a} = {best[b] / best[a]:.2f}x of {b}")


if __name__ == "__main__":
    main()
