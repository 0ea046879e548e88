"""Eager/native control-plane benchmark under BERT-style many-small-tensor
load (BASELINE.md's "tensor-fusion + autotune" keep-honest config).

The reference's entire layer-2 C++ (negotiation controller.cc:631-752,
response cache response_cache.h:45-102, 64MB fusion threshold
operations.cc:408) exists to make op-by-op training fast.  This benchmark
measures OUR re-design of that machinery end to end: ~340 gradient-sized
tensors (1KB-512KB, BERT-base-like mix) allreduced per step across real
launcher-spawned processes, comparing

  direct    HOROVOD_NATIVE=0 — every tensor its own immediate collective
  native    negotiation + tensor fusion + response-cache fast path
  autotune  native + the Bayesian parameter manager tuning fusion/cycle

and, separately, a 74-parameter-tensor torch model driven through
``hvd.torch.DistributedOptimizer`` (per-parameter hook submissions, the
reference's op-by-op pattern).

Run the driver (spawns everything):

    python benchmarks/eager_fusion.py [--nproc 2] [--steps 12]

Per-mode JSON lands on stdout; the driver prints a comparison table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# --- workload -----------------------------------------------------------------


def bert_style_tensors(layers: int = 24, hidden: int = 256, seed: int = 0):
    """~14 tensors per layer mirroring a transformer's gradient mix:
    4 square attention mats, 2 FFN mats, and 8 small vectors."""
    import numpy as np

    rng = np.random.RandomState(seed)
    out = []
    for layer in range(layers):
        for nm, shape in (
            ("wq", (hidden, hidden)), ("wk", (hidden, hidden)),
            ("wv", (hidden, hidden)), ("wo", (hidden, hidden)),
            ("w1", (hidden, 2 * hidden)), ("w2", (2 * hidden, hidden)),
            ("bq", (hidden,)), ("bk", (hidden,)), ("bv", (hidden,)),
            ("bo", (hidden,)), ("b1", (2 * hidden,)), ("b2", (hidden,)),
            ("ln1", (hidden,)), ("ln2", (hidden,)),
        ):
            out.append((f"grad.l{layer}.{nm}",
                        rng.randn(*shape).astype("float32")))
    return out


def run_allreduce_mode(args) -> dict:
    """Per-tensor async allreduce of the whole tensor set each step (the
    torch-hook submission pattern), timed after warmup."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import eager_runtime

    hvd.init()
    rt = eager_runtime.get()
    tensors = bert_style_tensors(args.layers, args.hidden)
    total_bytes = sum(a.nbytes for _, a in tensors)

    def one_step():
        handles = [hvd.allreduce_async(a, hvd.Average, name=nm)
                   for nm, a in tensors]
        for h in handles:
            hvd.synchronize(h)

    tuner = None
    if args.mode == "autotune":
        from horovod_tpu.autotune import Autotuner

        tuner = Autotuner(warmup_samples=1, steps_per_sample=3,
                          bo_samples=args.bo_samples)

    for _ in range(args.warmup):
        one_step()

    hits0 = rt.cache_hits() if rt else 0
    resp0 = rt.responses_executed if rt else 0
    tens0 = rt.tensors_executed if rt else 0
    steps = args.steps if tuner is None else args.autotune_steps
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        one_step()
        dt = time.perf_counter() - t0
        times.append(dt)
        if tuner is not None:
            tuner.record(total_bytes, dt)

    # Autotune: score the FINAL settings over a clean window, with the
    # observability counters re-snapshotted so hit rate / fusion ratio
    # describe the frozen settings, not the tuning transient.
    if tuner is not None:
        if rt is not None:
            hits0 = rt.cache_hits()
            resp0 = rt.responses_executed
            tens0 = rt.tensors_executed
        times = []
        for _ in range(args.steps):
            t0 = time.perf_counter()
            one_step()
            times.append(time.perf_counter() - t0)

    n = len(tensors)
    med = sorted(times)[len(times) // 2]
    result = {
        "mode": args.mode,
        "nproc": hvd.num_processes(),
        "tensors_per_step": n,
        "mbytes_per_step": round(total_bytes / 2**20, 1),
        "steps_per_s": round(1.0 / med, 3),
        "tensor_mb_per_s": round(total_bytes / 2**20 / med, 1),
    }
    if rt is not None:
        measured = len(times) * n
        result["cache_hit_rate"] = round(
            (rt.cache_hits() - hits0) / max(measured, 1), 3)
        dresp = rt.responses_executed - resp0
        dtens = rt.tensors_executed - tens0
        result["fusion_ratio"] = round(dtens / max(dresp, 1), 1)
    if tuner is not None:
        result["tuned_settings"] = {
            k: v for k, v in tuner.settings.items()
            if k in ("fusion_threshold", "cycle_time_ms", "cache_capacity")}
    if hvd.process_rank() == 0:
        print("EAGER-BENCH " + json.dumps(result), flush=True)
    hvd.shutdown()
    return result


def run_torch_mode(args) -> dict:
    """torch.DistributedOptimizer step loop: per-parameter grad-hook
    submissions through the runtime (reference torch/__init__.py:61-216
    op-by-op pattern)."""
    import torch

    import horovod_tpu.torch as hvd
    from horovod_tpu import eager_runtime

    hvd.init()
    rt = eager_runtime.get()
    torch.manual_seed(0)
    h = args.hidden
    blocks = []
    for _ in range(args.layers // 2):
        blocks += [torch.nn.Linear(h, h), torch.nn.Tanh(),
                   torch.nn.Linear(h, 2 * h), torch.nn.Tanh(),
                   torch.nn.Linear(2 * h, h)]
    model = torch.nn.Sequential(*blocks, torch.nn.Linear(h, 1))
    n_params = sum(1 for _ in model.parameters())
    total_bytes = sum(p.numel() * 4 for p in model.parameters())
    hvd.broadcast_parameters(model.state_dict(), root_rank=0)
    opt = hvd.DistributedOptimizer(
        torch.optim.SGD(model.parameters(), lr=1e-3),
        named_parameters=model.named_parameters())
    x = torch.randn(32, h)
    y = x.sum(dim=1, keepdim=True)

    def one_step():
        opt.zero_grad()
        torch.nn.functional.mse_loss(model(x), y).backward()
        opt.step()

    for _ in range(args.warmup):
        one_step()
    hits0 = rt.cache_hits() if rt else 0
    times = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        one_step()
        times.append(time.perf_counter() - t0)
    med = sorted(times)[len(times) // 2]
    result = {
        "mode": args.mode,
        "nproc": hvd.cross_size(),
        "params": n_params,
        "mbytes_per_step": round(total_bytes / 2**20, 1),
        "steps_per_s": round(1.0 / med, 3),
    }
    if rt is not None:
        result["cache_hit_rate"] = round(
            (rt.cache_hits() - hits0) / max(len(times) * n_params, 1), 3)
    if hvd.cross_rank() == 0:
        print("EAGER-BENCH " + json.dumps(result), flush=True)
    hvd.shutdown()
    return result


# --- driver -------------------------------------------------------------------


MODES = ("direct", "native", "autotune", "torch-direct", "torch-native")


def spawn(mode: str, args) -> dict:
    import socket

    from horovod_tpu.runner import launch
    from horovod_tpu.runner.hosts import HostSpec

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    out_dir = os.path.join(args.output_dir, mode)
    # Workers inherit the driver's full environment (XLA/thread config
    # materially changes CPU collective throughput) with the per-mode
    # knobs overriding.  Only --xla_force_host_platform_device_count is
    # stripped from XLA_FLAGS: the test harness exports it (8 virtual
    # chips), which would silently change both the semantics
    # (chip-weighted local_size) and the timings being compared; other
    # user XLA flags stay in force.
    xla_flags = " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env = {
        **os.environ,
        "XLA_FLAGS": xla_flags,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "HOROVOD_NUM_PROC": str(args.nproc),
        "HOROVOD_JAX_PORT": str(free_port()),
        "HOROVOD_NATIVE_PORT": str(free_port()),
        "HOROVOD_NATIVE": "0" if mode.endswith("direct") else "1",
        "HOROVOD_CYCLE_TIME": str(args.cycle_ms),
    }
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--mode", mode, "--steps", str(args.steps),
           "--warmup", str(args.warmup), "--layers", str(args.layers),
           "--hidden", str(args.hidden),
           "--autotune-steps", str(args.autotune_steps),
           "--bo-samples", str(args.bo_samples),
           "--cycle-ms", str(args.cycle_ms)]
    rc = launch.launch_job(cmd, [HostSpec("localhost", 1)] * args.nproc,
                           env=env, output_filename=out_dir)
    if rc != 0:
        err_path = os.path.join(out_dir, "rank.0.stderr")
        err = (open(err_path).read()[-3000:]
               if os.path.exists(err_path) else "<no rank output captured>")
        raise SystemExit(f"mode {mode} failed (rc={rc}):\n{err}")
    for line in open(os.path.join(out_dir, "rank.0.stdout")):
        # lines may carry the launcher's "[rank]<stream>:" tee prefix
        if "EAGER-BENCH " in line:
            return json.loads(line.split("EAGER-BENCH ", 1)[1])
    raise SystemExit(f"mode {mode}: no EAGER-BENCH line in rank 0 stdout")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--mode", default="native", choices=MODES)
    ap.add_argument("--modes", default="direct,native,autotune,"
                    "torch-direct,torch-native")
    ap.add_argument("--nproc", type=int, default=2)
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--autotune-steps", type=int, default=60)
    ap.add_argument("--bo-samples", type=int, default=8)
    ap.add_argument("--cycle-ms", type=float, default=1.0)
    ap.add_argument("--output-dir", default="/tmp/eager_fusion_bench")
    args = ap.parse_args()

    if args.worker:
        import jax

        jax.config.update("jax_platforms", "cpu")
        if args.mode.startswith("torch"):
            run_torch_mode(args)
        else:
            run_allreduce_mode(args)
        return

    results = [spawn(m, args) for m in args.modes.split(",")]
    print(f"\n== eager/native control plane, {args.nproc} processes ==")
    for r in results:
        extra = []
        if "cache_hit_rate" in r:
            extra.append(f"cache_hit={r['cache_hit_rate']:.0%}")
        if "fusion_ratio" in r:
            extra.append(f"fusion={r['fusion_ratio']}x")
        if "tuned_settings" in r:
            extra.append(f"tuned={r['tuned_settings']}")
        print(f"{r['mode']:>13}: {r['steps_per_s']:7.3f} steps/s  "
              + " ".join(extra))
    by_mode = {r["mode"]: r for r in results}
    if "native" in by_mode and "direct" in by_mode:
        speedup = (by_mode["native"]["steps_per_s"]
                   / by_mode["direct"]["steps_per_s"])
        print(json.dumps({
            "metric": "eager_fusion_native_vs_direct",
            "value": round(speedup, 2), "unit": "x",
            "detail": {m: r.get("steps_per_s") for m, r in by_mode.items()},
            "native_fusion_ratio": by_mode["native"].get("fusion_ratio"),
            "native_cache_hit_rate": by_mode["native"].get("cache_hit_rate"),
        }))


if __name__ == "__main__":
    main()
