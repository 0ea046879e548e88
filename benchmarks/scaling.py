"""Scaling-efficiency harness — the proxy for the reference's headline
claim (90% scaling efficiency for ResNet-101/Inception V3 at 512 GPUs,
``docs/benchmarks.rst:13-14``; protocol in
``examples/tensorflow2_synthetic_benchmark.py:36-131``).

Real multi-chip hardware is not available in this environment, so this
measures **weak scaling of the compiled SPMD train step over an N-device
host-platform (CPU) mesh**: per-device batch held constant, devices swept
1..8 via ``--xla_force_host_platform_device_count``.  That bounds the cost
the framework itself adds at scale — collective insertion, shard_map
partitioning, fusion buckets — though not ICI latency (virtual devices
share one host's memory bus; disclosed in the output).  The same step
function is what ``bench.py`` times on the real chip.

Efficiency definition matches the reference: ``(total img/s at N) /
(N x img/s at 1)`` (``docs/benchmarks.rst``: scaling efficiency).

Run:  python benchmarks/scaling.py [--devices 1 2 4 8] [--out SCALING.json]

Each device count runs in a fresh subprocess because
``xla_force_host_platform_device_count`` is fixed at backend init.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

WORKER = "__scaling_worker__"


def worker(n_devices: int, batch_per_device: int, iters: int, model: str) -> None:
    # Virtual CPU devices: pinned in code so the worker never opens a chip
    # whatever the environment says.
    import jax

    jax.config.update("jax_platforms", "cpu")
    from horovod_tpu._compat import set_cpu_device_count

    set_cpu_device_count(n_devices)
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import spmd

    hvd.init()
    assert hvd.size() == n_devices, (hvd.size(), n_devices)

    if model == "mlp":
        from horovod_tpu.models import mlp

        params = mlp.init_params(jax.random.PRNGKey(0), (784, 512, 512, 10))
        in_dim, n_classes = 784, 10

        def loss_fn(p, batch):
            return mlp.loss_fn(p, (batch["x"], batch["y"]))

    else:  # tiny resnet variant, CPU-sized
        from horovod_tpu.models import resnet

        net = resnet.ResNet(
            stage_sizes=[1, 1], block_cls=resnet.ResNetBlock, num_classes=10,
            num_filters=16, dtype=jnp.float32,
        )
        rng = jax.random.PRNGKey(0)
        variables = net.init(rng, jnp.zeros((2, 32, 32, 3), jnp.float32), train=True)
        params, stats = variables["params"], variables["batch_stats"]
        in_dim, n_classes = (32, 32, 3), 10

        def loss_fn(p, batch):
            logits, _ = net.apply(
                {"params": p, "batch_stats": stats}, batch["x"], train=True,
                mutable=["batch_stats"],
            )
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, batch["y"]
            ).mean()

    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9))
    # Control: identical step WITHOUT the gradient exchange.  Virtual CPU
    # devices share the host's physical cores, so raw weak-scaling numbers
    # mostly measure core contention; dividing by the exchange-free step on
    # the SAME n-device mesh cancels that and isolates what the reference's
    # scaling-efficiency claim actually measures — the cost the framework
    # adds for synchronous data parallelism.
    opt_local = optax.sgd(0.01, momentum=0.9)

    global_batch = batch_per_device * n_devices
    if model == "mlp":
        x = np.random.rand(global_batch, in_dim).astype(np.float32)
    else:
        x = np.random.rand(global_batch, *in_dim).astype(np.float32)
    y = np.random.randint(0, n_classes, (global_batch,))
    batch = spmd.shard_batch({"x": jnp.asarray(x), "y": jnp.asarray(y)})

    # Host-side master copy: the train step donates its params/opt-state
    # args, and device_put with an unchanged sharding can alias (not copy)
    # a device array — re-uploading from numpy gives each timed() run a
    # fresh donatable tree.
    params = jax.device_get(params)

    def timed(optimizer):
        step = spmd.make_train_step(loss_fn, optimizer)
        p = spmd.init_replicated(params)
        s = spmd.init_replicated(optimizer.init(params))
        for _ in range(3):  # warmup / compile
            p, s, loss = step(p, s, batch)
        float(loss)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            p, s, loss = step(p, s, batch)
            float(loss)  # value fetch = watertight barrier (see bench.py)
            times.append(time.perf_counter() - t0)
        return float(np.median(times))

    t_full = timed(opt)
    t_local = timed(opt_local)
    print(json.dumps({
        "n_devices": n_devices,
        "median_step_s": t_full,
        "median_step_s_no_exchange": t_local,
        "img_per_sec_total": global_batch / t_full,
        "dp_overhead_efficiency": min(t_local / t_full, 1.0),
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--batch-per-device", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--model", default="resnet", choices=["mlp", "resnet"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    results = []
    for n in args.devices:
        proc = subprocess.run(
            [sys.executable, __file__, WORKER, str(n),
             str(args.batch_per_device), str(args.iters), args.model],
            capture_output=True, text=True, timeout=900,
        )
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr)
            raise SystemExit(f"worker n={n} failed")
        line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
        results.append(json.loads(line))
        sys.stderr.write(f"n={n}: {results[-1]['img_per_sec_total']:.1f} img/s total\n")

    base = results[0]["img_per_sec_total"] / results[0]["n_devices"]
    curve = []
    for r in results:
        raw_eff = r["img_per_sec_total"] / (r["n_devices"] * base)
        curve.append({**r, "raw_weak_scaling_efficiency": round(raw_eff, 4)})

    out = {
        "protocol": (
            "compiled SPMD train step over an N-virtual-device CPU mesh, "
            "per-device batch fixed. dp_overhead_efficiency = (step time "
            "without gradient exchange) / (step time with exchange) on the "
            "SAME mesh — the framework's synchronous-DP cost, which is what "
            "the reference's scaling-efficiency claim measures, with host "
            "core contention cancelled. raw_weak_scaling_efficiency = "
            "total/(N x single) is also reported but on one host it mostly "
            "measures physical-core sharing, NOT the framework."
        ),
        "model": args.model,
        "batch_per_device": args.batch_per_device,
        "reference_claim": {
            "value": "90% scaling efficiency @ 512 GPUs (ResNet-101/Inception V3)",
            "source": "docs/benchmarks.rst:13-14",
        },
        "curve": curve,
    }
    print(json.dumps(out))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == WORKER:
        worker(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]), sys.argv[5])
    else:
        main()
