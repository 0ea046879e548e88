"""Time the tick's state update ALONE, on the chip, over the bytes of
stored state a grid step takes: ``ops.ssm.ssm_update(kernel=True)``
(``hvd_ssm_update``) at the two served shapes — MiniCPM-SALA's nine
linear layers, ``f32[9, 48, 32, 128, 128]`` at a group a head (``G =
32``), and Falcon-H1's nine mixers, ``bf16[9, 64, 32, 128, 256]`` at
``G = 2`` — every slot active, one layer of the stack a call, in place.
Each row sets ``ops.ssm._BLOCK_BYTES`` and compiles anew; ``groups`` is
what the rule then takes a step and ``grid`` the grid it gives (block
sizes that give one shape the same grid are timed once).  Changes no
default; the table is the input of the constant's value (PERF.md
section 6, PR 47).

``device_ms`` is the kernel's device time a layer, read from a trace by
its name (as ``moe_grouped_sweep.py`` reads its kernel's); ``wall_ms``
the host's clock over a burst of calls; ``gbps`` the layer's states read
once and written once over the device time, ``roof_pct`` that over the
chip's HBM peak (``chipbench/peaks.json``).

    chiprun -- python benchmarks/ssm_update_sweep.py

It needs a TPU and has no CPU mode.  Last stdout line: one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (states' shape (L, S, H, P, N), their dtype, groups G)
SHAPES = {
    "sala": ((9, 48, 32, 128, 128), "float32", 32),
    "falconh1": ((9, 64, 32, 128, 256), "bfloat16", 2),
}
LAYER = 4


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--block-kib", type=int, nargs="+",
                    default=[64, 256, 512, 1024, 2048])
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.moe_grouped_sweep import _kernel_seconds
    from chipbench import peaks
    from horovod_tpu.ops import ssm

    if jax.default_backend() != "tpu":
        print(f"ssm_update_sweep needs a TPU; JAX found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    device = jax.devices()[0]
    peak = peaks.peaks_for(device.device_kind)["hbm_bytes_per_s"]
    rows = []
    for name in args.shapes:
        shape, dtype, G = SHAPES[name]
        L, S, H, P, N = shape
        dtype = jnp.dtype(dtype)
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        states = None                     # a stack is 0.9-1.2 GB: free it
        states = jax.random.normal(ks[0], shape, jnp.float32).astype(dtype)
        x = jax.random.normal(ks[1], (S, H, P), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[2], (S, H)))
        a_neg = -jnp.exp(jax.random.normal(ks[3], (H,)))
        b, c = (jax.random.normal(k, (S, G, N), jnp.float32)
                for k in ks[4:])
        active = jnp.ones((S,), jnp.bool_)
        group_bytes = H // G * P * N * dtype.itemsize
        need = 2 * S * H * P * N * dtype.itemsize
        seen = set()
        for kib in args.block_kib:
            ssm._BLOCK_BYTES = kib * 1024
            gb = ssm._groups_a_step(G, group_bytes)
            if gb in seen:
                continue
            seen.add(gb)
            fn = jax.jit(lambda st, *a: ssm.ssm_update(
                st, LAYER, *a, kernel=True), donate_argnums=(0,))
            row = {"shape": name, "block_kib": kib, "groups": gb,
                   "grid": [S, G // gb],
                   "step_kib": gb * group_bytes // 1024}

            def burst(reps):
                nonlocal states
                for _ in range(reps):
                    y, states = fn(states, x, dt, a_neg, b, c, active)
                jax.block_until_ready((y, states))

            try:
                burst(1)
            except Exception as e:  # the compiler's refusal is the finding
                row["refused"] = str(e).strip().splitlines()[0][:160]
                rows.append(row)
                print(json.dumps(row), flush=True)
                continue
            t0 = time.perf_counter()
            burst(args.reps)
            wall = (time.perf_counter() - t0) / args.reps
            trace_dir = tempfile.mkdtemp(prefix="ssm_sweep_")
            jax.profiler.start_trace(trace_dir)
            burst(args.reps)
            jax.profiler.stop_trace()
            dev = _kernel_seconds(trace_dir, ssm.UPDATE_NAME) / args.reps
            shutil.rmtree(trace_dir, ignore_errors=True)
            row.update(device_ms=round(dev * 1e3, 4),
                       wall_ms=round(wall * 1e3, 4),
                       step_us=round(dev / (S * G // gb) * 1e6, 3),
                       gbps=round(need / dev / 1e9, 1),
                       roof_pct=round(need / peak / dev * 100, 2))
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"layer": LAYER, "reps": args.reps, "rows": rows,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
