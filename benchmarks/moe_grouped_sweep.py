"""Time the grouped expert product ALONE, on the chip, against the number
of experts that own a row: ``ops.moe.grouped_matmul`` at the serving
cells' shapes (A.X-K1's and DeepSeek-V3.2-Exp's tick and chunk, whose
29 MB matrices come in runs; Mellum2's, whose matrix is one block), one
layer of a stack of four, bf16.  A kernel that fetches a matrix only for
an expert that owns a row reads a time that GROWS with that number; one
that fetches for every grid item reads it flat.  Changes no default; the
table goes into PERF.md section 5 ("the grouped product alone").

``device_ms`` is the kernel's device time a call, read from a trace by
its name (``hvd_moe_experts``); ``wall_ms`` the host's clock over a burst
of calls (a short kernel reads the dispatch there); ``gbps`` the bytes of
the matrices TOUCHED (each once) over the device time, ``roof_pct`` that
over the chip's HBM peak (``chipbench/peaks.json``).

    chiprun -- python benchmarks/moe_grouped_sweep.py

It needs a TPU and has no CPU mode.  Last stdout line: one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: (M rows of a call, K, N, E held, rows that land here, touched counts)
SHAPES = {
    "axk1_tick_up": (256, 7168, 2048, 12, None, (1, 4, 9, 12)),
    "axk1_tick_down": (256, 2048, 7168, 12, None, (1, 4, 9, 12)),
    "dsv32_tick_up": (192, 7168, 2048, 8, None, (1, 3, 4, 8)),
    "axk1_chunk_up": (4096, 7168, 2048, 12, 258, (1, 4, 9, 12)),
    "dsv32_chunk_up": (4096, 7168, 2048, 8, 128, (1, 3, 4, 8)),
    "mellum2_tick_up": (256, 2304, 896, 64, 256, (1, 16, 64)),
    "mellum2_chunk_up": (4096, 2304, 896, 64, 4096, (64,)),
}
LAYERS, LAYER = 4, 1


def _counts(E: int, touched: int, rows):
    """``rows`` rows over ``touched`` of ``E`` experts, spread evenly; a
    row or two each (three rows a pair) where ``rows`` is not given."""
    import numpy as np

    rows = touched * 3 // 2 if rows is None else rows
    counts = np.zeros(E, np.int32)
    own = np.linspace(0, E - 1, touched).round().astype(int)
    counts[own] = rows // touched
    counts[own[:rows % touched]] += 1
    return counts


def _kernel_seconds(trace_dir: str, kernel: str) -> float:
    """The kernel's device time on chip 0, read as the benchmark reads
    its traces (``chipbench/xplane.py``)."""
    from chipbench import xplane

    # an operation's name in the trace: "%hvd_moe_experts.3 = ..." or bare
    named = re.compile(r"^%?" + re.escape(kernel) + r"(?![a-z_])")
    ops = xplane.device_ops(xplane.load(xplane.find_xplane(trace_dir)))
    secs = sum(end - start for name, start, end in ops.get(0, ())
               if named.match(name))
    if not secs:
        raise RuntimeError(f"{kernel} is missing from the trace: "
                           f"{sorted({n for n, _, _ in ops.get(0, ())})}")
    return secs


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench import peaks
    from horovod_tpu.ops import moe

    if jax.default_backend() != "tpu":
        print(f"moe_grouped_sweep needs a TPU; JAX found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    device = jax.devices()[0]
    peak = peaks.peaks_for(device.device_kind)["hbm_bytes_per_s"]
    fn = jax.jit(moe.grouped_matmul)
    rows, w, held = [], None, None
    for name in args.shapes:
        M, K, N, E, here, touched = SHAPES[name]
        if held != (E, K, N):
            w = None                      # a stack is 1.1-1.4 GB: free it
            kx, kw = jax.random.split(jax.random.PRNGKey(0))
            w = jax.random.normal(kw, (LAYERS, E, K, N), jnp.bfloat16)
            held = (E, K, N)
        xs = jax.random.normal(kx, (M, K), jnp.bfloat16)
        for n in touched:
            counts = jnp.asarray(_counts(E, n, here))

            def burst(reps):
                for _ in range(reps):
                    out = fn(xs, w, LAYER, counts)
                jax.block_until_ready(out)

            burst(1)
            t0 = time.perf_counter()
            burst(args.reps)
            wall = (time.perf_counter() - t0) / args.reps
            trace_dir = tempfile.mkdtemp(prefix="moe_sweep_")
            jax.profiler.start_trace(trace_dir)
            burst(args.reps)
            jax.profiler.stop_trace()
            dev = _kernel_seconds(trace_dir, moe.EXPERTS_NAME) / args.reps
            shutil.rmtree(trace_dir, ignore_errors=True)
            need = n * K * N * w.dtype.itemsize
            row = {"shape": name, "M": M, "K": K, "N": N, "E": E,
                   "k_tiles": K // moe._k_tile(K, N, w.dtype.itemsize),
                   "touched": n, "rows": int(counts.sum()),
                   "device_ms": round(dev * 1e3, 4),
                   "wall_ms": round(wall * 1e3, 4),
                   "gbps": round(need / dev / 1e9, 1),
                   "roof_pct": round(need / peak / dev * 100, 2)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"layers": LAYERS, "layer": LAYER, "reps": args.reps,
                      "rows": rows,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
