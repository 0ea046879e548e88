"""Time the three flash kernels ALONE over a grid of block sizes, on the
chip: ``hvd_flash_fwd``, ``hvd_flash_bwd_dkv`` and ``hvd_flash_bwd_dq`` at
one shape (default: the training cells', ``bf16[6*32, 4096, 128]``,
causal), each ``(block_q, block_k)`` traced on its own and read from the
device trace by the kernels' names.  Changes no default; the table is the
input of a block-size decision (PERF.md section 5).

A time is the kernel's device time per call; ``roof`` is the REQUIRED
FLOPs (causal: half the square; forward 2 products, backward 4 — as
``chipbench/costs.py flash_train_flops`` counts them) at the chip's bf16
peak (``chipbench/peaks.json``) over that time.  A pair the compiler refuses (VMEM) is reported as refused.

    chiprun -- python benchmarks/flash_block_sweep.py

It needs a TPU and has no CPU mode.  Last stdout line: one JSON object.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import re
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

KERNELS = ("hvd_flash_fwd", "hvd_flash_bwd_dkv", "hvd_flash_bwd_dq")
# an operation's name in the trace: "%hvd_flash_bwd_dq.3 = ..." or bare
_KERNEL = re.compile(r"^%?(" + "|".join(KERNELS) + r")(?![a-z_])")


def _kernel_seconds(trace_dir: str) -> dict:
    """Device time by kernel name on chip 0, read as the benchmark reads
    its traces (``chipbench/xplane.py``)."""
    from chipbench import xplane

    out = dict.fromkeys(KERNELS, 0.0)
    ops = xplane.device_ops(xplane.load(xplane.find_xplane(trace_dir)))
    for name, start, end in ops.get(0, ()):
        m = _KERNEL.match(name)
        if m:
            out[m.group(1)] += end - start
    if not all(out.values()):
        raise RuntimeError(f"a kernel is missing from the trace: {out}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=6)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--d-head", type=int, default=128)
    ap.add_argument("--block-q", type=int, nargs="+",
                    default=[512, 1024, 2048])
    ap.add_argument("--block-k", type=int, nargs="+",
                    default=[256, 512, 1024])
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench import peaks
    from horovod_tpu.ops import attention as A

    if jax.default_backend() != "tpu":
        print(f"flash_block_sweep needs a TPU; JAX found "
              f"{jax.default_backend()}", file=sys.stderr)
        return 2
    device = jax.devices()[0]
    peak = peaks.peaks_for(device.device_kind)["bf16_flops_per_s"]
    B, H, S, D = args.rows, args.heads, args.seq, args.d_head
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(x, (B, H, S, D), jnp.bfloat16)
                   for x in ks)
    scale = A._sm_scale(q, None)
    need_fwd = 2.0 * S * S * H * D * B      # causal: half the square
    need_bwd = 2.0 * need_fwd
    rows = []
    for bq, bk in itertools.product(args.block_q, args.block_k):
        row = {"block_q": bq, "block_k": bk}
        fwd = jax.jit(lambda q, k, v: A._flash_fwd(q, k, v, 0, None, bq, bk))
        bwd = jax.jit(lambda q, k, v, o, lse, do: A._flash_bwd_pallas(
            0, scale, bq, bk, q, k, v, o, lse, do))
        try:
            o, lse = jax.block_until_ready(fwd(q, k, v))
            jax.block_until_ready(bwd(q, k, v, o, lse, do))
        except Exception as e:  # the compiler's refusal is the finding
            row["refused"] = str(e).strip().splitlines()[0][:160]
            rows.append(row)
            print(json.dumps(row), flush=True)
            continue
        trace_dir = tempfile.mkdtemp(prefix="flash_sweep_")
        jax.profiler.start_trace(trace_dir)
        for _ in range(args.reps):
            o, lse = fwd(q, k, v)
            out = bwd(q, k, v, o, lse, do)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        secs = _kernel_seconds(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name in KERNELS:
            row[name + "_ms"] = round(secs[name] / args.reps * 1e3, 4)
        row["bwd_ms"] = round(row["hvd_flash_bwd_dkv_ms"]
                              + row["hvd_flash_bwd_dq_ms"], 4)
        row["fwd_roof_pct"] = round(
            need_fwd / peak / (row["hvd_flash_fwd_ms"] * 1e-3) * 100, 2)
        row["bwd_roof_pct"] = round(
            need_bwd / peak / (row["bwd_ms"] * 1e-3) * 100, 2)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"shape": [B * H, S, D], "causal": True,
                      "reps": args.reps, "rows": rows,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
