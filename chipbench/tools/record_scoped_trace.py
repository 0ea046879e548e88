"""Record the small device trace the scope and gap readers' tests read
(``tests/data/scoped_v5e.xplane.pb``): per repetition one matmul under
``jax.named_scope("mlp")`` and the program's named Pallas paged-attention
call under ``jax.named_scope("paged_attend")``, dispatched inside an
``hvd:tick_dispatch`` span and fetched inside an ``hvd:tick_device_wait``
span, the whole under ``chipbench:tiny``; between two repetitions the host
sleeps under no ``hvd:`` span.  Host tracers turned down so that the file
stays small.  ``python3 chipbench/tools/record_scoped_trace.py <out dir>``
on the chip."""

import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main() -> None:
    from horovod_tpu.obs import tracing
    from horovod_tpu.ops import paged_attention as pa

    out = sys.argv[1]
    slots, kv_heads, group, head_dim, page, max_pages = 4, 2, 8, 128, 16, 8
    pool = jnp.ones((slots * max_pages + 1, kv_heads, page, head_dim),
                    jnp.bfloat16)
    table = 1 + jnp.arange(slots * max_pages, dtype=jnp.int32).reshape(
        slots, max_pages)
    limit = jnp.full((slots,), page * max_pages // 2, jnp.int32)

    @jax.jit
    def tick(x, q):
        with jax.named_scope("mlp"):
            y = x @ x
        with jax.named_scope("paged_attend"):
            o, _ = pa.paged_attend(q, pool, pool, None, None, table, limit)
        return y, o

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    q = jnp.ones((slots, kv_heads, group, head_dim), jnp.bfloat16)
    jax.block_until_ready(tick(x, q))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = os.path.join(out, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench:tiny"):
        for i in range(3):
            with tracing.phase("tick_dispatch", k=i):
                res = tick(x, q)
            with tracing.phase("tick_device_wait"):
                jax.block_until_ready(res)
            time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    dst = os.path.join(out, "scoped_v5e.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    print(os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main()
