"""Record the small device trace the reducer's test reads
(``tests/data/tiny_v5e.xplane.pb``): a few matmuls and, on several chips, a
psum, with the host tracers turned down so that the file stays small.
``python3 chipbench/tools/record_tiny_trace.py <out dir>`` on the chip."""

import glob
import os
import shutil
import sys

import jax
import jax.numpy as jnp


def main() -> None:
    out = sys.argv[1]
    n = len(jax.devices())
    x = jnp.ones((n, 512, 512), jnp.bfloat16)
    f = jax.pmap(lambda a: jax.lax.psum(a @ a, "i") @ a, axis_name="i")
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = os.path.join(out, "_trace")
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench:tiny"):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, os.path.join(out, "tiny_v5e.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(out, "tiny_v5e.xplane.pb")), "bytes")


if __name__ == "__main__":
    main()
