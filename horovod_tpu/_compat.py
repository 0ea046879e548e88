"""The one JAX-installation helper the package still needs.

The codebase targets the single installation the sandbox and the chip
machine share (jax 0.9.0); there are no version shims.
"""

from __future__ import annotations

import jax


def set_cpu_device_count(n: int) -> None:
    """Request ``n`` virtual CPU devices (tests/benchmarks simulating a
    multi-chip slice).  Must run before the CPU backend initializes."""
    jax.config.update("jax_num_cpu_devices", n)
