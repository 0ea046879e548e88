"""Which chips does this child own.

A TPU chip belongs to one process at a time: a second process that opens
a chip its parent or sibling holds fails or hangs.  libtpu partitions a
host's chips among processes through environment variables it reads at
backend start-up — the set JAX's own multi-process harness writes
(``jax/_src/test_multiprocess.py``).  This module is the ONE place in
the package that writes them, for the two parents that spawn children
onto a TPU host:

* ``horovodrun -np N`` (:mod:`horovod_tpu.runner.launch`): N local ranks
  of ONE job, one chip each, joined into one slice — ``one_job=True``;
* the serving supervisor (:mod:`horovod_tpu.serving.router.supervisor`):
  N independent replicas, ``chips_per_proc`` (= tp) chips each, every
  replica a slice of its own — ``one_job=False``.

Neither parent may initialise a JAX backend (it would take the chips
its children need), so the host's chips are counted from PCI ids and
device nodes.
"""

from __future__ import annotations

import glob
from typing import Dict, List, Mapping

# Process / per-process chip grids for the host sizes JAX's harness
# knows (one host of 1, 4 or 8 chips; 2 = half of a 2x2 host).
_PROCESS_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "4,2,1"}
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}
# First slice-builder port; child ``i`` listens on BASE_PORT + i.
BASE_PORT = 8476


class ChipPartitionError(RuntimeError):
    """The children asked for cannot each be given chips of their own."""


def local_tpu_chips() -> int:
    """TPU chips this host lets a process open: the PCI count, capped by
    the device nodes present (a sandbox may expose fewer chips than the
    bus shows).  Never initialises a backend, so a launcher parent may
    call it."""
    from jax._src import hardware_utils

    n, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    nodes = glob.glob("/dev/accel[0-9]*") + glob.glob("/dev/vfio/[0-9]*")
    return min(n, len(nodes))


def usable_chips(env: Mapping[str, str]) -> int:
    """How many of this host's TPU chips a child started with ``env``
    would open: none when ``JAX_PLATFORMS`` holds it off the TPU."""
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return local_tpu_chips()


def chip_env(index: int, n_procs: int, *, chips_per_proc: int = 1,
             one_job: bool) -> Dict[str, str]:
    """The libtpu environment that gives child ``index`` of ``n_procs``
    its own ``chips_per_proc`` chips: ``[index * c, (index + 1) * c)``.

    A pure function of its arguments (ports are ``BASE_PORT + index``),
    so sibling children agree on each other's addresses without talking.
    ``one_job=True`` joins the children into one slice (one chip each);
    ``one_job=False`` makes each child a one-process slice of its own.
    """
    if not 0 <= index < n_procs:
        raise ValueError(f"child index {index} outside [0, {n_procs})")
    if chips_per_proc not in _CHIP_BOUNDS:
        raise ChipPartitionError(
            f"no chip grid for {chips_per_proc} chips per process "
            f"(known: {sorted(_CHIP_BOUNDS)})")
    ports = [BASE_PORT + i for i in range(n_procs)]
    if one_job:
        if chips_per_proc != 1:
            raise ChipPartitionError(
                "ranks of one job own one chip each; a process that "
                "drives several chips is the single-process shape")
        if n_procs not in _PROCESS_BOUNDS:
            raise ChipPartitionError(
                f"no process grid for {n_procs} local ranks "
                f"(known: {sorted(_PROCESS_BOUNDS)})")
        process_bounds = _PROCESS_BOUNDS[n_procs]
        addresses, task_id = ports, index
    else:
        process_bounds = "1,1,1"
        addresses, task_id = [ports[index]], 0
    first = index * chips_per_proc
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(first, first + chips_per_proc)),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[chips_per_proc],
        "TPU_PROCESS_BOUNDS": process_bounds,
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"localhost:{p}" for p in addresses),
        "TPU_PROCESS_PORT": str(ports[index]),
        "CLOUD_TPU_TASK_ID": str(task_id),
        # Siblings load libtpu side by side on one host.
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def local_rank_envs(n_local: int, env: Mapping[str, str]) -> List[Dict[str, str]]:
    """Per-rank chip environments for ``n_local`` ranks of one job that
    share this host.  Empty dicts when there is nothing to partition: a
    single rank owns every chip (the deployment shape), and a chipless
    or CPU-pinned job opens none.  Raises :class:`ChipPartitionError`
    BEFORE anything is spawned when the ranks cannot each own a chip."""
    chips = usable_chips(env) if n_local > 1 else 0
    if not chips:
        return [{} for _ in range(n_local)]
    if n_local > chips:
        raise ChipPartitionError(
            f"{n_local} local ranks but this host has {chips} TPU "
            f"chip(s), and a chip belongs to one process.  Run one "
            f"process that drives all chips (`horovodrun -np 1 ...`, "
            f"hvd.size() == {chips}) or at most {chips} ranks")
    return [chip_env(i, n_local, one_job=True) for i in range(n_local)]
