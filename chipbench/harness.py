"""What every cell shares: finding a cell's files by the names
``BENCHMARK.json`` gives, the device record, the readers of metrics, and
the result line.  Nothing here knows a configuration, a traffic mix or a
metric by name: a later PR adds files and one entry, and edits nothing."""

from __future__ import annotations

import importlib
import json
import os
from typing import Callable, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChipError(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration and its traffic, by name."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = dict(cells[workload])
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf_path = os.path.join(root, conf["file"])
    with open(conf_path) as f:
        cell["dims"] = json.load(f)
    # the traffic mix lives beside the configurations: <dir>/traffic/
    with open(os.path.join(os.path.dirname(os.path.dirname(conf_path)),
                           "traffic", cell["traffic"] + ".json")) as f:
        cell["traffic_params"] = json.load(f)
    cell["bench"] = bench
    return cell


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """Fail — never fall back — where the accelerator is missing."""
    dev = device_record()
    if dev["platform"] != "tpu" or dev["count"] < chips:
        raise NoChipError(
            f"this cell needs {chips} TPU chip(s); JAX found platform "
            f"{dev['platform']!r} with {dev['count']} device(s) of kind "
            f"{dev['kind']!r}.  Nothing was measured.")
    return dev


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip, as the backend reports it."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def place_caches() -> str:
    """The program's rule for the compile cache (its own entry point), and
    no lower threshold of ours on what is worth caching: the second run of
    a cell in a checkout has to find EVERY program."""
    import jax

    import horovod_tpu as hvd

    path = hvd.place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class WindowWatch:
    """What must NOT happen inside a measured window, counted from JAX's
    own monitoring events and the interpreter's collector: backend
    compilations (of any jitted function, not only those the program
    counts) and garbage collections long enough to stall every thread."""

    def __init__(self) -> None:
        self.compiles: list = []
        self.gc_pauses: list = []
        self._armed = False
        self._gc_t = 0.0

    def install(self) -> "WindowWatch":
        import gc
        import time

        import jax.monitoring as mon

        def on_duration(event, secs, **_):
            if self._armed and "backend_compile" in event:
                self.compiles.append(round(secs, 3))

        def on_gc(phase, info):
            if phase == "start":
                self._gc_t = time.monotonic()
            elif self._armed:
                d = time.monotonic() - self._gc_t
                if d > 0.05:
                    self.gc_pauses.append((info.get("generation"),
                                           round(d, 3)))

        mon.register_event_duration_secs_listener(on_duration)
        gc.callbacks.append(on_gc)
        return self

    def arm(self, on: bool) -> None:
        self._armed = on

    def line(self) -> str:
        return (f"inside the window: backend compilations "
                f"{len(self.compiles)} {self.compiles[:4]}, garbage "
                f"collections over 50 ms {self.gc_pauses[:4]}")


def metric_specs(cell: dict, which: str) -> Dict[str, dict]:
    """The cell's metrics of one kind (``end_to_end`` / ``per_layer``) as
    ``{name: its data file}``; ``setup_s`` is the harness's own."""
    out = {}
    for m in cell["bench"][which]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if m["name"] == "setup_s":
            continue
        folder = "end_metrics" if which == "end_to_end" else "layer_metrics"
        spec = load_json(folder, m["name"] + ".json")
        spec["unit"] = m["unit"]
        out[m["name"]] = spec
    return out


def read_metrics(cell: dict, which: str, obs: dict) -> Dict[str, dict]:
    """Run each metric's reader over what the run observed.  A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for name, spec in metric_specs(cell, which).items():
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        value = reader.read(obs, spec.get("args", {}))
        if value is not None:
            out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict,
                breakdown: Optional[dict] = None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    return json.dumps(line)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t0: float, control: bool = False, root: str = ROOT,
             need_chip: bool = True,
             out: Callable[[str], None] = print) -> int:
    """One run of one cell; prints the result line last.  ``need_chip``
    is False only in the CPU tests, which then never see a result line's
    device metrics judged (``correct`` and the flow are what they test)."""
    cell = load_cell(workload, root)
    device = require_chips(cell["chips"]) if need_chip else device_record()
    say(f"cell {workload}: config {cell['config']} traffic {cell['traffic']}"
        f" chips {cell['chips']} seed {seed} seconds {seconds} trace "
        f"{int(trace)} on platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['dims']['kind']}")
    res = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     control=control, t0=t0, device=device)
    obs = res["obs"]
    if device["platform"] != "tpu":
        # a time, a rate or a share from a CPU run is never written under
        # the name of a device metric
        say("not on a TPU: every metric withheld from the result line")
        metrics = {}
    elif trace:
        metrics = read_metrics(cell, "per_layer", obs)
    else:
        metrics = read_metrics(cell, "end_to_end", obs)
        metrics["setup_s"] = {"value": res["setup_s"], "unit": "s"}
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    breakdown = None
    if trace and obs.get("trace"):
        tr = obs["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    out(result_line(correct=res["correct"], attempted=res["attempted"],
                    failed=res["failed"], metrics=metrics, device=device,
                    breakdown=breakdown))
    return 0
