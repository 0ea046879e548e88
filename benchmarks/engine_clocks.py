"""The engine thread's time on two clocks, over one benchmark window.

``python3 benchmarks/engine_clocks.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>`` loads the cell as ``chipbench/run.py`` does, calls the
cell's own driver (``chipbench.drivers.<kind>.run``) unchanged, and prints
from the ``/stats`` snapshots the driver took at the window's ends: per loop
iteration each of the eleven phases' wall and CPU seconds, the time under no
phase, the collector's pauses, the window's slow-step records and the cell's
end-to-end metrics; with ``--trace 1`` also the program's spans on the trace
and the device's idle time by the ``hvd:`` span covering it.  Chip only."""

import time

_T0 = time.monotonic()  # set-up is counted from here, as in chipbench/run.py

import argparse  # noqa: E402
import collections  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.readers import trace_gap_by_span, trace_scope_per  # noqa: E402
from horovod_tpu.serving.metrics import PHASES, phase_key  # noqa: E402


def _grown(s0: dict, s1: dict, key: str) -> float:
    """What a ``/stats`` histogram's sum, or a counter, grew by."""
    a, b = s0[key], s1[key]
    return b["sum"] - a["sum"] if isinstance(b, dict) else float(b - a)


def _cpu_clock_step() -> float:
    """The step ``time.thread_time()`` takes here (where it is 10 ms, only
    a window's sums mean much: a phase reads 0 or 0.01)."""
    t0 = t = time.thread_time()
    while t == t0:
        t = time.thread_time()
    return t - t0


def _collections(s0: dict, s1: dict, key: str) -> str:
    grew = {edge: n - s0[key]["buckets"].get(edge, 0)
            for edge, n in s1[key]["buckets"].items()}
    longest = [edge for edge, n in grew.items() if n > 0][-1:] or ["-"]
    return (f"{s1[key]['count'] - s0[key]['count']} in "
            f"{_grown(s0, s1, key):.4f} s, longest bucket <= {longest[0]} s")


def table(s0: dict, s1: dict) -> str:
    """The window's means per loop iteration, in ms, on both clocks."""
    iters, ticks = (s1[k]["count"] - s0[k]["count"] for k in (
        "engine_loop_seconds", "tick_dispatch_seconds"))
    rows = [(name, _grown(s0, s1, phase_key(name)),
             _grown(s0, s1, phase_key(name, cpu=True))) for name in PHASES]
    loop, loop_cpu = (_grown(s0, s1, k) for k in (
        "engine_loop_seconds", "engine_loop_cpu_seconds"))
    rows += [("under no phase", loop - sum(r[1] for r in rows),
              loop_cpu - sum(r[2] for r in rows)),
             ("the loop", loop, loop_cpu)]
    lines = [f"engine loop over the window: {iters} iterations, {ticks} "
             f"decode ticks; ms per iteration (and the window's seconds)",
             " " * 18 + "".join(f"{h:>10}" for h in (
                 "wall", "cpu", "wall-cpu", "wall s", "cpu s"))]
    lines += [f"{name:18}{1e3 * w / iters:10.4f}{1e3 * c / iters:10.4f}"
              f"{1e3 * (w - c) / iters:10.4f}{w:10.4f}{c:10.4f}"
              for name, w, c in rows]
    lines += [f"collections, {which}: {_collections(s0, s1, key)}"
              for which, key in (("all generations", "gc_pause_seconds"),
                                 ("generation 2", "gc_pause_seconds_gen2"))]
    lines += [f"slow steps: {s1['slow_steps_total'] - s0['slow_steps_total']} "
              f"in {_grown(s0, s1, 'slow_step_seconds_total'):.4f} s"]
    lines += ["slow step: " + json.dumps(r) for r in s1["slow_steps"]
              if r not in s0["slow_steps"]]
    return "\n".join(lines)


def run(workload: str, seed: int, seconds: float, trace: bool) -> int:
    cell = harness.load_cell(workload)
    device = harness.require_chips(cell["chips"])
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['dims']['kind']}")
    res = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     control=False, t0=_T0, device=device)
    obs = res["obs"]
    print(f"[engine_clocks] {workload} seed {seed} on {device['platform']} "
          f"{device['kind']}: correct {res['correct']}, failed "
          f"{res['failed']} of {res['attempted']}, setup_s "
          f"{res['setup_s']:.1f}; the CPU clock steps by "
          f"{1e3 * _cpu_clock_step():.4f} ms")
    if "stats0" not in obs:     # a training cell
        return 0
    print(table(obs["stats0"], obs["stats1"]))
    for name, m in harness.read_metrics(cell, "end_to_end", obs).items():
        print(f"[engine_clocks] {name} {m['value']!r} {m['unit']}")
    tr = trace_scope_per.parsed(obs) if trace else None
    if tr is not None:
        names = collections.Counter(sp[0] for sp in tr["spans"])
        print("spans on the trace: " + ", ".join(
            f"{k} {n}" for k, n in sorted(names.items())))
        idle = trace_gap_by_span.idle_by_span(tr["ops"], tr["spans"])
        print("device idle by covering span (s): " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(idle.items(),
                                              key=lambda kv: -kv[1])))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    for flag, kind in (("--workload", str), ("--seed", int),
                       ("--seconds", float)):
        ap.add_argument(flag, type=kind, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    sys.exit(run(args.workload, args.seed, args.seconds, bool(args.trace)))
