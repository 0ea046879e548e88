"""From a profiler trace (``.xplane.pb``) to numbers: the one reduction
every PR's traced run goes through.

``load`` reads the file with nothing but JAX (``jax.profiler.ProfileData``)
into plain tuples; everything after that is arithmetic on intervals and is
tested on hand-made intervals and on a small recorded trace
(``tests/data/``).  Times are seconds.

What is read: on every DEVICE plane (``/device:TPU:n``) the line of XLA
operations (``XLA Ops``) — one event per operation the core ran, with its
start and duration.  Busy time is the union of those intervals; the traced
window is the span from the first device event to the last over all
chips; operations nest (a ``while`` holds its body), so a sum by name takes
each event's SELF time; a kernel's time is that sum over the names its
pattern matches; exposed collective time is the part of the collective
operations' intervals during which no other operation ran on that chip;
an idle gap is the space between two busy intervals, labelled by the host
span that covers most of it: the program's own (``hvd:*``: an engine
phase) where those cover at least half of the gap, else the benchmark
driver's (``chipbench:*``) on the same condition — the rule of
``readers/trace_gap_by_span`` — else whichever span covers most of it, or
``no benchmark span``."""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
# as the readers take them (``readers/trace_scope_per.SPAN_PREFIXES``), the
# program's before the benchmark's
SPAN_PREFIXES = ("hvd:", "chipbench:")
NO_SPAN = "no benchmark span"

Interval = Tuple[float, float]


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """``{plane name: {line name: [(event name, start_s, end_s)]}}``."""
    from jax.profiler import ProfileData

    out: Dict[str, Dict[str, list]] = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            ev = lines.setdefault(line.name, [])
            for e in line.events:
                s = e.start_ns * 1e-9
                ev.append((e.name, s, s + e.duration_ns * 1e-9))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union ``a`` not covered by union ``b`` (both merged)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _nest(events):
    """``[name, start, end, self seconds, is leaf]`` per event: operations
    nest (a ``while`` holds its body's operations)."""
    out: List[list] = []
    stack: List[Tuple[float, int]] = []          # (end, index into out)
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0] < e:   # inside only if it ends inside
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[3] -= e - s
            parent[4] = False
        out.append([n, s, e, e - s, True])
        stack.append((e, len(out) - 1))
    return out


def self_seconds(events) -> List[Tuple[str, float]]:
    """Each event's own time: its duration less that of the events nested
    directly inside it, so that a sum by name counts nothing twice."""
    return [(n, max(own, 0.0)) for n, _, _, own, _ in _nest(events)]


def leaves(events) -> List[Tuple[str, float, float]]:
    """The events that hold no other event (what actually ran)."""
    return [(n, s, e) for n, s, e, _, leaf in _nest(events) if leaf]


def device_ops(planes: dict) -> Dict[int, List[Tuple[str, float, float]]]:
    """chip index -> its XLA-operation events."""
    out = {}
    for name, lines in planes.items():
        m = DEVICE_PLANE.match(name)
        if m and lines.get(OPS_LINE):
            out[int(m.group(1))] = lines[OPS_LINE]
    return out


def host_spans(planes: dict) -> List[Tuple[str, float, float]]:
    """The program's and the benchmark's annotations, from any host line."""
    return [ev for name, lines in planes.items()
            if not DEVICE_PLANE.match(name)
            for events in lines.values() for ev in events
            if ev[0].startswith(SPAN_PREFIXES)]


def summarise(planes: dict) -> Optional[dict]:
    """Everything the per-layer readers ask of a trace; None if no device
    operation was traced."""
    ops = device_ops(planes)
    if not ops:
        return None
    first = min(e[1] for evs in ops.values() for e in evs)
    last = max(e[2] for evs in ops.values() for e in evs)
    spans = _by_start(host_spans(planes))
    busy, exposed, by_name, gaps = [], [], defaultdict(float), \
        defaultdict(float)
    for chip, evs in ops.items():
        merged = union((s, e) for _, s, e in evs)
        busy.append(total(merged))
        ran = leaves(evs)   # a container would hide what is inside it
        coll = union((s, e) for n, s, e in ran if COLLECTIVE.search(n))
        rest = union((s, e) for n, s, e in ran if not COLLECTIVE.search(n))
        exposed.append(total(subtract(coll, rest)))
        for n, sec in self_seconds(evs):
            by_name[n] += sec / len(ops)
        idle = subtract([(first, last)], merged)
        for s, e in idle:
            gaps[_label(s, e, spans)] += (e - s) / len(ops)
    n = len(ops)
    return {
        "chips": n, "window_s": last - first, "busy_s": sum(busy) / n,
        "exposed_collective_s": sum(exposed) / n,
        "op_seconds": dict(by_name),
        "device_ops": _top(by_name), "idle_gaps": _top(gaps),
    }


def _by_start(spans) -> Tuple[list, List[float], List[float]]:
    """The spans in order of their start, those starts, and the latest end
    among the spans up to each: what ``_label`` needs to look at only the
    spans near a gap (a serving trace holds thousands of both)."""
    spans = sorted(spans, key=lambda sp: sp[1])
    ends, latest = [], float("-inf")
    for _, _, b in spans:
        latest = max(latest, b)
        ends.append(latest)
    return spans, [a for _, a, _ in spans], ends


def _label(s: float, e: float, index) -> str:
    """The name, less its prefix, of the span the gap ``[s, e]`` goes
    under: the engine's phase wins where both kinds cover the gap."""
    spans, starts, ends = index
    best: Dict[str, Tuple[str, float]] = {}
    covered: Dict[str, float] = defaultdict(float)
    i = bisect.bisect_left(starts, e) - 1       # the last span begun by e
    while i >= 0 and ends[i] > s:
        name, a, b = spans[i]
        i -= 1
        c = min(e, b) - max(s, a)
        if c <= 0:
            continue
        prefix = next(p for p in SPAN_PREFIXES if name.startswith(p))
        covered[prefix] += c
        if c > best.get(prefix, ("", 0.0))[1]:
            best[prefix] = (name[len(prefix):], c)
    for prefix in SPAN_PREFIXES:
        if covered[prefix] >= 0.5 * (e - s):
            return best[prefix][0]
    if not best:
        return NO_SPAN
    return max(best.values(), key=lambda nc: nc[1])[0]


def _top(seconds_by_name: Dict[str, float], n: int = 10) -> list:
    return [[k[:96], v] for k, v in sorted(
        seconds_by_name.items(), key=lambda kv: -kv[1])[:n]]


def op_seconds(summary: dict, pattern: str) -> float:
    """Seconds (averaged over chips) in operations whose name matches."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["op_seconds"].items() if rx.search(k))
