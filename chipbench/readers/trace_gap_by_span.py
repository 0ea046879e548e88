"""The device's idle time by the PROGRAM'S span that covers it.

The idle intervals are those of ``xplane.summarise``'s arithmetic (per
chip: the traced window less the union of its operations).  Each is
labelled by the ``hvd:*`` span (the program's own: an engine phase, a
training step) that covers most of it, if such spans cover at least half
of the gap; else by the ``chipbench:*`` span (the benchmark driver's) that
does, on the same condition; else by nothing.

``{"span": "tick_host", "per": "trace_ticks", "scale": 1000}``: idle
seconds under that span per traced tick (averaged over chips).
``{"span": null}``: the share (percent) of the idle time under NO span at
all.  The spans come from the same decoded file as ``trace_scope_per``'s
operations (``parsed()``, once a process); the log gets the idle time by
label."""

from __future__ import annotations

from collections import defaultdict
from typing import Dict, List, Tuple

from chipbench import harness, xplane
from chipbench.readers import trace_scope_per

NO_SPAN = "(no span)"


def label(s: float, e: float, spans) -> str:
    """The span covering most of ``[s, e]``: a program span (``hvd:``)
    where those cover at least half of it, else the benchmark's own on
    the same condition."""
    best, covered = {}, defaultdict(float)
    for name, a, b in spans:
        c = min(e, b) - max(s, a)
        if c <= 0:
            continue
        prefix = next(p for p in trace_scope_per.SPAN_PREFIXES
                      if name.startswith(p))
        covered[prefix] += c
        if c > best.get(prefix, ("", 0.0))[1]:
            best[prefix] = (name, c)
    for prefix in trace_scope_per.SPAN_PREFIXES:
        if covered[prefix] >= 0.5 * (e - s):
            return best[prefix][0]
    return NO_SPAN


def idle_by_span(ops: Dict[int, List[Tuple]], spans) -> Dict[str, float]:
    """Idle seconds (averaged over chips) by the label of each gap."""
    first = min(ev[1] for evs in ops.values() for ev in evs)
    last = max(ev[2] for evs in ops.values() for ev in evs)
    near = [sp for sp in spans if sp[2] > first and sp[1] < last]
    out: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        busy = xplane.union((s, e) for _, s, e in evs)
        for s, e in xplane.subtract([(first, last)], busy):
            out[label(s, e, near)] += (e - s) / len(ops)
    return dict(out)


def read(obs: dict, args: dict):
    tr = trace_scope_per.parsed(obs)
    if tr is None:
        return None
    if "idle" not in tr:
        tr["idle"] = idle_by_span(tr["ops"], tr["spans"])
        harness.say("device idle by covering span (s): " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(tr["idle"].items(),
                                              key=lambda kv: -kv[1])[:8]))
    idle = tr["idle"]
    total = sum(idle.values())
    if args.get("span") is None:
        if total <= 0 or not tr["spans"]:
            return None
        return 100.0 * idle.get(NO_SPAN, 0.0) / total
    n = obs.get(args["per"])
    sec = idle.get(args["span"])
    if not n or sec is None:
        return None
    return args.get("scale", 1.0) * sec / n
