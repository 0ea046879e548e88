"""Device time by the PROGRAM'S OWN names: self time of the device
operations whose ``op_name`` holds a given scope, per traced tick or step.

Every operation has at most ONE name of the program: the INNERMOST
component of its ``op_name`` (the ``tf_op`` stat of an ``XLA Ops`` event's
metadata; a ``jax.named_scope``, a jitted function's name and a Pallas
call's ``name=`` are components of it, backward operations carry them
inside ``transpose(jvp(..))``) that is a scope of the vocabulary or a
kernel's name (``hvd_*``).  So the scopes PARTITION the device's time: the
kernel inside ``paged_attend`` is ``hvd_paged_attend``'s, the layer scan's
own slicing is ``layer_scan``'s and what the layer does inside it is the
layer's scopes'.  The vocabulary is the BENCHMARK'S, a data file
(``chipbench/scope_vocabulary.json``): what counts as unscoped is not the
program's to say, and a scope the program adds reads as unscoped until the
benchmark lists it (``chipbench/tests`` compare the two).

``{"scope": ["attn_qkv", "attn_out", "mlp"], "within": "_tick", "per":
"trace_ticks", "scale": 1000}``: self time of the operations whose name is
in ``scope`` — and, if ``within`` is given, whose ``op_name`` has that
component anywhere (``_tick`` of ``jit(_tick)``: one executable).
``"fallback"`` names the scope to read where nothing carries ``scope``.
``{"scope": null}`` is the share (percent) of device time in operations
with NO such name.  A trace in which no operation carries a name of the
program (the parent of the PR that brought the scopes) has nothing to
read, for either form; one that carries names but none of ``scope`` reads
0 (a window without a chunk, an update the compiler fused away): the
metric stays in the line.

``obs`` carries only the trace's summary, and ``xplane.load`` keeps only
``(name, start, end)``; what an operation's metadata says is in the file.
So this module finds the ``.xplane.pb`` this process wrote (the drivers
leave it under ``.chipbench_work/trace/<cell>/``; a file older than the
process is another run's and reads as nothing) and decodes it itself: a
minimal reader of the protobuf wire format, of the few fields it needs
(``tensorflow.tsl.profiler.protobuf.xplane_pb2`` is in the image, but
importing it brings all of TensorFlow into the benchmark's process, ~10 s).
It reads the device planes' ``XLA Ops`` lines whole and, of every other
plane, only the events named ``hvd:*`` / ``chipbench:*`` (the rest of a host
plane is skipped by its length, not decoded).  The arithmetic — nesting,
self time — is ``xplane``'s.  The file is parsed once a process
(``parsed()``, shared with ``trace_gap_by_span``); the log gets its size,
the seconds the parse took and the ten largest operations with their scope
and ``source`` line."""

from __future__ import annotations

import glob
import os
import re
import struct
import time
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import harness, xplane

SPAN_PREFIXES = ("hvd:", "chipbench:")
KERNEL_PREFIX = "hvd_"
_SPLIT = re.compile(r"[^A-Za-z0-9_.\-]+")

# -- the wire format, as far as an XSpace needs it -----------------------------


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf, i: int = 0, end: Optional[int] = None
           ) -> Iterator[Tuple[int, int, int, int]]:
    """``(field number, wire type, a, b)`` of one message: a varint's
    value in ``a``; a length-delimited field's span ``buf[a:b]``; a
    fixed64's span likewise."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield num, wire, v, 0
        elif wire == 2:
            n, i = _varint(buf, i)
            yield num, wire, i, i + n
            i += n
        elif wire == 1:
            yield num, wire, i, i + 8
            i += 8
        elif wire == 5:
            yield num, wire, i, i + 4
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _text(buf, a: int, b: int) -> str:
    return bytes(buf[a:b]).decode("utf-8", "replace")


def _map_entry(buf, a: int, b: int) -> Tuple[int, int, int]:
    """A ``map<int64, Message>`` entry: ``(key, value span)``."""
    key, va, vb = 0, a, a
    for num, wire, x, y in fields(buf, a, b):
        if num == 1 and wire == 0:
            key = x
        elif num == 2 and wire == 2:
            va, vb = x, y
    return key, va, vb


def _stat(buf, a: int, b: int, stat_names: Dict[int, str]):
    """An ``XStat``: ``(its name, its value)``; a string may be given by
    reference to a stat-metadata name."""
    name, value = None, None
    for num, wire, x, y in fields(buf, a, b):
        if num == 1:
            name = stat_names.get(x)
        elif num == 2 and wire == 1:
            value = struct.unpack_from("<d", buf, x)[0]
        elif num in (3, 4) and wire == 0:
            value = x
        elif num == 5 and wire == 2:
            value = _text(buf, x, y)
        elif num == 7 and wire == 0:
            value = stat_names.get(x, "")
    return name, value


def _event_metadata(buf, a: int, b: int, stat_names: Dict[int, str],
                    want=("tf_op", "source", "hlo_category")) -> dict:
    """``name`` is the short one where the file has both (``fusion.4`` of
    an HLO instruction's whole text); ``full`` the other (a host span's
    ``hvd:admit``, whose short form drops the prefix)."""
    out = {"name": "", "full": ""}
    for num, wire, x, y in fields(buf, a, b):
        if num == 2 and wire == 2:
            out["full"] = _text(buf, x, y)
        elif num == 4 and wire == 2:
            out["name"] = _text(buf, x, y)
        elif num == 5 and wire == 2 and want:
            k, v = _stat(buf, x, y, stat_names)
            if k in want:
                out[k] = v
    out["name"] = out["name"] or out["full"]
    return out


def _line_events(buf, a: int, b: int, wanted=None
                 ) -> Tuple[str, List[Tuple[int, float, float]]]:
    """A line's name and its events as ``(metadata id, start s, end s)``;
    with ``wanted`` (a set of metadata ids) every other event is skipped
    after its first field."""
    name, t0_ns, spans = "", 0, []
    for num, wire, x, y in fields(buf, a, b):
        if num == 2 and wire == 2:
            name = _text(buf, x, y)
        elif num == 3 and wire == 0:
            t0_ns = x
        elif num == 4 and wire == 2:
            spans.append((x, y))
    events = []
    for x, y in spans:
        mid = off = dur = 0
        for num, wire, v, _ in fields(buf, x, y):
            if num == 1:
                mid = v
                if wanted is not None and mid not in wanted:
                    break
            elif num == 2 and wire == 0:
                off = v
            elif num == 3 and wire == 0:
                dur = v
        else:
            s = t0_ns * 1e-9 + off * 1e-12
            events.append((mid, s, s + dur * 1e-12))
    return name, events


def decode(path: str) -> dict:
    """``{"bytes", "seconds", "ops": {chip: [(metadata id, start, end)]},
    "meta": {chip: {metadata id: {"name", "tf_op", "source", ..}}},
    "spans": [(name, start, end)]}`` of one ``.xplane.pb``."""
    t = time.monotonic()
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    ops, meta, spans = {}, {}, []
    for num, wire, pa, pb in fields(buf):
        if num != 1 or wire != 2:
            continue
        name, lines, emeta, smeta = "", [], [], []
        for n, w, x, y in fields(buf, pa, pb):
            if w != 2:
                continue
            if n == 2:
                name = _text(buf, x, y)
            elif n == 3:
                lines.append((x, y))
            elif n == 4:
                emeta.append((x, y))
            elif n == 5:
                smeta.append((x, y))
        chip = xplane.DEVICE_PLANE.match(name)
        if chip:
            stat_names = {}
            for x, y in smeta:
                key, va, vb = _map_entry(buf, x, y)
                for n, w, p, q in fields(buf, va, vb):
                    if n == 2 and w == 2:
                        stat_names[key] = _text(buf, p, q)
            table = {}
            for x, y in emeta:
                key, va, vb = _map_entry(buf, x, y)
                table[key] = _event_metadata(buf, va, vb, stat_names)
            for x, y in lines:
                lname, events = _line_events(buf, x, y)
                if lname == xplane.OPS_LINE and events:
                    ops[int(chip.group(1))] = events
                    meta[int(chip.group(1))] = table
            continue
        wanted = {}
        for x, y in emeta:
            key, va, vb = _map_entry(buf, x, y)
            md = _event_metadata(buf, va, vb, {}, want=())
            if md["full"].startswith(SPAN_PREFIXES):
                wanted[key] = md["full"]
        if wanted:
            ids = set(wanted)
            for x, y in lines:
                spans += [(wanted[m], s, e)
                          for m, s, e in _line_events(buf, x, y, ids)[1]]
    return {"bytes": len(buf), "seconds": time.monotonic() - t, "ops": ops,
            "meta": meta, "spans": spans, "path": path}


# -- the file this process wrote, parsed once ---------------------------------

_PARSED: Dict[str, Optional[dict]] = {}


def _process_started() -> float:
    try:
        return os.stat(f"/proc/{os.getpid()}").st_ctime
    except OSError:
        return 0.0


def find_trace(root: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``.chipbench_work/trace/`` that this
    process wrote (None if there is none: an older file is another
    run's)."""
    root = root or os.path.join(harness.ROOT, ".chipbench_work", "trace")
    files = glob.glob(os.path.join(root, "*", "plugins", "profile", "*",
                                   "*.xplane.pb"))
    files = [f for f in files
             if os.path.getmtime(f) >= _process_started() - 1.0]
    return max(files, key=os.path.getmtime) if files else None


def parsed(obs: dict) -> Optional[dict]:
    """The decoded trace of this run, with device self time by operation;
    None where the run took no trace or the file is not there."""
    if not obs.get("trace"):
        return None
    path = obs.get("trace_file") or find_trace()
    if path is None:
        return None
    if path not in _PARSED:
        try:
            tr = decode(path)
        except (OSError, ValueError, IndexError, struct.error) as e:
            harness.say(f"trace {path}: not decoded ({e!r})")
            tr = None
        if tr is not None and tr["ops"]:
            tr["self"] = self_by_operation(tr)
            harness.say(
                f"trace file {path}: {tr['bytes']} bytes, decoded in "
                f"{tr['seconds']:.2f} s; {sum(map(len, tr['ops'].values()))}"
                f" device operations on {len(tr['ops'])} chip(s), "
                f"{len(tr['spans'])} hvd:/chipbench: spans")
            for line in top_table(tr):
                harness.say(line)
        else:
            tr = None
        _PARSED[path] = tr
    return _PARSED[path]


# -- the arithmetic ------------------------------------------------------------


def components(op_name: str) -> List[str]:
    """``jit(_tick)/while/body/transpose(jvp(mlp))/dot_general:`` ->
    ``[jit, _tick, while, body, transpose, jvp, mlp, dot_general]``."""
    return [c for c in _SPLIT.split(op_name or "") if c]


def self_by_operation(tr: dict) -> List[dict]:
    """One row per distinct operation (metadata entry) of the device
    planes: its self seconds averaged over the chips, its name, ``op_name``
    components and ``source``."""
    rows: Dict[tuple, dict] = {}
    n = len(tr["ops"])
    for chip, events in tr["ops"].items():
        table = tr["meta"][chip]
        for mid, own in xplane.self_seconds(events):
            md = table.get(mid, {"name": str(mid)})
            key = (md["name"], md.get("tf_op") or "", md.get("source") or "")
            row = rows.get(key)
            if row is None:
                row = rows[key] = {
                    "name": key[0], "tf_op": key[1], "source": key[2],
                    "category": md.get("hlo_category") or "",
                    "parts": components(key[1]), "seconds": 0.0}
            row["seconds"] += own / n
    return sorted(rows.values(), key=lambda r: -r["seconds"])


def known_scopes() -> frozenset:
    """The scopes the benchmark knows (``scope_vocabulary.json``)."""
    return frozenset(harness.load_json("scope_vocabulary.json")["scopes"])


def named(row: dict, vocabulary) -> Optional[str]:
    """The program's name for an operation: the innermost component of
    its ``op_name`` that is a scope of the vocabulary or a kernel's
    name."""
    for part in reversed(row["parts"]):
        if part in vocabulary or part.startswith(KERNEL_PREFIX):
            return part
    return None


def seconds_under(rows: List[dict], scope, vocabulary,
                  within: Optional[str] = None) -> float:
    want = {scope} if isinstance(scope, str) else set(scope)
    return sum(r["seconds"] for r in rows
               if named(r, vocabulary) in want
               and (within is None or within in r["parts"]))


def top_table(tr: dict, n: int = 10) -> List[str]:
    known = known_scopes()
    total = sum(r["seconds"] for r in tr["self"]) or 1.0
    by_name: Dict[str, float] = defaultdict(float)
    for r in tr["self"]:
        by_name[named(r, known) or "-"] += r["seconds"]
    out = ["device self time by the program's name (s): " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(by_name.items(),
                                          key=lambda kv: -kv[1])),
           f"largest device operations (self s, share, program's name, "
           f"source | op_name) of {total:.3f} s:"]
    for r in tr["self"][:n]:
        out.append(
            f"  {r['name'][:40]:<40} {r['seconds']:.4f} "
            f"{100 * r['seconds'] / total:5.1f}% "
            f"{named(r, known) or '-':<16} "
            f"{r['source'] or '-'} | {r['tf_op'][:120] or '-'}")
    return out


def read(obs: dict, args: dict):
    tr = parsed(obs)
    if tr is None:
        return None
    rows, known = tr["self"], known_scopes()
    if args.get("scope") is None:
        total = sum(r["seconds"] for r in rows)
        bare = sum(r["seconds"] for r in rows if named(r, known) is None)
        if total <= 0 or bare >= total:
            return None
        return 100.0 * bare / total
    n = obs.get(args["per"])
    if not n:
        return None
    sec = seconds_under(rows, args["scope"], known, args.get("within"))
    if sec <= 0 and args.get("fallback"):
        sec = seconds_under(rows, args["fallback"], known,
                            args.get("within"))
    if sec <= 0 and not any(named(r, known) for r in rows):
        return None
    return args.get("scale", 1.0) * sec / n
