"""Tunable serving knobs with COMPILE-SAFE bounds.

The reference ``ParameterManager`` tunes knobs whose application is
free (buffer sizes, cycle times).  A serving engine's knobs are not
free by default: most config fields select XLA *shapes*, and an XLA
compile inside the serving loop blows the watchdog budget and every
latency SLO.  This module is the contract that makes online tuning
safe: a knob enters the online space ONLY with candidate values that
map to executables the engine has ALREADY WARMED, so the tuner can
apply any sample at any tick boundary and the engine never traces —
``decode_compilations`` stays at the warmed count across the whole
tuning trajectory (the acceptance guard in ``tests/test_tuning.py``).

The online space, derived from a warmed engine by
:func:`online_knob_space`:

* ``max_prefills_per_tick`` — BO'd integer in ``[1, warmed_kmax]``:
  warmup compiled batched prefill for every k up to the construction
  value, so any smaller k is a warm shape.  Applied by rebuilding the
  frozen ``EngineConfig`` (``dataclasses.replace``) AND mutating the
  live ``Scheduler.max_prefills_per_tick`` — both read the knob.
* ``prefill_chunk_tokens`` — BO'd integer WITHIN the warmed chunk
  bucket ``(B/2, B]`` (present only when chunking is on): every value
  in that interval buckets to the same power-of-two compile shape
  (``_ingest_step`` pads each chunk to ``_bucket(chunk)``), so the
  knob moves the per-tick ingestion/admission token budget at
  constant shape.  Cross-bucket moves mint new prefill + suffix
  shapes and are OFFLINE (replay) territory.
* ``page_grant_ahead`` — swept categorical {0, 1, 2} pages: how far
  past the write position decode growth grants pages
  (``_ensure_write_page``).  Pure page-table data — trades grant-call
  overhead against page-pressure eviction headroom.
* ``spec_enabled`` — swept categorical {on, off} (speculative engines
  only): both tick executables (draft/verify and plain) are warmed by
  construction, and the toggle is admission-mask DATA
  (``_spec_runtime_enabled``), so flipping it never compiles and —
  like every knob here — never changes emitted tokens.

Every knob also declares its score direction (informational — the
tuner scalarizes one weighted objective), the number of scoring
windows to DISCARD after an apply (settling time: in-flight requests
still reflect the old setting), and a human-readable apply path for
``GET /tuning`` and the docs table.

Constructor-level knobs (``kv_dtype``, ``n_slots``, ``page_size``,
``spec_k``, ``paged_kernel``) cannot be applied to a live engine at any
price — they are the offline space :mod:`horovod_tpu.tuning.replay`
explores by rebuilding an engine per sample (``paged_kernel`` is baked
into the tick executables at trace time, exactly like ``kv_dtype``:
``--set paged_kernel=true`` on a replay run A/Bs the fused Pallas
decode kernel against the unfused gather path).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Tuple

__all__ = ["Knob", "KnobSpace", "online_knob_space", "apply_settings"]


@dataclass(frozen=True)
class Knob:
    """One tunable knob and its compile-safe candidate set.

    ``kind`` routes it to the reference split: ``"sweep"`` knobs are
    exhaustively swept (``CategoricalSweep``), ``"bo"`` knobs form the
    jointly-BO'd box (integer-valued; suggestions are rounded then
    clamped).  ``candidates`` (sweep) / ``bounds`` (bo, inclusive)
    contain ONLY values the warmed engine can apply without tracing.
    """

    name: str
    default: object
    kind: str                      # "sweep" | "bo"
    candidates: Tuple = ()
    bounds: Tuple[int, int] = (0, 0)
    direction: str = "max"         # which way better scores move it
    #: scoring windows to discard after applying a new value —
    #: requests admitted under the old setting are still in flight.
    discard_windows: int = 1
    apply_path: str = ""           # human-readable, for /tuning + docs

    def clamp(self, value):
        if self.kind == "bo":
            lo, hi = self.bounds
            return int(min(max(int(round(float(value))), lo), hi))
        return value if value in self.candidates else self.default


class KnobSpace:
    """The online knob set for ONE engine, with apply machinery."""

    def __init__(self, knobs: List[Knob]):
        self.knobs = list(knobs)
        by_name = [k.name for k in knobs]
        if len(set(by_name)) != len(by_name):
            raise ValueError(f"duplicate knob names: {by_name}")

    @property
    def sweep_knobs(self) -> List[Knob]:
        return [k for k in self.knobs if k.kind == "sweep"]

    @property
    def bo_knobs(self) -> List[Knob]:
        return [k for k in self.knobs if k.kind == "bo"]

    def defaults(self) -> Dict[str, object]:
        return {k.name: k.default for k in self.knobs}

    def clamp(self, settings: Dict[str, object]) -> Dict[str, object]:
        """Round/clamp a proposal into the compile-safe set (unknown
        keys dropped — a stale proposal must never reach the engine)."""
        known = {k.name: k for k in self.knobs}
        return {name: known[name].clamp(v)
                for name, v in settings.items() if name in known}

    def describe(self) -> List[Dict]:
        """The /tuning + docs view of the space."""
        out = []
        for k in self.knobs:
            out.append({
                "name": k.name, "kind": k.kind,
                "default": k.default,
                "candidates": list(k.candidates) if k.kind == "sweep"
                else list(range(k.bounds[0], k.bounds[1] + 1)),
                "direction": k.direction,
                "discard_windows": k.discard_windows,
                "apply": k.apply_path,
            })
        return out


def online_knob_space(engine) -> KnobSpace:
    """Derive the compile-safe online space from a WARMED engine.

    Bounds come from the engine's actual warmed state — the prefill
    compile cache and construction-time config — never from what a
    config "could" support: a knob value outside what warmup compiled
    would trace mid-serving.
    """
    cfg = engine.engine_cfg
    knobs: List[Knob] = []

    # Warmup compiles batched prefill for every k in [1, kmax]:
    # any k <= the construction value is a warm shape.
    kmax = min(cfg.max_prefills_per_tick, cfg.n_slots)
    if kmax > 1:
        knobs.append(Knob(
            name="max_prefills_per_tick", default=kmax, kind="bo",
            bounds=(1, kmax),
            apply_path="EngineConfig replace + Scheduler."
                       "max_prefills_per_tick at the tick boundary"))

    # Chunk budget: only within the warmed power-of-two bucket — every
    # value in (B/2, B] pads to the same compile shape.
    chunk = cfg.prefill_chunk_tokens
    if chunk > 0:
        bucket = engine._bucket(chunk)
        lo = bucket // 2 + 1
        if bucket > lo:
            knobs.append(Knob(
                name="prefill_chunk_tokens", default=chunk, kind="bo",
                bounds=(lo, bucket),
                apply_path=f"EngineConfig replace; moves inside the "
                           f"warmed {bucket}-token chunk bucket"))

    knobs.append(Knob(
        name="page_grant_ahead", default=cfg.page_grant_ahead,
        kind="sweep",
        candidates=tuple(sorted({cfg.page_grant_ahead, 0, 1, 2})),
        apply_path="EngineConfig replace; page-table data only "
                   "(_ensure_write_page grant-ahead span)"))

    if getattr(engine, "_spec", False):
        knobs.append(Knob(
            name="spec_enabled", default=True, kind="sweep",
            candidates=(True, False),
            apply_path="engine._spec_runtime_enabled admission mask "
                       "(both tick executables pre-warmed)"))

    return KnobSpace(knobs)


def apply_settings(engine, settings: Dict[str, object]) -> Dict[str, object]:
    """THE apply path — the serving analogue of
    ``Controller::SynchronizeParameters``: swap knob values into a
    live engine at a tick boundary.  Caller holds the engine step lock
    (the tuner's on-tick hook runs inside :meth:`InferenceEngine.step`)
    or owns the engine exclusively (replay).  Returns what was
    actually applied."""
    applied: Dict[str, object] = {}
    cfg_updates: Dict[str, object] = {}
    for name, value in settings.items():
        if name == "max_prefills_per_tick":
            cfg_updates[name] = int(value)
            engine.scheduler.max_prefills_per_tick = int(value)
        elif name in ("prefill_chunk_tokens", "page_grant_ahead"):
            cfg_updates[name] = int(value)
        elif name == "spec_enabled":
            engine._spec_runtime_enabled = bool(value)
        else:
            continue
        applied[name] = settings[name]
    if cfg_updates:
        # EngineConfig is frozen by design — the swap is a replace +
        # reassign, atomic at the tick boundary the caller guarantees.
        engine.engine_cfg = dataclasses.replace(
            engine.engine_cfg, **cfg_updates)
    return applied
