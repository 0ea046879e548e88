"""Per-request sampling, vectorized as per-slot DATA inside the one
compiled decode tick.

The paper's move — fuse per-caller work into one batched device program
instead of per-caller programs — applied to sampling: every request
carries its own ``temperature`` / ``top_k`` / ``top_p`` / ``seed``, and
the engine rides them through the tick as per-slot parameter COLUMNS
plus per-slot PRNG key ROWS (``models/transformer.py:
sample_token_rows``).  One compiled sampled-decode executable serves
every parameter mix; greedy is just a ``temperature=0`` row, so mixed
greedy/sampled batches share the program and request churn never
recompiles (the same compile-count-guarded property as the paged and
speculative modes).

Reproducibility contract: slot output is token-identical to
``sample_decode`` (the per-request oracle) at the same seed/params.
The key for the token at logical position ``p`` is
``fold_in(fold_in(PRNGKey(seed), p), 0)`` — a pure function of (seed,
position), never of how generation was sliced across prefills — so a
restart-resume or router-failover re-prefill of ``prompt + emitted``
lands on the identical key stream with no extra state to carry.

This module owns the HOST half: parameter validation
(:func:`validate`), the host-side seed→key derivation
(:func:`seed_key` — no device op per submit), and the per-slot column
mirror (:class:`SlotSampling`) whose device copy is re-uploaded only
when a slot's parameters change (an async upload, never a host sync —
the engine's ≤ 1-sync-per-tick guarantee is untouched).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

from horovod_tpu.serving.scheduler import ServingError

__all__ = ["MAX_SEED", "SamplingParams", "SlotSampling", "seed_key",
           "validate"]

#: Seeds are capped to non-negative int32 range: ``jax.random.PRNGKey``
#: packs the seed into the low key word (the high word is 0 below
#: 2**32, and 32-bit jax builds truncate above it) — keeping seeds in
#: [0, 2**31) makes :func:`seed_key` exact on every jax config.
MAX_SEED = 2 ** 31


def validate(temperature=0.0, top_k=0, top_p=0.0,
             seed=None) -> Tuple[float, int, float, int]:
    """Normalize and validate one request's sampling parameters.

    Returns ``(temperature, top_k, top_p, seed)`` as plain
    ``(float, int, float, int)``; raises :class:`ServingError` (HTTP
    400) on anything the kernel cannot honor.  ``temperature=0`` is
    greedy; ``top_k=0`` and ``top_p`` of 0 or 1 disable their
    filters."""
    try:
        temperature = float(temperature if temperature is not None else 0.0)
        top_k = int(top_k if top_k is not None else 0)
        top_p = float(top_p if top_p is not None else 0.0)
        seed = int(seed if seed is not None else 0)
    except (TypeError, ValueError) as e:
        raise ServingError(f"bad sampling parameter: {e}")
    if not math.isfinite(temperature) or temperature < 0.0:
        raise ServingError(
            f"temperature must be finite and >= 0, got {temperature}")
    if top_k < 0:
        raise ServingError(f"top_k must be >= 0, got {top_k}")
    if not math.isfinite(top_p) or not 0.0 <= top_p <= 1.0:
        raise ServingError(f"top_p must be in [0, 1], got {top_p}")
    if not 0 <= seed < MAX_SEED:
        raise ServingError(
            f"seed must be in [0, {MAX_SEED}), got {seed}")
    return temperature, top_k, top_p, seed


def seed_key(seed: int) -> np.ndarray:
    """``np.asarray(jax.random.PRNGKey(seed))`` without the device op:
    the threefry key for a seed in [0, 2**31) is ``[seed >> 32, seed &
    0xffffffff] = [0, seed]`` uint32 (guarded by a unit test against
    the real ``PRNGKey`` so a jax-side layout change cannot drift
    silently)."""
    if not 0 <= seed < MAX_SEED:
        raise ValueError(f"seed out of range [0, {MAX_SEED}): {seed}")
    return np.array([0, seed], np.uint32)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """One request's sampling knobs, post-validation (a convenience
    bundle for callers that pass them around together; the scheduler's
    ``Request`` carries them as plain fields)."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 0.0
    seed: int = 0

    @classmethod
    def make(cls, temperature=0.0, top_k=0, top_p=0.0,
             seed=None) -> "SamplingParams":
        return cls(*validate(temperature, top_k, top_p, seed))

    @property
    def sampled(self) -> bool:
        return self.temperature > 0.0


class SlotSampling:
    """The per-slot sampling columns: host mirror + cached device copy.

    The engine sets a slot's row at admission and zeroes it at release
    (a zero row is greedy — exactly what inactive and greedy slots
    need); :meth:`device` re-uploads only when something changed, so
    steady-state decode adds zero transfers.  ``jnp`` is imported
    lazily to keep this module importable without a device runtime."""

    def __init__(self, n_slots: int):
        self.temperature = np.zeros(n_slots, np.float32)
        self.top_k = np.zeros(n_slots, np.int32)
        self.top_p = np.zeros(n_slots, np.float32)
        self.key = np.zeros((n_slots, 2), np.uint32)
        self._dev: Optional[tuple] = None  # dropped by any change

    def set(self, slot: int, *, temperature: float, top_k: int,
            top_p: float, seed: int) -> None:
        self.temperature[slot] = temperature
        self.top_k[slot] = top_k
        self.top_p[slot] = top_p
        self.key[slot] = seed_key(seed)
        self._dev = None

    def clear(self, slot: int) -> None:
        self.temperature[slot] = 0.0
        self.top_k[slot] = 0
        self.top_p[slot] = 0.0
        self.key[slot] = 0
        self._dev = None

    def reset(self) -> None:
        """Restart path: zero every column and drop the device copy
        (it belonged to the dead cache lineage); re-admissions repopulate."""
        self.temperature[:] = 0.0
        self.top_k[:] = 0
        self.top_p[:] = 0.0
        self.key[:] = 0
        self._dev = None

    def gates(self) -> Tuple[bool, bool, bool]:
        """``(draws, any top-k, any nucleus)`` over the host mirror: the
        predicates ``sample_token_rows`` branches on, by the same
        function, so the host knows which stages the next tick runs
        without asking the device (three reductions over ``n_slots``)."""
        from horovod_tpu.models.transformer import sample_gates

        draws, any_k, any_p = sample_gates(self.temperature, self.top_k,
                                           self.top_p)
        return bool(draws), bool(any_k), bool(any_p)

    def device(self) -> tuple:
        """The ``(temperature, top_k, top_p, keys)`` device columns the
        tick consumes — re-uploaded (async) only after a change."""
        if self._dev is None:
            import jax.numpy as jnp

            self._dev = (jnp.asarray(self.temperature),
                         jnp.asarray(self.top_k),
                         jnp.asarray(self.top_p),
                         jnp.asarray(self.key))
        return self._dev
