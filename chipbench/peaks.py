"""The one table of device peaks (``peaks.json``), keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default: a utilisation against a guessed peak is a guess."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDeviceError(KeyError):
    """``device_kind`` has no row in ``peaks.json``."""


def peaks_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDeviceError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}; known: "
            f"{sorted(table)}.  Add a row with its source; do not default.")
    return table[device_kind]
