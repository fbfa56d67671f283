"""The chip's published peaks, keyed by ``device_kind``.

``peaks.json`` beside this file is the only place the benchmark reads a
peak.  A device kind missing from it is an error, never a default.
"""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(LookupError):
    """The device kind has no row in the peaks table."""


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """``{"bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes"}`` of one chip."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in {path}; known: {sorted(table)}")
    return dict(table[device_kind])
