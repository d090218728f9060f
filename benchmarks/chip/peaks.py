"""The chip's published peaks, looked up by JAX's ``device_kind``.

A kind that is not in ``peaks.json`` is an error, never a default: a share
of a peak computed against the wrong chip would read as a measurement.
"""
from __future__ import annotations

import json
import os

_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind: str) -> dict:
    with open(_TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json knows {sorted(table)}")
    return table[device_kind]


def share(work: float, seconds: float, peak_per_s: float):
    """``work`` done in ``seconds`` as a percentage of ``peak_per_s``, or
    None where there is no time to divide by."""
    if seconds <= 0 or work <= 0:
        return None
    return 100.0 * work / (seconds * peak_per_s)
