"""The chips' peak FLOP/s, from ``bench/peaks.json``.

A window's useful FLOPs are counted by each model family's plain reference,
``bench/reference/<family>.py``'s ``window_flops``.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peak_flops(device_kind: str) -> float:
    """Peak FLOP/s of one chip of ``device_kind``; an unknown kind raises."""
    with open(PEAKS_FILE) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(peaks)}")
    return float(peaks[device_kind]["flops_per_s"])
