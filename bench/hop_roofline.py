"""The least time of a train step's diffusion hops on one chip: the floor of
a hop roofline share (least time over the measured hop time).

A hop ``Z_k = S Z_{k-1}`` over a batch of ``B`` windows of ``N`` nodes and
``C`` channels, for each of a DCGRU cell's two diffusion convolutions, ``K``
hops per support:

- FLOPs: ``2 nnz(S) C`` per window forward, as ``bench/flops.py`` counts
  them, and the 1x backward ``S^T dZ_k`` (the supports take no gradient).
- bytes: the support read once per hop for the batch, at least its nonzeros
  as a 4-byte value and a 4-byte column index (``8 nnz(S)``); ``Z_{k-1}``
  read and ``Z_k`` written, ``[B, N, C]`` float32 each (``8 B N C``).  The
  backward moves the same bytes again.

Recomputation (remat) is not counted, and the work is the same whatever
implements the hops, so no kernel can carry the share past 100%.  The least
time is the larger of FLOPs over the peak FLOP/s and bytes over the peak HBM
bytes/s (``bench/peaks.json``).
"""
from __future__ import annotations

import json

from bench import flops


def _hops(m: dict, cells):
    """``(C, hops per window)`` of each of the model's cells."""
    return [(c_in, 2 * calls * m["max_diffusion_step"]) for c_in, calls in cells]


def hop_flops(m: dict, cells, nnz: list[int], batch: int) -> float:
    """Useful FLOPs of one step's hops: forward plus the 1x backward."""
    return 2.0 * batch * sum(hops * 2.0 * e * c_in
                             for c_in, hops in _hops(m, cells) for e in nnz)


def hop_bytes(m: dict, cells, nnz: list[int], batch: int) -> float:
    """Least HBM bytes of one step's hops: forward plus the backward."""
    n = m["num_nodes"]
    return 2.0 * sum(hops * (8.0 * e + 8.0 * batch * n * c_in)
                     for c_in, hops in _hops(m, cells) for e in nnz)


def peak_hbm_bytes_per_s(device_kind: str) -> float:
    """Peak HBM bytes/s of one chip of ``device_kind``; an unknown kind
    raises."""
    with open(flops.PEAKS_FILE) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"{flops.PEAKS_FILE}; known: {sorted(peaks)}")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def least_ms(m: dict, cells, nnz: list[int], batch: int,
             device_kind: str) -> dict:
    """``{"flops_ms", "bytes_ms", "least_ms", "bound"}`` of one chip's hops
    over ``batch`` windows per step."""
    f = 1e3 * hop_flops(m, cells, nnz, batch) / flops.peak_flops(device_kind)
    b = 1e3 * hop_bytes(m, cells, nnz, batch) / peak_hbm_bytes_per_s(device_kind)
    return {"flops_ms": f, "bytes_ms": b, "least_ms": max(f, b),
            "bound": "bytes" if b > f else "flops"}
