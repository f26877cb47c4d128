"""Useful FLOPs of one training window, counted by the benchmark.

A DCGRU cell runs two diffusion convolutions, ``ru`` (input width ``C``,
output ``2h``) and ``c`` (``C`` -> ``h``).  For one window of ``N`` nodes:

- a diffusion hop ``Z_k = S Z_{k-1}`` costs ``2 nnz(S) C``; there are ``K``
  per support.  Its backward pass is ``S^T dZ_k`` alone, since the supports
  take no gradient: 1x the forward.
- the projection of the ``(1 + S K) C`` stacked hops to the output width
  costs ``2 N (1 + S K) C out`` forward and 2x that backward (input and
  weight gradients): 3x in all.  The read-out (``h`` -> ``out``) likewise.

With ``dense=True`` every support counts ``N^2`` entries, which is what a
dense matmul executes.  Recomputation (remat) is not useful work and is not
counted; nor are element-wise gates.  The model's structure (cells and their
input widths) comes from its plain reference, ``cells(m)`` / ``readouts(m)``.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def dconv_forward(nodes: int, c_in: int, c_out: int, k_hops: int,
                  support_entries: list[int]) -> tuple[float, float]:
    """``(hop FLOPs, projection FLOPs)`` of one diffusion convolution over one
    window, forward only."""
    hops = sum(2.0 * e * c_in * k_hops for e in support_entries)
    n_mat = 1 + len(support_entries) * k_hops
    return hops, 2.0 * nodes * n_mat * c_in * c_out


def cell_forward(nodes: int, c_in: int, hidden: int, k_hops: int,
                 support_entries: list[int]) -> tuple[float, float]:
    """``(hop, projection)`` FLOPs of one DCGRU cell (ru + c), forward."""
    ru = dconv_forward(nodes, c_in, 2 * hidden, k_hops, support_entries)
    c = dconv_forward(nodes, c_in, hidden, k_hops, support_entries)
    return ru[0] + c[0], ru[1] + c[1]


def window_flops(m: dict, cells, readouts: int, nnz: list[int], *,
                 dense: bool = False) -> float:
    """Forward plus backward FLOPs of one training window.

    ``cells`` is ``[(input width, calls per window), ...]`` and ``readouts``
    the read-out projections per window, both from the model's reference;
    ``nnz`` holds each support's nonzero count.
    """
    n = m["num_nodes"]
    entries = [n * n] * len(nnz) if dense else list(nnz)
    hops = proj = 0.0
    for c_in, calls in cells:
        h, p = cell_forward(n, c_in, m["hidden"], m["max_diffusion_step"],
                            entries)
        hops += calls * h
        proj += calls * p
    proj += readouts * 2.0 * n * m["hidden"] * m["out_features"]
    return 2.0 * hops + 3.0 * proj


def peak_flops(device_kind: str) -> float:
    """Peak FLOP/s of one chip of ``device_kind``; an unknown kind raises."""
    with open(PEAKS_FILE) as f:
        peaks = json.load(f)["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no peak for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}; known: {sorted(peaks)}")
    return float(peaks[device_kind]["flops_per_s"])
