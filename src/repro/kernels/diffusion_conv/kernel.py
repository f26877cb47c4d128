"""Pallas TPU kernel: one diffusion hop fused with projection accumulation.

Computes, for one support matrix S and hop weight W_k, per batch element b:

    Z_k[b] = S @ Z_{k-1}[b]       (the [N,N] x [N, C] hot matmul)
    Y[b]  += Z_k[b] @ W_k         (per-hop projection, fused)

TPU adaptation of the paper's GPU code path (dense torch.matmul chain):
the node dimension is tiled into MXU-aligned blocks that stream through VMEM;
the j grid axis reduces over node blocks of Z_{k-1} with output-revisiting
accumulation (TPU grids execute sequentially, so the (b, i) output tile stays
resident in VMEM across the j sweep).  Arrays stay batch-major ``[B, N, C]``,
so every in-kernel matmul is a plain 2-D dot on a leading-index slice — no
in-kernel reshape.  Each S tile is fetched once per batch block and reused
by its ``block_b`` batch elements.

Grid: (B/bb, N/bn_i, N/bn_j).
  s:     (bn_i, bn_j)    <- S[i, j]
  z_in:  (bb, bn_j, C)   <- Z_{k-1}[b, j]
  w:     (C, H)          (resident)
  y_in:  (bb, bn_i, H)   <- Y[b, i]
  z_out: (bb, bn_i, C)   -> Z_k[b, i]        (accumulator across j)
  y_out: (bb, bn_i, H)   -> Y[b, i] + Z_k[b, i] @ W_k   (written at last j)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _hop_project_kernel(s_ref, z_ref, w_ref, y_ref, z_out_ref, y_out_ref):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        z_out_ref[...] = jnp.zeros_like(z_out_ref)

    s = s_ref[...]
    for b in range(z_ref.shape[0]):
        part = jax.lax.dot(s, z_ref[b].astype(s.dtype),
                           preferred_element_type=jnp.float32)
        z_out_ref[b] = (z_out_ref[b] + part).astype(z_out_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _project():
        w = w_ref[...]
        for b in range(z_ref.shape[0]):
            proj = jax.lax.dot(z_out_ref[b].astype(w.dtype), w,
                               preferred_element_type=jnp.float32)
            y_out_ref[b] = y_ref[b] + proj.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_b", "interpret"))
def hop_project(s, z, w, y, *, block_n: int = 128, block_b: int = 8,
                interpret: bool = False):
    """One fused hop.  s: [N, N], z: [B, N, C], w: [C, H], y: [B, N, H].

    N must be a multiple of ``block_n`` and B of ``block_b`` (ops.py pads N
    and picks a dividing ``block_b``).  Returns (z_next, y_next).
    """
    b, n, c = z.shape
    h = w.shape[1]
    assert n % block_n == 0, (n, block_n)
    assert b % block_b == 0, (b, block_b)
    grid = (b // block_b, n // block_n, n // block_n)
    return pl.pallas_call(
        _hop_project_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_n), lambda bi, i, j: (i, j)),  # S
            pl.BlockSpec((block_b, block_n, c),
                         lambda bi, i, j: (bi, j, 0)),  # Z_{k-1}
            pl.BlockSpec((c, h), lambda bi, i, j: (0, 0)),  # W_k
            pl.BlockSpec((block_b, block_n, h),
                         lambda bi, i, j: (bi, i, 0)),  # Y in
        ],
        out_specs=[
            pl.BlockSpec((block_b, block_n, c),
                         lambda bi, i, j: (bi, i, 0)),  # Z_k
            pl.BlockSpec((block_b, block_n, h),
                         lambda bi, i, j: (bi, i, 0)),  # Y out
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, n, c), z.dtype),
            jax.ShapeDtypeStruct((b, n, h), y.dtype),
        ],
        interpret=interpret,
    )(s, z, w, y)
