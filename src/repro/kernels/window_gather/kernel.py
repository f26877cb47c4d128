"""Pallas TPU kernel: scalar-prefetch-driven window gather (index-batching).

The TPU-native equivalent of the paper's NumPy views: the int32 start-index
array is *scalar-prefetched* into SMEM before the grid runs, and each grid
step's BlockSpec index_map reads ``starts[b]`` to aim the HBM→VMEM DMA at the
right time-rows of the resident series.  No materialised snapshot array ever
exists in HBM — the paper's eq.-2 memory model holds on device.

The series is viewed as ``[T, 1, C]``: the unit axis makes the block's two
minor dims ``(1, C)`` equal the array's, which Mosaic accepts for any ``C``,
and leaves time as an untiled major axis, so a window may start at any row.

Grid: (B,)
  series block (span, 1, C)  <- series[starts[b] : starts[b] + span]  (one DMA)
  out    block (span, 1, C)  -> out[b]

The kernel body is a pure VMEM copy; the index indirection is resolved by
the scalar-prefetch unit while the previous window's DMA is in flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(starts_ref, series_ref, out_ref):
    # starts_ref lives in SMEM (scalar prefetch); the input block is aimed by
    # the index_map below, so the body is a straight VMEM copy.
    del starts_ref
    out_ref[...] = series_ref[...]


@functools.partial(jax.jit, static_argnames=("span", "interpret"))
def window_gather(
    series: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    span: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """series: [T, C], starts: [B] int32 -> [B, span, C].

    ``span`` is input_len + horizon — x/y are sliced from the result by the
    caller.
    """
    t, c = series.shape
    b = starts.shape[0]
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                # element offsets: the window starts at row starts[i]
                pl.BlockSpec((pl.Element(span), pl.Element(1), pl.Element(c)),
                             lambda i, starts: (starts[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, span, 1, c),
                                   lambda i, starts: (i, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, span, 1, c), series.dtype),
        interpret=interpret,
    )(starts, series.reshape(t, 1, c))
    return out.reshape(b, span, c)
