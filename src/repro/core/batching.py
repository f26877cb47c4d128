"""Standard (materialising) vs index batching — the paper's core contribution.

``materialize_windows`` is the faithful Alg.-1 baseline: it builds the full
(x, y) snapshot stacks with ~2·horizon× duplication.  ``gather_batch`` is
index-batching: the jitted training step receives the *resident series* and a
vector of window start indices and reconstructs the batch on-device with a
windowed gather — the TPU-native analogue of the paper's NumPy views.  XLA
keeps a single HBM copy of the series; the gather feeds the first layer
directly from it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def materialize_windows(
    series: np.ndarray, starts: np.ndarray, input_len: int, horizon: int
) -> tuple[np.ndarray, np.ndarray]:
    """Alg.-1 baseline: stack every (x, y) snapshot (paper eq. 1 memory)."""
    xs = np.stack([series[s : s + input_len] for s in starts], axis=0)
    ys = np.stack([series[s + input_len : s + input_len + horizon] for s in starts], axis=0)
    return xs, ys


def _window(series: jnp.ndarray, start: jnp.ndarray, length: int) -> jnp.ndarray:
    """One contiguous window ``series[start : start+length]`` via dynamic_slice."""
    sizes = (length,) + series.shape[1:]
    indices = (start,) + (0,) * (series.ndim - 1)
    return jax.lax.dynamic_slice(series, indices, sizes)


@functools.partial(jax.jit, static_argnames=("input_len", "horizon"))
def gather_batch(
    series: jnp.ndarray, starts: jnp.ndarray, *, input_len: int, horizon: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Index-batching: (x, y) for a batch of window starts, gathered on-device.

    series: [T, ...]   starts: [B] int32, each at most ``T - input_len - horizon``
    returns x: [B, input_len, ...], y: [B, horizon, ...]

    Each window's whole span is sliced once and split.  A separate x-only
    gather would feed nothing but the model's default-precision matmuls, so
    the compiler would move their bf16 conversion above the gather, where it
    no longer depends on the start, and hoist it out of the gather as a bf16
    copy of the whole resident series made every step.  The span also feeds
    y, which the loss reads in float32, so the conversion stays on x alone.
    """
    w = jax.vmap(lambda s: _window(series, s, input_len + horizon))(starts)
    return w[:, :input_len], w[:, input_len:]


def gather_batch_take(
    series: jnp.ndarray, starts: jnp.ndarray, *, input_len: int, horizon: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Gather-based variant (``jnp.take`` over explicit index grids).

    Functionally identical to :func:`gather_batch`; lowers to one fused gather
    instead of B dynamic slices.  Which wins depends on the backend — the
    benchmark harness measures both (see EXPERIMENTS.md §Perf).
    """
    offs_x = jnp.arange(input_len, dtype=starts.dtype)
    offs_y = input_len + jnp.arange(horizon, dtype=starts.dtype)
    x = jnp.take(series, starts[:, None] + offs_x[None, :], axis=0)
    y = jnp.take(series, starts[:, None] + offs_y[None, :], axis=0)
    return x, y


def gather_batch_fused(
    series: jnp.ndarray, starts: jnp.ndarray, *, input_len: int, horizon: int,
    use_pallas: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One gather of the whole span, split into (x, y).

    Halves the index traffic vs :func:`gather_batch` (x and y overlap reads of
    the same rows only at the span boundary, never inside).  With
    ``use_pallas=True`` the gather runs through the scalar-prefetch Pallas
    kernel (``kernels/window_gather``).
    """
    from repro.kernels.window_gather import gather_xy

    return gather_xy(series, starts, input_len=input_len, horizon=horizon,
                     use_pallas=use_pallas)


def gather_x_batch(series: jnp.ndarray, starts: jnp.ndarray, *, length: int) -> jnp.ndarray:
    """x-only gather (serving path / LM next-token windows where y = shift(x))."""
    return jax.vmap(lambda s: _window(series, s, length))(starts)


def lm_window_batch(
    stream: jnp.ndarray, starts: jnp.ndarray, *, seq_len: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Index-batching applied to an LM token stream (the nodes==1 case):
    inputs = stream[s : s+seq], labels = stream[s+1 : s+seq+1]."""
    w = jax.vmap(lambda s: _window(stream, s, seq_len + 1))(starts)
    return w[:, :-1], w[:, 1:]
