"""Public window-gather op: jnp oracle by default, Pallas kernel on request.

Handles arbitrary trailing shape by flattening to [T, C] and restoring the
shape afterwards.  The batching layer (`repro.core.batching`) routes through
here when ``use_pallas=True``.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.common import kernel_defaults
from repro.kernels.window_gather.kernel import window_gather as _window_gather_kernel
from repro.kernels.window_gather.ref import window_gather_ref


def window_gather(
    series: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    span: int,
    use_pallas: bool = False,
    backend: str | None = None,
    impl: str | None = None,
) -> jnp.ndarray:
    """series: [T, ...], starts: [B] -> [B, span, ...].

    Interpret mode resolves per call from ``backend`` (None = the ambient
    ``jax.default_backend()``, read now — never cached).  ``impl``
    overrides ``use_pallas``: ``"ref"`` / ``"pallas"`` force a lowering,
    ``"auto"`` routes through the measured shape-bucketed dispatcher
    (:mod:`repro.kernels.autotune`), which picks the fastest VERIFIED
    variant for this (backend, shape-bucket).
    """
    if impl == "auto":
        from repro.kernels.autotune import dispatch
        return dispatch("window_gather", series, starts, span=span)
    if impl is not None:
        if impl not in ("ref", "pallas"):
            raise ValueError(f"impl {impl!r}; expected ref|pallas|auto")
        use_pallas = impl == "pallas"
    if not use_pallas:
        return window_gather_ref(series, starts, span=span)

    t = series.shape[0]
    trailing = series.shape[1:]
    c = int(np.prod(trailing)) if trailing else 1
    out = _window_gather_kernel(series.reshape(t, c), starts.astype(jnp.int32),
                                span=span,
                                interpret=kernel_defaults(backend).interpret)
    return out.reshape((starts.shape[0], span) + trailing)


def gather_xy(
    series: jnp.ndarray,
    starts: jnp.ndarray,
    *,
    input_len: int,
    horizon: int,
    use_pallas: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One fused gather of the full span, split into (x, y) views."""
    w = window_gather(series, starts, span=input_len + horizon, use_pallas=use_pallas)
    return w[:, :input_len], w[:, input_len:]
