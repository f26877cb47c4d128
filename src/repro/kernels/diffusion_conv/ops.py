"""Public diffusion-conv op: jnp oracle by default, Pallas kernel on request.

On this CPU container the Pallas path runs in interpret mode (Python-level
execution of the kernel body) purely for correctness; on TPU ``interpret``
stays False and the same call sites get the real kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import trace_names
from repro.kernels.common import kernel_defaults
from repro.kernels.diffusion_conv.kernel import hop_project
from repro.kernels.diffusion_conv.ref import diffusion_conv_ref

def _pad_nodes(a: jnp.ndarray, n_pad: int, axes: tuple[int, ...]) -> jnp.ndarray:
    pads = [(0, 0)] * a.ndim
    for ax in axes:
        pads[ax] = (0, n_pad - a.shape[ax])
    return jnp.pad(a, pads) if any(p != (0, 0) for p in pads) else a


def diffusion_conv(
    x,
    supports,
    w,
    b,
    *,
    k_hops: int,
    use_pallas: bool = False,
    block_n: int | None = None,
    backend: str | None = None,
    impl: str | None = None,
):
    """x: [B, N, C] -> [B, N, H].  See ref.py for the weight layout.

    Tiling/interpret defaults resolve per call from ``backend`` (None =
    ambient, read now).  ``impl`` overrides ``use_pallas``:
    ``"ref"``/``"pallas"`` force a lowering, ``"auto"`` routes through the
    measured dispatcher (:mod:`repro.kernels.autotune`).
    """
    if impl == "auto":
        from repro.kernels.autotune import dispatch
        return dispatch("diffusion_conv", x, tuple(supports), w, b,
                        k_hops=k_hops, n_supports=len(supports))
    if impl is not None:
        if impl not in ("ref", "pallas"):
            raise ValueError(f"impl {impl!r}; expected ref|pallas|auto")
        use_pallas = impl == "pallas"
    if not use_pallas:
        return diffusion_conv_ref(x, supports, w, b, k_hops=k_hops)

    kd = kernel_defaults(backend)
    if block_n is None:
        block_n = kd.block_n
    bsz, n, c = x.shape
    h = w.shape[1]
    n_pad = int(np.ceil(n / block_n) * block_n)
    block_b = math.gcd(bsz, kd.block_b)

    z0 = _pad_nodes(x, n_pad, (1,))  # [B, Np, C]
    # Identity-hop projection (plain matmul — XLA handles it optimally).
    y = jnp.einsum("bnc,ch->bnh", z0, w[:c].astype(x.dtype))
    wk = w[c:].reshape(len(supports), k_hops, c, h)

    for si, s in enumerate(supports):
        s_p = _pad_nodes(s, n_pad, (0, 1))
        z = z0
        for k in range(k_hops):
            with jax.named_scope(trace_names.DCONV_HOP):
                z, y = hop_project(
                    s_p, z, wk[si, k].astype(x.dtype), y,
                    block_n=block_n, block_b=block_b, interpret=kd.interpret,
                )
    return y[:, :n] + b
