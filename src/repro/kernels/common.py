"""Shared kernel-op plumbing: per-call backend resolution + block defaults.

Every verdict derived from the jax backend is resolved LAZILY, PER CALL —
never at import, never cached at first use.  Two reasons:

- reading the backend at import would initialize jax before a multi-host
  launcher can call ``jax.distributed.initialize()`` (models/kernels are
  imported long before main runs);
- caching at first use would let whichever thread happens to call first pin
  the verdict for everyone.  The async feed prefetcher
  (:mod:`repro.pipeline.prefetch`) runs host threads that may race device
  init: its stage-1 thread is numpy-only by contract, but a stage-2
  transfer thread CAN touch jax early, and a first-use cache primed there
  would freeze whatever backend was visible at that instant.  With per-call
  resolution there is nothing to pin — every kernel call re-reads
  ``jax.default_backend()`` (cheap: jax caches the client itself), and an
  explicit ``backend=`` override always wins over the ambient default.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class KernelDefaults:
    """Per-backend default tiling for the Pallas kernel ops.

    ``lane``        last-dim tile quantum (TPU lane width); last-dim blocks
                    should be multiples of this.
    ``block_q/k``   flash-attention query/key tile lengths.
    ``block_n``     diffusion-conv node tile.
    ``block_b``     linear-scan and diffusion-conv batch tile (used when the
                    batch divides it).
    ``scan_chunk``  linear-scan sequence chunk.
    ``interpret``   run Pallas in interpret mode (CPU has no Mosaic/Triton
                    lowering; interpret executes the kernel body in Python
                    for correctness).
    """

    lane: int = 128
    block_q: int = 256
    block_k: int = 256
    block_n: int = 128
    block_b: int = 8
    scan_chunk: int = 256
    interpret: bool = False


#: Static per-backend table — selection from it happens per call in
#: :func:`kernel_defaults`; nothing here reads jax state.
_DEFAULTS = {
    "tpu": KernelDefaults(),
    "gpu": KernelDefaults(),
    "cpu": KernelDefaults(interpret=True),
}


def resolve_backend(backend: str | None = None) -> str:
    """The backend a kernel call should tile for: the explicit override when
    given, else ``jax.default_backend()`` read NOW (per call)."""
    return backend if backend is not None else jax.default_backend()


def kernel_defaults(backend: str | None = None) -> KernelDefaults:
    """Per-backend :class:`KernelDefaults`, resolved at call time.

    Unknown backends get the TPU-shaped defaults with interpret off — a new
    accelerator is better served by real lowering + lane-aligned tiles than
    by Python interpret mode.
    """
    return _DEFAULTS.get(resolve_backend(backend), KernelDefaults())


def interpret_on_cpu(backend: str | None = None) -> bool:
    """Whether Pallas kernels should run in interpret mode (CPU container).

    Kept as the historical entry point; equivalent to
    ``kernel_defaults(backend).interpret``.
    """
    return kernel_defaults(backend).interpret


def block_candidates(base: int, *, lo: int = 32,
                     hi: int = 4096) -> tuple[int, ...]:
    """The autotuner's block-size search space around a ``KernelDefaults``
    base tile: ``{base/2, base, base*2}`` clamped to ``[lo, hi]``, sorted and
    deduped (e.g. ``block_q=256 -> (128, 256, 512)``).  Small by design — the
    measured dispatcher (:mod:`repro.kernels.autotune`) times every candidate
    under jit, so the space must stay cheap to sweep."""
    return tuple(sorted({min(max(b, lo), hi)
                         for b in (base // 2, base, base * 2)}))
