"""Measured kernel autotuning: a variant registry + shape-bucketed dispatcher.

The static :class:`~repro.kernels.common.KernelDefaults` table guesses one
tiling per backend and never measures anything — and the smoke bench showed
where that leads: ``gather="pallas"`` (interpret mode on CPU) was ~2x SLOWER
than the dense lowering it was supposed to beat.  This module replaces the
guess with a measurement:

- every op declares its candidate lowerings (**variants**): the pure-jnp
  reference, the fused XLA alternatives (``gather_batch_take`` /
  ``gather_batch_fused``), and the Pallas kernel — compiled where the backend
  has a Mosaic/Triton lowering, interpret mode otherwise — each with a small
  block-size search space derived from ``KernelDefaults``
  (:func:`~repro.kernels.common.block_candidates`);
- the **tuner** times every candidate under jit (``block_until_ready``,
  warmup + median-of-N — the same contract as ``benchmarks/common.timed``,
  which is reused when importable) on synthetic inputs at the call's
  **shape bucket** (powers-of-two envelopes of every dimension), and only
  admits candidates whose VALUES match the reference (bit-exact for pure
  data-movement ops, allclose for float kernels);
- verdicts are keyed ``(op, backend, shape-bucket, dtype)`` and persisted to
  ``results/TUNING_<backend>.json`` — written atomically (tempfile +
  ``os.replace``) so concurrent tuners can interleave but a reader can never
  observe a torn file, and loaded defensively: a missing, corrupt, or
  foreign-backend cache yields ``{}`` (retune or static default), never an
  exception.

Dispatch discipline (same rules ``kernels/common.py`` documents): the jax
backend is resolved PER CALL — never at import, never cached at first use —
because the prefetcher's host threads race device init.  What IS memoized is
keyed BY backend (tuning verdicts, built callables), so nothing a racing
thread primes can pin the wrong backend for everyone.

Modes (``set_autotune(mode=...)`` / ``--autotune`` on the launcher):

- ``"off"``  — static heuristic defaults only (reference lowering on
  interpret-mode backends, Pallas at ``KernelDefaults`` tiles elsewhere);
  no file IO.
- ``"load"`` — use a persisted verdict when one covers the bucket, else the
  static default; never measures.  The default mode: committed caches make
  ``backend="auto"`` dispatch measured without paying tuning time.
- ``"tune"`` — like ``load`` but a cache miss triggers measurement and the
  verdict is persisted.  Delete the cache file to force a full retune.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import threading
import time
import warnings
from typing import Any, Callable

import jax
import numpy as np

from repro.kernels.common import (KernelDefaults, block_candidates,
                                  kernel_defaults, resolve_backend)

# --------------------------------------------------------------------- policy


@dataclasses.dataclass(frozen=True)
class AutotunePolicy:
    """Process-wide dispatch policy (see module docstring for the modes)."""

    mode: str = "load"          # off | load | tune
    cache_dir: str = "results"  # TUNING_<backend>.json lives here
    warmup: int = 1             # per-candidate warmup calls (absorbs jit)
    iters: int = 5              # timed calls per candidate; median wins


MODES = ("off", "load", "tune")

_LOCK = threading.RLock()
_policy = AutotunePolicy()
#: (bucket key, mode, cache_dir) -> Verdict — resolved dispatch decisions.
_MEMO: dict[tuple, "Verdict"] = {}
#: cache path -> entries dict loaded from disk (refreshed on policy change).
_FILE_MEMO: dict[str, dict] = {}
#: (op, variant, static, params) -> built callable.  Built callables wrap
#: ``jax.jit`` closures; memoizing them keeps the function identity stable so
#: jit's own cache works across dispatches.
_FN_MEMO: dict[tuple, Callable] = {}


def autotune_policy() -> AutotunePolicy:
    return _policy


def set_autotune(mode: str | None = None, cache_dir: str | None = None,
                 warmup: int | None = None,
                 iters: int | None = None) -> AutotunePolicy:
    """Update the process-wide policy; clears resolved-verdict memos."""
    global _policy
    if mode is not None and mode not in MODES:
        raise ValueError(f"autotune mode {mode!r}; expected one of {MODES}")
    kw = {k: v for k, v in dict(mode=mode, cache_dir=cache_dir, warmup=warmup,
                                iters=iters).items() if v is not None}
    with _LOCK:
        _policy = dataclasses.replace(_policy, **kw)
        _MEMO.clear()
        _FILE_MEMO.clear()
    return _policy


def reset_autotune() -> None:
    """Restore the default policy and drop every memo (tests)."""
    global _policy
    with _LOCK:
        _policy = AutotunePolicy()
        _MEMO.clear()
        _FILE_MEMO.clear()
        _FN_MEMO.clear()


@contextlib.contextmanager
def autotuning(**kw):
    """Scoped policy override: ``with autotuning(mode="tune", cache_dir=d):``"""
    global _policy
    with _LOCK:
        prev = _policy
    try:
        yield set_autotune(**kw)
    finally:
        with _LOCK:
            _policy = prev
            _MEMO.clear()
            _FILE_MEMO.clear()


# ------------------------------------------------------------ shape bucketing


def pow2_bucket(n: int) -> int:
    """The power-of-two envelope of ``n`` (1 for n <= 1)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def bucket_key(op: str, backend: str, dims: dict, dtype) -> str:
    """Cache key: every dim rounded up to its power-of-two envelope, so one
    measured verdict covers the whole envelope instead of one exact shape."""
    parts = ",".join(f"{k}={pow2_bucket(v)}" for k, v in dims.items())
    return f"{op}|{backend}|{parts}|{np.dtype(dtype).name}"


# ------------------------------------------------------------- tuning cache


def cache_path(backend: str, cache_dir: str | None = None) -> str:
    d = cache_dir if cache_dir is not None else _policy.cache_dir
    return os.path.join(d, f"TUNING_{backend}.json")


def load_cache(path: str, backend: str) -> dict:
    """The persisted entries, or ``{}`` — NEVER an exception.

    Missing file, torn/corrupt JSON (a crashed writer, a truncated copy), a
    non-object payload, or a cache tuned for a DIFFERENT backend all fall
    back to empty: the dispatcher then retunes (mode=tune) or uses the
    static defaults, which is always safe.
    """
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or data.get("backend") != backend:
        return {}
    entries = data.get("entries")
    return dict(entries) if isinstance(entries, dict) else {}


def save_cache(path: str, backend: str, entries: dict) -> None:
    """Merge ``entries`` into the persisted cache, atomically.

    Read-merge-replace: concurrent tuners (two processes tuning different
    buckets at once) interleave per-key last-writer-wins, but ``os.replace``
    of a same-directory tempfile guarantees no reader — nor a crash mid-write
    — can ever observe a torn file.
    """
    merged = load_cache(path, backend)
    merged.update(entries)
    payload = {"schema": 1, "backend": backend, "jax": jax.__version__,
               "entries": merged}
    d = os.path.dirname(path) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".tuning-", dir=d)
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        with contextlib.suppress(OSError):
            os.unlink(tmp)
        raise


# ----------------------------------------------------------------- registry


@dataclasses.dataclass(frozen=True)
class Variant:
    """One candidate lowering of an op.

    ``build(static, params) -> fn(*arrays)`` returns the jit-wrapped callable
    (memoized by the dispatcher, so jit caches hold across calls).
    ``grid(bucket_dims, kd) -> (params, ...)`` is the block-size search space,
    derived from :class:`KernelDefaults` and filtered to the bucket (a scan
    chunk longer than the sequence is the same candidate twice).
    ``exact`` selects the admission check the tuner runs against the
    reference variant: bit-equality for pure data movement, allclose for
    float kernels whose accumulation order differs.
    """

    name: str
    build: Callable[[dict, dict], Callable]
    grid: Callable[[dict, KernelDefaults], tuple] = lambda dims, kd: ({},)
    exact: bool = True
    atol: float = 1e-3
    rtol: float = 1e-3


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One tunable op: how to key it, synthesize it, and lower it.

    ``describe(args, static) -> (dims, dtype)`` extracts the bucketable
    dimensions (shapes only — safe on tracers).
    ``variants()`` returns the candidates, reference FIRST (it is the
    correctness oracle and the unconditional fallback); lowerings are
    imported lazily inside it so registering ops never imports jax kernels
    at module-import time.
    ``synth(bucket_dims, static, dtype)`` builds concrete inputs at the
    bucket envelope for timing (dispatch may fire at trace time, where the
    live args are tracers and cannot be timed).
    ``default(backend, dims) -> (variant, params)`` is the unmeasured
    heuristic: the reference on interpret-mode backends, Pallas at the
    ``KernelDefaults`` tiles elsewhere.
    """

    name: str
    describe: Callable[[tuple, dict], tuple[dict, Any]]
    variants: Callable[[], tuple[Variant, ...]]
    synth: Callable[[dict, dict, Any], tuple]
    default: Callable[[str, dict], tuple[str, dict]]


@dataclasses.dataclass(frozen=True)
class Verdict:
    """A resolved dispatch decision and where it came from."""

    variant: str
    params: dict
    us: float | None = None
    source: str = "default"  # default | cache | tuned


_OPS: dict[str, OpSpec] = {}


def register_op(spec: OpSpec) -> OpSpec:
    _OPS[spec.name] = spec
    return spec


def registered_ops() -> tuple[str, ...]:
    return tuple(_OPS)


# ------------------------------------------------------------------- tuning


def _timed(fn: Callable[[], Any], *, warmup: int, iters: int) -> float:
    """Median wall seconds (same contract as ``benchmarks/common.timed``,
    reused when the benchmarks package is importable)."""
    try:
        from benchmarks.common import timed
    except ImportError:
        for _ in range(max(warmup, 0)):
            jax.block_until_ready(fn())
        times = []
        for _ in range(max(iters, 1)):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
        times.sort()
        return times[len(times) // 2]
    return timed(fn, warmup=max(warmup, 0), iters=max(iters, 1))


def _values_match(ref, out, variant: Variant) -> bool:
    rl, ol = jax.tree.leaves(ref), jax.tree.leaves(out)
    if len(rl) != len(ol):
        return False
    for r, o in zip(rl, ol):
        r, o = np.asarray(r), np.asarray(o)
        if r.shape != o.shape or r.dtype != o.dtype:
            return False
        if variant.exact:
            if not np.array_equal(r, o):
                return False
        elif not np.allclose(r, o, atol=variant.atol, rtol=variant.rtol):
            return False
    return True


def _label(name: str, params: dict) -> str:
    if not params:
        return name
    inner = ",".join(f"{k}={v}" for k, v in sorted(params.items()))
    return f"{name}[{inner}]"


def _tune(spec: OpSpec, backend: str, dims: dict, static: dict, dtype,
          policy: AutotunePolicy) -> dict:
    """Measure every candidate at the bucket envelope; returns a cache entry.

    Inputs are SYNTHESIZED at the bucket (not the live args): the verdict
    represents the whole envelope, and dispatch may fire under a jit trace
    where the live args are tracers.  JAX trace state is thread-local, so
    the measurement body runs in a fresh worker thread: candidates execute
    EAGERLY on concrete arrays with real wall times, never staged into the
    surrounding trace.  (``ensure_compile_time_eval`` is not enough — it
    inlines inner jits, and ``lax.scan`` has no eager eval rule.)
    """
    box: list = []

    def _run():
        try:
            box.append((None, _tune_body(spec, backend, dims, static, dtype,
                                         policy)))
        except BaseException as e:  # noqa: BLE001 — re-raised in the caller
            box.append((e, None))

    t = threading.Thread(target=_run, name=f"autotune-{spec.name}",
                         daemon=True)
    t.start()
    t.join()
    err, entry = box[0]
    if err is not None:
        raise err
    return entry


def _tune_body(spec: OpSpec, backend: str, dims: dict, static: dict, dtype,
               policy: AutotunePolicy) -> dict:
    """The measurement loop proper; must run outside any ambient trace.

    Candidates that fail to lower or whose values diverge from the reference
    are recorded as rejected, never selected — a tuner can pick a slow
    candidate, never a wrong one.
    """
    kd = kernel_defaults(backend)
    bdims = {k: pow2_bucket(v) for k, v in dims.items()}
    candidates: dict[str, dict] = {}
    best: tuple[str, dict, float] | None = None
    sargs = spec.synth(bdims, static, dtype)
    variants = spec.variants()
    ref_out = _built(spec, variants[0], static, {})(*sargs)

    for v in variants:
        for params in v.grid(bdims, kd):
            label = _label(v.name, params)
            try:
                fn = _built(spec, v, static, params)
                out = fn(*sargs)
                if not _values_match(ref_out, out, v):
                    candidates[label] = {
                        "us": None, "rejected": "values diverge from ref"}
                    continue
                t = _timed(lambda: fn(*sargs), warmup=policy.warmup,
                           iters=policy.iters)
            except Exception as e:  # noqa: BLE001 — a candidate that
                # cannot lower on this backend is disqualified, not fatal,
                # but the refusal is reported, not only recorded
                reason = f"{type(e).__name__}: {e}"
                warnings.warn(f"autotune {spec.name}|{backend}: candidate "
                              f"{label} refused: {reason[:500]}",
                              RuntimeWarning, stacklevel=2)
                candidates[label] = {"us": None, "rejected": reason[:200]}
                continue
            us = 1e6 * t
            candidates[label] = {"us": round(us, 2)}
            if best is None or us < best[2]:
                best = (v.name, dict(params), us)
    if best is None:  # cannot happen: the reference always lowers
        raise RuntimeError(f"no candidate survived tuning for {spec.name}")
    return {"variant": best[0], "params": best[1], "us": round(best[2], 2),
            "dims": dict(dims), "bucket": bdims,
            "candidates": candidates,
            "tuned_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


# ----------------------------------------------------------------- dispatch


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


def _built(spec: OpSpec, variant: Variant, static: dict,
           params: dict) -> Callable:
    key = (spec.name, variant.name, _freeze(static), _freeze(params))
    with _LOCK:
        fn = _FN_MEMO.get(key)
    if fn is None:
        # jit every candidate: timing then measures the compiled lowering,
        # and under ``ensure_compile_time_eval`` a jitted call compiles and
        # runs where a bare one would need eager eval rules (lax.scan's
        # ``empty`` primitive has none).  Memoized so the jit cache is
        # stable across dispatches.
        fn = jax.jit(variant.build(static, params))
        with _LOCK:
            _FN_MEMO[key] = fn
    return fn


def _resolve(spec: OpSpec, backend: str, key: str, dims: dict, static: dict,
             dtype) -> Verdict:
    policy = _policy
    if policy.mode == "off":
        name, params = spec.default(backend, dims)
        return Verdict(name, params, source="default")
    memo_key = (key, policy.mode, policy.cache_dir)
    with _LOCK:
        hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit
    path = cache_path(backend, policy.cache_dir)
    with _LOCK:
        entries = _FILE_MEMO.get(path)
        if entries is None:
            entries = load_cache(path, backend)
            _FILE_MEMO[path] = entries
    entry = entries.get(key)
    if isinstance(entry, dict) and isinstance(entry.get("variant"), str):
        v = Verdict(entry["variant"], dict(entry.get("params") or {}),
                    entry.get("us"), "cache")
    elif policy.mode == "tune":
        entry = _tune(spec, backend, dims, static, dtype, policy)
        with _LOCK:
            entries[key] = entry
            save_cache(path, backend, {key: entry})
        v = Verdict(entry["variant"], dict(entry["params"]), entry["us"],
                    "tuned")
    else:
        name, params = spec.default(backend, dims)
        v = Verdict(name, params, source="default")
    with _LOCK:
        _MEMO[memo_key] = v
    return v


def verdict_for(op: str, *args, **static) -> Verdict:
    """The dispatch decision for this call, without executing it (benches)."""
    spec = _OPS[op]
    backend = resolve_backend(None)  # per call, never cached
    dims, dtype = spec.describe(args, static)
    return _resolve(spec, backend, bucket_key(op, backend, dims, dtype),
                    dims, static, dtype)


def dispatch(op: str, *args, **static):
    """Run ``op`` through its measured (or default) fastest lowering.

    Resolution happens per call: backend read NOW, bucket computed from the
    call shapes, verdict looked up (memoized per bucket — keyed by backend,
    so nothing a racing thread primes can pin a foreign backend's verdict).
    A stale cache entry naming a variant that no longer exists falls back to
    the static default.  A lowering that fails raises: swapping in another
    one would hide a kernel the compiler refuses.
    """
    spec = _OPS[op]
    backend = resolve_backend(None)
    dims, dtype = spec.describe(args, static)
    key = bucket_key(op, backend, dims, dtype)
    verdict = _resolve(spec, backend, key, dims, static, dtype)
    by_name = {v.name: v for v in spec.variants()}
    var = by_name.get(verdict.variant)
    if var is None:  # cache from an older registry revision
        name, params = spec.default(backend, dims)
        var, verdict = by_name[name], Verdict(name, params, source="default")
    return _built(spec, var, static, verdict.params)(*args)


# ------------------------------------------------------------- op specs
# Lowerings are imported lazily inside variants()/build closures: this module
# must stay importable before jax.distributed.initialize() runs, and the ops
# modules import US for impl="auto" — laziness breaks the cycle.


def _rng() -> np.random.Generator:
    return np.random.default_rng(0)


def _synth_series(t: int, c: int, dtype) -> np.ndarray:
    if np.issubdtype(np.dtype(dtype), np.integer):
        return _rng().integers(0, 100, size=(t, c)).astype(dtype)
    return _rng().standard_normal((t, c)).astype(dtype)


def _ref_default(backend: str, dims: dict) -> tuple[str, dict]:
    del dims
    return ("ref", {})


# window_gather: series [T, ...], starts [B] -> [B, span, ...]


def _wg_describe(args, static):
    series, starts = args
    trailing = series.shape[1:]
    c = int(np.prod(trailing)) if trailing else 1
    return ({"t": series.shape[0], "c": c, "b": starts.shape[0],
             "span": static["span"]}, series.dtype)


def _wg_synth(bdims, static, dtype):
    import jax.numpy as jnp
    span = static["span"]
    t = max(bdims["t"], span)
    series = _synth_series(t, bdims["c"], dtype)
    starts = _rng().integers(0, max(t - span + 1, 1),
                             bdims["b"]).astype(np.int32)
    return jnp.asarray(series), jnp.asarray(starts)


def _wg_variants() -> tuple[Variant, ...]:
    def ref(static, params):
        from repro.kernels.window_gather.ref import window_gather_ref
        span = static["span"]
        return jax.jit(lambda s, st: window_gather_ref(s, st, span=span))

    def take(static, params):
        import jax.numpy as jnp
        span = static["span"]

        def fn(series, starts):
            offs = jnp.arange(span, dtype=starts.dtype)
            return jnp.take(series, starts[:, None] + offs[None, :], axis=0)

        return jax.jit(fn)

    def pallas(static, params):
        from repro.kernels.window_gather.ops import window_gather
        span = static["span"]
        return jax.jit(lambda s, st: window_gather(s, st, span=span,
                                                   use_pallas=True))

    return (Variant("ref", ref),
            Variant("take", take),
            Variant("pallas", pallas))


def _pallas_or_ref(params_for_pallas: Callable[[KernelDefaults], dict]):
    """Static default: reference on interpret-mode backends (running the
    kernel body in Python is never the fast path), Pallas at the
    KernelDefaults tiles on backends with a real lowering."""

    def default(backend: str, dims: dict) -> tuple[str, dict]:
        kd = kernel_defaults(backend)
        if kd.interpret:
            return ("ref", {})
        return ("pallas", params_for_pallas(kd))

    return default


register_op(OpSpec(
    name="window_gather",
    describe=_wg_describe,
    variants=_wg_variants,
    synth=_wg_synth,
    default=_pallas_or_ref(lambda kd: {}),
))


# gather: the pipeline-level (x, y) window gather —
# gather(series, starts, input_len=, horizon=) -> (x, y)


def _xy_describe(args, static):
    series, starts = args
    trailing = series.shape[1:]
    c = int(np.prod(trailing)) if trailing else 1
    return ({"t": series.shape[0], "c": c, "b": starts.shape[0],
             "span": static["input_len"] + static["horizon"]}, series.dtype)


def _xy_synth(bdims, static, dtype):
    import jax.numpy as jnp
    span = static["input_len"] + static["horizon"]
    t = max(bdims["t"], span)
    series = _synth_series(t, bdims["c"], dtype)
    starts = _rng().integers(0, max(t - span + 1, 1),
                             bdims["b"]).astype(np.int32)
    return jnp.asarray(series), jnp.asarray(starts)


def _xy_variants() -> tuple[Variant, ...]:
    def _wrap(gather_fn, static):
        il, hz = static["input_len"], static["horizon"]
        return jax.jit(lambda s, st: gather_fn(s, st, input_len=il,
                                               horizon=hz))

    def slice_(static, params):
        from repro.core.batching import gather_batch
        return _wrap(gather_batch, static)

    def take(static, params):
        from repro.core.batching import gather_batch_take
        return _wrap(gather_batch_take, static)

    def fused(static, params):
        from repro.core.batching import gather_batch_fused
        return _wrap(gather_batch_fused, static)

    def pallas(static, params):
        from repro.kernels.window_gather.ops import window_gather
        il, hz = static["input_len"], static["horizon"]

        def fn(series, starts):
            w = window_gather(series, starts, span=il + hz, use_pallas=True)
            return w[:, :il], w[:, il:]

        return jax.jit(fn)

    return (Variant("slice", slice_),
            Variant("take", take),
            Variant("fused", fused),
            Variant("pallas", pallas))


def _xy_default(backend: str, dims: dict) -> tuple[str, dict]:
    kd = kernel_defaults(backend)
    if kd.interpret:
        return ("slice", {})  # the dense lowering the CPU bench crowns
    return ("pallas", {})


register_op(OpSpec(
    name="gather",
    describe=_xy_describe,
    variants=_xy_variants,
    synth=_xy_synth,
    default=_xy_default,
))


# linear_scan: h_t = a_t * h_{t-1} + b_t over [B, S, D]


def _ls_describe(args, static):
    a, b, h0 = args
    del b, h0, static
    return ({"b": a.shape[0], "s": a.shape[1], "d": a.shape[2]}, a.dtype)


def _ls_synth(bdims, static, dtype):
    import jax.numpy as jnp
    del static
    b, s, d = bdims["b"], bdims["s"], bdims["d"]
    rng = _rng()
    a = rng.uniform(0.7, 1.0, (b, s, d)).astype(dtype)
    bb = rng.standard_normal((b, s, d)).astype(dtype)
    h0 = np.zeros((b, d), dtype)
    return jnp.asarray(a), jnp.asarray(bb), jnp.asarray(h0)


def _ls_grid(dims: dict, kd: KernelDefaults) -> tuple:
    # chunks longer than the sequence all clamp to the same kernel — dedupe
    chunks = dict.fromkeys(min(c, dims["s"])
                           for c in block_candidates(kd.scan_chunk))
    return tuple({"chunk": c} for c in chunks)


def _ls_variants() -> tuple[Variant, ...]:
    def ref(static, params):
        from repro.kernels.linear_scan.ref import linear_scan_ref
        return jax.jit(linear_scan_ref)

    def pallas(static, params):
        from repro.kernels.linear_scan.ops import linear_scan
        chunk = params.get("chunk")
        return jax.jit(lambda a, b, h0: linear_scan(a, b, h0,
                                                    use_pallas=True,
                                                    chunk=chunk))

    return (Variant("ref", ref),
            Variant("pallas", pallas, grid=_ls_grid, exact=False))


register_op(OpSpec(
    name="linear_scan",
    describe=_ls_describe,
    variants=_ls_variants,
    synth=_ls_synth,
    default=_pallas_or_ref(lambda kd: {"chunk": kd.scan_chunk}),
))


# flash_attention: q [B, S, H, D], k/v [B, S, Hkv, D] (model layout)


def _fa_describe(args, static):
    q, k, v = args
    del v, static
    return ({"b": q.shape[0], "s": q.shape[1], "h": q.shape[2],
             "hkv": k.shape[2], "d": q.shape[3]}, q.dtype)


def _fa_synth(bdims, static, dtype):
    import jax.numpy as jnp
    del static
    rng = _rng()
    b, s, h, hkv, d = (bdims["b"], bdims["s"], bdims["h"], bdims["hkv"],
                       bdims["d"])
    h = max(h, hkv) // hkv * hkv  # grouped-query: H must divide by Hkv
    q = rng.standard_normal((b, s, h, d)).astype(dtype)
    k = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    v = rng.standard_normal((b, s, hkv, d)).astype(dtype)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def _fa_grid(dims: dict, kd: KernelDefaults) -> tuple:
    qs = dict.fromkeys(min(b, dims["s"]) for b in block_candidates(kd.block_q))
    return tuple({"block_q": b, "block_k": b} for b in qs)


def _fa_variants() -> tuple[Variant, ...]:
    def ref(static, params):
        from repro.kernels.flash_attention.ops import flash_attention
        causal = static["causal"]
        return jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                                       use_pallas=False))

    def pallas(static, params):
        from repro.kernels.flash_attention.ops import flash_attention
        causal = static["causal"]
        bq, bk = params.get("block_q"), params.get("block_k")
        return jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, use_pallas=True, block_q=bq, block_k=bk))

    return (Variant("ref", ref),
            Variant("pallas", pallas, grid=_fa_grid, exact=False,
                    atol=2e-3, rtol=2e-3))


register_op(OpSpec(
    name="flash_attention",
    describe=_fa_describe,
    variants=_fa_variants,
    synth=_fa_synth,
    default=_pallas_or_ref(lambda kd: {"block_q": kd.block_q,
                                       "block_k": kd.block_k}),
))


# diffusion_conv: x [B, N, C], supports (tuple of [N, N]), w, bias


def _dc_describe(args, static):
    x, supports, w, bias = args
    del supports, bias
    return ({"b": x.shape[0], "n": x.shape[1], "c": x.shape[2],
             "h": w.shape[1]}, x.dtype)


def _dc_synth(bdims, static, dtype):
    import jax.numpy as jnp
    rng = _rng()
    b, n, c, h = bdims["b"], bdims["n"], bdims["c"], bdims["h"]
    k, ns = static["k_hops"], static["n_supports"]
    supports = []
    for _ in range(ns):
        adj = rng.uniform(0, 1, (n, n)).astype(np.float32)
        adj[adj < 0.5] = 0
        np.fill_diagonal(adj, 1.0)
        supports.append(jnp.asarray(adj / adj.sum(1, keepdims=True)))
    x = rng.standard_normal((b, n, c)).astype(dtype)
    w = (rng.standard_normal(((1 + ns * k) * c, h)) * 0.1).astype(dtype)
    bias = np.zeros((h,), dtype)
    return (jnp.asarray(x), tuple(supports), jnp.asarray(w),
            jnp.asarray(bias))


def _dc_grid(dims: dict, kd: KernelDefaults) -> tuple:
    blocks = dict.fromkeys(min(b, pow2_bucket(dims["n"]))
                           for b in block_candidates(kd.block_n))
    return tuple({"block_n": b} for b in blocks)


def _dc_variants() -> tuple[Variant, ...]:
    def ref(static, params):
        from repro.kernels.diffusion_conv.ref import diffusion_conv_ref
        k = static["k_hops"]
        return jax.jit(lambda x, sup, w, b: diffusion_conv_ref(x, sup, w, b,
                                                               k_hops=k))

    def pallas(static, params):
        from repro.kernels.diffusion_conv.ops import diffusion_conv
        k, bn = static["k_hops"], params.get("block_n")
        return jax.jit(lambda x, sup, w, b: diffusion_conv(
            x, sup, w, b, k_hops=k, use_pallas=True, block_n=bn))

    return (Variant("ref", ref),
            Variant("pallas", pallas, grid=_dc_grid, exact=False))


register_op(OpSpec(
    name="diffusion_conv",
    describe=_dc_describe,
    variants=_dc_variants,
    synth=_dc_synth,
    default=_pallas_or_ref(lambda kd: {"block_n": kd.block_n}),
))
