"""Chip smoke: index-batched PGT-DCRNN training on a TPU at PeMS-All-LA width.

Drives the paper's main path once, through the launcher's own entry points
(``repro.launch.train``: ``stgnn_problem`` -> ``build_stgnn`` ->
``build_pipeline`` -> ``Engine.fit``), at the full width of the
``pgt-dcrnn-pems-all-la`` configuration (2,716 nodes, hidden 64, K = 2,
12 -> 12, float32) over the synthetic series at its Table-1 size
(105,120 steps x 2,716 nodes x 2 features, about 2.1 GiB, resident on the
chip).  Weights and data are random, made from ``--seed``.

One chip (the default) checks that:
  - the train step takes the series and the supports as arguments and
    embeds no constant over 1 MiB;
  - every training loss is finite and the last is below the first;
  - the Pallas window gather equals the ``slice`` gather bit for bit;
  - the Pallas diffusion conv matches ``diffusion_conv_ref`` (allclose);
  - a 4-window loss on the chip matches the host CPU backend's.
It prints compile seconds, each step's loss, peak device memory and step
milliseconds (information, not a metric).

``--chips 4`` runs only distributed-index-batching on a (4, 1) mesh:
PARTITIONED with ``halo=False`` and ONDEMAND (the DDP baseline), each a few
steps at global batch 256, printing each device's share of the series, the
gather lowering that ran and the compiled step's collectives.

Exits non-zero, printing no result, unless JAX's first device is a TPU; the
last line of a passing run is one JSON object naming the device.

  python chip_smoke.py
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "pgt-dcrnn-pems-all-la"
BATCH_ONE_CHIP = 64    # the spec's global batch (1,024) is a multi-device one
BATCH_FOUR_CHIPS = 256
MIB = 2**20
MAX_CONSTANT_BYTES = MIB
# Chip and CPU both at "highest" matmul precision compute in float32; they
# differ only in reduction order and transcendental rounding.
REF_LOSS_RTOL = 1e-4
DCONV_TOL = 1e-3       # atol and rtol of the diffusion-conv allclose


class Checks:
    """Collects pass/fail lines; a failed check does not stop later phases."""

    def __init__(self):
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str) -> bool:
        print(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})",
              flush=True)
        if not ok:
            self.failed.append(name)
        return ok

    def phase(self, name: str, fn, *a, **kw):
        """Run one phase; an exception fails it and the run goes on."""
        print(f"== {name}", flush=True)
        try:
            return fn(*a, **kw)
        except Exception as e:  # noqa: BLE001 — reported, then exit non-zero
            import traceback
            traceback.print_exc()
            self.check(name, False, f"{type(e).__name__}: {e}"[:500])
            return None


def launcher_args(entries: int, batch: int, steps: int, seed: int,
                  nodes: int = 0, extra: tuple = ()):
    from repro.launch import train as launcher
    argv = ["--arch", ARCH, "--entries", str(entries), "--batch", str(batch),
            "--steps", str(steps), "--log-every", "1", "--eval-every", "0",
            "--seed", str(seed), *extra]
    if nodes:
        argv += ["--nodes", str(nodes)]
    return launcher.parse_args(argv)


def build_problem(args):
    from repro.configs import get_arch
    from repro.launch import train as launcher
    t0 = time.perf_counter()
    problem = launcher.stgnn_problem(get_arch(ARCH), args)
    mcfg, series, _ = problem
    print(f"model: {ARCH} nodes={mcfg.num_nodes} hidden={mcfg.hidden} "
          f"K={mcfg.max_diffusion_step} {mcfg.input_len}->{mcfg.horizon} "
          f"float32")
    print(f"series: {series.shape} {series.dtype} "
          f"{series.nbytes / 2**30:.3f} GiB, generated in "
          f"{time.perf_counter() - t0:.1f} s (seed {args.seed})")
    return problem


def device_series_bytes(series) -> dict:
    per = {}
    for shard in series.addressable_shards:
        per[shard.device.id] = per.get(shard.device.id, 0) + shard.data.nbytes
    return per


def losses_of(history) -> list[float]:
    return [h["loss"] for h in history if "epoch_time_s" not in h]


def lower_step(pipe):
    """(lowered train step, first state, first batch) of an engine."""
    import jax
    import jax.numpy as jnp
    from repro.train.loop import init_train_state
    state = init_train_state(jax.tree.map(jnp.copy, pipe.init_params),
                             pipe.config.adam)
    starts = pipe.batch_of_starts(pipe.dataplane.epoch_grid(0)[0])
    return pipe.train_step.lower(state, starts), state, starts


def check_step_arguments(c: Checks, pipe, lowered) -> None:
    import jax
    from repro.launch.dryrun import largest_constant_bytes
    shapes = [a.shape for a in jax.tree.leaves(lowered.args_info)]
    series = pipe.dataset.series
    n = series.shape[1]
    c.check("series_is_argument", series.shape in shapes,
            f"series {series.shape} among {len(shapes)} step arguments")
    c.check("supports_are_arguments", shapes.count((n, n)) == 2,
            f"{shapes.count((n, n))} of 2 supports [{n}, {n}] are arguments")
    big = largest_constant_bytes(lowered)
    c.check("no_large_constant", big <= MAX_CONSTANT_BYTES,
            f"largest constant {big} bytes, limit {MAX_CONSTANT_BYTES}")


def train_one_chip(c: Checks, problem, args) -> dict:
    import jax
    import numpy as np
    from repro.configs import get_arch
    from repro.launch import train as launcher

    arch = get_arch(ARCH)
    print(f"batch: {args.batch} windows (cut from the spec's global batch "
          f"{arch.shapes[0].global_batch}, a multi-device batch, for one "
          f"chip)")
    t0 = time.perf_counter()
    pipe = launcher.build_stgnn(arch, args, problem)
    series = pipe.dataset.series
    jax.block_until_ready(series)
    print(f"placed: {pipe.describe()} in {time.perf_counter() - t0:.1f} s; "
          f"series bytes per device {device_series_bytes(series)}")

    lowered, _, _ = lower_step(pipe)
    check_step_arguments(c, pipe, lowered)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    print(f"compile: train step {time.perf_counter() - t0:.1f} s")
    mem = compiled.memory_analysis()
    if mem is not None:
        print(f"compiled memory: arguments "
              f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
              f"{mem.temp_size_in_bytes / 2**30:.3f} GiB")

    t0 = time.perf_counter()
    state, history = pipe.fit(resume=False, eval_fn=None)
    losses = losses_of(history)
    print(f"fit: {len(losses)} steps in {time.perf_counter() - t0:.1f} s "
          f"(first step includes its compile)")
    for i, loss in enumerate(losses, 1):
        print(f"step {i:3d} loss {loss:.6f}")
    c.check("steps_ran", len(losses) == args.steps,
            f"{len(losses)} of {args.steps} steps logged")
    c.check("losses_finite", bool(losses) and all(map(math.isfinite, losses)),
            "every training loss is finite")
    c.check("loss_decreased", len(losses) > 1 and losses[-1] < losses[0],
            f"first {losses[0] if losses else None} last "
            f"{losses[-1] if losses else None}")

    # Step time on the AOT-compiled step: information, not a metric.
    grid = pipe.dataplane.epoch_grid(0)
    times = []
    for row in grid[args.steps:args.steps + 5]:
        batch = pipe.batch_of_starts(row)
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch, *pipe.train_step.args)
        jax.block_until_ready((state, metrics))
        times.append(1e3 * (time.perf_counter() - t0))
    print(f"step ms (information, not a metric; host clock around "
          f"block_until_ready): {[round(t, 3) for t in times]} "
          f"median {float(np.median(times)):.3f}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')} "
          f"({(stats.get('peak_bytes_in_use') or 0) / 2**30:.3f} GiB)")
    return {"pipe": pipe, "starts": grid[0]}


def check_gather(c: Checks, pipe, window_ids) -> None:
    import functools
    import jax
    import numpy as np
    from repro.core.batching import gather_batch, gather_batch_fused
    series = pipe.dataset.series
    starts = pipe.batch_of_starts(window_ids)
    spec = pipe.spec
    kw = dict(input_len=spec.in_len, horizon=spec.horizon)
    ref = gather_batch(series, starts, **kw)
    pallas = jax.jit(functools.partial(gather_batch_fused, use_pallas=True,
                                       **kw))(series, starts)
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(ref, pallas))
    c.check("pallas_gather_bit_identical", same,
            f"{len(window_ids)} windows of span {spec.span} over series "
            f"{series.shape}")


def check_diffusion_conv(c: Checks, problem, seed: int, batch: int = 8) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.diffusion_conv import diffusion_conv, diffusion_conv_ref
    mcfg, _, supports = problem
    k = mcfg.max_diffusion_step
    cin, h = mcfg.in_features + mcfg.hidden, 2 * mcfg.hidden
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, mcfg.num_nodes, cin),
                                        np.float32))
    w = jnp.asarray(rng.standard_normal(((1 + 2 * k) * cin, h), np.float32)
                    / np.sqrt((1 + 2 * k) * cin))
    b = jnp.zeros((h,), jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: diffusion_conv_ref(*a, k_hops=k))(
            x, supports, w, b)
        pal = jax.jit(lambda *a: diffusion_conv(*a, k_hops=k,
                                                use_pallas=True))(
            x, supports, w, b)
    ref, pal = np.asarray(ref), np.asarray(pal)
    err = float(np.max(np.abs(ref - pal)))
    c.check("pallas_diffusion_conv_allclose",
            np.allclose(pal, ref, atol=DCONV_TOL, rtol=DCONV_TOL),
            f"x {x.shape}, max |pallas - ref| {err:.3e}, "
            f"atol=rtol={DCONV_TOL}")


def check_reference_loss(c: Checks, pipe, problem, window_ids) -> None:
    import jax
    import numpy as np
    from repro.core.batching import gather_batch
    from repro.models import pgt_dcrnn
    mcfg, _, supports = problem
    spec = pipe.spec
    starts = pipe.batch_of_starts(window_ids)
    x, y = gather_batch(pipe.dataset.series, starts, input_len=spec.in_len,
                        horizon=spec.horizon)
    params = pipe.init_params

    def loss(p, s, x, y):
        return pgt_dcrnn.loss_fn(p, mcfg, s, x, y)

    chip_default = float(jax.jit(loss)(params, supports, x, y))
    cpu = jax.devices("cpu")[0]
    host = jax.device_put(jax.device_get((params, supports, x, y)), cpu)
    with jax.default_matmul_precision("highest"):
        chip = float(jax.jit(loss)(params, supports, x, y))
        ref = float(jax.jit(loss)(*host))
    rel = abs(chip - ref) / abs(ref)
    print(f"4-window loss: chip {chip:.8f} (default precision "
          f"{chip_default:.8f}), host cpu {ref:.8f}")
    c.check("chip_matches_cpu_loss", rel <= REF_LOSS_RTOL,
            f"relative difference {rel:.3e}, rtol {REF_LOSS_RTOL}")


def one_chip(c: Checks, seed: int, steps: int, *, entries: int = 0,
             nodes: int = 0) -> None:
    from repro.configs import get_arch
    from repro.data import get_dataset_spec
    entries = entries or get_dataset_spec(get_arch(ARCH).dataset).entries
    args = launcher_args(entries, BATCH_ONE_CHIP, steps, seed, nodes)
    problem = c.phase("data", build_problem, args)
    if problem is None:
        return
    run = c.phase("train", train_one_chip, c, problem, args)
    if run is None:
        return
    pipe, row = run["pipe"], run["starts"]
    c.phase("pallas_gather", check_gather, c, pipe, row)
    c.phase("pallas_diffusion_conv", check_diffusion_conv, c, problem, seed)
    c.phase("reference_loss", check_reference_loss, c, pipe, problem, row[:4])


def distributed_run(c: Checks, problem, args, name: str) -> None:
    import jax
    from repro.configs import get_arch
    from repro.launch import train as launcher
    from repro.launch.dryrun import collective_bytes
    pipe = launcher.build_stgnn(get_arch(ARCH), args, problem)
    series = pipe.dataset.series
    desc = pipe.describe()
    per = device_series_bytes(series)
    print(f"{name}: placement {desc['placement'].value} halo {desc['halo']} "
          f"sampler {desc['sampler']} gather lowering "
          f"{desc['gather_lowering']} mesh {dict(pipe.mesh.shape)} global "
          f"batch {desc['global_batch']}")
    total = series.nbytes
    print(f"{name}: series bytes per device {per} (total {total}, "
          f"share {[round(v / total, 4) for v in per.values()]})")
    c.check(f"{name}_series_quarter_per_device",
            len(per) == 4 and all(abs(v / total - 0.25) < 0.01
                                  for v in per.values()),
            f"{len(per)} devices hold the series")
    lowered, _, _ = lower_step(pipe)
    check_step_arguments(c, pipe, lowered)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    print(f"{name}: compile {time.perf_counter() - t0:.1f} s")
    coll = collective_bytes(compiled.as_text())
    print(f"{name}: collectives per device {coll['counts']} bytes "
          f"{ {k: v for k, v in coll.items() if k != 'counts'} }")
    _, history = pipe.fit(resume=False, eval_fn=None)
    losses = losses_of(history)
    print(f"{name}: losses {[round(v, 6) for v in losses]}")
    c.check(f"{name}_losses_finite",
            len(losses) == args.steps and all(map(math.isfinite, losses)),
            f"{len(losses)} finite losses of {args.steps}")
    del pipe, series
    jax.clear_caches()


def four_chips(c: Checks, seed: int, steps: int, *, entries: int = 0,
               nodes: int = 0) -> None:
    from repro.configs import get_arch
    from repro.data import get_dataset_spec
    entries = entries or get_dataset_spec(get_arch(ARCH).dataset).entries
    base = launcher_args(entries, BATCH_FOUR_CHIPS, steps, seed, nodes)
    problem = c.phase("data", build_problem, base)
    if problem is None:
        return
    for name, extra in (("partitioned", ("--placement", "partitioned",
                                         "--no-halo")),
                        ("ondemand", ("--placement", "ondemand"))):
        args = launcher_args(entries, BATCH_FOUR_CHIPS, steps, seed, nodes,
                             extra)
        c.phase(name, distributed_run, c, problem, args, name)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0,
                    help="training steps (default 20 on one chip, 5 per "
                         "placement on four)")
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX's first device is "
              f"{devices[0].platform!r}; this script does not run elsewhere",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}")
    c = Checks()
    if args.chips == 1:
        one_chip(c, args.seed, args.steps or 20)
    else:
        four_chips(c, args.seed, args.steps or 5)
    if c.failed:
        print(f"chip_smoke: FAILED {c.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
