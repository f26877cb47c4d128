"""One run of one cell: set-up, warm-up, the measured window, the traced
window, the memory reading and the comparison that decides ``correct``.

The window drives the launcher's training entry, ``Engine.fit``, on an engine
built the way ``repro.launch.train.build_stgnn`` builds one (``train_config``
for Adam, the schedule and the loop; a ``Partial`` over the supports;
``make_host_mesh``; ``build_pipeline``), over an ``IndexDataset`` whose series
the benchmark made on the device in the cell's series sharding.

Set-up ends with a warm-up ``fit`` of ``WARMUP_STEPS`` steps.  It compiles
the one train-step shape the window uses, and its steps, which run through
the window's own step, feed and loop, are the ones the plain reference
follows afterwards.  The measured ``fit`` then runs as many steps as the
warm-up's rate says fill ``seconds``, from the same engine.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import math
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

from bench import flops, hlo, spec, trace_reduce
from bench.data import synthetic

WARMUP_STEPS = 3         # the reference follows these steps
TRACE_SECONDS = 2.0      # the traced window: a few steps, so the trace stays small
BYTES_PER_GIB = 2**30
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class NoChip(RuntimeError):
    """JAX's devices are not the TPUs the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileLog:
    """Durations of JAX's trace, lowering and compile events, as they come."""

    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name in COMPILE_EVENTS:
            self.events.append((name, secs))

    def mark(self) -> int:
        return len(self.events)

    def since(self, mark: int) -> list[tuple[str, float]]:
        return self.events[mark:]


class Recorder:
    """Wraps the engine's train step and feed during the warm-up, the
    measured window and the traced window.

    Warm-up: blocks after each step, times it, and keeps what the comparison
    needs (the starts fed, the loss, the optimizer's first moment after step
    1).  Window: keeps each step's loss on the device, unread until the
    window has closed.  Traced: opens the harness's host spans around the feed
    and the step dispatch, and between steps (``bench.log_sync`` after a
    logging step).
    """

    def __init__(self, pipe, compile_log: CompileLog, log_every: int):
        self.pipe = pipe
        self.step = pipe.train_step
        self.feed = pipe.dataplane.batch_of_starts
        self.compile_log = compile_log
        self.log_every = log_every
        self.mode = None
        self.calls = 0
        self.starts, self.losses, self.times, self.m1 = [], [], [], None
        self.window_losses = []
        self.compile_s: list[float] = []
        self._between = None

    def __enter__(self):
        self.pipe.train_step = self._step
        self.pipe.dataplane.batch_of_starts = self._feed
        return self

    def __exit__(self, *exc):
        self._close_between()
        self.pipe.train_step = self.step
        del self.pipe.dataplane.batch_of_starts
        return False

    def _close_between(self):
        if self._between is not None:
            self._between.__exit__(None, None, None)
            self._between = None

    def _feed(self, window_ids, **kw):
        if self.mode != "trace":
            return self.feed(window_ids, **kw)
        import jax
        self._close_between()
        with jax.profiler.TraceAnnotation("bench.feed"):
            return self.feed(window_ids, **kw)

    def _step(self, state, batch):
        import jax
        self.calls += 1
        if self.mode == "window":
            out = self.step(state, batch)
            self.window_losses.append(out[1]["loss"])
            return out
        if self.mode == "trace":
            self._close_between()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = self.step(state, batch)
            name = ("bench.log_sync" if self.calls % self.log_every == 0
                    else "bench.loop")
            self._between = jax.profiler.TraceAnnotation(name)
            self._between.__enter__()
            return out
        t0 = time.perf_counter()
        mark = self.compile_log.mark()
        self.starts.append(np.asarray(batch))
        out = self.step(state, batch)
        jax.block_until_ready(out)
        self.times.append(time.perf_counter() - t0)
        self.compile_s.append(sum(s for _, s in self.compile_log.since(mark)))
        if self.calls == 1:
            self.m1 = jax.device_get(out[0]["opt"]["m"])
        self.losses.append(float(out[1]["loss"]))
        return out


def _require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's devices are {devices[0].platform!r}, not TPUs; "
                     f"this benchmark runs on the chip only")
    if len(devices) != chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devices)}")


def _device_info(chips: int) -> dict:
    import jax
    devices = jax.devices()[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def _launcher_args(cfg, traffic, cell_name, seed):
    """The launcher's own flags for this cell."""
    from repro.launch import train as launcher
    o = cfg["optimizer"]
    argv = ["--arch", cfg["name"], "--batch", str(traffic["global_batch"]),
            "--steps", str(WARMUP_STEPS), "--lr", repr(o["lr"]),
            "--eval-every", "0", "--seed", str(seed),
            "--placement", traffic["placement"], "--gather", traffic["gather"]]
    if not traffic["halo"]:
        argv.append("--no-halo")
    args = launcher.parse_args(argv)
    total = max(args.steps, 100)
    if (total, total // 10) != (o["total_steps"], o["warmup_steps"]):
        raise ValueError(f"{cell_name}: the launcher's schedule ({total} steps, "
                         f"{total // 10} warm-up) is not the configuration's")
    return args


def build(cfg: dict, traffic: dict, seed: int, cell_name: str):
    """The cell's data and engine: ``(pipe, parts)``, where ``parts`` holds
    what the reference and the counts need."""
    import jax
    from repro.core import IndexDataset, Placement, WindowSpec
    from repro.core.distributed import dp_size, series_sharding
    from repro.data.normalize import Scaler
    from repro.launch import train as launcher
    from repro.launch.mesh import make_host_mesh
    from repro.pipeline import PipelineConfig, build_pipeline

    m, s = cfg["model"], cfg["series"]
    key = synthetic.seed_key(seed)
    k_graph, k_series, k_params = jax.random.split(key, 3)
    mesh = make_host_mesh()
    placement = Placement(traffic["placement"])
    sharding = series_sharding(mesh, placement)

    t0 = time.perf_counter()
    supports, smooth, nnz = synthetic.make_graph(k_graph, m["num_nodes"])
    raw = synthetic.make_series(k_series, s["entries"], smooth, sharding,
                                chunk=s["chunk"])
    del smooth
    spec_ = WindowSpec(horizon=m["horizon"], input_len=m["input_len"])
    starts, (tr, va, te), end = synthetic.window_layout(
        s["entries"], spec_.span, spec_.in_len)
    series, (mean, std) = synthetic.standardise(raw, end, sharding,
                                                chunk=s["chunk"])
    jax.block_until_ready(series)
    nnz = [int(x) for x in nnz]
    log(f"data: series {series.shape} {series.dtype} "
        f"{series.nbytes / BYTES_PER_GIB:.3f} GiB made on the device in "
        f"{time.perf_counter() - t0:.2f} s; support nonzero shares "
        f"{[round(x / m['num_nodes'] ** 2, 4) for x in nnz]}")
    ds = IndexDataset(series, starts, spec_, Scaler(mean, std), tr, va, te)

    prog = cfg["program"]
    mod = importlib.import_module(prog["module"])
    mcfg = getattr(mod, prog["config"])(**m)
    params = jax.jit(mod.init, static_argnums=1)(k_params, mcfg)
    args = _launcher_args(cfg, traffic, cell_name, seed)
    adam, sched, loop = launcher.train_config(args)

    def loss_fn(supports, p, x, y):
        return mod.loss_fn(p, mcfg, supports, x, y), {}

    dp = max(dp_size(mesh), 1)
    pipe = build_pipeline(
        None, spec_, mesh, jax.tree_util.Partial(loss_fn, supports), params,
        PipelineConfig(batch_per_rank=args.batch // dp, placement=placement,
                       gather=args.gather, halo=not args.no_halo,
                       seed=args.seed, adam=adam, schedule=sched, loop=loop),
        dataset=ds)
    if pipe.dataset.series is not series:
        raise RuntimeError("the engine re-placed the series the benchmark made")
    log(f"engine: {pipe.describe()}")
    return pipe, {"supports": supports, "nnz": nnz, "k_params": k_params}


def _set_max_steps(pipe, steps: int) -> None:
    cfg = pipe.dataplane.config
    pipe.dataplane.config = dataclasses.replace(
        cfg, loop=dataclasses.replace(cfg.loop, max_steps=steps))


def _fit(pipe, steps: int):
    _set_max_steps(pipe, steps)
    return pipe.fit(resume=False, eval_fn=None)


def _traced_fit(pipe, recorder, steps: int, keep: str | None) -> dict:
    import jax
    out_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        recorder.mode = "trace"
        recorder.calls = 0
        with jax.profiler.trace(out_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                state, _ = _fit(pipe, steps)
                jax.block_until_ready(state)
                recorder._close_between()
        paths = [os.path.join(d, f) for d, _, fs in os.walk(out_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one .xplane.pb, found {paths}")
        if keep:
            shutil.copy(paths[0], keep)
        return trace_reduce.reduce(trace_reduce.load(paths[0]), steps=steps)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def _compiled_step(pipe, state, batch) -> dict:
    """Memory and collectives of the compiled train step (from the cache)."""
    compiled = pipe.train_step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return {"bytes": int(need), "temp": int(mem.temp_size_in_bytes),
            "collectives": hlo.collective_bytes(compiled.as_text())}


def _leaf_norms(tree) -> list[float]:
    import jax
    return [float(np.linalg.norm(np.asarray(x, np.float64)))
            for x in jax.tree.leaves(tree)]


def _worst_leaf(prog: list[float], ref: list[float], keep: list[bool]) -> float:
    """Largest gap between the program's and the reference's norm of a leaf,
    over the larger of that leaf's reference norm and the median leaf's."""
    med = statistics.median(ref)
    gaps = [abs(p - r) / max(r, med) for p, r, k in zip(prog, ref, keep) if k]
    return max(gaps) if gaps else 0.0


def window_flops(cfg: dict, nnz: list[int]) -> tuple[float, float]:
    """(useful, dense) FLOPs of one training window of this configuration,
    as its family's plain reference counts them."""
    from bench.reference import train as ref_train
    mod = ref_train.model(cfg["family"])
    return tuple(mod.window_flops(cfg["model"], nnz, dense=dense)
                 for dense in (False, True))


def reference_batches(series, starts: list[np.ndarray], m: dict):
    """Host ``(x, y)`` of each warm-up step's windows, read window by window
    from the series' shards with a fixed-size slice: a gather over the whole
    series made XLA copy it into another layout, which full PeMS cannot
    afford, and a slice of a time-sharded series would gather it."""
    import jax
    span, in_len = m["input_len"] + m["horizon"], m["input_len"]
    shards = {}
    for sh in series.addressable_shards:
        shards.setdefault(sh.index[0].start or 0, sh.data)
    take = jax.jit(lambda a, i: jax.lax.dynamic_slice_in_dim(a, i, span, 0))
    out = []
    for row in starts:
        windows = []
        for st in map(int, row):
            rows = []
            for lo, a in sorted(shards.items()):
                hi = lo + a.shape[0]
                if st + span <= lo or st >= hi:
                    continue
                c = min(max(st - lo, 0), a.shape[0] - span)
                block = np.asarray(take(a, np.int32(c)))
                rows.append(block[max(st, lo) - lo - c:min(st + span, hi) - lo - c])
            windows.append(np.concatenate(rows))
        win = np.stack(windows)
        out.append((win[:, :in_len], win[:, in_len:]))
    return out


def follow_reference(cfg: dict, parts: dict, batches, *, block: int,
                     dtype: str = "float32", precision: str = "highest",
                     rows: slice | None = None) -> dict:
    """The plain reference over ``batches`` on the first device, from its own
    weights drawn from the cell's key.  ``rows`` plants a fault in the
    reference put in the program's place: only those rows of each batch."""
    import jax
    from bench.reference import train as ref_train
    m = cfg["model"]
    dev = jax.devices()[0]
    mod = ref_train.model(cfg["family"])
    params0 = jax.device_put(mod.init(parts["k_params"], m), dev)
    supports = jax.device_put(tuple(parts["supports"]), dev)
    picked = []
    for x, y in batches:
        if rows is not None:
            x, y = x[rows], y[rows]
        picked.append((jax.device_put(x, dev), jax.device_put(y, dev)))
    b = min(block, picked[0][0].shape[0])
    out = ref_train.follow(cfg["family"], m, cfg["optimizer"], params0,
                           supports, picked, block=b, dtype=dtype,
                           precision=precision)
    out["params0"] = params0
    return out


def reference_args(checks: dict) -> dict:
    """The reference's block of rows and matmul precision, from a cell's
    checks file: ``highest`` unless the file names another, such as the
    configuration's own ``default`` where a reference at ``highest`` sits
    as far from the program as the lower-precision control does."""
    return {"block": checks["reference_block"],
            "precision": checks.get("reference_precision", "highest")}


def readings(cfg: dict, prog: dict, ref: dict) -> dict:
    """The numbers compared: the worst step's relative loss gap, the worst
    leaf's gap in the first clipped gradient's norm, and the worst leaf's gap
    in the norm of the weights' change over the steps.  ``prog`` holds the
    program's ``losses``, first clipped gradient ``grad1`` (worked out from
    Adam's first moment after step 1) and weights ``p0`` and ``p_end``."""
    import jax
    n = len(ref["losses"])
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"][:n], ref["losses"]))
    g_ref = _leaf_norms(ref["grad1"])
    med = statistics.median(g_ref)
    # Leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: leave them out of the change.
    moved = [g >= 1e-3 * med for g in g_ref]
    g_prog = _leaf_norms(prog["grad1"])
    diff = lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64)
    d_prog = _leaf_norms(jax.tree.map(diff, prog["p_end"], prog["p0"]))
    d_ref = _leaf_norms(jax.tree.map(diff, ref["params"], ref["params0"]))
    return {"loss_gap": loss_gap,
            "grad1_gap": _worst_leaf(g_prog, g_ref, [True] * len(g_ref)),
            "update_gap": _worst_leaf(d_prog, d_ref, moved),
            "leaves_left_out": moved.count(False)}


@dataclasses.dataclass
class Setup:
    """A cell's engine after the warm-up, with what the comparison needs."""

    pipe: object
    parts: dict
    recorder: Recorder
    compile_log: CompileLog
    prog: dict          # losses, grad1, p0, p_end of the warm-up steps
    step_s: float       # warm-up step time, for sizing the window


def setup(cfg: dict, traffic: dict, seed: int, cell_name: str) -> Setup:
    """Data, engine and the warm-up ``fit``."""
    import jax
    compile_log = CompileLog()
    pipe, parts = build(cfg, traffic, seed, cell_name)
    recorder = Recorder(pipe, compile_log, pipe.config.loop.log_every)
    with recorder:
        recorder.mode = "warmup"
        state, _ = _fit(pipe, WARMUP_STEPS)
    b1 = cfg["optimizer"]["b1"]
    prog = {"losses": list(recorder.losses),
            "grad1": jax.tree.map(lambda m: np.asarray(m, np.float64) / (1 - b1),
                                  recorder.m1),
            "p0": jax.device_get(pipe.init_params),
            "p_end": jax.device_get(state["params"])}
    del state
    # The second step compiles again (its state arrives committed), so the
    # last warm-up step is the first that runs without compiling.
    step_s = recorder.times[-1]
    log(f"warm-up: {WARMUP_STEPS} steps {[round(t, 4) for t in recorder.times]}"
        f" s, losses {prog['losses']}, compile events per step "
        f"{[round(c, 3) for c in recorder.compile_s]} s")
    return Setup(pipe, parts, recorder, compile_log, prog, step_s)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, workload: dict | None = None,
        cfg: dict | None = None, traffic: dict | None = None,
        checks: dict | None = None, keep_trace: str | None = None) -> dict:
    """One run; returns the result line's object.  ``workload``, ``cfg``,
    ``traffic`` and ``checks`` default to the cell's entry and files (tests
    pass smaller ones); ``keep_trace`` is a path to copy the raw trace to."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    w = workload or spec.workload(cell_name)
    cfg = cfg or spec.config(w["config"])
    traffic = traffic or spec.traffic(w["traffic"])
    checks = checks or spec.checks(cell_name)
    t_run = time.perf_counter()
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_tpu:
        _require_chips(w["chips"])
    chips = len(jax.devices())
    t_devices = time.perf_counter()

    s = setup(cfg, traffic, seed, cell_name)
    pipe, gb = s.pipe, traffic["global_batch"]
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.2f} s: process start and imports "
        f"{t_run - t_start:.2f} s, devices {t_devices - t_run:.2f} s, data, "
        f"engine and warm-up {t_start + setup_s - t_devices:.2f} s")

    steps = max(WARMUP_STEPS, math.ceil(seconds / s.step_s))
    mark = s.compile_log.mark()
    with s.recorder:
        s.recorder.mode = "window"
        t0 = time.perf_counter()
        state, _ = _fit(pipe, steps)
        jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    compiles = sum(1 for n, _ in s.compile_log.since(mark)
                   if n == COMPILE_EVENTS[-1])
    windows_per_s = steps * gb / window_s
    losses = np.asarray(jax.device_get(s.recorder.window_losses), np.float64)
    finite_end = all(np.isfinite(x).all()
                     for x in jax.device_get(jax.tree.leaves(state["params"])))
    # A step has failed when its loss is not finite; the last step also when
    # the weights it leaves are not (earlier steps' weights show in the next
    # step's loss).
    failed = int((~np.isfinite(losses)).sum()
                 + (not finite_end and np.isfinite(losses[-1])))
    log(f"window: {steps} steps x {gb} windows in {window_s:.3f} s; "
        f"compilations inside the window: {compiles}; losses "
        f"{np.round(losses[:3], 5).tolist()}..{np.round(losses[-2:], 5).tolist()}"
        f"; steps failed {failed}")

    traced = None
    if trace:
        n_trace = min(max(3, math.ceil(TRACE_SECONDS / s.step_s)), 64)
        with s.recorder:
            traced = _traced_fit(pipe, s.recorder, n_trace, keep_trace)
        traced["steps"] = n_trace

    device = _device_info(chips)
    batch = pipe.dataplane.batch_of_starts(pipe.dataplane.epoch_grid(0)[0])
    compiled = _compiled_step(pipe, state, batch)
    hbm = max(device["memory_peak_bytes"], compiled["bytes"])
    log(f"memory: peak_bytes_in_use {device['memory_peak_bytes']} B, compiled "
        f"step needs {compiled['bytes']} B ({compiled['temp']} B temporaries);"
        f" collectives per device {compiled['collectives']}")

    # The comparison runs after the window, with the program's state freed.
    batches = reference_batches(pipe.dataset.series, s.recorder.starts, cfg["model"])
    duplicates = int(sum(x.size for x in s.recorder.starts)
                     - np.unique(np.concatenate(s.recorder.starts)).size)
    parts, prog = s.parts, s.prog
    compile_s = sum(s.recorder.compile_s)
    del state, batch, pipe, s
    gc.collect()
    t_ref = time.perf_counter()
    ref = follow_reference(cfg, parts, batches, **reference_args(checks))
    got = readings(cfg, prog, ref)
    got.update(duplicate_windows=duplicates, window_failed=failed)
    log(f"reference: {len(batches)} steps in {time.perf_counter() - t_ref:.2f} s; "
        f"losses program {prog['losses']} reference {ref['losses']};"
        f" leaves left out of the change {got['leaves_left_out']}")
    limits = checks["limits"]
    compared = {k: {"value": got[k], "limit": limits[k]} for k in limits}
    correct = all(v["value"] <= v["limit"] for v in compared.values())

    result = {"correct": correct, "attempted": steps, "failed": failed}
    useful, dense = window_flops(cfg, parts["nnz"])
    if trace:
        peak = flops.peak_flops(device["kind"])
        ctx = {"compile_s": compile_s,
               "windows_per_s": windows_per_s, "useful_flops": useful,
               "dense_flops": dense, "peak_flops_per_s": peak,
               "chips": chips, "trace": traced,
               "collectives": compiled["collectives"]}
        log(f"dense-count step share of peak: "
            f"{100 * dense * windows_per_s / (chips * peak):.4f} %")
        metrics = {}
        for m in spec.metrics_for(cell_name, "per_layer"):
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    else:
        values = {"windows_per_s": windows_per_s,
                  "hbm_peak_gib": hbm / BYTES_PER_GIB, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.metrics_for(cell_name, "end_to_end")}
    result.update(metrics=metrics, device=device, checks=compared)
    return result
