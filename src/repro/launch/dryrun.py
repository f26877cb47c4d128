"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

``main()`` forces 512 placeholder host devices FIRST THING — jax locks the
device count on first backend init, and the production meshes need them.
The override lives in main(), not at module scope: this module is also
imported as a library (``collective_bytes``, ``partitioned_halo_evidence``)
by tests and notebooks, which must keep their own device count.

Per cell this proves the distribution config is coherent with no hardware:
``jit(step, in_shardings, out_shardings).lower(*ShapeDtypeStructs).compile()``
must succeed; ``memory_analysis()`` proves the per-device footprint and
``cost_analysis()`` + HLO collective parsing feed §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen1.5-4b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all --out results/dryrun  # full matrix
"""
import os

import argparse
import json
import re
import time
import traceback

import jax

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_LINE_RE = re.compile(
    r"=\s+(.*?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)([\w\-.]*)\(")


def _shapes_bytes(shape_str: str) -> int:
    """Total bytes of all HLO shapes in a string like '(f32[8,128]{1,0}, u32[])'."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in (post-SPMD) HLO.

    The compiled module is the per-device program, so these are bytes moved
    per device.  Async pairs count the -start only; -done is skipped.
    """
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_LINE_RE.search(line)
        if m is None:
            continue
        shape_str, op, suffix = m.groups()
        if "done" in suffix:
            continue  # async pair: bytes were counted at the -start
        out[op] += _shapes_bytes(shape_str)
        counts[op] += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = counts
    return out


_CONST_RE = re.compile(r"stablehlo\.constant .*: tensor<([^>]*)>")
_MLIR_BITS = {"f64": 64, "f32": 32, "f16": 16, "bf16": 16, "i64": 64,
              "i32": 32, "i16": 16, "i8": 8, "i1": 8, "ui32": 32, "ui8": 8}


def largest_constant_bytes(lowered) -> int:
    """Bytes of the largest constant embedded in a lowered program.

    A concrete array closed over by a jitted function becomes such a
    constant: a second device copy, and a program that grows with it.
    Large constants are printed elided, so this never renders their data.
    """
    text = lowered.compiler_ir("stablehlo").operation.get_asm(
        large_elements_limit=16)
    largest = 0
    for ty in _CONST_RE.findall(text):
        *dims, dt = ty.split("x")
        n = 1
        for d in dims:
            n *= int(d)
        largest = max(largest, n * _MLIR_BITS.get(dt, 32) // 8)
    return largest


def partitioned_halo_evidence(mesh=None, *, entries: int = 256, nodes: int = 4,
                              features: int = 2, global_batch: int = 16,
                              input_len: int = 3, horizon: int = 3) -> dict:
    """Collective-bytes evidence for the PARTITIONED ``halo`` knob.

    ``halo=False`` (``PipelineConfig(halo=False)``) confines every sampled
    window to the series shard its rank's device owns, so the step lowers as
    a shard_map whose gathers are provably local — the compiled program's
    ONLY collective is the gradient all-reduce.  ``halo=True`` windows may
    spill ``span−1`` steps into the next shard, which forces the global-index
    lowering and materialises an all-gather of the resident series.

    Compiles both lowerings on ``mesh`` (default: the host mesh) against
    abstract shapes and returns their per-device collective-byte tables plus
    ``data_bytes`` = everything except the gradient all-reduce.
    """
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.batching import gather_batch_fused
    from repro.launch.mesh import make_host_mesh, shrink_mesh

    if mesh is None:
        # Cap the default at 8 data slots: the dryrun CLI forces 512 host
        # devices, which the small evidence shapes cannot divide.
        mesh = shrink_mesh(make_host_mesh(), 8)
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    all_axes = tuple(mesh.axis_names)
    series_sh = NamedSharding(mesh, P(dp))
    batch_sh = NamedSharding(mesh, P(dp))
    rep = NamedSharding(mesh, P())

    def loss(w, series, starts):
        x, y = gather_batch_fused(series, starts, input_len=input_len,
                                  horizon=horizon)
        return jnp.mean((x * w).sum(-1) ** 2) + jnp.mean(y)

    def step_global(w, series, starts):
        return jax.value_and_grad(loss)(w, series, starts)

    # Mirrors engine._shard_local_gather: inside the shard, global starts
    # become shard-local offsets (start − shard origin) before gathering.
    dp_total = 1
    for a in dp:
        dp_total *= int(mesh.shape[a])
    shard_len = entries // max(dp_total, 1)

    def body(w, series_shard, starts_shard):
        lo = jax.lax.axis_index(dp[0]) * shard_len
        l, g = jax.value_and_grad(loss)(w, series_shard, starts_shard - lo)
        return jax.lax.pmean(l, all_axes), jax.lax.pmean(g, all_axes)

    step_local = jax.shard_map(body, mesh=mesh,
                               in_specs=(P(), P(dp), P(dp)),
                               out_specs=(P(), P()), check_vma=False)

    sds = jax.ShapeDtypeStruct
    args = (sds((features,), jnp.float32),
            sds((entries, nodes, features), jnp.float32),
            sds((global_batch,), jnp.int32))

    def compile_and_count(fn):
        compiled = jax.jit(fn, in_shardings=(rep, series_sh, batch_sh),
                           out_shardings=(rep, rep)).lower(*args).compile()
        coll = collective_bytes(compiled.as_text())
        coll["data_bytes"] = coll["total"] - coll["all-reduce"]
        return coll

    return {
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "dims": {"entries": entries, "nodes": nodes, "features": features,
                 "global_batch": global_batch, "input_len": input_len,
                 "horizon": horizon},
        # halo=False contract: shard-local gathers (shard_map lowering)
        "halo_false": compile_and_count(step_local),
        # halo=True upper bound: global-index gathers over the sharded series
        "halo_true": compile_and_count(step_global),
    }


def run_cell(arch_id: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, **build_kw) -> dict:
    """Lower + compile one cell; return the §Dry-run / §Roofline record."""
    from repro.launch.mesh import make_production_mesh, mesh_chips
    from repro.launch.specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: dict = {"arch": arch_id, "shape": shape_name,
                 "mesh": "x".join(str(s) for s in mesh.devices.shape),
                 "chips": mesh_chips(mesh), "multi_pod": multi_pod,
                 "options": {k: str(v) for k, v in build_kw.items()}}
    t0 = time.time()
    try:
        prog = build_cell(arch_id, shape_name, mesh, **build_kw)
        donate = prog.meta.get("donate", ())
        with mesh:
            jitted = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                             out_shardings=prog.out_shardings,
                             donate_argnums=donate)
            lowered = jitted.lower(*prog.args)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_bytes": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
            }
            ca = compiled.cost_analysis()
            hlo_text = compiled.as_text()
            from repro.launch.costs import analyze_hlo

            hc = analyze_hlo(hlo_text)
            rec["cost"] = {
                # loop-aware (while bodies × trip count) — the roofline inputs
                "flops": hc.flops,
                "bytes_accessed": hc.bytes,
                # raw XLA numbers (loop bodies counted once) for reference
                "xla_flops": float(ca.get("flops", 0.0)),
                "xla_bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
            rec["collectives"] = {**{k: v for k, v in hc.coll_by_op.items()},
                                  "total": hc.coll_bytes}
            rec["meta"] = {k: (float(v) if isinstance(v, (int, float)) else v)
                           for k, v in prog.meta.items()}
            rec["kind"] = prog.kind
            rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — report, don't crash the matrix
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if verbose:
        if rec["status"] == "ok":
            mem = rec["memory"]
            print(f"[ok] {arch_id}:{shape_name} mesh={rec['mesh']} "
                  f"compile={rec['compile_s']}s "
                  f"peak/device={mem['peak_bytes']/2**30:.2f}GiB "
                  f"flops/device={rec['cost']['flops']:.3e} "
                  f"coll/device={rec['collectives']['total']/2**20:.1f}MiB")
        else:
            print(f"[ERR] {arch_id}:{shape_name} mesh={rec['mesh']}: {rec['error']}")
    return rec


def main() -> None:
    # Must precede the first backend init (jax.devices()/device_put/...);
    # imports above only bind the jax module and do not lock the count.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="full 40-cell matrix")
    ap.add_argument("--placement", default="replicated",
                    choices=["replicated", "partitioned", "ondemand"],
                    help="ST-GNN series placement")
    ap.add_argument("--halo-evidence", action="store_true",
                    help="compile the PARTITIONED step with shard-local "
                         "(halo=False) vs global-index (halo=True) gathers "
                         "and report per-device collective bytes")
    ap.add_argument("--out", default=None, help="write JSON records here")
    args = ap.parse_args()

    if args.halo_evidence:
        rec = partitioned_halo_evidence()
        print(json.dumps(rec, indent=1))
        if args.out:
            import os as _os
            _os.makedirs(_os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(rec, f, indent=1)
        df, dt = rec["halo_false"]["data_bytes"], rec["halo_true"]["data_bytes"]
        print(f"halo=False data-collective bytes/device: {df} "
              f"(communication-free: {df == 0}); halo=True: {dt}")
        return

    from repro.launch.specs import all_cells

    records = []
    if args.all:
        cells = list(all_cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, None)]

    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for aid, shape, skip in cells:
        if skip:
            records.append({"arch": aid, "shape": shape, "status": "skipped",
                            "reason": skip})
            print(f"[skip] {aid}:{shape} — {skip[:80]}")
            continue
        for mp in meshes:
            kw = {}
            from repro.configs import get_arch
            if get_arch(aid).family == "stgnn":
                kw["placement"] = args.placement
            records.append(run_cell(aid, shape, multi_pod=mp, **kw))

    if args.out:
        import os as _os
        _os.makedirs(_os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records -> {args.out}")
    n_err = sum(1 for r in records if r.get("status") == "error")
    if n_err:
        raise SystemExit(f"{n_err} cells failed")


if __name__ == "__main__":
    main()
