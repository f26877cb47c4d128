"""Step builders + ShapeDtypeStruct input specs for every (arch × shape) cell.

``build_cell(arch_id, shape_name, mesh, ...)`` returns a ``CellProgram``: the
step function to lower, its ShapeDtypeStruct args (weak-type-correct, no
allocation) and the in/out shardings — everything ``dryrun.py`` needs to
``.lower().compile()`` and everything ``train.py`` needs to run for real.

The paper's technique is baked into the train steps: the jitted program takes
the RESIDENT series/stream plus int32 window starts and reconstructs the
batch on-device (index-batching).  ``placement`` selects the paper's three
distributed designs: replicated (distributed-index-batching), partitioned
(generalized-…, local windows), ondemand (baseline DDP: partitioned series,
global windows → data collectives).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import ArchSpec, ShapeCell
from repro.core.batching import gather_batch_fused, lm_window_batch
from repro.models import a3tgcn, dcrnn, pgt_dcrnn
from repro.models.lm import model as lm
from repro.optim import AdamConfig, apply_updates
from repro.launch import sharding as shd
from repro.launch.mesh import dp_axes

# Dry-run token-stream length (resident series for LM index-batching).
STREAM_LEN = 1 << 22  # 4M tokens, 16 MiB int32 — replicated everywhere


@dataclasses.dataclass
class CellProgram:
    name: str
    kind: str  # train | prefill | decode
    fn: Callable
    args: tuple  # ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    meta: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _adam_for(arch: ArchSpec) -> AdamConfig:
    # bf16 optimizer state for the very large archs (grok) — see DESIGN.md
    state_dtype = "bfloat16" if arch.lm is not None and arch.lm.param_count() > 1e11 else "float32"
    return AdamConfig(lr=3e-4, weight_decay=0.1, state_dtype=state_dtype)


def _opt_shapes(params_shape, adam: AdamConfig):
    dt = jnp.dtype(adam.state_dtype)
    like = lambda p: _sds(p.shape, dt)
    return {"m": jax.tree.map(like, params_shape),
            "v": jax.tree.map(like, params_shape),
            "step": _sds((), jnp.int32)}


# ---------------------------------------------------------------------- LM
def _lm_params_shape(cfg):
    return jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))


def act_hints(cfg, mesh: Mesh, *, seq_shard: bool = False,
              batch_all_axes: bool = False) -> dict:
    """Activation-sharding hints for the LM stack on this mesh.

    act:    [B, S, d]     batch over dp (+ optionally sequence over model: SP)
    logits: [B, S, V]     batch over dp, vocab over model (when divisible)
    tokens: [B, S]        batch over dp
    kv/ckv: written cache rows — batch over dp, SEQUENCE over model, matching
            the resident cache so the prefill write is a local slice (without
            this the partitioner full-rematerializes k/v per layer: measured
            3.3 TiB/device of collectives on qwen prefill_32k)
    """
    dp = tuple(mesh.axis_names) if batch_all_axes else dp_axes(mesh)
    tp = 1 if batch_all_axes else int(mesh.shape.get("model", 1))
    seq_ax = "model" if seq_shard and not batch_all_axes else None
    vocab_ax = "model" if tp > 1 and cfg.padded_vocab % tp == 0 else None
    cache_seq_ax = "model" if tp > 1 else None
    return {
        "act": NamedSharding(mesh, P(dp, seq_ax, None)),
        "logits": NamedSharding(mesh, P(dp, None, vocab_ax)),
        "tokens": NamedSharding(mesh, P(dp, None)),
        "kv": NamedSharding(mesh, P(dp, cache_seq_ax, None, None)),
        "ckv": NamedSharding(mesh, P(dp, cache_seq_ax, None)),
        "qkv": NamedSharding(mesh, P(dp, seq_ax, None, None)),
        # MoE dispatch [E, C, d] sharding hint.  Measured on grok (E=8 ∤ 16):
        # capacity-over-model conflicts with TP expert weights (2.6× flops,
        # 3× collectives); capacity-over-data adds dispatch churn (+50%
        # collectives).  Baseline leaves dispatch buffers replicated across
        # model (weights TP on d_expert) — revisited in §Perf.
        "moe_cap": None,
    }


def _serve_params_shape(cfg):
    """Inference weights are served in bf16 (f32 master copies live with the
    trainer, not the server) — halves weight HBM and doubles streaming rate."""
    shapes = _lm_params_shape(cfg)
    return jax.tree.map(
        lambda s: _sds(s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype),
        shapes)


def build_lm_train(arch: ArchSpec, cell: ShapeCell, mesh: Mesh, *,
                   remat: bool = True, fsdp: tuple[str, ...] = ("data",),
                   microbatches: int | None = None,
                   mode2d: bool = False,
                   q_chunk: int | None = None,
                   kv_chunk: int | None = None) -> CellProgram:
    """``mode2d``: beyond-paper ZeRO-3/2D scheme — no TP, batch sharded over
    EVERY mesh axis, params fully FSDP-sharded across all axes.  Removes the
    tp-fold redundant attention/embedding compute that the baseline pays when
    head counts don't divide the model axis (see EXPERIMENTS.md §Perf)."""
    cfg = arch.lm
    if q_chunk or kv_chunk:
        cfg = dataclasses.replace(cfg, q_chunk=q_chunk or cfg.q_chunk,
                                  kv_chunk=kv_chunk or cfg.kv_chunk)
    adam = _adam_for(arch)
    seq, gb = cell.seq_len, cell.global_batch
    from repro.launch.mesh import dp_size, mesh_chips

    workers = mesh_chips(mesh) if mode2d else dp_size(mesh)
    if microbatches is None:
        # default: one sequence row per device per microbatch — bounds the
        # remat activation stack to [layers, 1, seq, d] per device
        microbatches = max(gb // workers, 1)
    big = cfg.param_count() > 1e11
    # >100B params: bf16 gradient accumulation / compression (halves both the
    # accumulator and the cross-pod gradient all-reduce bytes), and FSDP over
    # the pod axis too — a 314B f32 master + Adam state cannot fit one pod
    grad_dtype = jnp.bfloat16 if big else jnp.float32
    if big and "pod" in mesh.axis_names and "pod" not in fsdp:
        fsdp = ("pod",) + tuple(fsdp)
    if mode2d:
        fsdp = tuple(mesh.axis_names)
    params_shape = _lm_params_shape(cfg)
    state_shape = {"params": params_shape, "opt": _opt_shapes(params_shape, adam)}
    param_sh = shd.lm_param_shardings(params_shape, cfg, mesh, fsdp=fsdp,
                                      tp_rules=not mode2d)
    state_sh = shd.state_shardings(param_sh, mesh)

    n_prefix = cfg.n_prefix if cfg.frontend == "patches" else 0
    text_len = seq - n_prefix
    hints = act_hints(cfg, mesh, batch_all_axes=mode2d)

    def step(state, stream, starts, prefix_embeds=None):
        def loss(p):
            toks, labels = lm_window_batch(stream, starts, seq_len=text_len)
            # anchor activation sharding: batch over the data axes.  Without
            # this GSPMD replicates the batch dim through the gather and the
            # whole network (measured: 370 GiB/device temps on qwen train_4k).
            toks = jax.lax.with_sharding_constraint(toks, hints["tokens"])
            labels = jax.lax.with_sharding_constraint(labels, hints["tokens"])
            l, aux = lm.loss_fn(p, cfg, toks, labels, prefix_embeds=prefix_embeds,
                                remat=remat, shardings=hints)
            return l, aux

        if microbatches > 1:
            def one_mb(i):
                st = starts.reshape(microbatches, -1)[i]
                pe = (None if prefix_embeds is None else
                      prefix_embeds.reshape((microbatches, -1) + prefix_embeds.shape[1:])[i])
                def loss_mb(p):
                    toks, labels = lm_window_batch(stream, st, seq_len=text_len)
                    toks = jax.lax.with_sharding_constraint(toks, hints["tokens"])
                    labels = jax.lax.with_sharding_constraint(labels, hints["tokens"])
                    return lm.loss_fn(p, cfg, toks, labels, prefix_embeds=pe,
                                      remat=remat, shardings=hints)
                return jax.value_and_grad(lambda p: loss_mb(p)[0])(state["params"])

            def acc(carry, i):
                l_a, g_a = carry
                l, g = one_mb(i)
                return (l_a + l,
                        jax.tree.map(lambda a, b: a + b.astype(grad_dtype), g_a, g)), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, grad_dtype),
                                state["params"])
            (l, grads), _ = jax.lax.scan(acc, (jnp.zeros(()), zero),
                                         jnp.arange(microbatches))
            l, grads = l / microbatches, jax.tree.map(lambda g: g / microbatches, grads)
        else:
            (l, _), grads = jax.value_and_grad(loss, has_aux=True)(state["params"])
        new_p, new_opt, _ = apply_updates(state["params"], grads, state["opt"],
                                          adam, adam.lr)
        return {"params": new_p, "opt": new_opt}, l

    args = [state_shape, _sds((STREAM_LEN,), jnp.int32), _sds((gb,), jnp.int32)]
    in_sh = [state_sh, shd.replicated(mesh), shd.batch_sharding(mesh)]
    if n_prefix:
        args.append(_sds((gb, n_prefix, cfg.d_model), jnp.dtype(cfg.dtype)))
        in_sh.append(NamedSharding(mesh, P(dp_axes(mesh))))
    out_sh = (state_sh, shd.replicated(mesh))

    return CellProgram(
        name=f"{arch.id}:{cell.name}", kind="train", fn=step,
        args=tuple(args), in_shardings=tuple(in_sh), out_shardings=out_sh,
        meta={"tokens_per_step": gb * seq, "seq": seq, "batch": gb,
              "params": cfg.param_count(), "active_params": cfg.active_param_count(),
              "microbatches": microbatches},
    )


def build_lm_prefill(arch: ArchSpec, cell: ShapeCell, mesh: Mesh, *,
                     moe_groups: int = 1) -> CellProgram:
    cfg = arch.lm
    seq, gb = cell.seq_len, cell.global_batch
    params_shape = _serve_params_shape(cfg)
    param_sh = shd.lm_param_shardings(params_shape, cfg, mesh, fsdp=())
    cache_shape = jax.eval_shape(lambda: lm.init_cache(cfg, gb, seq))
    cache_sh = shd.cache_shardings(cache_shape, cfg, mesh)
    hints = act_hints(cfg, mesh)
    if moe_groups > 1:
        dp = dp_axes(mesh)
        hints = {**hints, "moe_group": NamedSharding(mesh, P(dp, None, None)),
                 "moe_disp": NamedSharding(mesh, P(dp, None, None, None))}

    def step(params, tokens, cache):
        logits, new_cache, lengths = lm.prefill(params, cfg, tokens, cache,
                                                shardings=hints,
                                                moe_groups=moe_groups)
        return logits, new_cache, lengths

    return CellProgram(
        name=f"{arch.id}:{cell.name}", kind="prefill", fn=step,
        args=(params_shape, _sds((gb, seq), jnp.int32), cache_shape),
        in_shardings=(param_sh, shd.batch_sharding(mesh), cache_sh),
        out_shardings=(NamedSharding(mesh, P(dp_axes(mesh))), cache_sh,
                       shd.batch_sharding(mesh)),
        meta={"tokens_per_step": gb * seq, "seq": seq, "batch": gb,
              "params": cfg.param_count(), "active_params": cfg.active_param_count(),
              "donate": (2,)},  # cache buffers alias in/out
    )


def build_lm_decode(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    cfg = arch.lm
    seq, gb = cell.seq_len, cell.global_batch
    params_shape = _serve_params_shape(cfg)
    param_sh = shd.lm_param_shardings(params_shape, cfg, mesh, fsdp=())
    cache_shape = jax.eval_shape(lambda: lm.init_cache(cfg, gb, seq))
    cache_sh = shd.cache_shardings(cache_shape, cfg, mesh)
    b_sh = shd.batch_sharding(mesh) if gb > 1 else shd.replicated(mesh)
    hints = act_hints(cfg, mesh)
    if gb == 1:  # long_500k: nothing to shard the batch over
        hints = {**hints, "act": None, "tokens": None,
                 "logits": hints["logits"]}

    def step(params, token, cache, lengths):
        return lm.decode_step(params, cfg, token, cache, lengths,
                              shardings=hints)

    return CellProgram(
        name=f"{arch.id}:{cell.name}", kind="decode", fn=step,
        args=(params_shape, _sds((gb, 1), jnp.int32), cache_shape,
              _sds((gb,), jnp.int32)),
        in_shardings=(param_sh, b_sh, cache_sh, b_sh),
        out_shardings=(b_sh, cache_sh),
        meta={"tokens_per_step": gb, "seq": seq, "batch": gb,
              "params": cfg.param_count(), "active_params": cfg.active_param_count(),
              "donate": (2,)},  # cache buffers alias in/out
    )


# -------------------------------------------------------------------- ST-GNN
def build_stgnn_train(arch, cell: ShapeCell, mesh: Mesh, *,
                      placement: str = "replicated",
                      use_pallas: bool = False,
                      compute_dtype: str | None = None,
                      series_len: int = 105_120) -> CellProgram:
    """DCRNN / PGT-DCRNN training cell.

    placement: replicated   — distributed-index-batching (paper §4.2): every
               device holds the series; window gathers are local by
               construction; only the gradient all-reduce crosses chips.
               partitioned  — generalized-distributed-index-batching (§5.4):
               series time-sharded over dp; the step is a ``shard_map`` whose
               per-rank body gathers windows with SHARD-LOCAL indices — the
               compiled program provably contains no data collectives, only
               the gradient psum (the paper's local-batch-shuffling contract).
               ondemand     — baseline DDP: series time-sharded but windows
               sampled globally — every gather crosses shards and the
               partitioner materialises the paper's Fig-7 communication wall.
    """
    mcfg = dataclasses.replace(arch.model, remat=True)
    adam = AdamConfig(lr=1e-2)
    gb = cell.global_batch
    n, f = mcfg.num_nodes, mcfg.in_features
    in_len, hor = mcfg.input_len, mcfg.horizon
    is_dcrnn = isinstance(mcfg, dcrnn.DCRNNConfig)
    mod = dcrnn if is_dcrnn else pgt_dcrnn

    params_shape = jax.eval_shape(lambda: mod.init(jax.random.PRNGKey(0), mcfg))
    param_sh = shd.stgnn_param_shardings(params_shape, mesh)
    state_shape = {"params": params_shape, "opt": _opt_shapes(params_shape, adam)}
    state_sh = shd.state_shardings(param_sh, mesh)
    series_sh = shd.series_sharding(mesh, partitioned=placement != "replicated")

    # the paper's DDP: every chip is one worker — batch shards over ALL axes
    batch_sh = shd.batch_sharding(mesh, pure_dp=True)
    if placement == "partitioned":
        step = _stgnn_partitioned_step(mod, mcfg, adam, mesh, in_len, hor,
                                       use_pallas)
    else:
        cdt = jnp.dtype(compute_dtype) if compute_dtype else None

        def step(state, series, starts, supports):
            def loss(p):
                x, y = gather_batch_fused(series, starts, input_len=in_len,
                                          horizon=hor, use_pallas=use_pallas)
                x = jax.lax.with_sharding_constraint(
                    x, shd.batch_sharding(mesh, pure_dp=True))
                if cdt is not None:
                    x = x.astype(cdt)
                    p = jax.tree.map(lambda w: w.astype(cdt), p)
                return mod.loss_fn(p, mcfg, supports, x, y)

            l, grads = jax.value_and_grad(loss)(state["params"])
            new_p, new_opt, _ = apply_updates(state["params"], grads,
                                              state["opt"], adam, adam.lr)
            return {"params": new_p, "opt": new_opt}, l

    # bf16 supports enter the program already cast — an in-program convert is
    # NOT hoisted out of the time scan (measured +13% traffic instead of -2x)
    sup_dt = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32
    supports_shape = (_sds((n, n), sup_dt), _sds((n, n), sup_dt))
    return CellProgram(
        name=f"{arch.id}:{cell.name}:{placement}", kind="train", fn=step,
        args=(state_shape, _sds((series_len, n, f), jnp.float32),
              _sds((gb,), jnp.int32), supports_shape),
        in_shardings=(state_sh, series_sh, batch_sh,
                      (shd.replicated(mesh), shd.replicated(mesh))),
        out_shardings=(state_sh, shd.replicated(mesh)),
        meta={"windows_per_step": gb, "nodes": n, "placement": placement,
              "series_len": series_len,
              "flops_model": stgnn_model_flops(mcfg, gb)},
    )


def _stgnn_partitioned_step(mod, mcfg, adam, mesh: Mesh, in_len, hor, use_pallas):
    """shard_map step for the generalized variant: per-rank local gathers.

    starts are SHARD-LOCAL offsets (the LocalBatchShuffleSampler emits them);
    each rank gathers from its own series shard, computes grads, and the only
    collective is the explicit gradient psum over the data axes.
    """
    from jax.sharding import PartitionSpec as PS

    dp = dp_axes(mesh)
    all_axes = tuple(mesh.axis_names)
    # series time-sharded over the data axes; every chip is one DDP worker,
    # so the window batch shards over ALL axes (model-axis workers share
    # their data rank's series shard)
    series_spec = PS(dp if len(dp) > 1 else dp[0])
    batch_spec = PS(all_axes)
    rep = PS()

    def body(state, series_shard, starts_shard, supports):
        def loss(p):
            x, y = gather_batch_fused(series_shard, starts_shard,
                                      input_len=in_len, horizon=hor,
                                      use_pallas=use_pallas)
            return mod.loss_fn(p, mcfg, supports, x, y)

        l, grads = jax.value_and_grad(loss)(state["params"])
        # the paper's ONLY collective: average gradients across workers
        grads = jax.lax.pmean(grads, all_axes)
        l = jax.lax.pmean(l, all_axes)
        new_p, new_opt, _ = apply_updates(state["params"], grads,
                                          state["opt"], adam, adam.lr)
        return {"params": new_p, "opt": new_opt}, l

    def step(state, series, starts, supports):
        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: rep, state), series_spec,
                      batch_spec, (rep, rep)),
            out_specs=(jax.tree.map(lambda _: rep, state), rep),
            check_vma=False,
        )
        return sm(state, series, starts, supports)

    return step


def stgnn_model_flops(mcfg, batch: int) -> float:
    """Analytic useful FLOPs per train step (fwd+bwd ≈ 3× fwd matmul FLOPs).

    Per diffusion-conv: K hops × 2 supports of [N,N]@[N,B·C] plus the
    [B·N, (1+2K)·C] @ [(1+2K)·C, H] projection.
    """
    n = mcfg.num_nodes
    k = mcfg.max_diffusion_step
    h = mcfg.hidden
    f = mcfg.in_features
    layers = getattr(mcfg, "layers", 1)  # PGT variant is single-layer
    t = mcfg.input_len + (mcfg.horizon if hasattr(mcfg, "layers") else 0)
    c_in = f + h  # gate input width
    n_mat = 1 + 2 * k
    per_dconv = 2 * k * 2 * n * n * batch * c_in + 2 * batch * n * n_mat * c_in * h
    # DCGRU cell: ru (2h out) + c (h out) ≈ 2 dconvs with different out widths
    per_cell = per_dconv * 2
    return 3.0 * per_cell * layers * t


# ------------------------------------------------------------------ registry
def build_cell(arch_id: str, shape_name: str, mesh: Mesh, **kw) -> CellProgram:
    arch = get_arch(arch_id)
    cell = next((s for s in arch.shapes if s.name == shape_name), None)
    if cell is None:
        raise KeyError(f"{arch_id} has no shape {shape_name!r}")
    if shape_name in arch.skips:
        raise ValueError(f"{arch_id}:{shape_name} skipped — {arch.skips[shape_name]}")
    if arch.family == "stgnn":
        return build_stgnn_train(arch, cell, mesh, **kw)
    if cell.kind == "train":
        return build_lm_train(arch, cell, mesh, **kw)
    if cell.kind == "prefill":
        return build_lm_prefill(arch, cell, mesh, **kw)
    return build_lm_decode(arch, cell, mesh, **kw)


def all_cells():
    """Yield (arch_id, shape_name, skip_reason | None) over the full matrix."""
    from repro.configs import ARCHS

    for aid, arch in ARCHS.items():
        for s in arch.shapes:
            yield aid, s.name, arch.skips.get(s.name)
