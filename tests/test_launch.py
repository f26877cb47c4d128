"""Launch layer on the single real device: sharding rules, cost analyzer,
cell builders (shapes only), and a tiny-mesh end-to-end sharded train step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import LM_ARCHS, get_arch
from repro.launch import costs as C
from repro.launch import sharding as shd
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import model_flops, roofline_terms


def _mesh1():
    return make_host_mesh(model=1)


# ------------------------------------------------------------- sharding rules
def test_lm_param_specs_tp_divisibility():
    """Rules must only shard dims that divide the axis; fall back otherwise."""
    cfg = get_arch("minitron-8b").lm  # heads 32, kv 8, d_ff 16384
    # tile to exactly 16 mesh slots regardless of the host's device count
    mesh16 = Mesh(np.array((jax.devices() * 16)[:16]).reshape(1, 16),
                  ("data", "model"))
    spec = shd.lm_param_spec("stages/0/sub0/attn/wq/w", (32, 4096, 4096),
                             cfg, mesh16)
    assert spec[-1] == "model"  # heads 32 % 16 == 0 -> column parallel
    spec_kv = shd.lm_param_spec("stages/0/sub0/attn/wk/w", (32, 4096, 1024),
                                cfg, mesh16)
    assert spec_kv[-1] is None  # kv heads 8 % 16 != 0 -> replicated on model

    qwen = get_arch("qwen1.5-4b").lm  # heads 20 -> not divisible
    spec_q = shd.lm_param_spec("stages/0/sub0/attn/wq/w", (40, 2560, 2560),
                               qwen, mesh16)
    assert "model" not in tuple(spec_q)


def test_lm_head_vocab_sharded():
    cfg = get_arch("qwen1.5-4b").lm
    mesh16 = Mesh(np.array(jax.devices() * 16)[:16].reshape(1, 16), ("data", "model"))
    spec = shd.lm_param_spec("lm_head/w", (2560, 151936), cfg, mesh16)
    assert spec[-1] == "model"


def test_fsdp_respects_divisibility_and_size():
    cfg = get_arch("qwen1.5-4b").lm
    mesh = Mesh(np.array(jax.devices() * 16)[:16].reshape(4, 4), ("data", "model"))
    # tiny leaf (< min_size elements): no FSDP
    spec = shd.lm_param_spec("stages/0/sub0/norm1", (40, 64), cfg, mesh)
    assert tuple(spec) == (None, None)
    # large leaf: largest divisible dim gets "data"
    spec2 = shd.lm_param_spec("stages/0/sub0/mlp/wi/w", (40, 2560, 6912), cfg, mesh)
    assert "data" in tuple(spec2)


# -------------------------------------------------------------- cost analyzer
def test_costs_scan_trip_rollup():
    def body(c, _):
        return c @ c, None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    r = C.analyze_hlo(jax.jit(f).lower(x).compile().as_text())
    assert r.flops == pytest.approx(7 * 2 * 128**3, rel=0.01)


def test_costs_nested_loops():
    def inner(c, _):
        return c @ c, None

    def outer(c, _):
        y, _ = jax.lax.scan(inner, c, None, length=3)
        return y, None

    def g(x):
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    r = C.analyze_hlo(jax.jit(g).lower(x).compile().as_text())
    assert r.flops == pytest.approx(15 * 2 * 64**3, rel=0.01)


def test_costs_bytes_scan_over_stack_slice_aware():
    """Per-iteration traffic must be slice-sized, not whole-stack-sized."""
    def body(c, x):
        return c + x, None

    def f(stack):
        y, _ = jax.lax.scan(body, jnp.zeros((256, 256)), stack)
        return y

    stack = jax.ShapeDtypeStruct((100, 256, 256), jnp.float32)
    r = C.analyze_hlo(jax.jit(f).lower(stack).compile().as_text())
    slice_bytes = 256 * 256 * 4
    assert r.bytes < 100 * 10 * slice_bytes  # far below whole-stack charging


def test_costs_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)

    a = jax.ShapeDtypeStruct((4, 32, 64), jnp.float32)
    b = jax.ShapeDtypeStruct((4, 64, 16), jnp.float32)
    r = C.analyze_hlo(jax.jit(f).lower(a, b).compile().as_text())
    assert r.flops == pytest.approx(2 * 4 * 32 * 64 * 16, rel=0.01)


# ------------------------------------------------------------------- roofline
def test_roofline_terms_shape():
    rec = {"cost": {"flops": 1e12, "bytes_accessed": 1e12},
           "collectives": {"total": 1e9}, "chips": 256, "kind": "train",
           "meta": {"active_params": 1e9, "tokens_per_step": 1e6}}
    t = roofline_terms(rec)
    assert t["dominant"] == "memory"
    assert t["model_flops"] == 6e15
    assert 0 < t["roofline_fraction"] < 1


def test_model_flops_moe_uses_active():
    arch = get_arch("deepseek-v2-lite-16b")
    assert arch.lm.active_param_count() < 0.25 * arch.lm.param_count()


# --------------------------------------------- tiny-mesh sharded training step
def test_sharded_stgnn_step_matches_unsharded():
    """The production step program on a 1-device mesh == plain step."""
    from repro.launch.specs import build_stgnn_train
    from repro.configs import get_arch
    import dataclasses as dc

    arch = get_arch("pgt-dcrnn-pems-all-la")
    small_model = dc.replace(arch.model, num_nodes=12)
    arch = dc.replace(arch, model=small_model)
    mesh = _mesh1()
    prog = build_stgnn_train(arch, arch.shapes[0], mesh, series_len=200)
    # replace the ShapeDtypeStructs with real arrays
    rng = np.random.default_rng(0)

    def realize(x):
        if x.dtype == jnp.int32:
            return jnp.asarray(rng.integers(0, 150, size=x.shape).astype(np.int32))
        return jnp.asarray(rng.standard_normal(x.shape).astype(np.float32) * 0.1)

    args = jax.tree.map(realize, prog.args,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    with mesh:
        step = jax.jit(prog.fn, in_shardings=prog.in_shardings,
                       out_shardings=prog.out_shardings)
        state, loss = step(*args)
    assert np.isfinite(float(loss))


def test_all_cells_enumerates_40():
    from repro.launch.specs import all_cells

    cells = list(all_cells())
    lm_cells = [c for c in cells if get_arch(c[0]).family != "stgnn"]
    assert len(lm_cells) == 40
    skips = [c for c in lm_cells if c[2]]
    assert len(skips) == 7  # pure full-attention archs skip long_500k
    assert all(s[1] == "long_500k" for s in skips)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="collectives need a >1-device mesh "
                           "(run with XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")
def test_halo_evidence_communication_free():
    """Dry-run evidence for the PipelineConfig.halo knob: the halo=False
    (shard-local) lowering of the PARTITIONED step moves ZERO data-collective
    bytes — only the gradient all-reduce — while halo=True's global-index
    lowering all-gathers the resident series."""
    from repro.launch.dryrun import partitioned_halo_evidence

    rec = partitioned_halo_evidence(make_host_mesh())
    assert rec["halo_false"]["data_bytes"] == 0
    assert rec["halo_false"]["all-reduce"] > 0  # grads still reduce
    assert rec["halo_true"]["data_bytes"] > 0
    assert rec["halo_true"]["counts"]["all-gather"] >= 1


# ------------------------------------------------------------ compile cache
def test_compile_cache_follows_env_var(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper configures nothing."""
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    """Without it, the cache is one fixed, git-ignored directory inside the
    checkout — the same path on every call."""
    import pathlib

    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        assert first == enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR.parent == repo
    ignored = (repo / ".gitignore").read_text().splitlines()
    assert f"{REPO_CACHE_DIR.name}/" in ignored
