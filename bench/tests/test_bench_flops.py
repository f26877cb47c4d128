"""The benchmark's FLOP count against XLA's, and the table of peaks."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops
from bench.reference import dcgru


@pytest.mark.parametrize("nodes,c_in,hidden,k", [(48, 10, 8, 2), (64, 66, 64, 2),
                                                  (32, 128, 64, 3)])
def test_dense_cell_forward_matches_xla_cost_analysis(nodes, c_in, hidden, k):
    """One DCGRU cell's forward, no scan, batch 1.  XLA also counts the
    element-wise work the count leaves out (bias adds, sigmoid, tanh, the
    reset product and the update): a few operations per output element of
    the cell's two convolutions (3h per node; XLA counts 4.67 on the CPU),
    so the tolerance is 8 operations per such element and no more."""
    f = c_in - hidden
    key = jax.random.PRNGKey(0)
    n_mat = 1 + 2 * k
    p = {"ru": {"w": jnp.ones((c_in * n_mat, 2 * hidden)),
                "b": jnp.zeros((2 * hidden,))},
         "c": {"w": jnp.ones((c_in * n_mat, hidden)), "b": jnp.zeros((hidden,))}}
    sup = (jax.random.uniform(key, (nodes, nodes)),) * 2
    x = jnp.ones((1, nodes, f))
    h = jnp.ones((1, nodes, hidden))
    cost = jax.jit(lambda p, s, x, h: dcgru.cell(p, s, x, h, k)).lower(
        p, sup, x, h).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    hops, proj = flops.cell_forward(nodes, c_in, hidden, k, [nodes * nodes] * 2)
    ours = hops + proj
    elementwise = 8.0 * nodes * 3 * hidden
    assert 0 <= xla - ours <= elementwise, (xla, ours)


def test_window_flops_counts_hops_on_nonzeros():
    m = {"num_nodes": 100, "hidden": 8, "max_diffusion_step": 2,
         "out_features": 1}
    cells = [(10, 12)]
    dense = flops.window_flops(m, cells, 12, [5000, 5000], dense=True)
    sparse = flops.window_flops(m, cells, 12, [5000, 5000])
    full = flops.window_flops(m, cells, 12, [10000, 10000])
    assert dense == full and sparse < dense
    hops_dense = 2 * 12 * flops.cell_forward(100, 10, 8, 2, [10000] * 2)[0]
    assert dense - sparse == pytest.approx(hops_dense / 2)


def test_peak_of_a_known_chip_and_an_unknown_one():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no peak"):
        flops.peak_flops("TPU v9 imaginary")
