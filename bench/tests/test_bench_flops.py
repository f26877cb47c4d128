"""The families' FLOP counts against XLA's and against the counts the
benchmark read before each family kept its own, and the table of peaks."""
import jax
import jax.numpy as jnp
import pytest

from bench import flops, spec
from bench.reference import dcgru
from bench.reference import train as ref_train
from bench.tests import small


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
    hops, proj = dcgru.cell_forward(nodes, c_in, hidden, k, [nodes * nodes] * 2)
    ours = hops + proj
    elementwise = 8.0 * nodes * 3 * hidden
    assert 0 <= xla - ours <= elementwise, (xla, ours)


def test_window_flops_counts_hops_on_nonzeros():
    m = {"num_nodes": 100, "hidden": 8, "max_diffusion_step": 2,
         "out_features": 1}
    cells = [(10, 12)]
    dense = dcgru.window_flops(m, cells, 12, [5000, 5000], dense=True)
    sparse = dcgru.window_flops(m, cells, 12, [5000, 5000])
    full = dcgru.window_flops(m, cells, 12, [10000, 10000])
    assert dense == full and sparse < dense
    hops_dense = 2 * 12 * dcgru.cell_forward(100, 10, 8, 2, [10000] * 2)[0]
    assert dense - sparse == pytest.approx(hops_dense / 2)


#: ``(configuration, size, nnz, dense, FLOPs)`` as ``bench/flops.py``'s
#: ``window_flops`` counted them before the count moved into the references,
#: so that ``step.mfu`` reads the same for the same windows/s.
BEFORE = [
    ("pgt-dcrnn-all-la", "published", [2980836, 2980836], False, 87948997632.0),
    ("pgt-dcrnn-all-la", "published", [2980836, 2980836], True, 199356659712.0),
    ("pgt-dcrnn-all-la", "small", [952, 950], False, 206763264.0),
    ("pgt-dcrnn-all-la", "small", [952, 950], True, 223211520.0),
    ("dcrnn-pems", "published", [50329316, 49000007], False, 7679141191872.0),
    ("dcrnn-pems", "published", [50329316, 49000007], True, 18807047700480.0),
    ("dcrnn-pems", "small", [952, 950], False, 1211488128.0),
    ("dcrnn-pems", "small", [952, 950], True, 1307934720.0),
]


@pytest.mark.parametrize("name,size,nnz,dense,want", BEFORE)
def test_dcgru_window_flops_are_the_counts_read_before(name, size, nnz, dense,
                                                       want):
    cfg = spec.config(name)
    m = dict(cfg["model"])
    if size == "small":
        m["num_nodes"] = small.NODES
    got = ref_train.model(cfg["family"]).window_flops(m, nnz, dense=dense)
    assert got == want


def test_peak_of_a_known_chip_and_an_unknown_one():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no peak"):
        flops.peak_flops("TPU v9 imaginary")
