"""The benchmark's on-device data: the same dataset ``IndexDataset.from_raw``
builds from the same raw series, a pure function of the seed, and the chunked
AR(1) noise equal to the sequential recurrence."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench.data import synthetic
from repro.core import IndexDataset, WindowSpec

NODES, ENTRIES, CHUNK = 24, 1800, 150
SPEC = WindowSpec(horizon=12, input_len=12)


def _sharding():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    return NamedSharding(mesh, P())


def _raw(seed):
    key = synthetic.seed_key(seed)
    _, smooth, _ = synthetic.make_graph(key, NODES)
    return synthetic.make_series(key, ENTRIES, smooth, _sharding(), chunk=CHUNK)


def test_device_dataset_matches_from_raw():
    raw = _raw(2**31 + 5)
    host = np.asarray(raw)
    ref = IndexDataset.from_raw(host, SPEC)
    starts, (tr, va, te), end = synthetic.window_layout(ENTRIES, SPEC.span,
                                                         SPEC.in_len)
    series, (mean, std) = synthetic.standardise(raw, end, _sharding(),
                                                chunk=CHUNK)
    for got, want in ((starts, ref.starts), (tr, ref.train_windows),
                      (va, ref.val_windows), (te, ref.test_windows)):
        np.testing.assert_array_equal(got, want)
    # numpy sums float32 pairwise, the device in chunks added in float64:
    # the two moments agree to float32 rounding, not bit for bit.
    assert mean == pytest.approx(ref.scaler.mean, rel=1e-6)
    assert std == pytest.approx(ref.scaler.std, rel=1e-6)
    np.testing.assert_allclose(np.asarray(series), ref.series, rtol=1e-5,
                               atol=1e-5)


def test_generation_is_a_pure_function_of_the_seed():
    a, b, c = _raw(7), _raw(7), _raw(8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    s = np.asarray(a)
    assert s[..., 0].min() >= synthetic.CLIP[0]
    assert s[..., 0].max() <= synthetic.CLIP[1]
    np.testing.assert_allclose(s[:288, 0, 1], np.arange(288) / 288, rtol=1e-6)


def test_chunked_noise_equals_the_sequential_recurrence():
    key = jax.random.PRNGKey(3)
    rows, nodes, n_chunks = 160, 5, 4
    draws = np.concatenate(
        [np.asarray(synthetic._chunk_noise(key, c, rows, nodes))
         for c in range(n_chunks)], axis=1)
    seq = draws.astype(np.float64).copy()
    for i in range(1, seq.shape[1]):
        seq[:, i] = synthetic.AR_SCALE * (seq[:, i]
                                          + synthetic.AR_KEEP * seq[:, i - 1])
    for c in range(n_chunks):
        prev = synthetic._chunk_noise(key, jnp.int32(c - 1), rows, nodes)
        carry = synthetic._ar1(prev, jnp.zeros((nodes,)), c == 1)[:, -1]
        carry = jnp.where(c == 0, 0.0, carry)
        got = synthetic._ar1(synthetic._chunk_noise(key, c, rows, nodes),
                             carry, c == 0)
        np.testing.assert_allclose(np.asarray(got),
                                   seq[:, c * rows:(c + 1) * rows],
                                   rtol=1e-5, atol=1e-5)


def test_graph_matches_the_program_adjacency_semantics():
    from repro.data.adjacency import gaussian_adjacency, transition_matrices
    coords = synthetic.sensor_coords(jax.random.PRNGKey(1), 70)
    adj = np.asarray(synthetic.gaussian_adjacency(coords))
    want = gaussian_adjacency(np.asarray(coords, np.float64))
    np.testing.assert_allclose(adj, want, rtol=1e-4, atol=1e-5)
    for got, ref in zip(synthetic.transition_matrices(jnp.asarray(want)),
                        transition_matrices(want)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=1e-5, atol=1e-6)


def test_seed_key_takes_seeds_past_32_bits():
    keys = [np.asarray(synthetic.seed_key(s)) for s in
            (5, 2**32 + 5, 2**33 + 5)]
    assert len({k.tobytes() for k in keys}) == 3
    with pytest.raises(ValueError):
        synthetic.seed_key(-1)
