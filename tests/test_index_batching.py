"""The paper's core claim, proven structurally: index-batching feeds the model
BIT-IDENTICAL batches to materialised (Alg.-1) batching — so accuracy parity
(paper Fig. 5 / Table 3) holds by construction."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (IndexDataset, WindowSpec, gather_batch,
                        gather_batch_fused, gather_batch_take, lm_window_batch,
                        materialize_windows)
from repro.data import make_traffic_series


@st.composite
def window_case(draw):
    t = draw(st.integers(20, 120))
    n = draw(st.integers(1, 8))
    f = draw(st.integers(1, 3))
    in_len = draw(st.integers(1, 6))
    hor = draw(st.integers(1, 6))
    if in_len + hor >= t:
        in_len, hor = 2, 2
    b = draw(st.integers(1, 8))
    last = t - (in_len + hor)
    starts = draw(st.lists(st.integers(0, last), min_size=b, max_size=b))
    return t, n, f, in_len, hor, np.asarray(starts, np.int32)


@given(window_case())
@settings(max_examples=60, deadline=None)
def test_index_equals_materialized(case):
    """Property: every (x, y) from the index path == the Alg.-1 snapshot."""
    t, n, f, in_len, hor, starts = case
    series = np.random.default_rng(42).standard_normal((t, n, f)).astype(np.float32)
    xs, ys = materialize_windows(series, starts, in_len, hor)
    xg, yg = gather_batch(jnp.asarray(series), jnp.asarray(starts),
                          input_len=in_len, horizon=hor)
    assert np.array_equal(xs, np.asarray(xg))
    assert np.array_equal(ys, np.asarray(yg))


@pytest.mark.parametrize("in_len,hor", [(12, 12), (1, 5), (5, 1)])
@pytest.mark.parametrize("edge", ["first", "last"])
def test_gather_batch_at_edge_starts(in_len, hor, edge):
    """The first and the last valid start (``T - input_len - horizon``): the
    span slice is not clamped, so x and y equal the Alg.-1 snapshot."""
    t = 40
    series = np.random.default_rng(3).standard_normal((t, 4, 2)).astype(np.float32)
    start = 0 if edge == "first" else t - in_len - hor
    starts = np.full(3, start, np.int32)
    xs, ys = materialize_windows(series, starts, in_len, hor)
    xg, yg = gather_batch(jnp.asarray(series), jnp.asarray(starts),
                          input_len=in_len, horizon=hor)
    assert np.array_equal(xs, np.asarray(xg))
    assert np.array_equal(ys, np.asarray(yg))


@given(window_case())
@settings(max_examples=30, deadline=None)
def test_gather_variants_agree(case):
    """dynamic-slice, fused-span and take-based gathers are interchangeable."""
    t, n, f, in_len, hor, starts = case
    series = jnp.asarray(
        np.random.default_rng(7).standard_normal((t, n, f)).astype(np.float32))
    s = jnp.asarray(starts)
    a = gather_batch(series, s, input_len=in_len, horizon=hor)
    b = gather_batch_take(series, s, input_len=in_len, horizon=hor)
    c = gather_batch_fused(series, s, input_len=in_len, horizon=hor)
    d = gather_batch_fused(series, s, input_len=in_len, horizon=hor,
                           use_pallas=True)
    for other in (b, c, d):
        assert np.array_equal(np.asarray(a[0]), np.asarray(other[0]))
        assert np.array_equal(np.asarray(a[1]), np.asarray(other[1]))


def test_lm_window_batch_shift():
    stream = jnp.arange(100, dtype=jnp.int32)
    toks, labels = lm_window_batch(stream, jnp.asarray([0, 10]), seq_len=5)
    assert np.array_equal(np.asarray(toks), [[0, 1, 2, 3, 4], [10, 11, 12, 13, 14]])
    assert np.array_equal(np.asarray(labels), [[1, 2, 3, 4, 5], [11, 12, 13, 14, 15]])


def test_index_dataset_accounting():
    series = make_traffic_series(300, 10)
    spec = WindowSpec(horizon=6, input_len=6)
    ds = IndexDataset.from_raw(series, spec)
    assert ds.n_windows == 300 - 12 + 1
    # the compact representation is much smaller than materialised snapshots
    assert ds.nbytes_index() < 0.15 * ds.nbytes_materialized()
    # splits follow the paper's 70/10/20
    assert len(ds.train_windows) == round(ds.n_windows * 0.7)


def test_index_dataset_standardisation_matches_alg1():
    """Normalising the single series == normalising every snapshot (Alg. 1)."""
    raw = make_traffic_series(200, 5)
    spec = WindowSpec(horizon=4)
    ds = IndexDataset.from_raw(raw, spec)
    x, _ = gather_batch(jnp.asarray(ds.series), jnp.asarray(ds.starts[:10]),
                        input_len=4, horizon=4)
    # manually standardise the raw snapshots with the same scaler
    xs, _ = materialize_windows(raw, ds.starts[:10], 4, 4)
    xs = xs.copy()
    xs[..., 0] = (xs[..., 0] - ds.scaler.mean) / ds.scaler.std
    assert np.allclose(np.asarray(x), xs, atol=1e-6)


def test_to_device_is_single_transfer():
    ds = IndexDataset.from_raw(make_traffic_series(50, 4), WindowSpec(horizon=3))
    ds2 = ds.to_device()
    assert isinstance(ds2.series, jnp.ndarray)
    assert np.allclose(np.asarray(ds2.series), ds.series)
