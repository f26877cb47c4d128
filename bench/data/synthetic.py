"""The benchmark's traffic series and sensor graph, made on the device from a seed.

A jnp copy of the program's host generators (``repro.data.synthetic``,
``repro.data.adjacency``) with the same statistical shape: per-sensor free
flow minus two rush-hour dips, AR(1) noise (``x_t = 0.55 (e_t + 0.85 x_{t-1})``)
smoothed once through the row-normalised adjacency, clipped to [3, 85], and
a time-of-day channel.  The random draws differ from the host generator's
(``jax.random`` instead of ``numpy``), so the numbers do too.

The series is written chunk by chunk into one device buffer, on every device
straight into its own shard of the requested sharding, so peak memory during
generation is the series plus one chunk's temporaries.  Each chunk is a pure
function of (key, chunk index): the AR(1) carry into a chunk is rebuilt from
the previous chunk's draws, started from zero.  The carry lost that way is
weighted by 0.4675**chunk, which underflows float32 for any chunk of 128 rows
or more, so the chunked series equals the sequential recurrence to rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

STEPS_PER_DAY = 288   # 5-minute bins, as PeMS
AR_KEEP = 0.85        # noise[i] += 0.85 * noise[i - 1]
AR_SCALE = 0.55       # noise[i] *= 0.55
NOISE_STD = 2.0
SMOOTH_WEIGHT = 0.5
CLIP = (3.0, 85.0)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


# ----------------------------------------------------------------- the graph
def sensor_coords(key, nodes: int) -> jax.Array:
    """[nodes, 2] sensors clustered along ``max(1, nodes // 64)`` straight
    roads (the host generator's layout, drawn with ``jax.random``)."""
    n_roads = max(1, nodes // 64)
    per, rem = divmod(nodes, n_roads)
    sizes = [per + (1 if r < rem else 0) for r in range(n_roads)]
    road = jnp.repeat(jnp.arange(n_roads), jnp.asarray(sizes),
                      total_repeat_length=nodes)
    k_start, k_dir, k_pos, k_jit = jax.random.split(key, 4)
    start = jax.random.uniform(k_start, (n_roads, 2), minval=0.0, maxval=100.0)
    direction = jax.random.normal(k_dir, (n_roads, 2))
    direction = direction / jnp.linalg.norm(direction, axis=1, keepdims=True)
    # Positions along each road, sorted within the road: roads are 1000 apart
    # on the sort key and positions lie in [0, 60).
    pos = jax.random.uniform(k_pos, (nodes,), minval=0.0, maxval=60.0)
    pos = jnp.sort(pos + 1000.0 * road) - 1000.0 * road
    pts = start[road] + pos[:, None] * direction[road]
    return pts + 0.5 * jax.random.normal(k_jit, (nodes, 2))


def gaussian_adjacency(coords, threshold: float = 0.1) -> jax.Array:
    """W_ij = exp(-d_ij^2 / sigma^2) with sigma the std of all distances,
    zeroed below ``threshold``, unit diagonal (DCRNN eq. 10)."""
    dx = coords[:, None, 0] - coords[None, :, 0]
    dy = coords[:, None, 1] - coords[None, :, 1]
    d = jnp.sqrt(dx * dx + dy * dy)
    sigma = jnp.std(d)
    sigma = jnp.where(sigma > 0, sigma, 1.0)
    w = jnp.exp(-jnp.square(d / sigma))
    w = jnp.where(w < threshold, 0.0, w)
    return jnp.fill_diagonal(w, 1.0, inplace=False)


def transition_matrices(adj) -> tuple[jax.Array, jax.Array]:
    """(D_O^-1 A, D_I^-1 A^T): the forward and reverse random walks."""
    out_deg = adj.sum(axis=1, keepdims=True)
    in_deg = adj.sum(axis=0, keepdims=True)
    return adj / jnp.maximum(out_deg, 1e-8), adj.T / jnp.maximum(in_deg.T, 1e-8)


@functools.partial(jax.jit, static_argnames=("nodes",))
def make_graph(key, nodes: int):
    """``(supports, smooth, nnz)``: the two diffusion supports, the
    row-normalised adjacency the noise is smoothed with, and each
    support's nonzero count."""
    adj = gaussian_adjacency(sensor_coords(key, nodes))
    supports = transition_matrices(adj)
    smooth = adj / (adj.sum(axis=1, keepdims=True) + 1e-6)
    nnz = jnp.stack([jnp.count_nonzero(s) for s in supports])
    return supports, smooth, nnz


# ------------------------------------------------------ the series, in place
def _chunk_noise(key, chunk_index, rows: int, nodes: int):
    """[nodes, rows] standard normal draws (times ``NOISE_STD``) of a chunk."""
    return NOISE_STD * jax.random.normal(jax.random.fold_in(key, chunk_index),
                                         (nodes, rows))


def _ar1(noise, carry, first_is_origin):
    """The AR(1) recurrence along the rows (axis 1) of one chunk, from
    ``carry`` (the value of the row before the chunk).  At the series origin
    the first row keeps its draw, as in the host generator."""
    a = AR_SCALE * AR_KEEP
    b = AR_SCALE * noise
    b = b.at[:, 0].set(jnp.where(first_is_origin, noise[:, 0],
                                 b[:, 0] + a * carry))

    def combine(lhs, rhs):
        return lhs[0] * rhs[0], rhs[0] * lhs[1] + rhs[1]

    coeff = jnp.full_like(b, a)
    return jax.lax.associative_scan(combine, (coeff, b), axis=1)[1]


def _chunk_values(key, chunk_index, rows, params):
    """Speed ``[nodes, rows]`` and time of day ``[rows]`` of the series'
    chunk ``chunk_index``.  Time is the minor axis, as in the layout the
    device keeps the series in, so writing a chunk moves no data."""
    smooth, free_flow, dip, phase = params
    nodes = smooth.shape[0]
    prev = _chunk_noise(key, chunk_index - 1, rows, nodes)
    carry = _ar1(prev, jnp.zeros((nodes,), prev.dtype), chunk_index == 1)[:, -1]
    carry = jnp.where(chunk_index == 0, 0.0, carry)
    noise = _ar1(_chunk_noise(key, chunk_index, rows, nodes), carry,
                 chunk_index == 0)
    noise = noise + SMOOTH_WEIGHT * (smooth @ noise)

    t = chunk_index * rows + jnp.arange(rows)
    tod = (t % STEPS_PER_DAY).astype(jnp.float32) / STEPS_PER_DAY

    def rush(center):
        return jnp.exp(-0.5 * jnp.square(
            (tod[None, :] - center - phase[:, None]) / 0.06))

    speed = free_flow[:, None] - dip[:, None] * (rush(0.33) + 0.8 * rush(0.71))
    return jnp.clip(speed + noise, *CLIP), tod


def _time_shards(sharding: NamedSharding) -> tuple[tuple[str, ...], int]:
    """The mesh axes the series' time dimension is split over, and how many
    shards that makes."""
    spec = tuple(sharding.spec)
    axes = spec[0] if spec and spec[0] is not None else ()
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    count = 1
    for a in axes:
        count *= sharding.mesh.shape[a]
    return axes, count


def _zeros(shape, sharding):
    return jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                   out_shardings=sharding)()


def _shard_index(axes, mesh):
    shard = 0
    for a in axes:
        shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
    return shard


def make_series(key, entries: int, smooth, sharding: NamedSharding, *,
                chunk: int) -> jax.Array:
    """[entries, nodes, 2] float32 traffic series in ``sharding``.

    ``chunk`` rows are made at a time; it must divide each time shard.  Each
    device makes its own shard's chunks, one program call per chunk, and a
    second call writes them into a zero buffer it donates.  The two are kept
    apart so that the buffer keeps the device's layout: in one loop, XLA
    copied the whole series into the layout the chunk's matmul prefers.  The
    key, the chunk index and the graph are arguments, so the programs are
    the same for every seed.
    """
    nodes = smooth.shape[0]
    axes, shards = _time_shards(sharding)
    mesh, spec = sharding.mesh, sharding.spec
    local = entries // shards
    if entries % shards or local % chunk:
        raise ValueError(f"{entries} steps over {shards} time shards do not "
                         f"split into chunks of {chunk}")

    def make_chunk(key, c, smooth):
        k_flow, k_dip, k_phase, k_noise = jax.random.split(key, 4)
        params = (
            smooth,
            jax.random.uniform(k_flow, (nodes,), minval=55.0, maxval=70.0),
            jax.random.uniform(k_dip, (nodes,), minval=10.0, maxval=30.0),
            jax.random.uniform(k_phase, (nodes,), minval=-0.05, maxval=0.05))
        first = _shard_index(axes, mesh) * (local // chunk)
        speed, tod = _chunk_values(k_noise, first + c, chunk, params)
        return jnp.stack([speed.T, jnp.broadcast_to(tod[:, None], speed.T.shape)],
                         axis=-1)

    def write(buf, rows, c):
        return jax.lax.dynamic_update_slice_in_dim(buf, rows, c * chunk, axis=0)

    make_chunk = jax.jit(jax.shard_map(
        make_chunk, mesh=mesh, in_specs=(P(), P(), P()), out_specs=spec,
        check_vma=False))
    write = jax.jit(jax.shard_map(
        write, mesh=mesh, in_specs=(spec, spec, P()), out_specs=spec,
        check_vma=False), donate_argnums=0)
    buf = _zeros((entries, nodes, 2), sharding)
    for c in range(local // chunk):
        c = jnp.int32(c)
        buf = write(buf, make_chunk(key, c, smooth), c)
    return buf


# ------------------------------------------------------------ standardising
def window_layout(entries: int, span: int, in_len: int, *,
                  train: float = 0.7, val: float = 0.1):
    """``(starts, (train, val, test) window ids, train_end_step)`` with the
    semantics of ``IndexDataset.from_raw``: every window start, a contiguous
    70/10/20 split of the window ids, and the scaler over the series range
    the training windows cover."""
    n = max(entries - span + 1, 0)
    starts = np.arange(n, dtype=np.int32)
    n_train, n_val = round(n * train), round(n * val)
    ids = np.arange(n, dtype=np.int32)
    splits = (ids[:n_train], ids[n_train:n_train + n_val],
              ids[n_train + n_val:])
    end = int(starts[splits[0][-1]]) + in_len if n_train else entries
    return starts, splits, end


def _moments(series, end: int, sharding, *, chunk: int):
    """Mean and population std of feature 0 over steps ``[0, end)``, two
    passes, as ``numpy.mean`` / ``numpy.std`` define them.  One call per
    chunk and pass returns each device's chunk sum, added on the host in
    float64: a reduction over the whole series made XLA copy feature 0 out,
    a [steps, nodes] temporary."""
    axes, shards = _time_shards(sharding)
    mesh, spec = sharding.mesh, sharding.spec
    local = series.shape[0] // shards

    def chunk_sum(buf, c, mean, power):
        rows = jax.lax.dynamic_slice_in_dim(buf, c * chunk, chunk, axis=0)
        t = _shard_index(axes, mesh) * local + c * chunk + jnp.arange(chunk)
        x = jnp.where((t < end)[:, None], rows[..., 0] - mean, 0.0)
        return jnp.sum(x ** power)[None]

    sums = {p: jax.jit(jax.shard_map(
        functools.partial(chunk_sum, power=p), mesh=mesh,
        in_specs=(spec, P(), P()), out_specs=P(axes or None),
        check_vma=False)) for p in (1, 2)}
    count = end * series.shape[1]

    def total(power, mean):
        parts = [sums[power](series, jnp.int32(c), jnp.float32(mean))
                 for c in range(local // chunk)]
        return float(np.sum(np.asarray(jax.device_get(parts), np.float64)))

    mean = total(1, 0.0) / count
    return mean, float(np.sqrt(total(2, mean) / count))


def _standardise(series, mean, std, *, chunk: int, sharding):
    """Feature 0 of ``series`` -> (x - mean) / std, in place, chunk by chunk."""
    _, shards = _time_shards(sharding)
    local = series.shape[0] // shards

    def local_fn(buf, mean, std):
        def body(c, buf):
            rows = jax.lax.dynamic_slice_in_dim(buf, c * chunk, chunk, axis=0)
            rows = rows.at[..., 0].set((rows[..., 0] - mean) / std)
            return jax.lax.dynamic_update_slice_in_dim(buf, rows, c * chunk,
                                                       axis=0)
        return jax.lax.fori_loop(0, local // chunk, body, buf)

    fn = jax.shard_map(local_fn, mesh=sharding.mesh,
                       in_specs=(sharding.spec, P(), P()),
                       out_specs=sharding.spec, check_vma=False)
    return jax.jit(fn, donate_argnums=0, out_shardings=sharding)(
        series, mean, std)


def standardise(series, train_end_step: int, sharding, *, chunk: int):
    """``(standardised series, (mean, std))``: feature 0 scaled by the
    moments of ``series[:train_end_step, :, 0]``; the input is donated."""
    mean, std = _moments(series, train_end_step, sharding, chunk=chunk)
    std = std or 1.0
    out = _standardise(series, jnp.float32(mean), jnp.float32(std),
                       chunk=chunk, sharding=sharding)
    return out, (mean, std)
