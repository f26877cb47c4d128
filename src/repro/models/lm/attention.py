"""Attention variants: GQA/MQA full attention, blockwise (flash-style) online
softmax for long sequences, banded attention for sliding-window (SWA/local),
and single-step decode against a KV cache.

KV heads are never materialised ``G×`` — scores are computed grouped
([B, Hkv, G, Sq, Skv]) so MQA (granite kv=1) reads each KV element once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _grouped(q, n_kv):
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def full_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                   q_offset=0, kv_valid_from=0, scale: float | None = None):
    """q: [B, Sq, H, D], k/v: [B, Skv, Hkv, D] -> [B, Sq, H, D].

    ``q_offset``: position of q[0] relative to k[0] (decode / banded chunks).
    ``kv_valid_from``: keys below this index are masked (padding).
    ``scale``: the softmax scale (default ``1/sqrt(D)``).
    Materialises the [Sq, Skv] score matrix — use :func:`blockwise_attention`
    for long sequences.
    """
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    qg = _grouped(q, n_kv)
    scale = 1.0 / jnp.sqrt(jnp.float32(d)) if scale is None else scale
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = kpos >= kv_valid_from
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)


def blockwise_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                        q_chunk: int = 512, kv_chunk: int = 512,
                        scale: float | None = None):
    """Flash-style online-softmax attention in pure JAX.

    Structure matters for BOTH directions of autodiff:
    - outer ``lax.map`` over q chunks, with ``jax.checkpoint`` on the chunk
      body: backward RECOMPUTES each chunk's score blocks instead of storing
      them (without this, autodiff stacks every kv-step's probs — measured
      8×20 GiB per layer on qwen train_4k);
    - inner ``lax.scan`` over kv chunks with online-softmax (m, l, acc)
      carry: peak live score block is [qc, kc], never [Sq, Skv].
    Causal q-chunks also skip kv blocks entirely above the diagonal via
    masking-free early bounds (the mask zeroes them; XLA DCEs full-block
    no-ops only with static bounds, so we keep the scan dense — acceptable:
    2× the minimal FLOPs on the strictly-lower triangle).
    Sequences that do not divide the chunks are padded at the end; padded
    keys are masked and padded queries dropped.
    """
    b, s_in, h, d = q.shape
    n_kv = k.shape[2]
    skv_in = k.shape[1]
    s, skv = -(-s_in // q_chunk) * q_chunk, -(-skv_in // kv_chunk) * kv_chunk
    pad = lambda a, n: jnp.pad(a, ((0, 0), (0, n - a.shape[1]), (0, 0), (0, 0)))
    q, k, v = pad(q, s), pad(k, skv), pad(v, skv)
    nq, nk = s // q_chunk, skv // kv_chunk
    g = h // n_kv

    kc_all = k.reshape(b, nk, kv_chunk, n_kv, d)
    vc_all = v.reshape(b, nk, kv_chunk, n_kv, d)
    scale = 1.0 / jnp.sqrt(jnp.float32(d)) if scale is None else scale

    @jax.checkpoint
    def one_q_chunk(qi):
        qg = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        qg = _grouped(qg, n_kv)  # [b, qc, kv, g, d]
        qpos = qi * q_chunk + jnp.arange(q_chunk)[:, None]  # [qc, 1]

        def kv_step(carry, inp):
            m, l, acc = carry  # [b,qc,kv,g], same, [b,qc,kv,g,d]
            ki, k_blk, v_blk = inp
            s_blk = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                               k_blk.astype(jnp.float32)) * scale
            kpos = ki * kv_chunk + jnp.arange(kv_chunk)[None, :]
            mask = jnp.broadcast_to(kpos < skv_in, (q_chunk, kv_chunk))
            if causal:
                mask &= kpos <= qpos
            if window is not None:
                mask &= kpos > qpos - window
            mask5 = mask[None, :, None, None, :]  # [1,qc,1,1,kc]
            s_blk = jnp.where(mask5, s_blk, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
            # exp(NEG_INF - NEG_INF) would be 1 for fully-masked rows: zero them.
            p = jnp.where(mask5, jnp.exp(s_blk - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, q_chunk, n_kv, g), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, q_chunk, n_kv, g), jnp.float32)
        a0 = jnp.zeros((b, q_chunk, n_kv, g, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kc_all, 1, 0), jnp.moveaxis(vc_all, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, q_chunk, h, d).astype(q.dtype)

    out = jax.lax.map(one_q_chunk, jnp.arange(nq))  # [nq, b, qc, h, d]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)[:, :s_in]


def banded_attention(q, k, v, *, window: int, q_chunk: int = 512):
    """Sliding-window attention with true sub-quadratic FLOPs.

    For each q chunk, only the ``window + q_chunk`` KV band is gathered
    (static shapes via dynamic_slice), so compute is O(S · window) — the
    long-context enabler for SWA archs (h2o-danube, recurrentgemma local attn).
    """
    b, s, h, d = q.shape
    assert s % q_chunk == 0, (s, q_chunk)
    nq = s // q_chunk
    band = window + q_chunk  # worst-case KV extent one q chunk can see
    kp = jnp.pad(k, ((0, 0), (band, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (band, 0), (0, 0), (0, 0)))

    def one_chunk(qi):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * q_chunk, q_chunk, axis=1)
        # Band ends at the chunk's last position; padded coords shift by +band.
        kc = jax.lax.dynamic_slice_in_dim(kp, qi * q_chunk + q_chunk, band, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, qi * q_chunk + q_chunk, band, axis=1)
        # k index j is absolute position qi*q_chunk + q_chunk - band + j;
        # entries with absolute position < 0 are left-padding -> mask them.
        valid_from = band - q_chunk * (qi + 1)
        return full_attention(qc, kc, vc, causal=True, window=window,
                              q_offset=band - q_chunk, kv_valid_from=valid_from)

    out = jax.lax.map(one_chunk, jnp.arange(nq))  # [nq, B, qc, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def decode_attention(q1, k_cache, v_cache, length, *, window: int | None = None):
    """One-token decode.  q1: [B, 1, H, D]; caches: [B, S_max, Hkv, D];
    ``length``: number of valid cache entries (the new token's position)."""
    b, _, h, d = q1.shape
    n_kv = k_cache.shape[2]
    qg = _grouped(q1, n_kv)[:, 0]  # [B, Hkv, G, D]
    scores = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                        k_cache.astype(jnp.float32)) / jnp.sqrt(jnp.float32(d))
    kpos = jnp.arange(k_cache.shape[1])[None, :]
    length = jnp.asarray(length)
    length = length.reshape(-1, 1) if length.ndim else length[None, None]
    mask = kpos < length
    if window is not None:
        mask &= kpos >= length - window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs.astype(v_cache.dtype), v_cache)
    return out.reshape(b, 1, h, d)
