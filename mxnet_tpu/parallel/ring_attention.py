"""Ring attention — sequence/context parallelism for long sequences.

Reference parity: none (the reference has no SP/CP — SURVEY §5); this is the
TPU-native long-context capability the task brief makes first-class.

Design (Liu et al. ring attention, scaling-book recipe): shard the sequence
axis of Q/K/V over a mesh axis ('sp'). Each device holds one Q block and
iterates over all K/V blocks, which rotate around the ring via
lax.ppermute (ICI neighbor exchange) while the device accumulates
flash-attention-style online-softmax partial results — comm overlaps compute
because the permute for step i+1 is issued alongside the matmuls of step i
(XLA latency-hiding scheduler).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def _block_attn(q, k, v, m_prev, l_prev, acc, scale, mask=None):
    """One online-softmax accumulation step.
    q: (b, h, sq, d); k/v: (b, h, sk, d); m/l: (b, h, sq, 1); acc like q."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows (m_new == -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    correction = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - m_safe,
                                   -jnp.inf))
    correction = jnp.where(jnp.isfinite(m_prev), correction, 0.0)
    l_new = correction * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = correction * acc + jnp.einsum("bhqk,bhkd->bhqd",
                                            p.astype(v.dtype), v)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, mesh, axis="sp", causal=False, scale=None):
    """Sequence-sharded attention.

    q, k, v: (batch, heads, seq, head_dim) jax arrays (or mx ndarrays),
    sharded (or shardable) over `axis` on the seq dimension. Returns the
    attention output with the same sharding.
    """
    from ..numpy.multiarray import ndarray, _wrap
    wrap = isinstance(q, ndarray)
    if wrap:
        q, k, v = q._data, k._data, v._data
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    n = mesh.shape[axis]
    perm = [(i, (i + 1) % n) for i in range(n)]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, None, axis, None),) * 3,
        out_specs=P(None, None, axis, None))
    def _ring(qb, kb, vb):
        my = jax.lax.axis_index(axis)
        sq = qb.shape[2]

        def step(i, carry):
            kc, vc, m, l, acc = carry
            if causal:
                src = (my - i) % n  # ring shifts K/V forward each step
                q_pos = my * sq + jax.lax.broadcasted_iota(
                    jnp.int32, (sq, sq), 0)
                k_pos = src * sq + jax.lax.broadcasted_iota(
                    jnp.int32, (sq, sq), 1)
                mask = (q_pos >= k_pos)[None, None]
            else:
                mask = None
            m, l, acc = _block_attn(qb, kc, vc, m, l, acc, scale, mask)
            kc = jax.lax.ppermute(kc, axis, perm)
            vc = jax.lax.ppermute(vc, axis, perm)
            return kc, vc, m, l, acc

        b, h = qb.shape[0], qb.shape[1]
        m0 = jnp.full((b, h, sq, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
        acc0 = jnp.zeros(qb.shape, jnp.float32)
        # constants start unvarying over the mesh axis; the loop makes them
        # varying — cast up front so the scan carry types match
        if hasattr(jax.lax, "pcast"):
            m0, l0, acc0 = (jax.lax.pcast(t, (axis,), to="varying")
                            for t in (m0, l0, acc0))
        _, _, m, l, acc = jax.lax.fori_loop(
            0, n, step, (kb, vb, m0, l0, acc0))
        return (acc / jnp.maximum(l, 1e-20)).astype(qb.dtype)

    out = _ring(q, k, v)
    return _wrap(out) if wrap else out
