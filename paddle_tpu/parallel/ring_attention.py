"""Ring attention: sequence/context parallelism for long sequences.

The reference has no long-context sharding (SURVEY §5: its "sequence
parallelism" is LoD ragged batching); this is the TPU-native extension the
capability maps onto: the sequence axis is sharded over mesh axis 'seq',
each device holds an L/n block of Q/K/V, and K/V blocks rotate around the
ring (lax.ppermute over ICI) while each device accumulates its Q block's
attention with an online softmax — full attention over sequences n times
longer than one chip could hold, with communication overlapped around the
ring (Liu et al., Ring Attention with Blockwise Transformers).

Written with shard_map so the collective schedule is explicit (this is the
one place XLA's automatic SPMD cannot derive the rotation pattern). The
flash_attention op dispatches here automatically when the sequence axis of
its mesh is sharded (ops/attention_ops.py:flash_attention_spmd), so ring is
the long-context execution mode of the same op, not a separate API.

Causal masking skips invisible K/V blocks with lax.cond (real compute
saved, not just masked), and `zigzag=True` rebalances the causal triangle:
the sequence is laid out so device d holds chunks d and 2n-1-d, giving
every device an equal share of visible blocks (the classic striped/zig-zag
context-parallel layout). Block visibility is decided from true sequence
positions, which rotate around the ring with their K/V blocks.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ['ring_attention', 'zigzag_permutation']

_NEG_INF = -1e30


def zigzag_permutation(ln, n):
    """Permutation putting global rows into the zig-zag layout: shard d of
    the permuted sequence holds original chunks d and 2n-1-d (each ln/(2n)
    rows), so causal work per device is balanced. Returns (perm, inv_perm)
    as numpy int32 arrays; permuted[r] = original[perm[r]]."""
    if ln % (2 * n):
        raise ValueError(
            "zigzag layout needs seq len %d divisible by 2*%d" % (ln, n))
    half = ln // (2 * n)
    chunks = []
    for d in range(n):
        chunks.append(np.arange(d * half, (d + 1) * half))
        hi = 2 * n - 1 - d
        chunks.append(np.arange(hi * half, (hi + 1) * half))
    perm = np.concatenate(chunks).astype(np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(ln, dtype=np.int32)
    return perm, inv


def _ring_inner(axis_name, scale, causal, q, k, v, q_pos):
    """Per-device body: q/k/v [B, H, Lb, dh] local blocks; q_pos [Lb] true
    sequence positions of the local rows."""
    n = lax.psum(1, axis_name)
    b, h, lb, dh = q.shape

    qf = q.astype(jnp.float32)
    q_max = jnp.max(q_pos) if causal else None

    def accumulate(m, el, acc, k_cur, v_cur, k_pos):
        """Online-softmax update with one rotated K/V block."""
        scores = jnp.einsum('bhqd,bhkd->bhqk', qf,
                            k_cur.astype(jnp.float32)) * scale
        mask = None
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :])[None, None]
            scores = jnp.where(mask, scores, _NEG_INF)
        blk_max = jnp.max(scores, axis=-1)               # [b,h,lb]
        m_new = jnp.maximum(m, blk_max)
        alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
        p = jnp.exp(scores - m_new[..., None])
        if mask is not None:
            # masked entries contribute exactly zero even in the
            # fully-masked-row corner where m_new is still _NEG_INF
            # (exp(-1e30 - -1e30) would otherwise be 1)
            p = jnp.where(mask, p, 0.0)
        el_new = el * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            'bhqk,bhkd->bhqd', p, v_cur.astype(jnp.float32))
        return m_new, el_new, acc_new

    def visible_update(m, el, acc, k_cur, v_cur, k_pos):
        if not causal:
            return accumulate(m, el, acc, k_cur, v_cur, k_pos)
        # skip blocks with no visible keys — lax.cond executes one branch,
        # so the causal triangle costs half the FLOPs of the masked square
        return lax.cond(
            jnp.min(k_pos) <= q_max,
            lambda c: accumulate(c[0], c[1], c[2], k_cur, v_cur, k_pos),
            lambda c: c,
            (m, el, acc))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        m, el, acc, k_cur, v_cur, k_pos = carry
        m, el, acc = visible_update(m, el, acc, k_cur, v_cur, k_pos)
        # rotate k/v (and their true positions) one step around the ring
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        kp_next = lax.ppermute(k_pos, axis_name, perm)
        return m, el, acc, k_next, v_next, kp_next

    m0 = jnp.full((b, h, lb), _NEG_INF, jnp.float32)
    el0 = jnp.zeros((b, h, lb), jnp.float32)
    acc0 = jnp.zeros((b, h, lb, dh), jnp.float32)
    # n-1 rotated steps, then the final block WITHOUT the useless closing
    # rotation (saves one full K/V round over ICI per call)
    m, el, acc, k_last, v_last, kp_last = lax.fori_loop(
        0, n - 1, step, (m0, el0, acc0, k, v, q_pos))
    m, el, acc = visible_update(m, el, acc, k_last, v_last, kp_last)
    out = acc / jnp.maximum(el, 1e-20)[..., None]
    return out.astype(q.dtype)


def _shard_map(fn, mesh, in_specs, out_specs, axis_names=None):
    """axis_names: restrict MANUAL axes to this subset — the other mesh
    axes stay under the automatic SPMD partitioner, so e.g. gpipe over
    mesh(data=2, pipe=4) with axis_names={'pipe'} keeps the feed's
    'data' sharding (and the backward psum over 'data') instead of
    replicating the whole batch per data replica. None: manual over all
    axes."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=frozenset(axis_names or ()))


def ring_attention(q, k, v, mesh, axis_name='seq', scale=None, causal=True,
                   batch_axis=None, head_axis=None, zigzag=False):
    """Blockwise ring attention. q/k/v: [B, H, L, dh] GLOBAL arrays whose
    L dimension is (or will be) sharded over `mesh` axis `axis_name`;
    returns attention output with the same sharding. L must be divisible
    by the axis size. batch_axis/head_axis optionally name mesh axes
    sharding B and H (so ring composes with dp/tp instead of forcing an
    all-gather). zigzag=True permutes the sequence into the balanced
    zig-zag layout internally (production pipelines should pre-permute at
    data-loading time and call with zigzag=False + their own layout)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    naxis = mesh.shape[axis_name]
    ln = q.shape[2]
    if ln % naxis != 0:
        raise ValueError(
            "ring_attention: sequence length %d not divisible by mesh "
            "axis %r size %d" % (ln, axis_name, naxis))

    inv = None
    if zigzag and naxis > 1:
        perm, inv = zigzag_permutation(ln, naxis)
        perm = jnp.asarray(perm)
        q = jnp.take(q, perm, axis=2)
        k = jnp.take(k, perm, axis=2)
        v = jnp.take(v, perm, axis=2)
        positions = perm.astype(jnp.int32)
    else:
        positions = jnp.arange(ln, dtype=jnp.int32)

    spec = P(batch_axis, head_axis, axis_name, None)
    inner = functools.partial(_ring_inner, axis_name, float(scale),
                              bool(causal))
    fn = _shard_map(inner, mesh, (spec, spec, spec, P(axis_name)), spec)
    out = fn(q, k, v, positions)
    if inv is not None:
        out = jnp.take(out, jnp.asarray(inv), axis=2)
    return out
