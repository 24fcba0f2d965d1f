"""The three ops a block of today's open mixture-of-experts models adds
to the Program path (OLMoE: models/transformer.py, LMConfig(norm=
'rms_norm', position='rope', qk_norm=True, ffn='moe')):

- ``rms_norm``: ``x * rsqrt(mean(x^2) + eps) * w`` over the trailing
  dimensions from ``begin_norm_axis``, computed in float32.
- ``rotary_embedding``: rotate every head of ``X [..., H, dh]`` by the
  angle ``Positions * theta^(-2i/dh)``, ``rotate_half`` convention (the
  two halves of a head are dims ``[0, dh/2)`` and ``[dh/2, dh)``).
  Positions are the ones the decode and prefill programs already feed
  (``gen_pos``), one per leading row of ``X``, so a prefix-shared suffix
  rotates by its GLOBAL positions.
- ``moe_ffn``: a DROPLESS top-k expert FFN. There is no capacity: every
  one of the ``N * top_k`` (row, expert) assignments is computed.

      p = softmax(x @ RouterW)          float32, matmul at `highest`
      (p_e, e) = the top_k largest p    NOT renormalised unless asked
      out = sum_e p_e * (silu(x @ GateW[e]) * (x @ UpW[e])) @ DownW[e]

  The grouped expert matmul: the assignments are sorted by expert, and
  each of the three matmuls is ONE ``jax.lax.ragged_dot`` over the
  sorted rows with the per-expert counts as group sizes; the rows are
  then unsorted and weighted. On a TPU XLA lowers ``ragged_dot`` to a
  Mosaic grouped-matmul kernel that visits (row tile, expert) pairs of
  NON-EMPTY groups only: an expert no row routes to is never read, and a
  prefill of T rows computes ``T * top_k`` expert rows, not ``T * E``
  (read on the chip and in the compiled program's FLOPs: PERF.md, PR 28).
  On the CPU it lowers to masked dense matmuls — the tests' toy widths.

  ``ExpertLoad [E]`` counts the rows routed to each expert. Rows that
  are not a request's are left out of the COUNT (they are still
  computed: a row's result never depends on another row): a prefill
  bucket's pad rows by ``Length`` (rows at or past it), a decode step's
  idle slots by ``Valid`` (zero = idle).
"""
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op


@register_op('rms_norm')
def _rms_norm(ctx, op):
    x = ctx.in1(op, 'X')
    scale = ctx.in1(op, 'Scale')
    eps = float(op.attr('epsilon', 1e-5))
    bna = int(op.attr('begin_norm_axis', 1))
    xf = x.astype(jnp.float32)
    axes = tuple(range(bna, x.ndim))
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=axes, keepdims=True) + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32).reshape(x.shape[bna:])
    ctx.out(op, 'Out', y.astype(x.dtype))


def rotate(x, positions, theta):
    """`x [..., H, dh]` rotated by `positions` (one per leading row)."""
    dh = x.shape[-1]
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    angle = positions.reshape(x.shape[:-2]).astype(jnp.float32)[..., None] \
        * inv_freq                                          # [..., dh/2]
    cos = jnp.cos(angle)[..., None, :]
    sin = jnp.sin(angle)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@register_op('rotary_embedding', share_lod=False)
def _rotary_embedding(ctx, op):
    x = ctx.in1(op, 'X')                        # [..., H, dh]
    pos = ctx.in1(op, 'Positions')              # one per leading row
    if x.shape[-1] % 2:
        raise ValueError('rotary_embedding: odd head size %d' % x.shape[-1])
    ctx.out(op, 'Out', rotate(x, pos, float(op.attr('theta', 10000.0))))


def route(x, router_w, top_k, norm_topk_prob):
    """(weights [N, k] float32, experts [N, k] int32): softmax over ALL
    experts in float32, then the top_k largest."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx.astype(jnp.int32)


def grouped_ffn(x, w, idx, gate_w, up_w, down_w):
    """sum_j w[n, j] * FFN_{idx[n, j]}(x[n]) through three ragged_dots
    over the assignments sorted by expert."""
    n, k = idx.shape
    n_experts = gate_w.shape[0]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)                   # stable: by expert, row
    sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                    axis=0, dtype=jnp.int32)    # [E]
    xs = x[order // k]                          # [N*k, d]
    h = jax.nn.silu(lax.ragged_dot(xs, gate_w, sizes)) \
        * lax.ragged_dot(xs, up_w, sizes)
    y = lax.ragged_dot(h, down_w, sizes)        # [N*k, d], sorted
    y = y[jnp.argsort(order)].reshape(n, k, -1)
    return jnp.einsum('nk,nkd->nd', w.astype(y.dtype), y)


@register_op('moe_ffn', share_lod=False)
def _moe_ffn(ctx, op):
    x = ctx.in1(op, 'X')                        # [N, d]
    router_w = ctx.in1(op, 'RouterW')           # [d, E]
    gate_w = ctx.in1(op, 'GateW')               # [E, d, w]
    up_w = ctx.in1(op, 'UpW')                   # [E, d, w]
    down_w = ctx.in1(op, 'DownW')               # [E, w, d]
    length = ctx.in1(op, 'Length')              # optional: real rows
    valid = ctx.in1(op, 'Valid')                # optional [N]/[N, 1]
    top_k = int(op.attr('top_k'))
    w, idx = route(x, router_w, top_k, bool(op.attr('norm_topk_prob',
                                                    False)))
    out = grouped_ffn(x, w, idx, gate_w, up_w, down_w)
    counted = jnp.ones((x.shape[0],), bool)
    if length is not None:
        counted &= jnp.arange(x.shape[0]) < \
            length.reshape(-1)[0].astype(jnp.int32)
    if valid is not None:
        counted &= valid.reshape(-1) != 0
    hit = (idx[:, :, None] == jnp.arange(gate_w.shape[0])[None, None, :]) \
        & counted[:, None, None]
    ctx.out(op, 'Out', out.astype(x.dtype))
    ctx.out(op, 'TopkIdx', idx)
    ctx.out(op, 'ExpertLoad', jnp.sum(hit, axis=(0, 1), dtype=jnp.int32))
