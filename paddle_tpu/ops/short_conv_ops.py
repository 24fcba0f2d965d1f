"""The gated short convolution's state in the block pool (LFM2's ``conv``
layers: models/transformer.py, LMConfig(layer_types=...)).

A convolution layer's mixer is ``y = (C * conv(B * u)) W_out`` with
``conv`` a causal depthwise convolution of ``K`` taps over ``g = B * u``:

    c_t = sum_j w[:, j] * g_{t - (K - 1) + j}        g before 0 is zero

What a token leaves behind is not a key and a value but ``g`` of the last
``K - 1`` positions: the layer's TAIL. The tails live in a pool of their
own, ``[num_blocks, conv_layers, K - 1, d_model]`` float32, indexed by the
SAME block ids as the K/V pools (models/transformer.py `CONV_CACHE`), with
one contract:

    the entry of block b holds g of the last K - 1 positions written up
    to and into b

so a block carries, beside its keys and values, the state a computation
resumes from behind it — and prefix sharing, chunked prefill,
copy-on-write, eviction and release need no bookkeeping of their own
(serving/generate.py). A FULL block's entry is final: g of its last
``K - 1`` rows.

- ``short_conv_decode_paged``: every slot's one new row at its position
  ``p``. Reads the entry of the block of ``p - 1`` (zeros at ``p == 0``),
  convolves, writes ``[tail[1:], g_p]`` to the entry of the block of
  ``p``. An idle slot (position 0, an all-zero table row) reads zeros and
  writes the trash block.
- ``short_conv_prefill_paged``: one prompt suffix or chunk of ``T`` rows
  from position ``off = Positions[0]`` on. History: zeros if ``off == 0``,
  else the entry of the block of ``off - 1``. Writes the entry of EVERY
  block the real rows touch (each as of the block's last real row); a
  block only pad rows reach goes to the trash block, as their K/V do.

Both are elementwise float32 work on ``[rows, d_model]`` beside the
mixer's two matmuls, under the named scope ``paddle_tpu:short_conv`` so
that a device trace tells their fusions from the rest.
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_op

SCOPE = 'paddle_tpu:short_conv'


def _taps(window, w):
    """sum_j w[:, j] * window[..., j, :]: a product and a sum a tap, on the
    VPU in float32 (an einsum over K = 3 would be a matmul at the
    backend's default precision)."""
    return sum(window[..., j, :] * w[:, j] for j in range(w.shape[1]))


@register_op('short_conv_decode_paged', share_lod=False)
def _short_conv_decode_paged(ctx, op):
    g = ctx.in1(op, 'X')                        # [S, d]
    w = ctx.in1(op, 'Weight')                   # [d, K]
    cache = ctx.in1(op, 'Cache')                # [NB, Lc, K-1, d]
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)   # [S, MB]
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)
    layer = int(op.attr('layer'))
    bs = int(op.attr('block_size'))
    last = tables.shape[1] - 1

    def block_of(p):
        idx = jnp.clip(p // bs, 0, last)
        return jnp.take_along_axis(tables, idx[:, None], axis=1)[:, 0]

    with jax.named_scope(SCOPE):
        tail = cache[block_of(pos - 1), layer]              # [S, K-1, d]
        tail = jnp.where((pos > 0)[:, None, None], tail, 0.0)
        window = jnp.concatenate(
            [tail, g.astype(cache.dtype)[:, None, :]], axis=1)  # [S, K, d]
        out = _taps(window, w.astype(cache.dtype))
        new = cache.at[block_of(pos), layer].set(window[:, 1:, :])
    ctx.out(op, 'Out', out.astype(g.dtype))
    ctx.out(op, 'CacheOut', new)


@register_op('short_conv_prefill_paged', share_lod=False)
def _short_conv_prefill_paged(ctx, op):
    g = ctx.in1(op, 'X')                        # [1, T, d]
    w = ctx.in1(op, 'Weight')                   # [d, K]
    cache = ctx.in1(op, 'Cache')                # [NB, Lc, K-1, d]
    table = ctx.in1(op, 'BlockTable').reshape(-1).astype(jnp.int32)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)  # [T]
    length = ctx.in1(op, 'Length').reshape(-1).astype(jnp.int32)[0]
    layer = int(op.attr('layer'))
    bs = int(op.attr('block_size'))
    T, K = g.shape[1], w.shape[1]
    last = table.shape[0] - 1
    off = pos[0]
    with jax.named_scope(SCOPE):
        hist = cache[table[jnp.clip((off - 1) // bs, 0, last)], layer]
        hist = jnp.where(off > 0, hist, 0.0)                # [K-1, d]
        ext = jnp.concatenate([hist, g[0].astype(cache.dtype)], axis=0)
        window = jnp.stack([ext[j:j + T] for j in range(K)], axis=1)
        out = _taps(window, w.astype(cache.dtype))          # [T, d]
        # the blocks rows 0..T-1 can lie in, and the last REAL row of each
        # (relative to `off`): ext[e + 1 .. e + K - 1] are g's last K - 1
        # rows up to and with row e
        blocks = off // bs + jnp.arange(-(-T // bs) + 1)
        e = jnp.minimum((blocks + 1) * bs - off, length) - 1
        real = e >= jnp.maximum(blocks * bs - off, 0)
        entries = ext[e[:, None] + 1 + jnp.arange(K - 1)[None, :]]
        ids = jnp.where(real, table[jnp.clip(blocks, 0, last)], 0)
        new = cache.at[ids, layer].set(entries)
    ctx.out(op, 'Out', out[None].astype(g.dtype))
    ctx.out(op, 'CacheOut', new)
