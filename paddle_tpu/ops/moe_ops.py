"""The three ops a block of today's open mixture-of-experts models adds
to the Program path (OLMoE: models/transformer.py, LMConfig(norm=
'rms_norm', position='rope', qk_norm=True, ffn='moe')):

- ``rms_norm``: ``x * rsqrt(mean(x^2) + eps) * w`` over the trailing
  dimensions from ``begin_norm_axis``, computed in float32.
- ``rotary_embedding``: rotate every head of ``X [..., H, dh]`` by the
  angle ``Positions * theta^(-2i/dh)``, ``rotate_half`` convention (the
  two halves of a head are dims ``[0, dh/2)`` and ``[dh/2, dh)``), or with
  ``interleave`` the pairs ``(2i, 2i + 1)``, each rotated where it lies
  (DeepSeek-V3's ``rope_interleave``; HF moves the pairs apart first and
  then rotates halves: q and k are permuted alike, the scores are the
  same).
  Positions are the ones the decode and prefill programs already feed
  (``gen_pos``), one per leading row of ``X``, so a prefix-shared suffix
  rotates by its GLOBAL positions.
- ``moe_ffn``: a DROPLESS top-k expert FFN. There is no capacity: every
  one of the ``N * top_k`` (row, expert) assignments is computed.

      p = softmax(x @ RouterW)          float32, matmul at `highest`
      (p_e, e) = the top_k largest p    NOT renormalised unless asked
      out = sum_e p_e * (silu(x @ GateW[e]) * (x @ UpW[e])) @ DownW[e]

  WITHOUT GateW (``layers.moe_ffn(form='relu2')``, Nemotron-H's experts)
  an expert is ``relu(x @ UpW[e])^2 @ DownW[e]`` -- two grouped matmuls,
  not three.

  The grouped expert matmul: the assignments are sorted by expert, and
  each of the three matmuls is ONE ``jax.lax.ragged_dot`` over the
  sorted rows with the per-expert counts as group sizes; the rows are
  then unsorted and weighted. On a TPU XLA lowers ``ragged_dot`` to a
  Mosaic grouped-matmul kernel that visits (row tile, expert) pairs of
  NON-EMPTY groups only: an expert no row routes to is never read, and a
  prefill of T rows computes ``T * top_k`` expert rows, not ``T * E``
  (read on the chip and in the compiled program's FLOPs: PERF.md, PR 28).
  On the CPU it lowers to masked dense matmuls — the tests' toy widths.

  The DeepSeek-V3 router (``score='sigmoid'``): ``s = sigmoid(x @
  RouterW)``, the top_k largest of ``s + SelectBias`` (the bias chooses
  only), weights ``s_e`` of the chosen, divided by ``sum + router_eps``
  (1e-20; LFM2 says 1e-6) with ``norm_topk_prob``, times ``routed_scale``.

  A SHARE of the experts (``experts_held = (first, count)``, the chip's
  share under expert parallelism): the router still scores ALL
  ``n_experts`` and every row still chooses ``top_k`` of them; GateW, UpW
  and DownW hold the ``count`` experts from ``first`` on, and ``Out`` is
  the part of the sum these give. An assignment to an expert held
  elsewhere sorts behind every group of the grouped matmul, which never
  visits it: it is neither computed nor read, and nothing stands in for
  the chip that holds it. ``ExpertLoad`` then has ``count + 1`` entries,
  the last the assignments that went elsewhere. With every expert held
  the op is bit for bit what it was.

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


def rotate(x, positions, theta, interleave=False):
    """`x [..., H, dh]` rotated by `positions` (one per leading row): the
    pair of dims (i, i + dh/2), or with `interleave` (2i, 2i + 1), by the
    angle `position * theta^(-2i/dh)`."""
    dh = x.shape[-1]
    half = dh // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    angle = positions.reshape(x.shape[:-2]).astype(jnp.float32)[..., None] \
        * inv_freq                                          # [..., dh/2]
    cos = jnp.cos(angle)[..., None, :]
    sin = jnp.sin(angle)[..., None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


@register_op('rotary_embedding', share_lod=False)
def _rotary_embedding(ctx, op):
    x = ctx.in1(op, 'X')                        # [..., H, dh]
    pos = ctx.in1(op, 'Positions')              # one per leading row
    if x.shape[-1] % 2:
        raise ValueError('rotary_embedding: odd head size %d' % x.shape[-1])
    ctx.out(op, 'Out', rotate(x, pos, float(op.attr('theta', 10000.0)),
                              bool(op.attr('interleave', False))))


def route(x, router_w, top_k, norm_topk_prob, score='softmax',
          select_bias=None, routed_scale=1.0, eps=1e-20):
    """(weights [N, k] float32, experts [N, k] int32): the scores of ALL
    experts in float32, then the top_k largest. `score='sigmoid'`: the
    choice is by score + `select_bias`, the weights are the scores alone,
    normalised over the chosen with `norm_topk_prob` and scaled."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if score == 'softmax':
        w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        if norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w, idx.astype(jnp.int32)
    s = jax.nn.sigmoid(logits)
    idx = lax.top_k(s + select_bias.astype(jnp.float32)[None, :], top_k)[1]
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return w * routed_scale, idx.astype(jnp.int32)


def grouped_ffn(x, w, idx, gate_w, up_w, down_w, first=None, routed=None):
    """sum_j w[n, j] * FFN_{idx[n, j]}(x[n]) through three ragged_dots
    over the assignments sorted by expert; with `gate_w` None the
    UNGATED expert ``relu(x W_up)^2 W_down``, two. `first` (not None): the
    weights are those of experts first .. first + E - 1 of `routed`, and
    the sum runs over the assignments to these alone."""
    n, k = idx.shape
    n_experts = up_w.shape[0]
    flat = idx.reshape(-1)
    if first is not None:
        # an expert held elsewhere: behind every group, in none
        flat = flat - first
        flat = jnp.where((flat >= 0) & (flat < n_experts), flat, n_experts)
    order = jnp.argsort(flat)                   # stable: by expert, row
    sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                    axis=0, dtype=jnp.int32)    # [E]

    def experts(rows):
        """The first `rows` sorted assignments through the experts:
        [rows, d], sorted."""
        xs = x[(order if rows == n * k else order[:rows]) // k]
        up = lax.ragged_dot(xs, up_w, sizes)
        h = jnp.square(jax.nn.relu(up)) if gate_w is None \
            else jax.nn.silu(lax.ragged_dot(xs, gate_w, sizes)) * up
        return lax.ragged_dot(h, down_w, sizes)

    if first is None:
        y = experts(n * k)
    else:
        # The grouped matmul's time goes with the rows it is GIVEN, in a
        # group or not (on the v5e a tile of 128 rows in no group costs
        # what one in a group does, PERF.md PR 32), and a share of the
        # experts gets its share of the assignments: the matmuls take
        # one and a half times the expected number of rows, in whole
        # tiles, and ALL rows in the step where more are held than that
        # (the device decides, on what it sees: dropless either way).
        held = jnp.sum(sizes)
        cap = -(-3 * n * k * n_experts // (2 * routed * 128)) * 128

        def some():
            return jnp.pad(experts(cap), ((0, n * k - cap), (0, 0)))
        y = experts(n * k) if cap >= n * k \
            else lax.cond(held <= cap, some, lambda: experts(n * k))
        # what the grouped matmul leaves in the rows of no group is not a
        # result: drop it before the weights see it
        y = jnp.where((jnp.arange(n * k) < held)[:, None], y, 0.0)
    y = y[jnp.argsort(order)].reshape(n, k, -1)
    return jnp.einsum('nk,nkd->nd', w.astype(y.dtype), y)


@register_op('moe_ffn', share_lod=False)
def _moe_ffn(ctx, op):
    x = ctx.in1(op, 'X')                        # [N, d]
    router_w = ctx.in1(op, 'RouterW')           # [d, E]
    gate_w = ctx.in1(op, 'GateW')               # [E, d, w]; None: ungated
    up_w = ctx.in1(op, 'UpW')                   # [E, d, w]
    down_w = ctx.in1(op, 'DownW')               # [E, w, d]
    length = ctx.in1(op, 'Length')              # optional: real rows
    valid = ctx.in1(op, 'Valid')                # optional [N]/[N, 1]
    top_k = int(op.attr('top_k'))
    w, idx = route(x, router_w, top_k,
                   bool(op.attr('norm_topk_prob', False)),
                   op.attr('score', 'softmax'), ctx.in1(op, 'SelectBias'),
                   float(op.attr('routed_scale', 1.0)),
                   float(op.attr('router_eps', 1e-20)))
    held = up_w.shape[0]
    # the experts held here: all of them, or `held` from `first` on
    first = None if held == router_w.shape[1] \
        else int(op.attr('first_expert', 0))
    out = grouped_ffn(x, w, idx, gate_w, up_w, down_w, first,
                      router_w.shape[1])
    counted = jnp.ones((x.shape[0],), bool)
    if length is not None:
        counted &= jnp.arange(x.shape[0]) < \
            length.reshape(-1)[0].astype(jnp.int32)
    if valid is not None:
        counted &= valid.reshape(-1) != 0
    local = idx if first is None else idx - first
    hit = (local[:, :, None] == jnp.arange(held)[None, None, :]) \
        & counted[:, None, None]
    ctx.out(op, 'Out', out.astype(x.dtype))
    ctx.out(op, 'TopkIdx', idx)
    load = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
    if first is not None:
        elsewhere = top_k * jnp.sum(counted, dtype=jnp.int32) - jnp.sum(load)
        load = jnp.concatenate([load, elsewhere[None]])
    ctx.out(op, 'ExpertLoad', load)
