"""The three ops a block of today's open mixture-of-experts models adds
to the Program path (OLMoE: models/transformer.py, LMConfig(norm=
'rms_norm', position='rope', qk_norm=True, ffn='moe')):

- ``rms_norm``: ``x * rsqrt(mean(x^2) + eps) * w`` over the trailing
  dimensions from ``begin_norm_axis``, computed in float32; with
  ``zero_centred`` times ``1 + w``.
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

  The kernel is XLA's, the tiles are ours (`grouped_matmul_tiling`, PR
  50): each `ragged_dot` states `ragged_dot_tiling="tm,tk,tn"` from its
  own rows, K, N and the precision in force. Left alone XLA takes the
  largest of 512 / 256 / 128 that DIVIDES each dimension (Nemotron's 2688
  x 1856: 128 x 128, 64 KB a grid step). The rule leaves XLA its choice
  where that moves 1 MB a step over no more rows than the MXU hides;
  else tm = the largest divisor of the rows whose MXU time stays under
  the matrix's bytes' time (80 rows at `highest`, at most 256), and of
  the (tk | K or K whole, tn in 128s) blocks inside 10 MB of VMEM the one
  with the least padding of N, then the fewest steps. The backward's
  grouped matmuls state nothing. Counted at lowering:
  `moe_grouped_matmul_tiling_total{tiling="tm,tk,tn"|"xla"}`.

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
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.xla_metadata import set_xla_metadata

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
        scale = scale.astype(jnp.float32).reshape(x.shape[bna:])
        y = y * (1.0 + scale if op.attr('zero_centred', False) else scale)
    ctx.out(op, 'Out', y.astype(x.dtype))


def yarn_inv_freq(dh, theta, factor, original_max_position, beta_fast,
                  beta_slow):
    """YaRN's ``dh / 2`` frequencies, as HF `_compute_yarn_parameters` has
    them (numpy float64: the table is a constant of the program). Pair
    ``i`` turns ``original_max_position theta^(-2i/dh) / 2 pi`` times over
    the original context: a pair that turns more than `beta_fast` times
    keeps ``theta^(-2i/dh)`` (i <= low), one that turns fewer than
    `beta_slow` times has it divided by `factor` (i >= high), and between
    the two the share of the divided one rises linearly."""
    i = np.arange(dh // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dh)

    def pair_that_turns(times):
        return dh * math.log(original_max_position / (times * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), dh - 1)
    ramp = np.clip((i - low) / ((high if high != low else high + 0.001)
                                - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def rotate(x, positions, theta, interleave=False, yarn=None,
           rotary_dim=None):
    """`x [..., H, dh]` rotated by `positions` (one per leading row): the
    pair of dims (i, i + dh/2), or with `interleave` (2i, 2i + 1), by the
    angle `position * theta^(-2i/dh)`. `yarn` (factor, original_max_position,
    beta_fast, beta_slow, attention_factor): by `yarn_inv_freq`'s
    frequencies, cos and sin times the attention factor. `rotary_dim`: the
    first that many numbers of a head are rotated as a head of that size,
    the others pass through."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [rotate(x[..., :rotary_dim], positions, theta, interleave, yarn),
             x[..., rotary_dim:]], axis=-1)
    dh = x.shape[-1]
    half = dh // 2
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(dh, theta, *yarn[:4]),
                               jnp.float32)
    angle = positions.reshape(x.shape[:-2]).astype(jnp.float32)[..., None] \
        * inv_freq                                          # [..., dh/2]
    cos = jnp.cos(angle)[..., None, :]
    sin = jnp.sin(angle)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn[4], sin * yarn[4]
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
    yarn = None
    if op.attr('yarn_factor', None) is not None:
        yarn = tuple(op.attr('yarn_' + key) for key in (
            'factor', 'original_max_position', 'beta_fast', 'beta_slow',
            'attention_factor'))
    ctx.out(op, 'Out', rotate(x, pos, float(op.attr('theta', 10000.0)),
                              bool(op.attr('interleave', False)), yarn,
                              op.attr('rotary_dim', None)))


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


# The tiles of XLA:TPU's grouped matmul (`lax.ragged_dot`'s Mosaic kernel):
# a grid step multiplies a [tm, tk] block of rows by a [tk, tn] block of ONE
# group's matrix; a (row tile, group) pair streams the group's whole [K, N]
# matrix in K/tk x N/tn steps and multiplies ALL tm rows by it. Left alone
# XLA takes each of tm, tk, tn from `_XLA_TILES`, the largest that DIVIDES
# its dimension; `frontend_attributes={ragged_dot_tiling="tm,tk,tn"}` on the
# op states another and XLA takes it (tm | rows; tk | K in multiples of 128,
# or K whole; tn a multiple of 128, a partial last tile is fine). The
# constants are the v5e's, from the sweep `tools/kernbench.py --cases
# grouped_matmul --size bench` (PERF.md, PR 50; ms a call, float32 weights):
_XLA_TILES = (512, 256, 128)
# a weight block from which a grid step's fixed cost no longer shows: XLA's
# own 512 x 512 (1 MB) streams at 687-695 GB/s (OLMoE, K-EXAONE: no stated
# tiling beat it by over 2.6 %), its 512 x 256 at 547-567 (LFM2 up / down,
# JoyAI up / down: 0.86 / 0.82 / 0.67 / 0.56 ms -> 0.66 / 0.68 / 0.54 / 0.46
# at 1.5-3 MB), its 128 x 128 (Nemotron: 2688 = 21 x 128, 1856 = 14.5 x
# 128; 64 KB, 5 040 steps a matmul) at 125 (2.57 / 2.57 ms -> 0.56 / 0.50)
_MIN_WEIGHT_BLOCK = 1 << 20
# the double-buffered row and weight blocks, the output block and its
# accumulator, of the 16 MB a kernel has (12.3 MB compiled, ~12.5 did not)
_TILE_VMEM_BYTES = 10 << 20
# the chip's peaks (benchmark/peaks.json's): a pair's MXU time, `passes`
# passes a float32 product, stays under the time its matrix's bytes take --
# 80 rows at `highest`, 481 at the default. Nemotron's up matmul at
# `highest`, tm 256 / 128 / 64 / 32 over the same tk, tn: 1.53 / 0.76 /
# 0.57 / 0.57 ms
_MXU_FLOPS = 197e12
_HBM_BYTES_PER_S = 819e9
# and never more rows a tile than leave the weight block its VMEM: JoyAI's
# widest prefill (6 144 rows, one pass), tm 512 (XLA's) / 384 / 256: 1.07 /
# 0.75 / 0.71 ms; OLMoE's b128 (1 024 rows) 512 / 256: 1.02 / 0.78
_MAX_ROW_TILE = 256
_PASSES = {'highest': 6, 'float32': 6, 'high': 3, 'bfloat16_3x': 3}


def matmul_passes():
    """MXU passes a float32 product takes at the precision in force
    (`jax.default_matmul_precision`, which `Program.matmul_precision`
    sets round a program's lowering)."""
    return _PASSES.get(jax.config.jax_default_matmul_precision, 1)


def grouped_matmul_tiling(rows, k, n, passes):
    """(tm, tk, tn) for a `lax.ragged_dot` of `rows` rows GIVEN against
    groups of `[k, n]` float32 matrices at `passes` MXU passes a product,
    or None: nothing stated, XLA's own choice -- where that already moves
    `_MIN_WEIGHT_BLOCK` a step over no more rows than the MXU hides, and
    where the shapes leave no whole tile to state."""
    if rows % 8 or k % 8 or n % 8 or min(k, n) < 128:
        return None
    most = 4 * _MXU_FLOPS / (2 * passes * _HBM_BYTES_PER_S)
    own = [next((t for t in _XLA_TILES if d % t == 0), d)
           for d in (rows, k, n)]
    if own[0] <= most and 4 * own[1] * own[2] >= _MIN_WEIGHT_BLOCK:
        return None
    tm = max(t for t in range(8, min(rows, _MAX_ROW_TILE) + 1, 8)
             if rows % t == 0 and (t <= most or t == 8))
    best = None
    for tk in [k] + [t for t in range(128, k, 128) if k % t == 0]:
        for tn in range(128, n + 128, 128):
            if 4 * (2 * (tm * tk + tk * tn) + 2 * tm * tn) \
                    > _TILE_VMEM_BYTES:
                continue
            # the least of N's padding (a partial tile is multiplied
            # whole), then the fewest grid steps a pair
            tiles = -(-n // tn)
            key = (tiles * tn, (k // tk) * tiles)
            if best is None or key < best[0]:
                best = (key, (tm, tk, tn))
    return best and best[1]


def tiling_label(tiling):
    """'tm,tk,tn' as XLA reads and prints it; 'xla' for None."""
    return '%d,%d,%d' % tiling if tiling else 'xla'


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tiled_ragged_dot(xs, mat, sizes, tiling):
    with set_xla_metadata(ragged_dot_tiling=tiling_label(tiling)):
        return lax.ragged_dot(xs, mat, sizes)


def _tiled_fwd(xs, mat, sizes, tiling):
    return _tiled_ragged_dot(xs, mat, sizes, tiling), (xs, mat, sizes)


def _tiled_bwd(tiling, saved, g):
    # the backward's two grouped matmuls have other shapes: they state
    # nothing (an equation differentiated under the attribute inherits it)
    xs, mat, sizes = saved
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), xs, mat)
    return vjp(g) + (None,)


_tiled_ragged_dot.defvjp(_tiled_fwd, _tiled_bwd)


def grouped_matmul(xs, mat, sizes):
    """`lax.ragged_dot(xs [rows, K], mat [E, K, N], sizes)` with the tiling
    `grouped_matmul_tiling` states for these shapes, if it states one."""
    from .. import monitor
    tiling = None
    if xs.dtype == mat.dtype == jnp.float32:    # the rule's bytes, the sweep's
        tiling = grouped_matmul_tiling(xs.shape[0], mat.shape[1],
                                       mat.shape[2], matmul_passes())
    monitor.inc('moe_grouped_matmul_tiling_total',
                labels={'tiling': tiling_label(tiling)})
    if tiling is None:
        return lax.ragged_dot(xs, mat, sizes)
    return _tiled_ragged_dot(xs, mat, sizes, tiling)


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
        up = grouped_matmul(xs, up_w, sizes)
        h = jnp.square(jax.nn.relu(up)) if gate_w is None \
            else jax.nn.silu(grouped_matmul(xs, gate_w, sizes)) * up
        return grouped_matmul(h, down_w, sizes)

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
