"""The Mamba-2 mixer's recurrence and its state, a row a slot (Nemotron-H's
`M` layers: models/transformer.py, LMConfig(layer_types=...) ``'ssd'``).

Between the mixer's two projections (``[z | xBC | dt] = h W_in`` and ``out
= g W_out``, ordinary `fc`s in models/transformer.py) a Mamba-2 layer is
(state-spaces/mamba `Mamba2`, arXiv:2405.21060; ``H`` heads of ``P``
channels, ``d_inner = H P``; ``G`` groups of ``H / G`` heads; ``N`` states)

    xBC = silu(conv(xBC) + b_conv)          causal depthwise, K taps, over
                                            all d_inner + 2 G N channels
    [x | B | C] = xBC                       x [H, P], B [G, N], C [G, N]
    dt = softplus(dt + dt_bias)   [H]       A = -exp(A_log)   [H], a SCALAR
                                            a head; head h reads group
                                            h // (H / G)
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
    g = RMSNorm_groups(y * silu(z))         the gate FIRST, then a norm over
                                            each of the G groups' d_inner / G
                                            channels, one weight [d_inner]

What a token leaves behind is ``S`` after it, ``H x P x N`` numbers a layer
(2 MB in Nemotron-3-Nano: 6.4 times a Jamba2 layer's), and the
convolution's last ``K - 1`` inputs. Both live A ROW A SLOT in two pools of
their own (models/transformer.py `SSD_STATE` ``[slots + 1, ssd layers, N,
d_inner]`` and `SSD_TAIL` ``[slots + 1, ssd layers, 8, d_inner + 2 G N]``;
row 0 is the trash row), addressed through the feed 'gen_srow' as the
Mamba-1 layers' are (ops/ssm_ops.py, whose tail kernel `decode_conv` and
`_taps` serve both kinds: the convolution is the same operation at another
width).

THE STATE LIES ``[N, d_inner]``: the states on the sublanes, a head's
channels side by side on the lanes (``h P + p``), as the Mamba-1 state
does. So ``x``, the decay and ``y`` of a row are lane vectors ``[1,
d_inner]`` as the projections give and take them -- no transpose anywhere
--, ``B_t[g]`` and ``C_t[g]`` are COLUMNS ``[N, 1]`` that broadcast over
their group's lanes, and the read-out ``S C`` is a sum over sublanes
(vreg adds; with ``N`` minor it is a lane reduction a head and ``y`` comes
out a column a head that has to be pieced together).

- ``ssd_decode``: every slot's one new row. The tail kernel, then
  `decode_update`: a grid of (slots, strips of whole groups); a step moves
  a ``[N, strip]`` block of the slot's state HBM -> VMEM -> HBM IN PLACE
  (the block is named by the prefetched row ids and the layer: no gather,
  no scatter, no copy of the pool). The decay ``exp(dt A)`` is a scalar a
  head, computed outside on ``[S, H]`` and handed over as a lane vector:
  the kernel has no ``exp``. A row fed 0 reads zeros and writes the trash
  row.
- ``ssd_prefill``: one prompt suffix or chunk of ``T`` rows from position
  ``off = Positions[0]`` on, THE CHUNKED FORM (SSD), not the recurrence a
  position: in blocks of ``chunk`` rows, inside a block ``y = ((C B^T) * L)
  (dt x)`` with ``L[i, j] = exp(sum_{j < k <= i} dt_k A)`` the lower
  triangle of decays, between blocks the state carried: ``y += exp(cum) *
  (C S_in)``, ``S_out = exp(cum_last) S_in + B^T (exp(cum_last - cum) * dt
  x)``. Every sum over positions or states is a matmul on the MXU at
  `Precision.HIGHEST` (the decays compound over hundreds of positions).
  History: zeros if ``off == 0`` -- whatever the row's last tenant left is
  never read -- else the row as an earlier chunk left it. A PAD ROW'S
  ``dt`` IS SET TO 0: its decay is exp(0) = 1 and its input 0, the
  identity on the state; the state and the tail are written as of the last
  real row.

Lowerings behind `kernel_tier.dispatch`: ``pallas`` / ``interpret`` are
the kernels (device operations ``mosaic:ssd_decode_update``,
``mosaic:ssd_prefill_scan`` and the shared ``mosaic:ssm_decode_conv``);
``xla`` / ``off`` gather and scatter the rows and write the chunked form as
einsums. Everything of both ops lies under the named scope
``paddle_tpu:ssd_scan``. The prefill's kernel is no faster than its einsums
(0.42 ms a call against 0.43-0.45 on the v5e); it is there because XLA, given
the einsums, writes a layer's last state into a COPY of the pool (it
rematerialises the in-place update, and the K/V pools' with it): a prefill
program of the published model at 128 slots then holds 4.04 GB of
temporaries where it holds 0.40 with the kernel (`size_serve_pools.py`,
device-less, PERF.md PR 48), and three programs in flight no longer fit.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from . import ssm_ops

SCOPE = 'paddle_tpu:ssd_scan'
_LANES = 128
_PRECISION = lax.Precision.HIGHEST
# bytes of a slot's state a step of the decode grid holds at most
_STRIP_BYTES = 1 << 20


def shapes_ok(d_inner, n_state, groups, heads, rows=None, chunk=None):
    """The kernels' tiling rule: a group's lanes are whole vregs, the
    states whole sublane tiles; for the prefill a group's heads too (the
    block of the transposed decays), and the prompt whole chunks of whole
    lane tiles (or one chunk)."""
    ok = d_inner % (groups * _LANES) == 0 and n_state % 8 == 0
    if rows is not None:
        per = heads // groups
        ok = ok and rows % chunk == 0 \
            and (chunk % _LANES == 0 or chunk == rows) and chunk % 8 == 0 \
            and (per % 8 == 0 or groups == 1)
    return ok


def _groups_a_strip(d_inner, n_state, groups):
    """Whole groups of lanes a step of the decode grid holds: the most
    that keep its block of the state within `_STRIP_BYTES`."""
    block = d_inner // groups * n_state * 4
    return max(g for g in range(1, groups + 1)
               if groups % g == 0 and (g == 1 or g * block <= _STRIP_BYTES))


def _columns(x, per):
    """``[rows, G, N]`` -> ``[rows, G / per, N, per]``: a strip's groups'
    vectors as the columns of one ``[N, per]`` block."""
    rows, g, n = x.shape
    return x.reshape(rows, g // per, per, n).transpose(0, 1, 3, 2)


# ---------------------------------------------------------------------------
# the decode update


def _decode_update_kernel(rows_ref, layer_ref, da_ref, dx_ref, b_ref, c_ref,
                          s_ref, y_ref, o_ref):
    import jax.experimental.pallas as pl
    del layer_ref
    per = b_ref.shape[3]
    width = da_ref.shape[2] // per
    live = rows_ref[pl.program_id(0)] > 0
    for j in range(per):
        at = pl.ds(j * width, width)
        s = jnp.where(live, s_ref[0, 0, :, at], 0.0)        # [N, width]
        s = da_ref[0, :, at] * s \
            + dx_ref[0, :, at] * b_ref[0, 0, :, j:j + 1]
        y_ref[0, :, at] = jnp.sum(s * c_ref[0, 0, :, j:j + 1], axis=0,
                                  keepdims=True)
        o_ref[0, 0, :, at] = s


@functools.partial(jax.jit, static_argnames=('interpret',))
def decode_update(state, rows, layer, decay, dx, b, c, *, interpret=False):
    """One step of the recurrence for every slot, the pool updated IN
    PLACE: ``state [R, L, N, di]``, ``rows [S]`` int32 (0: no row),
    ``layer`` an int32 scalar, ``decay = exp(dt A)`` and ``dx = dt x``
    ``[S, di]`` (a head's scalar on each of its lanes), ``b`` / ``c`` ``[S,
    G, N]``. Returns (``y [S, di]`` with ``y[h] = S[h] c[g]``, the pool).
    Jitted, with `layer` an operand: the layers of a program share one
    traced kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, di = decay.shape
    n, groups = state.shape[2], b.shape[1]
    per = _groups_a_strip(di, n, groups)
    strips = groups // per
    width = di // strips
    row = pl.BlockSpec((1, 1, width), lambda i, j, *_: (i, 0, j))
    cols = pl.BlockSpec((1, 1, n, per), lambda i, j, *_: (i, j, 0, 0))
    block = pl.BlockSpec(
        (1, 1, n, width), lambda i, j, rows, layer: (rows[i], layer[0], 0, j))
    y, state = pl.pallas_call(
        _decode_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, strips),
            in_specs=[row, row, cols, cols, block],
            out_specs=[row, block]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, di), decay.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool (the last operand, the prefetched scalars counted) IS
        # the second output
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name='ssd_decode_update',
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      decay.reshape(S, 1, di), dx.reshape(S, 1, di), _columns(b, per),
      _columns(c, per), state)
    return y.reshape(S, di), state


def _over_groups(x, width):
    """``[S, G, N]`` -> ``[S, N, G * width]``: each group's column over its
    lanes."""
    return jnp.repeat(x.transpose(0, 2, 1), width, axis=2)


def _decode_update_xla(state, rows, layer, decay, dx, b, c):
    """`decode_update` as a gather, the step and a scatter."""
    width = decay.shape[1] // b.shape[1]
    s = jnp.where((rows > 0)[:, None, None], state[rows, layer], 0.0)
    s = decay[:, None, :] * s + dx[:, None, :] * _over_groups(b, width)
    return jnp.sum(s * _over_groups(c, width), axis=1), \
        state.at[rows, layer].set(s)


# ---------------------------------------------------------------------------
# the prefill scan, chunked


def _dot(a, b):
    return jnp.dot(a, b, precision=_PRECISION,
                   preferred_element_type=jnp.float32)


def _prefill_scan_kernel(dx_ref, cx_ref, ct_ref, bt_ref, c_ref, s0_ref,
                         y_ref, last_ref, s_scr):
    import jax.experimental.pallas as pl
    rows, width = dx_ref.shape
    heads = ct_ref.shape[0]
    size = width // heads

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = s0_ref[...]

    dx, cx, c = dx_ref[...], cx_ref[...], c_ref[0]
    s_in = s_scr[...]                                       # [N, width]
    cb = _dot(c, bt_ref[0])                                 # [rows, rows]
    later = lax.broadcasted_iota(jnp.int32, (rows, rows), 0) \
        >= lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    # what the block's rows read of the state before it
    y = _dot(c, s_in) * jnp.exp(cx)
    for h in range(heads):
        # L[i, j] = exp(cum_i - cum_j) at or under the diagonal: a decay,
        # never over 1 (the difference is taken before the exp)
        decays = jnp.exp(jnp.minimum(
            cx[:, h * size:h * size + 1] - ct_ref[h:h + 1, :], 0.0))
        mine = (lane >= h * size) & (lane < (h + 1) * size)
        y = y + _dot(jnp.where(later, decays, 0.0) * cb,
                     jnp.where(mine, dx, 0.0))
    y_ref[...] = y
    total = cx[rows - 1:rows, :]                            # [1, width]
    s = jnp.exp(total) * s_in + _dot(bt_ref[0], jnp.exp(total - cx) * dx)
    s_scr[...] = s
    last_ref[...] = s


def _scan_operands(dx, cum, b, c):
    """What both lowerings of the scan take: the cumulated exponents over
    their heads' lanes ``[T, di]``, and ``b`` / ``c`` a group first."""
    return _over_heads(cum, dx.shape[1]), b.transpose(1, 0, 2), \
        c.transpose(1, 0, 2)


@functools.partial(jax.jit, static_argnames=('chunk', 'interpret'))
def prefill_scan(dx, cum, b, c, s0, *, chunk, interpret=False):
    """The recurrence over one prompt's ``T`` rows from the state ``s0 [N,
    di]``, in blocks of ``chunk`` rows: ``dx = dt x`` ``[T, di]`` (a pad
    row's 0), ``cum [T, H]`` the sums of ``dt A`` from each block's first
    row on (a pad row adds 0), ``b`` / ``c`` ``[T, G, N]``. Returns (``y
    [T, di]``, the state after the last row). The grid is (groups, blocks
    of rows): a group's ``[N, di / G]`` strip of the state stays in VMEM
    between its blocks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, di = dx.shape
    n, groups, heads = s0.shape[0], b.shape[1], cum.shape[1]
    width, per = di // groups, heads // groups
    cx, _, cg = _scan_operands(dx, cum, b, c)
    rows = pl.BlockSpec((chunk, width), lambda g, j: (j, g))
    strip = pl.BlockSpec((n, width), lambda g, j: (0, g))
    return pl.pallas_call(
        _prefill_scan_kernel,
        grid=(groups, T // chunk),
        in_specs=[rows, rows,
                  pl.BlockSpec((per, chunk), lambda g, j: (g, j)),
                  pl.BlockSpec((1, n, chunk), lambda g, j: (g, 0, j)),
                  pl.BlockSpec((1, chunk, n), lambda g, j: (g, j, 0)),
                  strip],
        out_specs=[rows, strip],
        out_shape=[jax.ShapeDtypeStruct((T, di), dx.dtype),
                   jax.ShapeDtypeStruct((n, di), s0.dtype)],
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name='ssd_prefill_scan',
    )(dx, cx, cum.T, b.transpose(1, 2, 0), cg, s0)


def _prefill_scan_xla(dx, cum, b, c, s0, chunk):
    """`prefill_scan` as einsums over ``[blocks, chunk, ...]``, the state
    carried from block to block in a `lax.scan`."""
    T, di = dx.shape
    n, groups, heads = s0.shape[0], b.shape[1], cum.shape[1]
    blocks, width = T // chunk, di // groups
    ein = functools.partial(jnp.einsum, precision=_PRECISION)
    cx, bg, cg = _scan_operands(dx, cum, b, c)
    cum = cum.reshape(blocks, chunk, heads)
    diff = cum[:, :, None, :] - cum[:, None, :, :]          # [c, i, j, H]
    later = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :])
    decays = jnp.where(later[None, :, :, None],
                       jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    bg, cg = [x.reshape(groups, blocks, chunk, n) for x in (bg, cg)]
    cb = ein('gcin,gcjn->cgij', cg, bg)
    per = heads // groups
    m = jnp.repeat(cb, per, axis=1).transpose(0, 2, 3, 1) * decays
    dxh = dx.reshape(blocks, chunk, heads, -1)
    y = ein('cijh,cjhp->cihp', m, dxh).reshape(blocks, chunk, di)
    cx = cx.reshape(blocks, chunk, di)
    total = cx[:, -1:, :]                                   # [c, 1, di]
    # a block's own addition to the state: B^T (decay to the end * dt x)
    w = (jnp.exp(total - cx) * dx.reshape(blocks, chunk, di)).reshape(
        blocks, chunk, groups, width)
    add = ein('gcjn,cjgw->cngw', bg, w).reshape(blocks, n, di)

    def carry(s, xs):
        add_c, total_c = xs
        return jnp.exp(total_c) * s + add_c, s

    last, s_in = lax.scan(carry, s0, (add, total))          # [c, N, di]
    off = ein('gcin,cngw->cigw', cg,
              s_in.reshape(blocks, n, groups, width)).reshape(
                  blocks, chunk, di)
    return (y + off * jnp.exp(cx)).reshape(T, di), last


# ---------------------------------------------------------------------------
# the IR ops

_WEIGHTS = ('ConvW', 'ConvB', 'DtBias', 'ALog', 'D', 'NormW')


def _operands(ctx, op):
    p = {name: ctx.in1(op, name).astype(jnp.float32) for name in _WEIGHTS}
    return (p, ctx.in1(op, 'State'), ctx.in1(op, 'Tail'),
            ctx.in1(op, 'Rows').reshape(-1).astype(jnp.int32),
            int(op.attr('layer')), float(op.attr('epsilon')),
            int(op.attr('groups')))


def _split(uc, di, groups):
    """The convolved rows ``[rows, di + 2 G N]`` as x ``[rows, di]``, B
    and C ``[rows, G, N]``."""
    rows = uc.shape[0]
    n = (uc.shape[1] - di) // (2 * groups)
    return uc[:, :di], uc[:, di:di + groups * n].reshape(rows, groups, n), \
        uc[:, di + groups * n:].reshape(rows, groups, n)


def _over_heads(x, di):
    """``[..., H]`` -> ``[..., di]``: a head's number on each of its
    lanes."""
    return jnp.repeat(x, di // x.shape[-1], axis=-1)


def _gated_norm(y, z, w, groups, eps):
    """``RMSNorm_groups(y * silu(z))``: the gate first, then the norm over
    each group's channels, one weight ``[di]``."""
    g = y * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[0], groups, -1)
    grouped = grouped * lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(g.shape) * w


@register_op('ssd_decode', share_lod=False)
def _ssd_decode(ctx, op):
    xbc = ctx.in1(op, 'X')                      # [S, di + 2 G N]
    z = ctx.in1(op, 'Z')                        # [S, di]
    dt = ctx.in1(op, 'Dt').astype(jnp.float32)  # [S, H]
    p, state, tails, rows, layer, eps, groups = _operands(ctx, op)
    di, heads = z.shape[1], dt.shape[1]
    impl = ssm_ops.tier(
        'ssd_decode', shapes_ok(di, state.shape[2], groups, heads)
        and ssm_ops.shapes_ok(xbc.shape[1], 8))
    conv, update = ssm_ops._decode_conv_xla, _decode_update_xla
    if impl in ('pallas', 'interpret'):
        conv, update = [functools.partial(f, interpret=impl == 'interpret')
                        for f in (ssm_ops.decode_conv, decode_update)]
    with jax.named_scope(SCOPE):
        uc, tails = conv(tails, rows, layer, xbc.astype(tails.dtype),
                         p['ConvW'], p['ConvB'])
        x, b, c = _split(uc, di, groups)
        dt = jax.nn.softplus(dt + p['DtBias'])
        y, state = update(
            state, rows, layer,
            _over_heads(jnp.exp(dt * -jnp.exp(p['ALog'])), di),
            _over_heads(dt, di) * x, b, c)
        out = _gated_norm(y + _over_heads(p['D'], di) * x, z, p['NormW'],
                          groups, eps)
    ctx.out(op, 'Out', out.astype(z.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)


@register_op('ssd_prefill', share_lod=False)
def _ssd_prefill(ctx, op):
    xbc = ctx.in1(op, 'X')                      # [1, T, di + 2 G N]
    z = ctx.in1(op, 'Z')
    dt = ctx.in1(op, 'Dt')[0].astype(jnp.float32)           # [T, H]
    p, state, tails, rows, layer, eps, groups = _operands(ctx, op)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)  # [T]
    length = ctx.in1(op, 'Length').reshape(-1).astype(jnp.int32)[0]
    T, K = xbc.shape[1], p['ConvW'].shape[1]
    di, heads = z.shape[2], dt.shape[1]
    chunk = min(int(op.attr('chunk')), T)
    impl = ssm_ops.tier('ssd_prefill', shapes_ok(
        di, state.shape[2], groups, heads, T, chunk))
    row, resumes = rows[0], pos[0] > 0
    with jax.named_scope(SCOPE):
        hist = jnp.where(resumes, tails[row, layer, :K - 1], 0.0)
        ext = jnp.concatenate([hist, xbc[0].astype(tails.dtype)], axis=0)
        window = jnp.stack([ext[j:j + T] for j in range(K)], axis=1)
        uc = jax.nn.silu(ssm_ops._taps(window, p['ConvW'], p['ConvB']))
        x, b, c = _split(uc, di, groups)
        # a pad row leaves the state as it is: exp(0 x A) = 1, 0 x x = 0
        dt = jnp.where((jnp.arange(T) < length)[:, None],
                       jax.nn.softplus(dt + p['DtBias']), 0.0)
        pad = -T % chunk                        # the xla tier's odd bucket
        dx = _over_heads(dt, di) * x
        if pad:
            dt, dx = [jnp.pad(v, ((0, pad), (0, 0))) for v in (dt, dx)]
            b, c = [jnp.pad(v, ((0, pad), (0, 0), (0, 0))) for v in (b, c)]
        cum = jnp.cumsum((dt * -jnp.exp(p['ALog'])).reshape(
            -1, chunk, heads), axis=1).reshape(-1, heads)
        s0 = jnp.where(resumes, state[row, layer], 0.0)     # [N, di]
        if impl in ('pallas', 'interpret'):
            y, last = prefill_scan(dx, cum, b, c, s0, chunk=chunk,
                                   interpret=impl == 'interpret')
        else:
            y, last = _prefill_scan_xla(dx, cum, b, c, s0, chunk)
        out = _gated_norm(y[:T] + _over_heads(p['D'], di) * x, z[0],
                          p['NormW'], groups, eps)
        state = state.at[row, layer].set(last)
        # ext[length + j] is the convolution's input K - 1 - j rows before
        # the last real one's successor: its last K - 1 inputs
        tails = tails.at[row, layer, :K - 1].set(
            lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0))
    ctx.out(op, 'Out', out[None].astype(z.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)
