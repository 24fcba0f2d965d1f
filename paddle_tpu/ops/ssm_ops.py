"""The state-space mixer's recurrence and its state, a row a slot
(Jamba's Mamba-1 layers: models/transformer.py, LMConfig(layer_types=...)
``'ssm'``).

Between the mixer's two projections (``[u | z] = h W_in`` and ``out = g
W_out``, ordinary `fc`s in models/transformer.py) a state-space layer is

    u   = silu(conv(u) + b_conv)            causal depthwise, K taps
    [dt | B | C] = u W_x                    R + N + N numbers a row
    dt, B, C = RMSNorm(dt), RMSNorm(B), RMSNorm(C)   (Jamba's addition)
    delta = softplus(dt W_dt + b_dt)        [rows, d_inner]
    s_t = exp(delta_t x A) * s_{t-1} + (delta_t * u_t) x B_t     A = -exp(A_log)
    y_t = s_t . C_t + D * u_t
    g   = y * silu(z)

with ``s`` a ``[N, d_inner]`` matrix a layer (``d_inner`` minor: whole
vregs of lanes). What a token leaves behind is neither a key nor a value
but ``s`` after it and the convolution's last ``K - 1`` inputs: the
layer's STATE and TAIL. Both are a fixed size whatever the context, and
``s`` is 26 x 328 KB a slot in Jamba2-3B where a block's K/V is 64 KB: they
cannot ride in the block pool an entry a block, as LFM2's tails do
(ops/short_conv_ops.py). They live in two pools of their own, A ROW A SLOT
(models/transformer.py `SSM_STATE` ``[slots + 1, ssm layers, N, d_inner]``
and `SSM_TAIL` ``[slots + 1, ssm layers, 8, d_inner]``, the ``K - 1`` rows
a layer keeps in a sublane tile of `TAIL_ROWS` = 8 of its own -- as ``[..,
K - 1, d_inner]`` the TPU pads 3 rows to a tile of 4 and XLA, short of
memory, re-laid the WHOLE pool out around every layer's scatter, 0.75 ms a
copy, 40 ms a step; a layer's rows side by side, ``[.., layers x (K - 1),
d_inner]``, cost a copy a layer all the same (PERF.md, PR 43) --; row 0 is
the trash row), addressed through the feed 'gen_srow' (serving/generate.py
gives slot ``i`` row ``i + 1`` while it is resident and 0 otherwise).

- ``ssm_decode``: every slot's one new row. Reads its state row and tail,
  takes one step of the recurrence, writes both back. A row fed 0 (an idle
  slot, one a chunked prefill holds, one the step leaves out) reads zeros
  and writes the trash row: no other row is touched.
- ``ssm_prefill``: one prompt suffix or chunk of ``T`` rows from position
  ``off = Positions[0]`` on. History: zeros if ``off == 0`` -- whatever
  the row's last tenant left is never read -- else the row as an earlier
  chunk left it. The scan runs over the ``Length`` real rows ONLY: a pad
  row's ``delta`` is set to 0, so it multiplies the state by exp(0) and
  adds 0. Writes the state and the tail as of the last real row.

The recurrence is sequential in ``t`` and cheap in bytes: ``T x N x
d_inner`` multiply-adds and as many ``exp`` a layer, on the VPU and the
EUP. Three lowerings behind `kernel_tier.dispatch`: ``pallas`` /
``interpret`` are the three kernels below -- `ssm_decode_update` moves each
live row's state HBM -> VMEM -> HBM in place (its block is named by the
prefetched row ids: no gather, no scatter, no copy of the pool),
`ssm_decode_conv` does the same for its tail and the convolution, and
`ssm_prefill_scan` keeps a ``[N, 512]`` strip of the state in registers
and walks the rows, ``T`` steps a strip; ``xla`` / ``off`` gather and
scatter the rows and scan a prompt in chunks of `_XLA_CHUNK` rows, an
associative scan inside each (a step a row would be ``T`` dependent
fusions a layer). Everything of both ops lies under the named scope
``paddle_tpu:ssm_scan``; the kernels are the device operations
``mosaic:ssm_decode_update``, ``mosaic:ssm_decode_conv`` and
``mosaic:ssm_prefill_scan``.

The two small inner projections (W_x, W_dt: 4 % of the mixer's
multiply-adds) run at `lax.Precision.HIGHEST`: ``delta`` sits inside an
``exp`` that the recurrence compounds over hundreds of positions, and one
bfloat16 pass there costs more accuracy than the whole rest of the layer.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op

SCOPE = 'paddle_tpu:ssm_scan'
_LANES = 128
# rows of the tail pool a layer keeps for a slot: its K - 1 rows in a
# sublane tile of their own
TAIL_ROWS = 8
# rows of a prompt the xla tier scans at once (an associative scan inside
# the chunk, a `lax.scan` over the chunks)
_XLA_CHUNK = 16
_PRECISION = lax.Precision.HIGHEST


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _taps(window, w, b):
    """sum_j w[:, j] * window[..., j, :] + b: a product and a sum a tap, on
    the VPU in float32."""
    return sum(window[..., j, :] * w[:, j] for j in range(w.shape[1])) + b


def _inner(u, p, eps):
    """(delta [rows, d_inner], B [rows, N], C [rows, N]) of the convolved
    rows ``u``: the projection, Jamba's three norms, the step size."""
    n = p['ALog'].shape[0]
    r = p['DtProj'].shape[0]
    x = jnp.dot(u, p['XProj'], precision=_PRECISION,
                preferred_element_type=jnp.float32)
    dt = _rms(x[:, :r], p['DtNorm'], eps)
    b = _rms(x[:, r:r + n], p['BNorm'], eps)
    c = _rms(x[:, r + n:], p['CNorm'], eps)
    delta = jax.nn.softplus(
        jnp.dot(dt, p['DtProj'], precision=_PRECISION,
                preferred_element_type=jnp.float32) + p['DtBias'])
    return delta, b, c


def shapes_ok(d_inner, n_state, rows=8):
    """The kernels' tiling rule: the state's lanes are whole vregs, its
    ``N`` rows whole sublane tiles, a prompt's rows too."""
    return d_inner % _LANES == 0 and n_state % 8 == 0 and rows % 8 == 0


def _step_kernel_call(kernel, name, rows, layer, operands, pool, out_row,
                      interpret):
    """A kernel a slot (grid ``[S]``) that reads, changes and writes back
    the slot's block of `pool` ``[R, L, ...]`` IN PLACE: the block is named
    by the prefetched row ids and the layer. `operands`: (array, its
    BlockSpec) pairs ahead of the pool; `out_row`: the ShapeDtypeStruct of
    the ``[S, 1, d_inner]`` output beside it. Returns (that output, the
    pool)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    block = pl.BlockSpec((1, 1) + pool.shape[2:],
                         lambda i, rows, layer: (rows[i], layer[0], 0, 0))
    row = pl.BlockSpec((1, 1, out_row.shape[2]), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(rows.shape[0],),
            in_specs=[spec for _x, spec in operands] + [block],
            out_specs=[row, block]),
        out_shape=[out_row, jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool (the last operand, the prefetched scalars counted) IS
        # the second output
        input_output_aliases={2 + len(operands): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name,
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *[x for x, _spec in operands], pool)


def _strip(d_inner):
    """Lanes of the state the prefill kernel keeps in registers at once."""
    return next(w for w in (512, 256, 128) if d_inner % w == 0)


def _lanes(x, width):
    """``[N, 128]`` (every lane the same) -> ``[N, width]``."""
    return x if width == _LANES \
        else jnp.concatenate([x] * (width // _LANES), axis=1)


def _lane_broadcast(x):
    """``[rows, N]`` -> ``[rows, N, 128]``: a kernel takes B_t and C_t as
    ``[N, 128]`` tiles, N on the sublanes as the state has it."""
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


# ---------------------------------------------------------------------------
# the decode update


def _decode_update_kernel(rows_ref, layer_ref, dt_ref, du_ref, b_ref, c_ref,
                          a_ref, s_ref, y_ref, o_ref):
    import jax.experimental.pallas as pl
    del layer_ref
    width = a_ref.shape[1]
    live = rows_ref[pl.program_id(0)] > 0
    s = jnp.where(live, s_ref[0, 0], 0.0)                   # [N, di]
    s = jnp.exp(dt_ref[0] * a_ref[...]) * s \
        + du_ref[0] * _lanes(b_ref[0], width)
    y_ref[0] = jnp.sum(s * _lanes(c_ref[0], width), axis=0, keepdims=True)
    o_ref[0, 0] = s


@functools.partial(jax.jit, static_argnames=('interpret',))
def decode_update(state, rows, layer, delta, du, b, c, a, *,
                  interpret=False):
    """One step of the recurrence for every slot, the pool updated IN
    PLACE: ``state [R, L, N, di]``, ``rows [S]`` int32 (0: no row),
    ``layer`` an int32 scalar, ``delta`` / ``du = delta * u`` ``[S, di]``,
    ``b`` / ``c`` ``[S, N]``, ``a = -exp(A_log)`` ``[N, di]``. Returns
    (``y [S, di]`` with ``y_s = s_s . c_s``, the pool). Jitted, with
    `layer` an operand: the layers of a program share one traced kernel
    (ops/paged_decode_attention.py says what that saves)."""
    import jax.experimental.pallas as pl
    S, di = delta.shape
    n = a.shape[0]
    row = pl.BlockSpec((1, 1, di), lambda i, *_: (i, 0, 0))
    tile = pl.BlockSpec((1, n, _LANES), lambda i, *_: (i, 0, 0))
    whole = pl.BlockSpec((n, di), lambda i, *_: (0, 0))
    y, state = _step_kernel_call(
        _decode_update_kernel, 'ssm_decode_update', rows, layer,
        [(delta.reshape(S, 1, di), row), (du.reshape(S, 1, di), row),
         (_lane_broadcast(b), tile), (_lane_broadcast(c), tile), (a, whole)],
        state, jax.ShapeDtypeStruct((S, 1, di), delta.dtype), interpret)
    return y.reshape(S, di), state


def _decode_conv_kernel(rows_ref, layer_ref, u_ref, w_ref, b_ref, t_ref,
                        c_ref, o_ref):
    import jax.experimental.pallas as pl
    del layer_ref
    taps = w_ref.shape[0]
    live = rows_ref[pl.program_id(0)] > 0
    tail = jnp.where(live, t_ref[0, 0], 0.0)                # [8, di]
    window = [tail[j:j + 1] for j in range(taps - 1)] + [u_ref[0]]
    c = b_ref[...]
    for j in range(taps):
        c = c + window[j] * w_ref[j:j + 1, :]
    c_ref[0] = c * jax.nn.sigmoid(c)                        # silu
    o_ref[0, 0] = tail
    for j in range(taps - 1):
        o_ref[0, 0, j:j + 1, :] = window[j + 1]


@functools.partial(jax.jit, static_argnames=('interpret',))
def decode_conv(tails, rows, layer, u, w, bias, *, interpret=False):
    """The convolution's step for every slot, the tails' pool updated IN
    PLACE: ``tails [R, L, 8, di]`` (rows ``0 .. K - 2`` of a block the
    layer's tail, oldest first), ``u [S, di]``, ``w [di, K]``, ``bias
    [di]``. Returns (``silu(conv(u) + bias) [S, di]``, the pool)."""
    import jax.experimental.pallas as pl
    S, di = u.shape
    taps = w.shape[1]
    row = pl.BlockSpec((1, 1, di), lambda i, *_: (i, 0, 0))
    c, tails = _step_kernel_call(
        _decode_conv_kernel, 'ssm_decode_conv', rows, layer,
        [(u.reshape(S, 1, di), row),
         (w.T, pl.BlockSpec((taps, di), lambda i, *_: (0, 0))),
         (bias.reshape(1, di), pl.BlockSpec((1, di), lambda i, *_: (0, 0)))],
        tails, jax.ShapeDtypeStruct((S, 1, di), u.dtype), interpret)
    return c.reshape(S, di), tails


def _decode_conv_xla(tails, rows, layer, u, w, bias):
    """`decode_conv` as a gather, the taps and a scatter."""
    k1 = w.shape[1] - 1
    tail = jnp.where((rows > 0)[:, None, None], tails[rows, layer, :k1], 0.0)
    window = jnp.concatenate([tail, u[:, None, :]], axis=1)     # [S, K, di]
    return jax.nn.silu(_taps(window, w, bias)), \
        tails.at[rows, layer, :k1].set(window[:, 1:, :])


def _decode_update_xla(state, rows, layer, delta, du, b, c, a):
    """`decode_update` as a gather, the step and a scatter."""
    s = jnp.where((rows > 0)[:, None, None], state[rows, layer], 0.0)
    s = jnp.exp(delta[:, None, :] * a[None]) * s \
        + du[:, None, :] * b[:, :, None]
    return jnp.sum(s * c[:, :, None], axis=1), state.at[rows, layer].set(s)


# ---------------------------------------------------------------------------
# the prefill scan


def _prefill_scan_kernel(dt_ref, du_ref, b_ref, c_ref, a_ref, s0_ref,
                         y_ref, last_ref, s_scr):
    import jax.experimental.pallas as pl
    width = a_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = s0_ref[...]

    a = a_ref[...]

    def row(t, s):
        at = pl.ds(t, 1)
        s = jnp.exp(dt_ref[at, :] * a) * s \
            + du_ref[at, :] * _lanes(b_ref[t], width)
        y_ref[at, :] = jnp.sum(s * _lanes(c_ref[t], width), axis=0,
                               keepdims=True)
        return s

    s = lax.fori_loop(0, dt_ref.shape[0], row, s_scr[...])
    s_scr[...] = s
    last_ref[...] = s


@functools.partial(jax.jit, static_argnames=('interpret',))
def prefill_scan(delta, du, b, c, a, s0, *, interpret=False):
    """The recurrence over one prompt's ``T`` rows from the state ``s0 [N,
    di]``: ``delta`` / ``du`` ``[T, di]`` (a pad row's both 0), ``b`` /
    ``c`` ``[T, N]``, ``a [N, di]``. Returns (``y [T, di]``, the state
    after the last row). The grid is (strips of the state's lanes, chunks
    of rows): a strip's ``[N, width]`` stays in registers through a
    chunk's rows and in VMEM between chunks."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, di = delta.shape
    n = a.shape[0]
    width = _strip(di)
    chunk = next(r for r in (128, 64, 32, 16, 8) if T % r == 0)
    rows = pl.BlockSpec((chunk, width), lambda i, j: (j, i))
    tiles = pl.BlockSpec((chunk, n, _LANES), lambda i, j: (j, 0, 0))
    strip = pl.BlockSpec((n, width), lambda i, j: (0, i))
    return pl.pallas_call(
        _prefill_scan_kernel,
        grid=(di // width, T // chunk),
        in_specs=[rows, rows, tiles, tiles, strip, strip],
        out_specs=[rows, strip],
        out_shape=[jax.ShapeDtypeStruct((T, di), delta.dtype),
                   jax.ShapeDtypeStruct((n, di), s0.dtype)],
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name='ssm_prefill_scan',
    )(delta, du, _lane_broadcast(b), _lane_broadcast(c), a, s0)


def _prefill_scan_xla(delta, du, b, c, a, s0):
    """`prefill_scan` as a `lax.scan` over chunks of `_XLA_CHUNK` rows, an
    associative scan of (decay, input) pairs inside each: ``(a2, b2) o
    (a1, b1) = (a1 a2, a2 b1 + b2)``. No ``exp(-sum)`` anywhere: a chunk's
    pairs are products of decays <= 1."""
    T, di = delta.shape
    n = a.shape[0]
    L = _XLA_CHUNK
    pad = -T % L
    if pad:
        delta, du = [jnp.pad(x, ((0, pad), (0, 0))) for x in (delta, du)]
        b, c = [jnp.pad(x, ((0, pad), (0, 0))) for x in (b, c)]

    def chunked(x):
        return x.reshape((T + pad) // L, L, x.shape[1])

    def step(s, xs):
        d, x, bb, cc = xs
        decay = jnp.exp(d[:, None, :] * a[None])            # [L, N, di]
        add = x[:, None, :] * bb[:, :, None]
        decay, add = lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (decay, add))
        states = decay * s[None] + add
        return states[-1], jnp.sum(states * cc[:, :, None], axis=1)

    last, y = lax.scan(step, s0, tuple(chunked(x)
                                       for x in (delta, du, b, c)))
    return y.reshape(T + pad, di)[:T], last


# ---------------------------------------------------------------------------
# the IR ops

_WEIGHTS = ('ConvW', 'ConvB', 'XProj', 'DtNorm', 'BNorm', 'CNorm', 'DtProj',
            'DtBias', 'ALog', 'D')


def _operands(ctx, op):
    p = {name: ctx.in1(op, name).astype(jnp.float32) for name in _WEIGHTS}
    return (p, ctx.in1(op, 'State'), ctx.in1(op, 'Tail'),
            ctx.in1(op, 'Rows').reshape(-1).astype(jnp.int32),
            int(op.attr('layer')), float(op.attr('epsilon')))


def tier(name, tiles):
    """The lowering of the op `name`: the kernels where its shapes tile
    (`tiles`) and no mesh is active."""
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    return kernel_tier.dispatch(name, pallas_ok=tiles and not meshed,
                                mesh=mesh)


# ---------------------------------------------------------------------------
# a snapshot of a slot's row (serving/kv_blocks.py `SlotRows`)

def _copy_row_kernel(ids_ref, pool_ref, out_ref, sem):
    from jax.experimental.pallas import tpu as pltpu
    del pool_ref                                # the pool IS the output
    copy = pltpu.make_async_copy(out_ref.at[ids_ref[0]],
                                 out_ref.at[ids_ref[1]], sem)
    copy.start()
    copy.wait()


@functools.partial(jax.jit, static_argnames=('interpret',))
def copy_row(pool, src, dst, *, interpret=False):
    """Row ``src`` of ``pool [R, ...]`` copied over row ``dst`` IN PLACE,
    every layer of it: one DMA HBM -> HBM, no gather, no scatter, no copy
    of the pool (the device operation ``mosaic:state_snapshot_copy``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ids = jnp.stack([jnp.asarray(src, jnp.int32).reshape(()),
                     jnp.asarray(dst, jnp.int32).reshape(())])
    return pl.pallas_call(
        _copy_row_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={1: 0},
        interpret=interpret, name='state_snapshot_copy')(ids, pool)


def snapshot_copy(pool, src, dst, scope):
    """A 'row' pool with row ``src[0]`` copied over row ``dst[0]``, under
    the named scope `scope` (``paddle_tpu:state_snapshot``): the kernel
    `copy_row` where kernels run, else a gather and a scatter."""
    impl = tier('state_snapshot_copy', True)
    with jax.named_scope(scope):
        if impl in ('pallas', 'interpret'):
            return copy_row(pool, src[0], dst[0],
                            interpret=impl == 'interpret')
        return pool.at[dst].set(pool[src])


def _impl(name, d_inner, n_state, rows=8):
    return tier(name, shapes_ok(d_inner, n_state, rows))


@register_op('ssm_decode', share_lod=False)
def _ssm_decode(ctx, op):
    u = ctx.in1(op, 'X')                        # [S, di]
    z = ctx.in1(op, 'Z')                        # [S, di]
    p, state, tails, rows, layer, eps = _operands(ctx, op)
    impl = _impl('ssm_decode', u.shape[1], p['ALog'].shape[0])
    conv, update = _decode_conv_xla, _decode_update_xla
    if impl in ('pallas', 'interpret'):
        conv, update = [functools.partial(f, interpret=impl == 'interpret')
                        for f in (decode_conv, decode_update)]
    with jax.named_scope(SCOPE):
        uc, tails = conv(tails, rows, layer, u.astype(tails.dtype),
                         p['ConvW'], p['ConvB'])
        delta, b, c = _inner(uc, p, eps)
        y, state = update(state, rows, layer, delta, delta * uc, b, c,
                          -jnp.exp(p['ALog']))
        out = (y + p['D'] * uc) * jax.nn.silu(z.astype(jnp.float32))
    ctx.out(op, 'Out', out.astype(u.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)


@register_op('ssm_prefill', share_lod=False)
def _ssm_prefill(ctx, op):
    u = ctx.in1(op, 'X')                        # [1, T, di]
    z = ctx.in1(op, 'Z')
    p, state, tails, rows, layer, eps = _operands(ctx, op)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)  # [T]
    length = ctx.in1(op, 'Length').reshape(-1).astype(jnp.int32)[0]
    T, K = u.shape[1], p['ConvW'].shape[1]
    impl = _impl('ssm_prefill', u.shape[2], p['ALog'].shape[0], T)
    row, resumes = rows[0], pos[0] > 0
    with jax.named_scope(SCOPE):
        hist = jnp.where(resumes, tails[row, layer, :K - 1], 0.0)
        ext = jnp.concatenate([hist, u[0].astype(tails.dtype)], axis=0)
        window = jnp.stack([ext[j:j + T] for j in range(K)], axis=1)
        uc = jax.nn.silu(_taps(window, p['ConvW'], p['ConvB']))   # [T, di]
        delta, b, c = _inner(uc, p, eps)
        # a pad row leaves the state as it is: exp(0 x A) = 1, 0 x u = 0
        delta = jnp.where((jnp.arange(T) < length)[:, None], delta, 0.0)
        a = -jnp.exp(p['ALog'])
        s0 = jnp.where(resumes, state[row, layer], 0.0)     # [N, di]
        if impl in ('pallas', 'interpret'):
            y, last = prefill_scan(delta, delta * uc, b, c, a, s0,
                                   interpret=impl == 'interpret')
        else:
            y, last = _prefill_scan_xla(delta, delta * uc, b, c, a, s0)
        out = (y + p['D'] * uc) * jax.nn.silu(z[0].astype(jnp.float32))
        state = state.at[row, layer].set(last)
        # ext[length + j] is the convolution's input K - 1 - j rows before
        # the last real one's successor: its last K - 1 inputs
        tails = tails.at[row, layer, :K - 1].set(
            lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0))
    ctx.out(op, 'Out', out[None].astype(u.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)
