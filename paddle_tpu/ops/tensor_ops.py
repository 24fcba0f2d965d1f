"""Tensor manipulation ops: reshape/transpose/concat/split/gather/pad/...

Reference: operators/reshape_op.cc (reshape2 carries XShape for grad — not
needed under JAX AD but emitted for program parity), transpose_op.cc,
concat_op.cc, split_op.cc, squeeze/unsqueeze/flatten/stack/unstack/expand/
pad/slice/gather/scatter/lookup_table/top_k/arg_{max,min}/argsort ops.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from .common import np_dtype


def _infer_reshape(x, shape):
    shape = list(shape)
    # fluid semantics: 0 means copy input dim; -1 inferred
    for i, s in enumerate(shape):
        if s == 0:
            shape[i] = x.shape[i]
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape[shape.index(-1)] = int(x.size) // max(known, 1)
    return tuple(shape)


@register_op('reshape')
def _reshape(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', x.reshape(_infer_reshape(x, op.attr('shape'))))


@register_op('reshape2')
def _reshape2(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', x.reshape(_infer_reshape(x, op.attr('shape'))))
    if op.output('XShape'):
        ctx.out(op, 'XShape', jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op('transpose', share_lod=False)
def _transpose(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jnp.transpose(x, op.attr('axis')))


@register_op('transpose2', share_lod=False)
def _transpose2(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jnp.transpose(x, op.attr('axis')))
    if op.output('XShape'):
        ctx.out(op, 'XShape', jnp.zeros((0,) + x.shape, dtype=x.dtype))


@register_op('concat')
def _concat(ctx, op):
    xs = ctx.in_list(op, 'X')
    ctx.out(op, 'Out', jnp.concatenate(xs, axis=op.attr('axis', 0)))


@register_op('split')
def _split(ctx, op):
    x = ctx.in1(op, 'X')
    axis = op.attr('axis', 0)
    num = op.attr('num', 0)
    sections = op.attr('sections', [])
    outs = op.output('Out')
    if sections:
        idx = np.cumsum(sections)[:-1]
        parts = jnp.split(x, idx, axis=axis)
    else:
        parts = jnp.split(x, num or len(outs), axis=axis)
    for i, p in enumerate(parts):
        ctx.out(op, 'Out', p, idx=i)


def _register_shape_ops():
    @register_op('squeeze')
    def _squeeze(ctx, op):
        x = ctx.in1(op, 'X')
        axes = op.attr('axes', [])
        if axes:
            out = x.reshape(tuple(s for i, s in enumerate(x.shape)
                                  if not (i in axes and s == 1)))
        else:
            out = jnp.squeeze(x)
        ctx.out(op, 'Out', out)

    @register_op('squeeze2')
    def _squeeze2(ctx, op):
        _squeeze(ctx, op)
        if op.output('XShape'):
            x = ctx.in1(op, 'X')
            ctx.out(op, 'XShape', jnp.zeros((0,) + x.shape, dtype=x.dtype))

    @register_op('unsqueeze')
    def _unsqueeze(ctx, op):
        x = ctx.in1(op, 'X')
        out = x
        for a in sorted(op.attr('axes')):
            out = jnp.expand_dims(out, a)
        ctx.out(op, 'Out', out)

    @register_op('unsqueeze2')
    def _unsqueeze2(ctx, op):
        _unsqueeze(ctx, op)
        if op.output('XShape'):
            x = ctx.in1(op, 'X')
            ctx.out(op, 'XShape', jnp.zeros((0,) + x.shape, dtype=x.dtype))

    @register_op('flatten')
    def _flatten(ctx, op):
        x = ctx.in1(op, 'X')
        axis = op.attr('axis', 1)
        lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
        ctx.out(op, 'Out', x.reshape(lead, -1))

    @register_op('flatten2')
    def _flatten2(ctx, op):
        _flatten(ctx, op)
        if op.output('XShape'):
            x = ctx.in1(op, 'X')
            ctx.out(op, 'XShape', jnp.zeros((0,) + x.shape, dtype=x.dtype))


_register_shape_ops()


@register_op('stack', share_lod=False)
def _stack(ctx, op):
    xs = ctx.in_list(op, 'X')
    ctx.out(op, 'Y', jnp.stack(xs, axis=op.attr('axis', 0)))


@register_op('unstack', share_lod=False)
def _unstack(ctx, op):
    x = ctx.in1(op, 'X')
    axis = op.attr('axis', 0)
    parts = jnp.split(x, x.shape[axis], axis=axis)
    for i, p in enumerate(parts):
        ctx.out(op, 'Y', jnp.squeeze(p, axis=axis), idx=i)


@register_op('expand')
def _expand(ctx, op):
    x = ctx.in1(op, 'X')
    times = op.attr('expand_times')
    ctx.out(op, 'Out', jnp.tile(x, times))


@register_op('tile')
def _tile(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jnp.tile(x, op.attr('repeat_times')))


@register_op('pad')
def _pad(ctx, op):
    x = ctx.in1(op, 'X')
    paddings = op.attr('paddings')
    pad_value = op.attr('pad_value', 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    ctx.out(op, 'Out', jnp.pad(x, cfg, constant_values=pad_value))


@register_op('pad2d')
def _pad2d(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    p = op.attr('paddings')  # [top, bottom, left, right]
    mode = op.attr('mode', 'constant')
    value = op.attr('pad_value', 0.0)
    cfg = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == 'constant':
        out = jnp.pad(x, cfg, constant_values=value)
    elif mode == 'reflect':
        out = jnp.pad(x, cfg, mode='reflect')
    else:
        out = jnp.pad(x, cfg, mode='edge')
    ctx.out(op, 'Out', out)


@register_op('pad_constant_like')
def _pad_constant_like(ctx, op):
    x = ctx.in1(op, 'X')
    y = ctx.in1(op, 'Y')
    value = op.attr('pad_value', 0.0)
    cfg = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    ctx.out(op, 'Out', jnp.pad(y, cfg, constant_values=value))


@register_op('slice')
def _slice(ctx, op):
    x = ctx.in1(op, 'Input')
    axes = op.attr('axes')
    starts = op.attr('starts')
    ends = op.attr('ends')
    idx = [slice(None)] * x.ndim
    for a, s, e in zip(axes, starts, ends):
        dim = x.shape[a]
        s = max(s + dim, 0) if s < 0 else min(s, dim)
        e = max(e + dim, 0) if e < 0 else min(e, dim)
        idx[a] = slice(s, e)
    ctx.out(op, 'Out', x[tuple(idx)])


@register_op('strided_slice', share_lod=False)
def _strided_slice(ctx, op):
    x = ctx.in1(op, 'Input')
    axes = op.attr('axes')
    starts = op.attr('starts')
    ends = op.attr('ends')
    strides = op.attr('strides')
    idx = [slice(None)] * x.ndim
    for a, s, e, st in zip(axes, starts, ends, strides):
        idx[a] = slice(s, e, st)
    ctx.out(op, 'Out', x[tuple(idx)])


@register_op('crop', share_lod=False)
def _crop(ctx, op):
    x = ctx.in1(op, 'X')
    offsets = op.attr('offsets')
    shape = op.attr('shape')
    idx = tuple(slice(o, o + s) for o, s in zip(offsets, shape))
    ctx.out(op, 'Out', x[idx])


@register_op('gather', share_lod=False)
def _gather(ctx, op):
    x = ctx.in1(op, 'X')
    index = ctx.in1(op, 'Index').reshape(-1).astype(jnp.int32)
    ctx.out(op, 'Out', jnp.take(x, index, axis=0))


@register_op('scatter', share_lod=False)
def _scatter(ctx, op):
    x = ctx.in1(op, 'X')
    ids = ctx.in1(op, 'Ids').reshape(-1).astype(jnp.int32)
    updates = ctx.in1(op, 'Updates')
    overwrite = op.attr('overwrite', True)
    if overwrite:
        out = x.at[ids].set(updates)
    else:
        out = x.at[ids].add(updates)
    ctx.out(op, 'Out', out)


@register_op('gather_nd', share_lod=False)
def _gather_nd(ctx, op):
    x = ctx.in1(op, 'X')
    index = ctx.in1(op, 'Index').astype(jnp.int32)
    ctx.out(op, 'Out', x[tuple(jnp.moveaxis(index, -1, 0))])


@register_op('lookup_table')
def _lookup_table(ctx, op):
    """Embedding gather (reference operators/lookup_table_op.cc). The
    is_sparse SelectedRows grad path is realized by the backward lowering
    (core/lowering.py): in 'scout' mode we record this site's ids; in 'apply'
    mode the table is held out of AD and a zero dummy of the gathered-rows
    shape carries the gradient instead, so no dense [vocab, dim] cotangent is
    ever built."""
    w = ctx.in1(op, 'W')
    ids = ctx.in1(op, 'Ids')
    padding_idx = op.attr('padding_idx', -1)
    flat = ids.reshape(-1).astype(jnp.int32)
    if op.attr('is_distributed', False):
        # vocab-sharded table (reference is_distributed prefetch path,
        # operators/distributed/parameter_prefetch.cc:177): pin dim 0 to the
        # 'model' mesh axis; XLA partitions the take into shard-local masked
        # gathers + psum over ICI — the split_ids/prefetch/merge_ids RPC
        # pipeline as one compiled SPMD gather (ops/dist_ops.py)
        from .dist_ops import table_sharding_constraint
        w = table_sharding_constraint(w)

    from .embedding_ops import count_dispatch
    count_dispatch(ctx, 'lookup_table')
    out = lookup_gather(ctx, op, w, flat)
    ctx.out(op, 'Out', embedding_epilogue(out, flat, ids, w, padding_idx))


def lookup_gather(ctx, op, w, flat, bias=None):
    """Shared lookup_table / fused_embedding_gather gather body: routes
    the is_sparse scout/apply mechanism (core/lowering.py sparse grads)
    around the gather."""
    from .embedding_ops import embedding_gather
    w_name = op.input('W')[0]
    sparse = w_name in getattr(ctx, 'sparse_tables', ())
    mode = getattr(ctx, 'sparse_mode', None)
    if sparse and mode == 'scout':
        ctx.sparse_sites.append((w_name, flat, w.shape[1], w.dtype))
    if sparse and mode == 'apply':
        k = ctx.sparse_counter[0]
        ctx.sparse_counter[0] += 1
        # the table is stop_gradient'd and the dummy carries its sparse
        # grad; a trainable Bias is not held out, so it adds after the
        # dummy, on plain-jnp AD
        out = embedding_gather(lax.stop_gradient(w), flat) \
            + ctx.env['@sparse%d' % k]
        if bias is not None:
            out = out + bias.reshape(1, -1)
    else:
        out = embedding_gather(w, flat, bias=bias)
    return out


def embedding_epilogue(out, flat, ids, w, padding_idx):
    """Shared lookup_table / lookup_sparse_table tail: zero the padding_idx
    rows and restore the ids' leading shape (a trailing 1 folds into the
    embedding dim, fluid convention)."""
    if padding_idx is not None and padding_idx != -1:
        pad = padding_idx if padding_idx >= 0 else padding_idx + w.shape[0]
        out = jnp.where((flat == pad)[:, None], 0.0, out)
    out_shape = ids.shape[:-1] + (w.shape[1],) if ids.shape and \
        ids.shape[-1] == 1 else ids.shape + (w.shape[1],)
    return out.reshape(out_shape)


@register_op('top_k')
def _top_k(ctx, op):
    x = ctx.in1(op, 'X')
    k = op.attr('k', 1)
    vals, idx = lax.top_k(x, k)
    ctx.out(op, 'Out', vals)
    ctx.out(op, 'Indices', idx.astype(jnp.int64))


@register_op('arg_max')
def _arg_max(ctx, op):
    x = ctx.in1(op, 'X')
    axis = op.attr('axis', -1)
    ctx.out(op, 'Out', jnp.argmax(x, axis=axis).astype(jnp.int64))


@register_op('arg_min')
def _arg_min(ctx, op):
    x = ctx.in1(op, 'X')
    axis = op.attr('axis', -1)
    ctx.out(op, 'Out', jnp.argmin(x, axis=axis).astype(jnp.int64))


@register_op('argsort', share_lod=False)
def _argsort(ctx, op):
    x = ctx.in1(op, 'X')
    axis = op.attr('axis', -1)
    idx = jnp.argsort(x, axis=axis)
    ctx.out(op, 'Indices', idx.astype(jnp.int64))
    ctx.out(op, 'Out', jnp.sort(x, axis=axis))


@register_op('reverse', share_lod=False)
def _reverse(ctx, op):
    x = ctx.in1(op, 'X')
    axes = op.attr('axis')
    if not isinstance(axes, (list, tuple)):
        axes = [axes]
    ctx.out(op, 'Out', jnp.flip(x, axis=tuple(axes)))


@register_op('multiplex', share_lod=False)
def _multiplex(ctx, op):
    ids = ctx.in1(op, 'Ids').reshape(-1).astype(jnp.int32)
    xs = jnp.stack(ctx.in_list(op, 'X'), axis=0)
    ctx.out(op, 'Out', xs[ids, jnp.arange(xs.shape[1])])


@register_op('where', share_lod=False)
def _where(ctx, op):
    cond = ctx.in1(op, 'Condition')
    x = ctx.in1(op, 'X')
    y = ctx.in1(op, 'Y')
    ctx.out(op, 'Out', jnp.where(cond, x, y))


@register_op('space_to_depth')
def _space_to_depth(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    bs = op.attr('blocksize')
    n, c, h, w = x.shape
    out = x.reshape(n, c, h // bs, bs, w // bs, bs)
    out = out.transpose(0, 3, 5, 1, 2, 4).reshape(n, c * bs * bs,
                                                  h // bs, w // bs)
    ctx.out(op, 'Out', out)


@register_op('shuffle_channel')
def _shuffle_channel(ctx, op):
    x = ctx.in1(op, 'X')
    group = op.attr('group')
    n, c, h, w = x.shape
    out = x.reshape(n, group, c // group, h, w).swapaxes(1, 2) \
           .reshape(n, c, h, w)
    ctx.out(op, 'Out', out)


@register_op('label_smooth')
def _label_smooth(ctx, op):
    x = ctx.in1(op, 'X')
    dist = ctx.in1(op, 'PriorDist')
    eps = op.attr('epsilon', 0.0)
    if dist is not None:
        out = (1.0 - eps) * x + eps * dist
    else:
        out = (1.0 - eps) * x + eps / x.shape[-1]
    ctx.out(op, 'Out', out)


def position_encoding_table(max_len, d_model):
    """The sinusoid table add_position_encoding applies, as a
    [max_len, d_model] float32 array. ALSO gathered row-wise by the
    generative decode path (models/transformer.py): a token's embedding
    must be identical whether it entered via a full prefill forward or a
    single decode step, so both paths MUST build the table through this
    one function."""
    pos = np.arange(max_len)[:, None]
    half = d_model // 2
    freq = np.power(10000.0, -np.arange(half) / float(half))
    enc = np.zeros((max_len, d_model), dtype=np.float32)
    enc[:, :half] = np.sin(pos * freq)
    enc[:, half:2 * half] = np.cos(pos * freq)
    return enc


@register_op('add_position_encoding')
def _add_position_encoding(ctx, op):
    x = ctx.in1(op, 'X')  # (N, L, D)
    alpha = op.attr('alpha', 1.0)
    beta = op.attr('beta', 1.0)
    n, l, d = x.shape
    enc = position_encoding_table(l, d)
    ctx.out(op, 'Out', alpha * x + beta * jnp.asarray(enc))


@register_op('sampling_id')
def _sampling_id(ctx, op):
    x = ctx.in1(op, 'X')  # (N, C) probs
    key = ctx.rng()
    ids = jax.random.categorical(key, jnp.log(jnp.clip(x, 1e-20, 1.0)),
                                 axis=-1)
    ctx.out(op, 'Out', ids.astype(jnp.int64))


@register_op('hash')
def _hash(ctx, op):
    x = ctx.in1(op, 'X').astype(jnp.uint32)
    num_hash = op.attr('num_hash', 1)
    mod_by = op.attr('mod_by', 100000)
    outs = []
    v = x.reshape(x.shape[0], -1)
    for i in range(num_hash):
        h = jnp.sum(v * jnp.uint32(2654435761 + i * 97), axis=-1)
        outs.append((h % jnp.uint32(mod_by)).astype(jnp.int64))
    ctx.out(op, 'Out', jnp.stack(outs, axis=-1)[:, :, None])


@register_op('diag', share_lod=False)
def _diag(ctx, op):
    d = ctx.in1(op, 'Diagonal')
    ctx.out(op, 'Out', jnp.diag(d))


@register_op('get_tensor_from_selected_rows')
def _get_tensor_from_selected_rows(ctx, op):
    """reference get_tensor_from_selected_rows_op.cc: the values tensor."""
    from ..core.selected_rows import SelectedRows
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', x.values if isinstance(x, SelectedRows) else x)


@register_op('merge_selected_rows')
def _merge_selected_rows(ctx, op):
    """reference merge_selected_rows_op.cc (MergeAdd: sum duplicate rows).
    Static-shape version: freed slots park on an out-of-range sentinel row."""
    from ..core.selected_rows import SelectedRows
    x = ctx.in1(op, 'X')
    if isinstance(x, SelectedRows):
        rows, vals = x.merged()
        x = SelectedRows(rows, vals, x.height)
    ctx.out(op, 'Out', x)
