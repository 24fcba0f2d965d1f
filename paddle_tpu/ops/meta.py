"""Meta / framework ops: backward, feed/fetch boundary, constants, casts.

Reference counterparts: controlflow/feed_op.cc, fetch_op.cc (subsumed by the
compiled function's inputs/outputs), fill_constant_op.cc, assign_op.cc,
cast_op.cc, scale_op.cc, increment_op.cc, clip_op.cc, clip_by_norm_op.cc,
fill_zeros_like_op.cc, shape_op.cc, print_op.cc.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax  # noqa: F401

from ..core.registry import register_op
from .common import np_dtype


@register_op('backward')
def _backward(ctx, op):
    # Never lowered directly: core/lowering.py:lower_block intercepts it and
    # runs the forward segment under jax.vjp. Reaching here is a bug.
    raise RuntimeError("'backward' op must be handled by lower_block")


@register_op('feed')
def _feed(ctx, op):
    # feed values are function inputs; nothing to do (kept for program parity)
    pass


@register_op('fetch')
def _fetch(ctx, op):
    ctx.out(op, 'Out', ctx.in1(op, 'X'))


@register_op('fill_constant')
def _fill_constant(ctx, op):
    dtype = np_dtype(op.attr('dtype'))
    shape = tuple(op.attr('shape', ()))
    value = op.attr('value', 0.0)
    ctx.out(op, 'Out', jnp.full(shape, value, dtype=dtype))
    # the value is a trace-time constant; record it so shape-bearing
    # consumers (TensorArray write indices etc.) can stay static. Only
    # small constants — the consumers need scalars, not zeroed buffers.
    if int(np.prod(shape or (1,))) <= 16:
        ctx.set_static(op.output('Out')[0],
                       np.full(shape, value, dtype=dtype))


@register_op('fill_constant_batch_size_like')
def _fill_constant_bsl(ctx, op):
    x = ctx.in1(op, 'Input')
    dtype = np_dtype(op.attr('dtype'))
    shape = list(op.attr('shape'))
    in_idx = op.attr('input_dim_idx', 0)
    out_idx = op.attr('output_dim_idx', 0)
    shape[out_idx] = x.shape[in_idx]
    ctx.out(op, 'Out', jnp.full(tuple(shape), op.attr('value', 0.0),
                                dtype=dtype))


@register_op('fill_zeros_like')
def _fill_zeros_like(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jnp.zeros_like(x))


@register_op('fill')
def _fill(ctx, op):
    dtype = np_dtype(op.attr('dtype'))
    shape = tuple(op.attr('shape'))
    value = np.asarray(op.attr('value'), dtype=dtype).reshape(shape)
    ctx.out(op, 'Out', jnp.asarray(value))


@register_op('assign')
def _assign(ctx, op):
    ctx.out(op, 'Out', ctx.in1(op, 'X'))


@register_op('assign_value')
def _assign_value(ctx, op):
    dtype = np_dtype(op.attr('dtype'))
    shape = tuple(op.attr('shape'))
    values = op.attr('values')
    ctx.out(op, 'Out', jnp.asarray(np.asarray(values, dtype=dtype)
                                   .reshape(shape)))


@register_op('shape')
def _shape(ctx, op):
    x = ctx.in1(op, 'Input')
    ctx.out(op, 'Out', jnp.asarray(np.asarray(x.shape, dtype=np.int32)))


@register_op('cast')
def _cast(ctx, op):
    x = ctx.in1(op, 'X')
    out_dtype = np_dtype(op.attr('out_dtype'))
    ctx.out(op, 'Out', x.astype(out_dtype))


@register_op('scale')
def _scale(ctx, op):
    x = ctx.in1(op, 'X')
    scale = op.attr('scale', 1.0)
    bias = op.attr('bias', 0.0)
    bias_after_scale = op.attr('bias_after_scale', True)
    from ..core.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        # reference scale_op SelectedRows kernel: scale values, keep rows.
        # A bias would have to touch every implicit zero row too -> densify.
        if bias != 0.0:
            x = x.to_dense()
        else:
            ctx.out(op, 'Out', x.scale(scale))
            return
    if bias_after_scale:
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    ctx.out(op, 'Out', out.astype(x.dtype))


@register_op('increment')
def _increment(ctx, op):
    x = ctx.in1(op, 'X')
    step = op.attr('step', 1.0)
    ctx.out(op, 'Out', x + jnp.asarray(step, dtype=x.dtype))


@register_op('clip')
def _clip(ctx, op):
    x = ctx.in1(op, 'X')
    from ..core.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        # merge duplicates first: clip does not distribute over addition,
        # so clipping per-occurrence values would diverge from the dense
        # equivalent when an id repeats in the batch
        rows, vals = x.merged()
        ctx.out(op, 'Out', SelectedRows(
            rows, jnp.clip(vals, op.attr('min'), op.attr('max')), x.height))
        return
    ctx.out(op, 'Out', jnp.clip(x, op.attr('min'), op.attr('max')))


@register_op('clip_by_norm')
def _clip_by_norm(ctx, op):
    """reference clip_by_norm_op.h (dense + SelectedRows kernel: merge rows,
    then clip values by the merged norm)."""
    x = ctx.in1(op, 'X')
    max_norm = op.attr('max_norm')
    from ..core.selected_rows import SelectedRows
    if isinstance(x, SelectedRows):
        rows, vals = x.merged()
        norm = jnp.sqrt(jnp.sum(vals.astype(jnp.float32) ** 2))
        factor = jnp.where(norm > max_norm,
                           max_norm / jnp.maximum(norm, 1e-12), 1.0)
        ctx.out(op, 'Out', SelectedRows(
            rows, vals * factor.astype(vals.dtype), x.height))
        return
    norm = jnp.sqrt(jnp.sum(x.astype(jnp.float32) ** 2))
    factor = jnp.where(norm > max_norm, max_norm / jnp.maximum(norm, 1e-12),
                       1.0)
    ctx.out(op, 'Out', (x * factor.astype(x.dtype)))


@register_op('print')
def _print(ctx, op):
    x = ctx.in1(op, 'X')
    message = op.attr('message', '')
    if ctx.params.get('host_eager'):
        # executor host segment: the value is concrete — print directly
        print(message, np.asarray(x))
    else:
        jax.debug.print(message + " {}", x)
    ctx.out(op, 'Out', x)


@register_op('one_hot')
def _one_hot(ctx, op):
    x = ctx.in1(op, 'X')
    depth = op.attr('depth')
    ids = x.reshape(x.shape[:-1]) if x.shape and x.shape[-1] == 1 else x
    out = jax.nn.one_hot(ids, depth, dtype=jnp.float32)
    ctx.out(op, 'Out', out)


@register_op('sharding_constraint')
def _sharding_constraint(ctx, op):
    """Pin an activation's sharding (TPU-native primitive; no reference
    analog — this is how sequence/activation parallelism is expressed).
    No-op when traced outside a mesh context."""
    x = ctx.in1(op, 'X')
    spec = tuple(op.attr('spec', ()))
    try:
        from jax.sharding import PartitionSpec, NamedSharding
        from ..parallel import api as _papi
        mesh = _papi.get_active_mesh()
        if mesh is not None:
            axes = set(mesh.axis_names)
            ok = all((a is None or
                      (a in axes if isinstance(a, str)
                       else all(s in axes for s in a)))
                     for a in spec)
            if ok:
                x = jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, PartitionSpec(*spec)))
    except Exception:
        pass
    ctx.out(op, 'Out', x)


@register_op('is_empty')
def _is_empty(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jnp.asarray(x.size == 0))


@register_op('delete_var')
def _delete_var(ctx, op):
    pass
