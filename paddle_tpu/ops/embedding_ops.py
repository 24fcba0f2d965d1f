"""Fused embedding gather(+bias) — the sparse-path kernel tier.

The reference serves embedding lookups through lookup_table_op.cc (dense
gather) and the distributed prefetch pipeline; here the gather itself
becomes a Pallas kernel when the tier allows: ids are SCALAR-PREFETCHED
(pltpu.PrefetchScalarGridSpec) so each grid step's BlockSpec index_map
picks the table row to DMA — the classic Pallas embedding idiom: row
fetches pipeline back-to-back without materializing an index tensor on
the vector unit, and the optional per-feature bias adds inside the same
kernel (one HBM pass instead of gather-then-add).

Gradients: the dense path carries a custom_vjp whose backward is the
scatter-add transpose (XLA's native scatter — already a single fused HLO,
which is why there is no Pallas scatter tier; the fallback rule is
documented in docs/executor_performance.md). The SPARSE path
(is_sparse=True embeddings) never differentiates through the gather at
all: core/lowering.py's scout/dummy mechanism holds the table out of AD,
so the kernel simply gathers stop_gradient rows — composing with
SelectedRows grads unchanged.

Used by the lookup_table lowering (tensor_ops) and the program-level
``fused_embedding_gather`` op registered here (W, Ids, optional Bias).
"""
import functools

import jax
import jax.numpy as jnp

from ..core.registry import register_op


def pallas_shapes_ok(w, n_ids):
    """Kernel tiling rule: features must fill whole lanes (the row DMA is
    one [1, 1, D] block); any id count works (grid is per-id)."""
    return w.ndim == 2 and w.shape[1] % 128 == 0 and n_ids >= 1 and \
        w.dtype == jnp.float32


def spmd_gather_ok(mesh, w, n_ids, w_spec=None):
    """Mesh-partitioning rule for the gather kernel: ids partition over
    'data' (kernel per shard via kernel_tier.partitioned_call, table
    replicated into each shard) — so the TABLE itself must be replicated.
    A sharded table (`w_spec` names a mesh axis, or the is_distributed
    vocab-sharded pin) keeps the XLA gather, which the SPMD partitioner
    turns into shard-local masked gathers + psum; an explicitly
    replicated spec (P() or P(None, ...)) stays eligible."""
    if w_spec is not None and any(e is not None for e in tuple(w_spec)):
        return False
    from .kernel_tier import mesh_axis
    data_ax = mesh_axis(mesh, 'data', n_ids)
    n_loc = n_ids // mesh.shape[data_ax] if data_ax else n_ids
    return pallas_shapes_ok(w, n_loc)


def _gather_kernel(has_bias, *refs):
    if has_bias:
        ids_ref, row_ref, bias_ref, out_ref = refs
        out_ref[...] = row_ref[...] + bias_ref[...]
    else:
        ids_ref, row_ref, out_ref = refs
        out_ref[...] = row_ref[...]


def _gather_pallas(w, flat_ids, bias, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = flat_ids.shape[0]
    d = w.shape[1]
    has_bias = bias is not None
    # clamp like jnp.take's default TPU behavior (out-of-range ids clamp)
    ids32 = jnp.clip(flat_ids.astype(jnp.int32), 0, w.shape[0] - 1)
    # rows ride as [V, 1, D]: Mosaic wants a block's last two dims to be
    # (8k, 128k) or the array's own, and a one-row (1, D) block of a
    # [V, D] table is neither — (1, 1, D) of [V, 1, D] is the array's own
    row = pl.BlockSpec((1, 1, d), lambda i, ids: (ids[i], 0, 0))
    in_specs = [row]
    ins = [w.reshape(w.shape[0], 1, d)]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, d), lambda i, ids: (0, 0, 0)))
        ins.append(bias.reshape(1, 1, d))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d), lambda i, ids: (i, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_gather_kernel, has_bias),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), w.dtype),
        interpret=interpret,
        name='embedding_gather',
    )(ids32, *ins).reshape(n, d)


def _gather_ref(w, flat_ids, bias):
    out = jnp.take(w, flat_ids, axis=0)
    return out if bias is None else out + bias.reshape(1, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gather_grad(w, flat_ids, bias, impl, w_shape, w_dtype_str):
    return _gather_impl(w, flat_ids, bias, impl)


def _gather_impl(w, flat_ids, bias, impl):
    if impl in ('pallas', 'interpret'):
        return _gather_pallas(w, flat_ids, bias, impl == 'interpret')
    return _gather_ref(w, flat_ids, bias)


def _gather_grad_fwd(w, flat_ids, bias, impl, w_shape, w_dtype_str):
    return _gather_impl(w, flat_ids, bias, impl), \
        (flat_ids, bias is not None)


def _gather_grad_bwd(impl, w_shape, w_dtype_str, res, ct):
    flat_ids, has_bias = res
    dw = jnp.zeros(w_shape, w_dtype_str).at[flat_ids].add(
        ct.astype(w_dtype_str), mode='drop')
    db = jnp.sum(ct, axis=0) if has_bias else None
    return dw, None, db


_gather_grad.defvjp(_gather_grad_fwd, _gather_grad_bwd)


def _gather_dispatch(w, flat_ids, bias, impl, differentiable):
    if differentiable:
        return _gather_grad(w, flat_ids, bias, impl,
                            tuple(w.shape), str(w.dtype))
    return _gather_pallas(w, flat_ids, bias, impl == 'interpret')


def embedding_gather(w, flat_ids, bias=None, impl='off', differentiable=True):
    """Rows of ``w`` at ``flat_ids`` (+ optional per-feature ``bias``).

    impl: 'off'/'xla' -> plain jnp gather (+add) with jnp's own AD (the
    transpose IS XLA's scatter-add — bitwise today's path);
    'pallas'/'interpret' -> the scalar-prefetch kernel, wrapped in a
    custom_vjp whose backward is the same scatter-add transpose.
    ``differentiable=False`` skips the vjp wrapper (the sparse scout/apply
    path holds w out of AD already).

    Under an active >1-device mesh the kernel runs PER SHARD via
    kernel_tier.partitioned_call: ids partition over 'data', the table
    rides replicated into every shard (dispatch only picks pallas here
    when the table IS replicated — spmd_gather_ok), and the dense
    backward's scatter-add cotangent psums across the data axis through
    shard_map's transpose. The sparse path's replicated-rows pin
    (core/lowering.py) is untouched — it operates on the optimizer-side
    SelectedRows scatter, not this gather."""
    flat_ids = flat_ids.astype(jnp.int32)
    if impl in ('pallas', 'interpret'):
        from ..parallel.api import get_active_mesh
        mesh = get_active_mesh()
        if mesh is not None and mesh.size > 1:
            from jax.sharding import PartitionSpec as P
            from .kernel_tier import partitioned_call, mesh_axis
            data_ax = mesh_axis(mesh, 'data', flat_ids.shape[0])
            has_bias = bias is not None

            def inner(wl, il, *mb):
                return _gather_dispatch(wl, il, mb[0] if mb else None,
                                        impl, differentiable)

            in_specs = [P(), P(data_ax)] + ([P()] if has_bias else [])
            args = [w, flat_ids] + ([bias] if has_bias else [])
            return partitioned_call(inner, mesh, tuple(in_specs),
                                    P(data_ax, None))(*args)
        return _gather_dispatch(w, flat_ids, bias, impl, differentiable)
    return _gather_ref(w, flat_ids, bias)


@register_op('fused_embedding_gather')
def _fused_embedding_gather(ctx, op):
    """Program-level fused gather+bias: inputs W [V, D], Ids (any shape,
    trailing 1 folds like lookup_table), optional Bias [D]; output
    Out [..., D]. Rides the same sparse scout/apply mechanism as
    lookup_table when W is an is_sparse wrt table."""
    from . import kernel_tier
    from .tensor_ops import embedding_epilogue, lookup_gather
    from ..parallel.api import get_active_mesh, get_active_param_spec
    w = ctx.in1(op, 'W')
    ids = ctx.in1(op, 'Ids')
    bias = ctx.in1(op, 'Bias')
    flat = ids.reshape(-1).astype(jnp.int32)
    mesh = get_active_mesh()
    if mesh is not None and mesh.size > 1:
        # mesh-native: ids partition over 'data' via partitioned_call
        # (embedding_gather routes through shard_map); a SHARDED table
        # falls back to the XLA gather the partitioner can split
        spec_fn = get_active_param_spec()
        w_spec = spec_fn(op.input('W')[0]) if spec_fn else None
        ok = spmd_gather_ok(mesh, w, int(flat.shape[0]), w_spec)
    else:
        ok = pallas_shapes_ok(w, int(flat.shape[0]))
    impl = kernel_tier.dispatch(
        'fused_embedding_gather', pallas_ok=ok, mesh=mesh,
        count=getattr(ctx, 'sparse_mode', None) != 'scout')
    out = lookup_gather(ctx, op, w, flat, bias=bias, impl=impl)
    ctx.out(op, 'Out', embedding_epilogue(
        out, flat, ids, w, op.attr('padding_idx', -1)))
