"""Embedding lookup (+bias): rows copied out of the table where it lies.

The reference serves embedding lookups through lookup_table_op.cc (dense
gather) and the distributed prefetch pipeline; here the lookup is XLA's
own gather of ``[V, D]`` as the weight is stored — one HLO that reads the
rows it needs and nothing else of the table, at every PADDLE_FUSED_TIER
(``fused_kernel_dispatch_total{op=lookup_table}`` reads ``impl=off``).
Why no Pallas kernel: Mosaic takes no one-row block or DMA slice of an
``(8, 128)``-tiled ``[V, D]`` ref, and handing it ``w.reshape(V, 1, D)``
is a copy of the WHOLE table in every dispatch (3.5 ms of a 19 ms decode
step at 129 280 rows) to fetch 4-64 rows; a kernel that fetches each
row's 8-row tile group in place is no faster than the gather at any
shape a benchmark cell runs (``tools/kernbench.py --cases
embedding_gather --size bench``; PERF.md, PR 33).

Gradients are jnp's own: the transpose of the gather is XLA's
scatter-add, a single HLO. The SPARSE path (is_sparse=True embeddings)
never differentiates through the gather at all: core/lowering.py's
scout/dummy mechanism holds the table out of AD, so the lookup gathers
stop_gradient rows — composing with SelectedRows grads unchanged. Under
a mesh it is the same gather, which the SPMD partitioner splits: ids
over 'data' against a replicated table, or a sharded table (the
is_distributed vocab pin or a param rule) as shard-local masked gathers
+ psum.

Used by the lookup_table lowering (tensor_ops) and the program-level
``fused_embedding_gather`` op registered here (W, Ids, optional Bias).
"""
import jax.numpy as jnp

from ..core.registry import register_op


def embedding_gather(w, flat_ids, bias=None):
    """Rows of ``w`` at ``flat_ids`` (+ optional per-feature ``bias``);
    an id outside the table reads its nearest row, as the TPU's gather
    does (and its gradient lands on that row)."""
    out = jnp.take(w, flat_ids, axis=0, mode='clip')
    return out if bias is None else out + bias.reshape(1, -1)


def count_dispatch(ctx, op_type):
    """The lookup has one lowering whatever the tier asks for: count it
    as the unfused one, once per site (not again on the sparse scout
    pass, core/lowering.py)."""
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    kernel_tier.dispatch(
        op_type, pallas_ok=False, xla_ok=False, mesh=get_active_mesh(),
        count=getattr(ctx, 'sparse_mode', None) != 'scout')


@register_op('fused_embedding_gather')
def _fused_embedding_gather(ctx, op):
    """Program-level gather+bias: inputs W [V, D], Ids (any shape,
    trailing 1 folds like lookup_table), optional Bias [D]; output
    Out [..., D]. Rides the same sparse scout/apply mechanism as
    lookup_table when W is an is_sparse wrt table."""
    from .tensor_ops import embedding_epilogue, lookup_gather
    w = ctx.in1(op, 'W')
    ids = ctx.in1(op, 'Ids')
    flat = ids.reshape(-1).astype(jnp.int32)
    count_dispatch(ctx, 'fused_embedding_gather')
    out = lookup_gather(ctx, op, w, flat, bias=ctx.in1(op, 'Bias'))
    ctx.out(op, 'Out', embedding_epilogue(
        out, flat, ids, w, op.attr('padding_idx', -1)))
