"""Control-flow ops: while / conditional_block / recurrent (Static & Dynamic
RNN) / TensorArray ops / beam search — lowered to lax.while_loop, lax.cond and
lax.scan, the XLA-traceable equivalents of the reference's sub-block
interpreters.

Reference semantics (studied, not ported):
- while_op.cc:50,125 — runs its sub-block repeatedly via a nested Executor
  with one StepScope per iteration while a bool Condition var is true; vars
  of the parent scope modified in the block persist across iterations.
  TPU design: the "scope delta" (vars written by the block that already live
  in the parent env, plus every TensorArray touched) becomes the
  lax.while_loop carry pytree; everything else is closed over read-only.
- conditional_block_op.cc:72 — runs the block iff its (scalar) condition is
  true. TPU design: lax.cond over the written-vars carry; the false branch
  is identity, so only vars that pre-exist in the parent env may be written
  (the reference's Switch/IfElse usage — assigning pre-created vars like a
  learning-rate global — satisfies this).
- recurrent_op.cc — StaticRNN: per-step sub-block over time-major inputs
  with boot memories; lowered to lax.scan (MXU-batched per step).
  DynamicRNN additionally handles ragged LoD batches; the reference sorts by
  length and shrinks the batch (lod_rank_table + shrink_rnn_memory); on TPU
  we keep a static [N] batch and mask finished rows — identical math, XLA
  static shapes.
- tensor_array_read_write_op.cc (write_to_array/read_from_array),
  lod_array_length, tensor_array_to_tensor_op.cc, lod_tensor_to_array /
  array_to_lod_tensor (split rows per lod_rank_table) — TensorArray pytree
  in core/tensor_array.py.
- beam_search_op.cc / beam_search_decode_op.cc — LoD-encoded beams; our
  TPU-native design keeps a dense [batch*beam] layout (scores masked with
  -inf for dead lanes) and backtracks parent pointers with a reverse scan.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from ..core.tensor_array import TensorArray
from ..core.lod import lengths_from_offsets
from .rnn_ops import _padded_maps, _to_padded, _to_ragged


class EmptyTensorArray(object):
    """Placeholder for `create_array` before the first write: elem shape is
    unknown until a value is written. Writes during an abstract probe trace
    record shape/dtype (python side effect) so loop carries can be
    materialized with the right structure."""

    def __init__(self, capacity):
        self.capacity = int(capacity)
        self.elem_shape = None
        self.dtype = None

    def materialize(self):
        if self.elem_shape is None:
            raise ValueError(
                "TensorArray read/stacked before any write — write to it "
                "first (write_to_array) so its element shape is known")
        return TensorArray.empty(self.capacity, self.elem_shape, self.dtype)

    def record(self, value):
        self.elem_shape = tuple(value.shape)
        self.dtype = value.dtype


def _sub_block(ctx, op, attr='sub_block'):
    return ctx.program.block(int(op.attr(attr)))


def _bind_parent_declared(ctx, written):
    """Vars a block writes that are declared in the parent block but not yet
    bound in the env: materialize a zero init from the declared shape/dtype
    so the write is carried (reference: create var in parent, first assign
    inside the block). Unknowable shapes raise instead of silently dropping
    the write (ADVICE round 1)."""
    for n in sorted(written):
        if ctx.has(n):
            continue
        var = ctx.block._find_var_recursive(n)
        if var is None or getattr(var, 'persistable', False):
            continue  # block-local temporary (declared in sub-block) or state
        shape = getattr(var, 'shape', None)
        dtype = getattr(var, 'dtype', None)
        if shape is None or dtype is None or any(
                d is None or int(d) < 0 for d in shape):
            raise ValueError(
                "variable %r is declared in the parent block and first "
                "written inside a control-flow block, but its shape/dtype "
                "(%s, %s) is not fully known — assign it an initial value "
                "in the parent block first" % (n, shape, dtype))
        ctx.env[n] = jnp.zeros(tuple(int(d) for d in shape), dtype=dtype)


def _written_names(program, block, acc=None):
    """All var names any op in `block` (or nested sub-blocks) writes."""
    if acc is None:
        acc = set()
    from ..framework import SUB_BLOCK_ATTRS
    for op in block.ops:
        for n in op.output_arg_names:
            acc.add(n)
        for a in SUB_BLOCK_ATTRS:
            try:
                idx = op.attr(a)
            except Exception:
                idx = None
            if idx is not None:
                _written_names(program, program.block(int(idx)), acc)
    return acc


def _touched_arrays(ctx, block):
    """Names of TensorArray/placeholder vars in the parent env that ops of
    the block touch (read or write) — they must ride in the carry."""
    names = set()
    for op in block.ops:
        for n in list(op.input_arg_names) + list(op.output_arg_names):
            if ctx.has(n) and isinstance(
                    ctx.env[n], (TensorArray, EmptyTensorArray)):
                names.add(n)
    return names


def _materialize_empties(ctx, block, carried, run_probe):
    """Replace EmptyTensorArray placeholders that the loop body writes with
    concrete zero-filled TensorArrays, discovering element shapes via an
    abstract probe trace of the body (jax.eval_shape → no ops emitted)."""
    empties = [n for n in carried
               if isinstance(ctx.env.get(n), EmptyTensorArray)]
    if not empties:
        return
    try:
        jax.eval_shape(run_probe)
    except ValueError:
        # probe may fail on reads of not-yet-written arrays mid-block; any
        # placeholder that did get recorded is still materialized below
        pass
    for n in empties:
        ph = ctx.env[n]
        if ph.elem_shape is not None:
            ctx.env[n] = ph.materialize()
        else:
            # never written in the loop: drop from carry by materializing a
            # 1-element float buffer (kept structurally stable)
            ctx.env[n] = TensorArray.empty(ph.capacity, (1,), 'float32')


# ---------------------------------------------------------------------------
# while
# ---------------------------------------------------------------------------

@register_op('while', stateful=True)
def _while(ctx, op):
    from ..core.lowering import lower_ops
    block = _sub_block(ctx, op)
    cond_name = op.input('Condition')[0]

    written = _written_names(ctx.program, block)
    _bind_parent_declared(ctx, written)
    carried = sorted(n for n in written if ctx.has(n))
    carried += sorted(_touched_arrays(ctx, block) - set(carried))
    if cond_name not in carried:
        raise ValueError(
            "while: condition %r is never updated inside the loop body — "
            "the loop would not terminate" % cond_name)

    def run_body(carry):
        env2 = dict(ctx.env)
        env2.update(carry)
        sub = ctx.child(env2, block=block)
        lower_ops(sub, block.ops, 0, len(block.ops))
        return {n: env2[n] for n in carried}

    _materialize_empties(
        ctx, block, carried,
        lambda: run_body({n: ctx.env[n] for n in carried}))

    init = {n: ctx.env[n] for n in carried}
    # dtype/weak-type stabilization: one abstract round-trip so the carry in
    # and out of the body agree (e.g. python-int increments promoting)
    out_shapes = jax.eval_shape(run_body, init)
    init = {n: jnp.asarray(v, out_shapes[n].dtype)
            if not isinstance(v, TensorArray) else v.clear_static()
            for n, v in init.items()}

    def cond_fn(carry):
        return jnp.reshape(jnp.asarray(carry[cond_name], bool), ())

    # Under the backward meta-op (ctx.wrt nonempty) lax.while_loop has no
    # reverse-mode rule (reference supports while_grad, while_op.cc:125);
    # lower to a bounded lax.scan with an active-mask instead. The bound
    # comes from While(max_trip_count=...) or, failing that, the smallest
    # capacity of a carried TensorArray (loops that write one slot per
    # iteration cannot exceed it).
    bound = op.attr('max_trip_count', None)
    if ctx.wrt:
        if bound is None:
            # infer only from arrays the body WRITES (a read-only array's
            # capacity says nothing about the trip count); loops appending
            # one slot per iteration cannot exceed the capacity. Loops that
            # overwrite a fixed slot should pass max_trip_count explicitly.
            caps = [v.capacity for n, v in init.items()
                    if isinstance(v, TensorArray) and n in written]
            bound = min(caps) if caps else None
        if bound is None:
            raise ValueError(
                "while inside a differentiated (training) program needs a "
                "static trip-count bound for reverse-mode AD: pass "
                "layers.While(cond, max_trip_count=N) or carry a "
                "TensorArray whose capacity bounds the loop")

        def scan_step(carry, _):
            new = lax.cond(cond_fn(carry), run_body, lambda c: c, carry)
            return new, None

        inferred = op.attr('max_trip_count', None) is None
        final, _ = lax.scan(scan_step, init, None, length=int(bound))
        if inferred:
            # An inferred bound (TensorArray capacity) is a heuristic: loops
            # that overwrite a fixed slot, or append past capacity, iterate
            # more times than it. Silent truncation would train on wrong
            # numbers — check the condition actually went false. (A
            # user-passed max_trip_count is an explicit contract and is not
            # checked.)
            def _check_exhausted(c, _bound=int(bound)):
                if bool(np.any(np.asarray(c))):
                    raise RuntimeError(
                        "while: inferred trip-count bound %d (from TensorArray "
                        "capacity) was too small — the loop condition is still "
                        "true after %d iterations. Pass layers.While(cond, "
                        "max_trip_count=N) with the real bound." %
                        (_bound, _bound))
            jax.debug.callback(_check_exhausted, final[cond_name])
    else:
        final = lax.while_loop(cond_fn, run_body, init)
    for n in carried:
        ctx.set(n, final[n])


# ---------------------------------------------------------------------------
# conditional_block
# ---------------------------------------------------------------------------

@register_op('conditional_block', stateful=True)
def _conditional_block(ctx, op):
    from ..core.lowering import lower_ops
    block = _sub_block(ctx, op)
    cond_names = op.input('Cond') or op.input('Condition')
    is_scalar = bool(op.attr('is_scalar_condition', True))
    cond_vals = [ctx.get(n) for n in cond_names]
    written = _written_names(ctx.program, block)
    _bind_parent_declared(ctx, written)
    if not is_scalar:
        # reference semantics (conditional_block_op.cc:72): non-scalar mode
        # runs the block iff the Input tensors are non-empty (numel != 0) —
        # a STATIC property under XLA, so the branch resolves at trace time
        # and the block is inlined (or skipped) with no lax.cond round-trip
        if all(int(np.prod(np.shape(c))) != 0 for c in cond_vals):
            exported = {n for n in written if ctx.has(n)}
            exported |= _touched_arrays(ctx, block)
            sub = ctx.child(dict(ctx.env), block=block)
            lower_ops(sub, block.ops, 0, len(block.ops))
            for n in exported:
                if n in sub.env:
                    ctx.set(n, sub.env[n])
        return
    pred = jnp.reshape(jnp.asarray(cond_vals[0], bool), ())

    carried = sorted(n for n in written if ctx.has(n))
    carried += sorted(_touched_arrays(ctx, block) - set(carried))

    def run_body(carry):
        env2 = dict(ctx.env)
        env2.update(carry)
        sub = ctx.child(env2, block=block)
        lower_ops(sub, block.ops, 0, len(block.ops))
        return {n: env2[n] for n in carried}

    _materialize_empties(
        ctx, block, carried,
        lambda: run_body({n: ctx.env[n] for n in carried}))

    init = {n: ctx.env[n] for n in carried}
    out_shapes = jax.eval_shape(run_body, init)
    init = {n: jnp.asarray(v, out_shapes[n].dtype)
            if not isinstance(v, TensorArray) else v.clear_static()
            for n, v in init.items()}

    final = lax.cond(pred, run_body, lambda c: c, init)
    for n in carried:
        ctx.set(n, final[n])


# ---------------------------------------------------------------------------
# recurrent (StaticRNN + DynamicRNN)
# ---------------------------------------------------------------------------

@register_op('recurrent', stateful=True)
def _recurrent(ctx, op):
    from ..core.lowering import lower_ops
    block = _sub_block(ctx, op)
    xs_outer = list(op.input('X'))                 # sequence inputs
    xs_inner = list(op.attr('xs_inner'))           # per-step names in block
    boots = list(op.input('Boot'))                 # initial memories
    pre_names = list(op.attr('pre_names'))         # memory names (read)
    post_names = list(op.attr('post_names'))       # updated memory names
    ys_inner = list(op.attr('ys_inner'))           # step outputs in block
    outs = list(op.output('Out'))                  # stacked outputs
    last_outs = list(op.output('LastMem'))         # final memory values
    is_dynamic = bool(op.attr('is_dynamic', False))
    reverse = bool(op.attr('is_reverse', False))

    if is_dynamic:
        lod = ctx.in1_lod(op, 'X')
        if not lod:
            raise ValueError("DynamicRNN input needs LoD (ragged batch)")
        offsets = lod[-1]
        gidx, sidx, n, maxt = _padded_maps(offsets, reverse=reverse)
        lens = jnp.asarray(
            np.asarray(lengths_from_offsets(offsets), np.int32))
        seqs = [_to_padded(ctx.get(nm), gidx, n, maxt).swapaxes(0, 1)
                for nm in xs_outer]              # [maxT, N, ...]
        steps = maxt
        mask_tn = (jnp.arange(maxt)[:, None] < lens[None, :])  # [maxT, N]
    else:
        seqs = [ctx.get(nm) for nm in xs_outer]  # time-major [T, N, ...]
        steps = seqs[0].shape[0] if seqs else int(op.attr('max_steps', 0))
        mask_tn = None

    init_mems = {p: jnp.asarray(ctx.get(b))
                 for p, b in zip(pre_names, boots)}

    def step(carry, xt):
        xs_t, mask_t = xt
        env2 = dict(ctx.env)
        env2.update(carry)
        env2.update(xs_t)
        sub = ctx.child(env2, block=block)
        lower_ops(sub, block.ops, 0, len(block.ops))
        new_mems = {}
        for p, q in zip(pre_names, post_names):
            new = jnp.asarray(env2[q], carry[p].dtype)
            if mask_t is not None:
                m = mask_t.reshape((-1,) + (1,) * (new.ndim - 1))
                new = jnp.where(m, new, carry[p])
            new_mems[p] = new
        ys = []
        for y in ys_inner:
            v = env2[y]
            if mask_t is not None:
                m = mask_t.reshape((-1,) + (1,) * (v.ndim - 1))
                v = jnp.where(m, v, jnp.zeros_like(v))
            ys.append(v)
        return new_mems, tuple(ys)

    xs_scan = ({nm: s for nm, s in zip(xs_inner, seqs)},
               mask_tn if mask_tn is not None else None)
    final_mems, stacked = lax.scan(step, init_mems, xs_scan, length=steps)

    for i, o in enumerate(outs):
        y = stacked[i]                            # [T, N, ...]
        if is_dynamic:
            y = _to_ragged(y.swapaxes(0, 1), sidx)
            ctx.set(o, y)
            ctx.set_lod(o, (offsets,))
        else:
            ctx.set(o, y)
    for i, o in enumerate(last_outs):
        ctx.set(o, final_mems[pre_names[i]])


@register_op('drnn_boot_memory')
def _drnn_boot_memory(ctx, op):
    """DynamicRNN.memory(shape=, value=): a [num_seqs, *shape] constant
    boot memory — num_seqs comes from the static LoD of the RNN's first
    sequence input (the TPU analog of the reference's batch-ref memory)."""
    lod = ctx.in1_lod(op, 'X')
    if not lod:
        raise ValueError("drnn_boot_memory: sequence input has no LoD")
    n = len(lod[-1]) - 1
    shape = [int(s) for s in op.attr('shape')]
    val = float(op.attr('value', 0.0))
    dtype = op.attr('dtype', 'float32')
    ctx.out(op, 'Out', jnp.full([n] + shape, val, dtype=dtype))
    ctx.lod_explicit.add(op.output('Out')[0])


# ---------------------------------------------------------------------------
# TensorArray ops
# ---------------------------------------------------------------------------

@register_op('create_tensor_array', stateful=True)
def _create_tensor_array(ctx, op):
    cap = int(op.attr('capacity', 128))
    ctx.out(op, 'Out', EmptyTensorArray(cap))


@register_op('write_to_array', stateful=True)
def _write_to_array(ctx, op):
    """The array var is the op's Out (same var across writes, reference
    tensor_array_read_write_op.cc): read the current array value from the
    env under the output name, write, rebind."""
    x = ctx.in1(op, 'X')
    i = ctx.in1(op, 'I')
    out_name = op.output('Out')[0]
    arr = ctx.env.get(out_name)
    if isinstance(arr, EmptyTensorArray):
        arr.record(x)
        arr = arr.materialize()
    elif not isinstance(arr, TensorArray):
        ph = EmptyTensorArray(int(op.attr('capacity', 128)))
        ph.record(x)
        arr = ph.materialize()
    i_name = op.input('I')[0]
    static_i = ctx.statics.get(i_name)
    if static_i is not None:
        static_i = int(np.asarray(static_i).reshape(-1)[0])
    ctx.set(out_name, arr.write(i, x, static_i=static_i))


@register_op('read_from_array')
def _read_from_array(ctx, op):
    arr = ctx.in1(op, 'X')
    i = ctx.in1(op, 'I')
    if isinstance(arr, EmptyTensorArray):
        arr = arr.materialize()
    ctx.out(op, 'Out', arr.read(i))


@register_op('lod_array_length')
def _lod_array_length(ctx, op):
    arr = ctx.in1(op, 'X')
    n = arr.length if isinstance(arr, TensorArray) else jnp.asarray(0)
    ctx.out(op, 'Out', jnp.reshape(n, (1,)).astype('int64'))


@register_op('tensor_array_to_tensor')
def _tensor_array_to_tensor(ctx, op):
    """Concatenate/stack exactly the WRITTEN elements (reference
    tensor_array_to_tensor_op.cc concatenates size() tensors, not the
    backing capacity). With a static length the buffer is sliced to it. A
    traced length (array written under a lax.while_loop) cannot produce a
    dynamic output shape under XLA: the documented deviation is a
    capacity-sized output with unwritten slots masked to zero — consumers
    needing the exact extent read OutIndex[0] (= length) at runtime."""
    arr = ctx.in1(op, 'X')
    axis = int(op.attr('axis', 0))
    use_stack = bool(op.attr('use_stack', False))
    if isinstance(arr, EmptyTensorArray):
        arr = arr.materialize()
    static_len = arr.static_length is not None
    if static_len:
        length = int(arr.static_length)
        buf = arr.stack()[:length]                 # [len, ...]
    else:
        length = arr.capacity
        buf = arr.masked_stack()                   # [cap, ...], zeros beyond
    if use_stack:
        out = buf if axis == 0 else jnp.moveaxis(buf, 0, axis)
    else:
        parts = [buf[i] for i in range(length)]
        out = jnp.concatenate(parts, axis=axis) if parts else buf
    # per-element extent along the concat axis, one entry per written element
    extent = buf.shape[1 + axis] if buf.ndim > 1 + axis else 1
    if static_len:
        idx = jnp.full((max(length, 1),), extent, dtype='int32')
    else:
        # dynamic: [length, extent, extent, ...] — OutIndex[0] carries the
        # true element count so downstream can mask
        idx = jnp.full((length,), extent, dtype='int32').at[0].set(
            arr.length.astype('int32'))
    ctx.out(op, 'Out', out)
    ctx.out(op, 'OutIndex', idx)


# -- LoD <-> array glue (static-LoD versions) -------------------------------

@register_op('lod_rank_table')
def _lod_rank_table(ctx, op):
    """Static rank table: sequences sorted by decreasing length. Stored as a
    trace-time constant (set_static) — consumed by max_sequence_len etc."""
    lod = ctx.in1_lod(op, 'X')
    if not lod:
        raise ValueError("lod_rank_table: input has no LoD")
    level = int(op.attr('level', 0))
    lens = lengths_from_offsets(lod[level])
    order = sorted(range(len(lens)), key=lambda i: -lens[i])
    table = np.asarray([(i, lens[i]) for i in order], np.int64)
    name = op.output('Out')[0]
    ctx.set(name, jnp.asarray(table))
    ctx.set_static(name, table)


@register_op('max_sequence_len')
def _max_sequence_len(ctx, op):
    table = ctx.in1_static(op, 'RankTable')
    mx = int(table[0][1]) if len(table) else 0
    ctx.out(op, 'Out', jnp.asarray([mx], dtype='int64'))


@register_op('lod_tensor_to_array', stateful=True)
def _lod_tensor_to_array(ctx, op):
    """Split ragged rows into a TensorArray of per-timestep batches, sorted
    by the rank table (longest first) — reference
    lod_tensor_to_array_op.cc. Static LoD → static gather maps."""
    x = ctx.in1(op, 'X')
    lod = ctx.in1_lod(op, 'X')
    offsets = lod[-1]
    gidx, _, n, maxt = _padded_maps(offsets)
    lens = lengths_from_offsets(offsets)
    order = np.argsort(-np.asarray(lens), kind='stable')
    padded = _to_padded(x, gidx[order], n, maxt)   # [N_sorted, maxT, ...]
    tm = padded.swapaxes(0, 1)                     # [maxT, N, ...]
    ctx.out(op, 'Out', TensorArray(tm, jnp.asarray(maxt, jnp.int32)))
    name = op.output('Out')[0]
    ctx.set_static(name + '@order', np.asarray(order))
    ctx.set_static(name + '@lens', np.asarray(lens))


@register_op('array_to_lod_tensor')
def _array_to_lod_tensor(ctx, op):
    arr = ctx.in1(op, 'X')
    table_name = op.input('RankTable')[0]
    table = np.asarray(ctx.static_value(table_name))
    order = table[:, 0].astype(np.int64)
    lens_sorted = table[:, 1].astype(np.int64)
    tm = arr.stack()                               # [maxT, N, ...]
    padded = tm.swapaxes(0, 1)                     # [N_sorted, maxT, ...]
    lens = np.zeros(len(order), np.int64)
    lens[order] = lens_sorted
    # back to ragged in original sequence order
    parts = []
    inv = {int(o): i for i, o in enumerate(order)}
    for seq in range(len(order)):
        parts.append(padded[inv[seq], :int(lens[seq])])
    out = jnp.concatenate(parts, axis=0)
    ctx.out(op, 'Out', out)
    off = np.concatenate([[0], np.cumsum(lens)])
    ctx.set_lod(op.output('Out')[0], (tuple(int(v) for v in off),))


@register_op('shrink_rnn_memory')
def _shrink_rnn_memory(ctx, op):
    """Reference shrinks the batch as sorted sequences finish; with static
    masking the batch never shrinks — identity (mask handles validity)."""
    ctx.out(op, 'Out', ctx.in1(op, 'X'))


@register_op('reorder_lod_tensor_by_rank')
def _reorder_lod_tensor_by_rank(ctx, op):
    x = ctx.in1(op, 'X')
    table = np.asarray(ctx.in1_static(op, 'RankTable'))
    order = table[:, 0].astype(np.int64)
    lod = ctx.in1_lod(op, 'X')
    if lod:
        offsets = lod[-1]
        rows = np.concatenate(
            [np.arange(offsets[i], offsets[i + 1]) for i in order]
        ) if len(order) else np.zeros((0,), np.int64)
        out = jnp.take(x, jnp.asarray(rows), axis=0)
        lens = lengths_from_offsets(offsets)
        new_lens = [lens[i] for i in order]
        off = np.concatenate([[0], np.cumsum(new_lens)])
        ctx.out(op, 'Out', out)
        ctx.set_lod(op.output('Out')[0], (tuple(int(v) for v in off),))
    else:
        ctx.out(op, 'Out', jnp.take(x, jnp.asarray(order), axis=0))


@register_op('split_lod_tensor')
def _split_lod_tensor(ctx, op):
    """IfElse splitter. TPU design: no dynamic-shape split — both branches
    see the full batch; OutTrue/OutFalse are the input (merge selects by
    mask). Keeps shapes static; identical final results for row-wise
    bodies (the reference IfElse contract)."""
    x = ctx.in1(op, 'X')
    ctx.out(op, 'OutTrue', x)
    ctx.out(op, 'OutFalse', x)


@register_op('merge_lod_tensor')
def _merge_lod_tensor(ctx, op):
    mask = ctx.in1(op, 'Mask')
    t = ctx.in1(op, 'InTrue')
    f = ctx.in1(op, 'InFalse')
    m = jnp.asarray(mask, bool).reshape((-1,) + (1,) * (t.ndim - 1))
    ctx.out(op, 'Out', jnp.where(m, t, f))


# ---------------------------------------------------------------------------
# beam search (dense TPU layout)
# ---------------------------------------------------------------------------

@register_op('beam_search')
def _beam_search(ctx, op):
    """Dense beam-search step. pre_ids/pre_scores: [batch*beam, 1]; ids:
    [batch*beam, K] candidate token ids; scores: [batch*beam, K] accumulated
    log-probs of each candidate (reference beam_search_op.cc semantics with
    accumulated scores). Finished lanes (pre_id == end_id) contribute a
    single survival candidate (end_id, pre_score)."""
    pre_ids = ctx.in1(op, 'pre_ids')
    pre_scores = ctx.in1(op, 'pre_scores')
    ids = ctx.in1(op, 'ids')
    scores = ctx.in1(op, 'scores')
    beam = int(op.attr('beam_size'))
    end_id = int(op.attr('end_id'))

    bw = scores.shape[0]
    k = scores.shape[1]
    batch = bw // beam
    neg_inf = jnp.asarray(-1e9, scores.dtype)

    finished = (pre_ids.reshape(bw) == end_id)
    # finished lanes: candidate 0 = (end_id, pre_score); others -inf
    cand0 = jnp.zeros((bw, k), bool).at[:, 0].set(True)
    scores = jnp.where(finished[:, None],
                       jnp.where(cand0, pre_scores.reshape(bw, 1), neg_inf),
                       scores)
    ids = jnp.where(finished[:, None], end_id, ids)

    flat = scores.reshape(batch, beam * k)
    top_scores, top_idx = lax.top_k(flat, beam)        # [batch, beam]
    parent_beam = top_idx // k                         # [batch, beam]
    batch_base = jnp.arange(batch, dtype=top_idx.dtype)[:, None] * beam
    parent_row = (batch_base + parent_beam).reshape(bw)
    sel_ids = ids.reshape(batch, beam * k)[
        jnp.arange(batch)[:, None], top_idx].reshape(bw, 1)
    ctx.out(op, 'selected_ids', sel_ids.astype('int64'))
    ctx.out(op, 'selected_scores', top_scores.reshape(bw, 1))
    ctx.out(op, 'parent_idx', parent_row.astype('int32'))


@register_op('beam_search_decode')
def _beam_search_decode(ctx, op):
    """Backtrack stored (ids, parents) TensorArrays into full sentences:
    SentenceIds [batch, beam, T] (post-EOS positions filled with end_id),
    SentenceScores [batch, beam]."""
    ids_arr = ctx.in1(op, 'Ids')
    parents_arr = ctx.in1(op, 'Parents')
    scores_arr = ctx.in1(op, 'Scores', None)
    beam = int(op.attr('beam_size'))
    end_id = int(op.attr('end_id'))

    ids_buf = ids_arr.stack()                      # [T, bw, 1] or [T, bw]
    par_buf = parents_arr.stack()                  # [T, bw]
    T = ids_buf.shape[0]
    bw = par_buf.shape[1] if par_buf.ndim > 1 else par_buf.shape[0]
    ids_buf = ids_buf.reshape(T, bw)
    par_buf = par_buf.reshape(T, bw).astype('int32')
    n_steps = ids_arr.length

    def back(carry, xt):
        row = carry                                # [bw] row to follow
        step_ids, step_parents, t = xt
        valid = t < n_steps
        tok = jnp.where(valid, step_ids[row], end_id)
        new_row = jnp.where(valid, step_parents[row], row)
        return new_row, tok

    init_row = jnp.arange(bw, dtype='int32')
    _, toks = lax.scan(
        back, init_row,
        (ids_buf[::-1], par_buf[::-1], jnp.arange(T - 1, -1, -1)))
    sent = toks[::-1].swapaxes(0, 1)               # [bw, T]
    batch = bw // beam
    ctx.out(op, 'SentenceIds',
            sent.reshape(batch, beam, T).astype('int64'))
    if scores_arr is not None and op.output('SentenceScores'):
        sc_buf = scores_arr.stack().reshape(T, bw)
        last = jnp.maximum(n_steps - 1, 0)
        final_scores = lax.dynamic_index_in_dim(sc_buf, last, 0,
                                                keepdims=False)
        ctx.out(op, 'SentenceScores', final_scores.reshape(batch, beam))
