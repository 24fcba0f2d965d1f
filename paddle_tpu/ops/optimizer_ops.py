"""Optimizer ops: functional (param, grad, state) -> (param', state') updates.

Reference: operators/optimizers/*.cc (sgd, momentum, lars_momentum, adagrad,
adam, adamax, adadelta, decayed_adagrad, ftrl, rmsprop, proximal_gd,
proximal_adagrad — each with dense + SelectedRows kernels). Here each is a pure
jnp expression inside the compiled step; XLA buffer donation makes the update
in-place. sgd/momentum/adam/adagrad additionally handle SelectedRows sparse
grads row-wise (scatter updates touch only the looked-up embedding rows);
the rest densify via _dense_grad like reference ops without a SelectedRows
kernel.
"""
import numpy as np
import jax
import jax.numpy as jnp

from .. import monitor
from ..core.registry import register_op
from ..core.selected_rows import SelectedRows


def _lr(ctx, op):
    lr = ctx.in1(op, 'LearningRate')
    return lr.reshape(()) if lr.ndim else lr


def _dense_grad(ctx, op):
    """Grad input, densified if sparse (for optimizers without a row-wise
    kernel — the analog of ops lacking a SelectedRows kernel in the
    reference, which would densify via scatter first)."""
    g = ctx.in1(op, 'Grad')
    return g.to_dense() if isinstance(g, SelectedRows) else g


@register_op('sgd')
def _sgd(ctx, op):
    """reference operators/optimizers/sgd_op.h: dense kernel + SelectedRows
    kernel (row-wise axpy). Sparse: scatter-add touches only the looked-up
    rows; duplicate rows accumulate, exactly matching the dense result."""
    p = ctx.in1(op, 'Param')
    g = ctx.in1(op, 'Grad')
    lr = _lr(ctx, op)
    if isinstance(g, SelectedRows):
        upd = (-lr).astype(p.dtype) * g.values.astype(p.dtype)
        ctx.out(op, 'ParamOut', p.at[g.rows].add(upd, mode='drop'))
        return
    ctx.out(op, 'ParamOut', p - lr.astype(p.dtype) * g.astype(p.dtype))


@register_op('momentum')
def _momentum(ctx, op):
    """reference operators/optimizers/momentum_op.h (dense +
    SparseMomentumFunctor: merged rows, velocity/param updated row-wise;
    untouched rows keep stale velocity — 'lazy' semantics)."""
    p = ctx.in1(op, 'Param')
    g = ctx.in1(op, 'Grad')
    v = ctx.in1(op, 'Velocity')
    lr = _lr(ctx, op)
    mu = op.attr('mu')
    nesterov = op.attr('use_nesterov', False)
    if isinstance(g, SelectedRows):
        rows, gv = g.merged()
        gv = gv.astype(p.dtype)
        v_r = mu * v[rows] + gv
        if nesterov:
            p_r = p[rows] - (gv + mu * v_r) * lr
        else:
            p_r = p[rows] - lr * v_r
        ctx.out(op, 'ParamOut', p.at[rows].set(p_r, mode='drop'))
        ctx.out(op, 'VelocityOut', v.at[rows].set(v_r, mode='drop'))
        return
    v_out = mu * v + g
    if nesterov:
        p_out = p - (g + mu * v_out) * lr
    else:
        p_out = p - lr * v_out
    ctx.out(op, 'ParamOut', p_out)
    ctx.out(op, 'VelocityOut', v_out)


@register_op('lars_momentum')
def _lars_momentum(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    v = ctx.in1(op, 'Velocity')
    lr = _lr(ctx, op)
    mu = op.attr('mu')
    coeff = op.attr('lars_coeff', 0.001)
    decay = op.attr('lars_weight_decay', 0.0005)
    pn = jnp.sqrt(jnp.sum(p * p))
    gn = jnp.sqrt(jnp.sum(g * g))
    local_lr = jnp.where(pn > 0, lr * coeff * pn / (gn + decay * pn + 1e-12),
                         lr)
    v_out = mu * v + local_lr * (g + decay * p)
    ctx.out(op, 'ParamOut', p - v_out)
    ctx.out(op, 'VelocityOut', v_out)


@register_op('adam')
def _adam(ctx, op):
    """reference operators/optimizers/adam_op.h: dense + SparseAdamFunctor
    over merged grad rows (lazy semantics: only touched rows advance their
    moments; BetaPow still advances globally).

    A dense MATRIX's update is a pass of its own over the finished
    gradient (`optimization_barrier`): left to itself XLA:TPU takes the
    update — three float32 streams in, three out — into the fusion of the
    weight-gradient GEMM that produces the gradient, and tiles the GEMM
    round the epilogue's footprint (1.5-3.9 x its forward twin's cycles
    at d_model 1024; PERF.md, PR 54). Behind the barrier the GEMM is tiled
    for itself and the update is a plain loop fusion over the donated
    buffers, as a vector's always was; the values are the same bit for
    bit. `adam_update_form_total{form=own_pass|inline}` counts the choice,
    once a call site at trace time; what the op sees of the gradient
    decides (`_own_pass`)."""
    p = ctx.in1(op, 'Param')
    g = ctx.in1(op, 'Grad')
    m1 = ctx.in1(op, 'Moment1')
    m2 = ctx.in1(op, 'Moment2')
    b1p = ctx.in1(op, 'Beta1Pow').reshape(())
    b2p = ctx.in1(op, 'Beta2Pow').reshape(())
    lr = _lr(ctx, op)
    b1 = op.attr('beta1', 0.9)
    b2 = op.attr('beta2', 0.999)
    eps = op.attr('epsilon', 1e-8)
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    own_pass = _own_pass(g)
    monitor.inc('adam_update_form_total',
                labels={'form': 'own_pass' if own_pass else 'inline'})
    if isinstance(g, SelectedRows):
        po, m1o, m2o = _adam_sparse(p, g, m1, m2, lr_t, b1, b2, eps)
    else:
        if own_pass:
            g = jax.lax.optimization_barrier(g)
        po, m1o, m2o = _adam_dense(p, g, m1, m2, lr_t, b1, b2, eps)
    ctx.out(op, 'ParamOut', po)
    ctx.out(op, 'Moment1Out', m1o)
    ctx.out(op, 'Moment2Out', m2o)
    ctx.out(op, 'Beta1PowOut', (b1p * b1).reshape(1))
    ctx.out(op, 'Beta2PowOut', (b2p * b2).reshape(1))


# The fewest elements of a gradient whose update `adam` cuts off the GEMM.
# fd355m-train-2k on the v5e, us a layer a step (my chip runs, PR 54):
# attn.proj.w, 1 048 576 elements: 92.4 inline (tiled like its forward twin,
# 96.7), 92.7 + 41.8 cut; attn.qkv.w, 3 145 728: 564.5 inline, 279.5 + 25.4
# cut. The whole step with proj inline: 27 622 | 27 638 tokens/s, cut:
# 27 426 | 27 541.
_OWN_PASS_MIN_ELEMENTS = 2 * 1024 * 1024


def _own_pass(g):
    """Whether the `adam` op cuts gradient `g` off its producer: a dense
    gradient of rank >= 2 and `_OWN_PASS_MIN_ELEMENTS` on one device —
    there its producer is the weight-gradient GEMM, and an epilogue of
    that size is what XLA:TPU tiles the GEMM round. Under a mesh of
    several devices the gradient leaves a collective (the reduce-scatter
    or all-reduce of the data axis) and the update already is a pass of
    its own; a cut there only splits the gradient's widening convert off
    the update (48 `convert_convert_fusion`s more in
    `fd1.3b-train-4chip`'s compiled step, 8 B a parameter of a chip's
    shard)."""
    if isinstance(g, SelectedRows) or g.ndim < 2 \
            or g.size < _OWN_PASS_MIN_ELEMENTS:
        return False
    from ..parallel.api import get_active_mesh
    mesh = get_active_mesh()
    return mesh is None or mesh.size == 1


def _adam_dense(p, g, m1, m2, lr_t, b1, b2, eps):
    """The exact per-parameter dense Adam expressions of the `adam` op —
    shared so fused_adam's 'off' tier is bit-identical by construction."""
    m1o = b1 * m1 + (1 - b1) * g
    m2o = b2 * m2 + (1 - b2) * g * g
    return p - lr_t * m1o / (jnp.sqrt(m2o) + eps), m1o, m2o


def _adam_sparse(p, g, m1, m2, lr_t, b1, b2, eps):
    """The adam op's SelectedRows (lazy) row-wise update — ONE copy shared
    by `adam` and `fused_adam` so their sparse semantics cannot drift."""
    rows, gv = g.merged()
    gv = gv.astype(p.dtype)
    m1r = b1 * m1[rows] + (1 - b1) * gv
    m2r = b2 * m2[rows] + (1 - b2) * gv * gv
    p_r = p[rows] - lr_t * m1r / (jnp.sqrt(m2r) + eps)
    return (p.at[rows].set(p_r, mode='drop'),
            m1.at[rows].set(m1r, mode='drop'),
            m2.at[rows].set(m2r, mode='drop'))


def _fused_adam_kernel(b1, b2, eps, lrt_ref, p_ref, g_ref, m1_ref, m2_ref,
                       po_ref, m1o_ref, m2o_ref):
    lrt = lrt_ref[0, 0]
    g = g_ref[...]
    m1o = b1 * m1_ref[...] + (1 - b1) * g
    m2o = b2 * m2_ref[...] + (1 - b2) * g * g
    po_ref[...] = p_ref[...] - lrt * m1o / (jnp.sqrt(m2o) + eps)
    m1o_ref[...] = m1o
    m2o_ref[...] = m2o


def _mesh_spec_ok(mesh, spec, shape):
    """True when `spec` evenly tiles `shape` over `mesh` — shard_map's
    divisibility rule; a param that fails it takes the per-param
    fallback instead of the partitioned fused path."""
    entries = tuple(spec) if spec is not None else ()
    if len(entries) > len(shape):
        return False
    for dim, ax in zip(shape, entries):
        if ax is None:
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        size = 1
        for a in axes:
            if a not in mesh.axis_names:
                return False
            size *= int(mesh.shape[a])
        if size and dim % size != 0:
            return False
    return True


def _fused_adam_group_spmd(mesh, spec, ps, gs, m1s, m2s, lr_t, b1, b2,
                           eps, impl):
    """One fused Adam pass for a group of params sharing PartitionSpec
    `spec`, partitioned per shard via kernel_tier.partitioned_call: each
    shard flattens+concats its LOCAL blocks and runs the elementwise
    kernel — the update is elementwise, so any partitioning is exact and
    comms-free (replicated params redundantly update on every device,
    the replicated path). Returns (params_out, m1_out, m2_out) lists."""
    from jax.sharding import PartitionSpec as P
    from .kernel_tier import partitioned_call
    k = len(ps)

    def inner(lrt, *blocks):
        lp, lg = blocks[:k], blocks[k:2 * k]
        lm1, lm2 = blocks[2 * k:3 * k], blocks[3 * k:]
        shapes = [b.shape for b in lp]
        sizes = [int(np.prod(s)) for s in shapes]
        cat = lambda vs: jnp.concatenate([v.reshape(-1) for v in vs]) \
            if k > 1 else vs[0].reshape(-1)
        pf, gf, m1f, m2f = cat(lp), cat(lg), cat(lm1), cat(lm2)
        if impl in ('pallas', 'interpret'):
            po, m1o, m2o = _fused_adam_flat(pf, gf, m1f, m2f, lrt, b1,
                                            b2, eps, impl == 'interpret')
        else:
            po, m1o, m2o = _adam_dense(pf, gf, m1f, m2f, lrt, b1, b2, eps)
        outs = []
        for which in (po, m1o, m2o):
            off = 0
            for s, sz in zip(shapes, sizes):
                outs.append(which[off:off + sz].reshape(s))
                off += sz
        return tuple(outs)

    in_specs = (P(),) + (spec,) * (4 * k)
    out_specs = (spec,) * (3 * k)
    outs = partitioned_call(inner, mesh, in_specs, out_specs)(
        lr_t, *(list(ps) + list(gs) + list(m1s) + list(m2s)))
    return outs[:k], outs[k:2 * k], outs[2 * k:]


def _fused_adam_flat(p, g, m1, m2, lr_t, b1, b2, eps, interpret):
    """One elementwise Pallas pass over the flattened-and-concatenated
    parameter set ([L] padded to (R, 128) tiles)."""
    import functools
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    L = p.shape[0]
    bn = 256
    row_bytes = bn * 128
    R = -(-L // row_bytes) * bn                  # rows, multiple of bn
    pad = R * 128 - L

    def shape2(v):
        return jnp.pad(v, (0, pad)).reshape(R, 128)

    lrt2 = lr_t.astype(jnp.float32).reshape(1, 1)
    spec = pl.BlockSpec((bn, 128), lambda i: (i, 0))
    po, m1o, m2o = pl.pallas_call(
        functools.partial(_fused_adam_kernel, float(b1), float(b2),
                          float(eps)),
        grid=(R // bn,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec, spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((R, 128), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='fused_adam',
    )(lrt2, shape2(p), shape2(g), shape2(m1), shape2(m2))
    return (po.reshape(-1)[:L], m1o.reshape(-1)[:L], m2o.reshape(-1)[:L])


@register_op('fused_adam')
def _fused_adam(ctx, op):
    """Whole-parameter-set Adam as ONE op (reference operators/fused — the
    multi_tensor_adam idea): list inputs Params/Grads/Moment1s/Moment2s/
    Beta1Pows/Beta2Pows, one LearningRate. Attribution-wise the entire
    update is a single unit (one row under PADDLE_PROFILE_OPS) instead of
    N per-param op dispatches.

    Tiers (ops/kernel_tier.py): 'off' applies the adam op's exact per-
    param expressions (bitwise legacy parity); 'xla' flattens+concats the
    dense group into one vector so the update is one fused elementwise
    loop; 'pallas'/'interpret' run that vector through one Pallas kernel.
    SelectedRows (sparse) grads always take the per-param row-wise path —
    the per-op fallback rule. The fused tiers read the FIRST fused
    param's beta-pows for the shared lr_t: every program this op is
    built into initializes and advances all beta-pow accumulators
    identically.

    Under an active >1-device mesh the update partitions instead of
    falling back: params group by their own PartitionSpec (the active
    runner's rules via parallel.api.get_active_param_spec) and each
    group runs per shard through kernel_tier.partitioned_call — local
    blocks flattened+concatenated, no all-gather of sharded state;
    replicated params take the replicated path, and a spec that does
    not evenly tile its param falls back per-param (_mesh_spec_ok).
    """
    from . import kernel_tier
    names_p = op.input('Params')
    ps = [ctx.get(n) for n in names_p]
    gs = [ctx.get(n) for n in op.input('Grads')]
    m1s = [ctx.get(n) for n in op.input('Moment1s')]
    m2s = [ctx.get(n) for n in op.input('Moment2s')]
    b1ps = [ctx.get(n) for n in op.input('Beta1Pows')]
    b2ps = [ctx.get(n) for n in op.input('Beta2Pows')]
    lr = _lr(ctx, op)
    b1 = op.attr('beta1', 0.9)
    b2 = op.attr('beta2', 0.999)
    eps = op.attr('epsilon', 1e-8)

    dense = [i for i, g in enumerate(gs)
             if not isinstance(g, SelectedRows)
             and ps[i].dtype == jnp.float32]
    from ..parallel.api import get_active_mesh, get_active_param_spec
    mesh = get_active_mesh()
    sharded = mesh is not None and mesh.size > 1
    groups = None
    if sharded and dense:
        # mesh-native path: partition each flattened segment by the
        # param's OWN PartitionSpec (kernel_tier.partitioned_call per
        # spec-group) — no all-gather of a sharded parameter set, and
        # replicated params take the replicated path. A param whose spec
        # does not evenly tile its shape falls back per-param.
        from jax.sharding import PartitionSpec as P
        spec_fn = get_active_param_spec() or (lambda n: P())
        groups = {}
        for i in dense:
            spec = spec_fn(names_p[i]) or P()
            if _mesh_spec_ok(mesh, spec, ps[i].shape):
                groups.setdefault(tuple(spec), []).append(i)
        fusable = sorted(i for idxs in groups.values() for i in idxs)
    else:
        fusable = list(dense)
    impl = kernel_tier.dispatch('fused_adam',
                                pallas_ok=bool(fusable),
                                xla_ok=bool(fusable), mesh=mesh)

    fused = set(fusable) if impl != 'off' else set()
    if fused:
        first = fusable[0]
        lr_t0 = lr * jnp.sqrt(1 - b2ps[first].reshape(())) \
            / (1 - b1ps[first].reshape(()))
        dense_g = lambda i: gs[i].astype(jnp.float32)
        if sharded:
            from jax.sharding import PartitionSpec as P
            for spec_key, idxs in sorted(groups.items(),
                                         key=lambda kv: kv[1][0]):
                po, m1o, m2o = _fused_adam_group_spmd(
                    mesh, P(*spec_key), [ps[i] for i in idxs],
                    [dense_g(i) for i in idxs],
                    [m1s[i] for i in idxs], [m2s[i] for i in idxs],
                    lr_t0, b1, b2, eps, impl)
                for j, i in enumerate(idxs):
                    ctx.out(op, 'ParamsOut', po[j], idx=i)
                    ctx.out(op, 'Moment1sOut', m1o[j], idx=i)
                    ctx.out(op, 'Moment2sOut', m2o[j], idx=i)
        else:
            sizes = [int(np.prod(ps[i].shape)) for i in fusable]
            cat = lambda vs: jnp.concatenate(
                [vs[i].reshape(-1) for i in fusable])
            p_f, g_f = cat(ps), cat([g.astype(jnp.float32) if not
                                     isinstance(g, SelectedRows) else g
                                     for g in gs])
            m1_f, m2_f = cat(m1s), cat(m2s)
            if impl in ('pallas', 'interpret'):
                po, m1o, m2o = _fused_adam_flat(
                    p_f, g_f, m1_f, m2_f, lr_t0, b1, b2, eps,
                    impl == 'interpret')
            else:
                po, m1o, m2o = _adam_dense(p_f, g_f, m1_f, m2_f, lr_t0,
                                           b1, b2, eps)
            off = 0
            for k, i in enumerate(fusable):
                sl = slice(off, off + sizes[k])
                ctx.out(op, 'ParamsOut', po[sl].reshape(ps[i].shape),
                        idx=i)
                ctx.out(op, 'Moment1sOut', m1o[sl].reshape(ps[i].shape),
                        idx=i)
                ctx.out(op, 'Moment2sOut', m2o[sl].reshape(ps[i].shape),
                        idx=i)
                off += sizes[k]

    for i in range(len(ps)):
        b1p = b1ps[i].reshape(())
        b2p = b2ps[i].reshape(())
        if i not in fused:
            p, g, m1, m2 = ps[i], gs[i], m1s[i], m2s[i]
            lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
            if isinstance(g, SelectedRows):
                po_i, m1o_i, m2o_i = _adam_sparse(p, g, m1, m2, lr_t,
                                                  b1, b2, eps)
            else:
                po_i, m1o_i, m2o_i = _adam_dense(
                    p, g.astype(p.dtype), m1, m2, lr_t, b1, b2, eps)
            ctx.out(op, 'ParamsOut', po_i, idx=i)
            ctx.out(op, 'Moment1sOut', m1o_i, idx=i)
            ctx.out(op, 'Moment2sOut', m2o_i, idx=i)
        ctx.out(op, 'Beta1PowsOut', (b1p * b1).reshape(1), idx=i)
        ctx.out(op, 'Beta2PowsOut', (b2p * b2).reshape(1), idx=i)


@register_op('adamax')
def _adamax(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    m = ctx.in1(op, 'Moment')
    inf = ctx.in1(op, 'InfNorm')
    b1p = ctx.in1(op, 'Beta1Pow').reshape(())
    lr = _lr(ctx, op)
    b1 = op.attr('beta1', 0.9)
    b2 = op.attr('beta2', 0.999)
    eps = op.attr('epsilon', 1e-8)
    mo = b1 * m + (1 - b1) * g
    info = jnp.maximum(b2 * inf, jnp.abs(g))
    lr_t = lr / (1 - b1p)
    ctx.out(op, 'ParamOut', p - lr_t * mo / (info + eps))
    ctx.out(op, 'MomentOut', mo)
    ctx.out(op, 'InfNormOut', info)


@register_op('adagrad')
def _adagrad(ctx, op):
    """reference operators/optimizers/adagrad_op.h (dense + SparseAdagrad:
    merged rows, moment/param updated row-wise)."""
    p = ctx.in1(op, 'Param')
    g = ctx.in1(op, 'Grad')
    m = ctx.in1(op, 'Moment')
    lr = _lr(ctx, op)
    eps = op.attr('epsilon', 1e-6)
    if isinstance(g, SelectedRows):
        rows, gv = g.merged()
        gv = gv.astype(p.dtype)
        m_r = m[rows] + gv * gv
        p_r = p[rows] - lr * gv / (jnp.sqrt(m_r) + eps)
        ctx.out(op, 'ParamOut', p.at[rows].set(p_r, mode='drop'))
        ctx.out(op, 'MomentOut', m.at[rows].set(m_r, mode='drop'))
        return
    mo = m + g * g
    ctx.out(op, 'ParamOut', p - lr * g / (jnp.sqrt(mo) + eps))
    ctx.out(op, 'MomentOut', mo)


@register_op('decayed_adagrad')
def _decayed_adagrad(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    m = ctx.in1(op, 'Moment')
    lr = _lr(ctx, op)
    decay = op.attr('decay', 0.95)
    eps = op.attr('epsilon', 1e-6)
    mo = decay * m + (1 - decay) * g * g
    ctx.out(op, 'ParamOut', p - lr * g / (jnp.sqrt(mo) + eps))
    ctx.out(op, 'MomentOut', mo)


@register_op('adadelta')
def _adadelta(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    eg = ctx.in1(op, 'AvgSquaredGrad')
    ex = ctx.in1(op, 'AvgSquaredUpdate')
    rho = op.attr('rho', 0.95)
    eps = op.attr('epsilon', 1e-6)
    ego = rho * eg + (1 - rho) * g * g
    update = -jnp.sqrt((ex + eps) / (ego + eps)) * g
    exo = rho * ex + (1 - rho) * update * update
    ctx.out(op, 'ParamOut', p + update)
    ctx.out(op, 'AvgSquaredGradOut', ego)
    ctx.out(op, 'AvgSquaredUpdateOut', exo)


@register_op('rmsprop')
def _rmsprop(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    ms = ctx.in1(op, 'MeanSquare')
    mom = ctx.in1(op, 'Moment')
    lr = _lr(ctx, op)
    rho = op.attr('decay', 0.95)
    eps = op.attr('epsilon', 1e-6)
    momentum = op.attr('momentum', 0.0)
    centered = op.attr('centered', False)
    mso = rho * ms + (1 - rho) * g * g
    ctx.out(op, 'MeanSquareOut', mso)
    if centered:
        mg = ctx.in1(op, 'MeanGrad')
        mgo = rho * mg + (1 - rho) * g
        denom = mso - mgo * mgo + eps
        ctx.out(op, 'MeanGradOut', mgo)
    else:
        denom = mso + eps
    momo = momentum * mom + lr * g / jnp.sqrt(denom)
    ctx.out(op, 'MomentOut', momo)
    ctx.out(op, 'ParamOut', p - momo)


@register_op('ftrl')
def _ftrl(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    sq = ctx.in1(op, 'SquaredAccumulator')
    lin = ctx.in1(op, 'LinearAccumulator')
    lr = _lr(ctx, op)
    l1 = op.attr('l1', 0.0)
    l2 = op.attr('l2', 0.0)
    power = op.attr('lr_power', -0.5)
    new_sq = sq + g * g
    sigma = (new_sq ** -power - sq ** -power) / lr
    lino = lin + g - sigma * p
    y = new_sq ** -power / lr + 2 * l2
    p_out = jnp.where(jnp.abs(lino) > l1,
                      (jnp.sign(lino) * l1 - lino) / y, 0.0)
    ctx.out(op, 'ParamOut', p_out)
    ctx.out(op, 'SquaredAccumOut', new_sq)
    ctx.out(op, 'LinearAccumOut', lino)


@register_op('proximal_gd')
def _proximal_gd(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    lr = _lr(ctx, op)
    l1 = op.attr('l1', 0.0)
    l2 = op.attr('l2', 0.0)
    prox = p - lr * g
    p_out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0) / \
        (1.0 + lr * l2)
    ctx.out(op, 'ParamOut', p_out)


@register_op('proximal_adagrad')
def _proximal_adagrad(ctx, op):
    p = ctx.in1(op, 'Param')
    g = _dense_grad(ctx, op)
    m = ctx.in1(op, 'Moment')
    lr = _lr(ctx, op)
    l1 = op.attr('l1', 0.0)
    l2 = op.attr('l2', 0.0)
    mo = m + g * g
    lr_t = lr / jnp.sqrt(mo)
    prox = p - lr_t * g
    p_out = jnp.sign(prox) * jnp.maximum(jnp.abs(prox) - lr_t * l1, 0.0) / \
        (1.0 + lr_t * l2)
    ctx.out(op, 'ParamOut', p_out)
    ctx.out(op, 'MomentOut', mo)


@register_op('average_accumulates')
def _average_accumulates(ctx, op):
    # ModelAverage support (reference optimizer.py:1484 + operators/
    # average_accumulates_op.cc): accumulate sums of params over windows.
    p = ctx.in1(op, 'param')
    sum1 = ctx.in1(op, 'in_sum_1')
    sum2 = ctx.in1(op, 'in_sum_2')
    sum3 = ctx.in1(op, 'in_sum_3')
    num_acc = ctx.in1(op, 'in_num_accumulates').reshape(())
    old_num = ctx.in1(op, 'in_old_num_accumulates').reshape(())
    num_upd = ctx.in1(op, 'in_num_updates').reshape(())
    avg_window = op.attr('average_window', 10000.0)
    max_avg = op.attr('max_average_window', 10000)
    min_avg = op.attr('min_average_window', 10000)
    k_max_num_accumulates = 16384  # reference average_accumulates_op.h
    num_acc = num_acc + 1
    num_upd = num_upd + 1
    sum1 = sum1 + p
    # periodic fold of sum1 into sum2 to bound fp error
    fold = (num_upd % k_max_num_accumulates) == 0
    sum2 = jnp.where(fold, sum2 + sum1, sum2)
    sum1 = jnp.where(fold, jnp.zeros_like(sum1), sum1)
    # window shift: reference condition uses min(max_window, updates*rate)
    window = jnp.minimum(jnp.asarray(float(max_avg)),
                         num_upd.astype(jnp.float32) * avg_window)
    do_shift = (num_acc >= min_avg) & \
        (num_acc.astype(jnp.float32) >= window)
    sum3o = jnp.where(do_shift, sum1 + sum2, sum3)
    sum1o = jnp.where(do_shift, jnp.zeros_like(sum1), sum1)
    sum2o = jnp.where(do_shift, jnp.zeros_like(sum2), sum2)
    old_o = jnp.where(do_shift, num_acc, old_num)
    acc_o = jnp.where(do_shift, jnp.zeros_like(num_acc), num_acc)
    ctx.out(op, 'out_sum_1', sum1o)
    ctx.out(op, 'out_sum_2', sum2o)
    ctx.out(op, 'out_sum_3', sum3o)
    ctx.out(op, 'out_num_accumulates', acc_o.reshape(1))
    ctx.out(op, 'out_old_num_accumulates', old_o.reshape(1))
    ctx.out(op, 'out_num_updates', num_upd.reshape(1))
