"""NN ops: softmax/losses, convolutions, pooling, normalization, resize.

Reference: operators/softmax_op.cc, cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, sigmoid_cross_entropy_with_logits_op.cc,
conv_op.cc (+conv_cudnn), conv_transpose_op.cc, pool_op.cc, batch_norm_op.cc,
layer_norm_op.cc, group_norm_op.cc, data_norm_op.cc, lrn_op.cc,
interpolate_op.cc, affine_channel_op.cc, nce_op.cc, hierarchical_sigmoid_op.cc.

Convs/matmuls use lax.conv_general_dilated / dot so XLA tiles them on the MXU;
bf16 inputs keep fp32 accumulation via preferred_element_type.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core import amp
from ..core.registry import register_op


@register_op('softmax')
def _softmax(ctx, op):
    x = ctx.in1(op, 'X')
    ctx.out(op, 'Out', jax.nn.softmax(x, axis=-1))


def _gather_label(x, label):
    lab = label.reshape(-1).astype(jnp.int32)
    return jnp.take_along_axis(x, lab[:, None], axis=-1), lab


@register_op('cross_entropy')
def _cross_entropy(ctx, op):
    x = ctx.in1(op, 'X')           # (N, C) probabilities
    label = ctx.in1(op, 'Label')
    soft_label = op.attr('soft_label', False)
    ignore_index = op.attr('ignore_index', -100)
    xc = jnp.clip(x, 1e-20, 1.0)
    if soft_label:
        out = -jnp.sum(label * jnp.log(xc), axis=-1, keepdims=True)
    else:
        p, lab = _gather_label(xc, label)
        out = -jnp.log(p)
        mask = (lab != ignore_index)[:, None]
        out = jnp.where(mask, out, 0.0)
    ctx.out(op, 'Y', out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce_hard(logits, lab, ignore_index):
    """Hard-label softmax cross entropy that residualizes ONLY the logits:
    the default AD path saves both logits and log_softmax — for an LM head
    that is two [tokens, vocab] HBM buffers; the analytic gradient
    softmax(x) - onehot needs just one."""
    lse = jax.scipy.special.logsumexp(
        logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits.astype(jnp.float32),
                                 lab[:, None], axis=-1)[:, 0]
    loss = lse - picked
    return jnp.where(lab != ignore_index, loss, 0.0)


def _ce_hard_fwd(logits, lab, ignore_index):
    return _ce_hard(logits, lab, ignore_index), (logits, lab)


def _ce_hard_bwd(ignore_index, res, ct):
    logits, lab = res
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=p.dtype)
    g = (p - onehot) * ct[:, None]
    g = jnp.where((lab != ignore_index)[:, None], g, 0.0)
    return g.astype(logits.dtype), None


_ce_hard.defvjp(_ce_hard_fwd, _ce_hard_bwd)


@register_op('softmax_with_cross_entropy')
def _softmax_with_ce(ctx, op):
    logits = ctx.in1(op, 'Logits')
    label = ctx.in1(op, 'Label')
    soft_label = op.attr('soft_label', False)
    ignore_index = op.attr('ignore_index', -100)
    if soft_label:
        log_sm = jax.nn.log_softmax(logits, axis=-1)
        ctx.out(op, 'Softmax', jnp.exp(log_sm))
        loss = -jnp.sum(label * log_sm, axis=-1, keepdims=True)
        ctx.out(op, 'Loss', loss)
        return
    lab = label.reshape(-1).astype(jnp.int32)
    impl = 'off'
    meshed = False
    if logits.ndim == 2:
        from . import kernel_tier
        from .ce_ops import (fused_softmax_ce, fused_softmax_ce_spmd,
                             pallas_shapes_ok, spmd_shapes_ok)
        from ..parallel.api import get_active_mesh
        mesh = get_active_mesh()
        meshed = mesh is not None and mesh.size > 1
        if meshed:
            # the kernel runs PER SHARD via kernel_tier.partitioned_call
            # (a pallas custom call cannot be auto-partitioned), so the
            # tiling rule applies to the post-partitioning local block
            pallas_ok = spmd_shapes_ok(mesh, logits.shape[0],
                                       logits.shape[1])
        else:
            pallas_ok = pallas_shapes_ok(logits.shape[0], logits.shape[1])
        impl = kernel_tier.dispatch(
            'softmax_with_cross_entropy', pallas_ok=pallas_ok, mesh=mesh,
            count=getattr(ctx, 'sparse_mode', None) != 'scout')
    if impl == 'off':
        loss = _ce_hard(logits, lab, ignore_index)
    elif meshed and impl in ('pallas', 'interpret'):
        # mesh-partitioned kernels: batch rows over 'data' (comms-free),
        # lse-aware all-reduce when 'model' shards the vocab
        loss = fused_softmax_ce_spmd(logits, lab, mesh, ignore_index,
                                     impl)
    else:
        # fused tier (ops/ce_ops.py): online-softmax single pass, backward
        # recomputed from (logits, lse) — no [N, V] one-hot/softmax
        # residual ever materializes. The xla emission is plain jnp, so
        # under a mesh the XLA SPMD partitioner shards it natively.
        loss = fused_softmax_ce(logits, lab, ignore_index, impl)
    ctx.out(op, 'Loss', loss[:, None])
    # the Softmax output only materializes if the program consumes it
    if op.output('Softmax'):
        ctx.out(op, 'Softmax', jax.nn.softmax(logits, axis=-1))


@register_op('sigmoid_cross_entropy_with_logits')
def _sigmoid_ce(ctx, op):
    x = ctx.in1(op, 'X')
    label = ctx.in1(op, 'Label')
    ignore_index = op.attr('ignore_index', -100)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    loss = jnp.where(label == ignore_index, 0.0, loss)
    ctx.out(op, 'Out', loss)


# ---------------------------------------------------------------------------
# Convolution / pooling
# ---------------------------------------------------------------------------

def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


@register_op('conv2d')
def _conv2d(ctx, op):
    x = ctx.in_nhwc(op, 'Input')   # channels-minor twin (or transposed)
    w = ctx.in1(op, 'Filter')      # OIHW (I = C/groups)
    strides = _pair(op.attr('strides', [1, 1]))
    pads = _pair(op.attr('paddings', [0, 0]))
    dilations = _pair(op.attr('dilations', [1, 1]))
    groups = op.attr('groups', 1) or 1
    out_dtype = x.dtype
    x, w = amp.cast_compute(op, x, w)
    # compute in NHWC: the TPU conv path is an order of magnitude faster
    # with channels-minor layouts (measured 11x on v5e). The output is
    # emitted as a layout twin (out_nhwc): downstream BN/pool/relu/
    # elementwise consume the NHWC value directly, so whole conv stacks
    # stay channels-minor in HBM (measured ~5x again over per-op
    # transpose round-trips) while env keeps the public NCHW contract.
    out = lax.conv_general_dilated(
        x, jnp.transpose(w, (2, 3, 1, 0)),
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'),
        feature_group_count=groups,
        preferred_element_type=amp.accum_dtype(x))
    ctx.out_nhwc(op, 'Output',
                 out.astype(amp.result_dtype(op, x, out_dtype)))


@register_op('depthwise_conv2d')
def _depthwise_conv2d(ctx, op):
    _conv2d(ctx, op)


@register_op('conv3d')
def _conv3d(ctx, op):
    x = ctx.in1(op, 'Input')       # NCDHW
    w = ctx.in1(op, 'Filter')
    strides = _pair(op.attr('strides', [1, 1, 1]), 3)
    pads = _pair(op.attr('paddings', [0, 0, 0]), 3)
    dilations = _pair(op.attr('dilations', [1, 1, 1]), 3)
    groups = op.attr('groups', 1) or 1
    out_dtype = x.dtype
    x, w = amp.cast_compute(op, x, w)
    # NDHWC internally — same channels-minor win as conv2d
    out = lax.conv_general_dilated(
        jnp.transpose(x, (0, 2, 3, 4, 1)),
        jnp.transpose(w, (2, 3, 4, 1, 0)),
        window_strides=strides,
        padding=[(p, p) for p in pads], rhs_dilation=dilations,
        dimension_numbers=('NDHWC', 'DHWIO', 'NDHWC'),
        feature_group_count=groups,
        preferred_element_type=amp.accum_dtype(x))
    ctx.out(op, 'Output',
            jnp.transpose(out, (0, 4, 1, 2, 3)).astype(out_dtype))


def _transpose_kernel(w, groups, n_sp):
    """(C_in, C_out/g, k...) deconv filter -> (C_out, C_in/g, k...) conv
    kernel with flipped spatial dims, handling groups (reference
    conv_transpose_op.cc grouped deconvolution)."""
    c_in = w.shape[0]
    c_out_g = w.shape[1]
    sp = w.shape[2:]
    if groups == 1:
        k = jnp.swapaxes(w, 0, 1)
    else:
        k = w.reshape((groups, c_in // groups, c_out_g) + sp)
        k = jnp.swapaxes(k, 1, 2)
        k = k.reshape((groups * c_out_g, c_in // groups) + sp)
    flip = (slice(None), slice(None)) + (slice(None, None, -1),) * n_sp
    return k[flip]


@register_op('conv2d_transpose')
def _conv2d_transpose(ctx, op):
    x = ctx.in1(op, 'Input')       # NCHW
    w = ctx.in1(op, 'Filter')      # (C_in, C_out/groups, kh, kw)
    strides = _pair(op.attr('strides', [1, 1]))
    pads = _pair(op.attr('paddings', [0, 0]))
    dilations = _pair(op.attr('dilations', [1, 1]))
    groups = op.attr('groups', 1) or 1
    kh = (w.shape[2] - 1) * dilations[0] + 1
    kw = (w.shape[3] - 1) * dilations[1] + 1
    out_dtype = x.dtype
    x, w = amp.cast_compute(op, x, w)
    # gradient-of-conv formulation: lhs-dilate input by stride
    out = lax.conv_general_dilated(
        x, _transpose_kernel(w, groups, 2),
        window_strides=(1, 1),
        padding=[(kh - 1 - pads[0], kh - 1 - pads[0]),
                 (kw - 1 - pads[1], kw - 1 - pads[1])],
        lhs_dilation=strides, rhs_dilation=dilations,
        dimension_numbers=('NCHW', 'OIHW', 'NCHW'),
        feature_group_count=groups,
        preferred_element_type=amp.accum_dtype(x))
    ctx.out(op, 'Output', out.astype(out_dtype))


@register_op('depthwise_conv2d_transpose')
def _depthwise_conv2d_transpose(ctx, op):
    _conv2d_transpose(ctx, op)


def _pool(x, ksize, strides, pads, ptype, exclusive, adaptive, global_pool,
          ceil_mode, channels_last=False):
    """Window pooling. channels_last=True pools a channels-minor (NHWC)
    value — the layout-twin path that keeps conv stacks transpose-free."""
    n_sp = len(ksize)
    sp0 = 1 if channels_last else 2         # first spatial axis
    sp_shape = x.shape[sp0:sp0 + n_sp]
    if global_pool:
        ksize = sp_shape
        pads = (0,) * n_sp
        strides = (1,) * n_sp
    if adaptive:
        # adaptive: output size = ksize; use even splits
        out_sz = ksize
        in_sz = sp_shape
        strides = tuple(i // o for i, o in zip(in_sz, out_sz))
        ksize = tuple(i - (o - 1) * s for i, o, s in
                      zip(in_sz, out_sz, strides))
        pads = (0,) * n_sp
    if channels_last:
        window = (1,) + tuple(ksize) + (1,)
        strides_full = (1,) + tuple(strides) + (1,)
        sp_pad = [(p, p) for p in pads]
    else:
        window = (1, 1) + tuple(ksize)
        strides_full = (1, 1) + tuple(strides)
        sp_pad = [(p, p) for p in pads]
    if ceil_mode:
        sp_pad = []
        for i, (p, k, s) in enumerate(zip(pads, ksize, strides)):
            in_dim = sp_shape[i]
            out_dim = -(-(in_dim + 2 * p - k) // s) + 1  # ceil
            needed = (out_dim - 1) * s + k - in_dim - p
            sp_pad.append((p, max(p, needed)))
    pad_full = ([(0, 0)] + sp_pad + [(0, 0)]) if channels_last else \
        ([(0, 0), (0, 0)] + sp_pad)
    if ptype == 'max':
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides_full,
                                 pad_full)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides_full, pad_full)
    if exclusive:
        cnt = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, window,
                                strides_full, pad_full)
        return s / cnt
    return s / float(np.prod(ksize))


@register_op('pool2d')
def _pool2d(ctx, op):
    args = (_pair(op.attr('ksize')), _pair(op.attr('strides', [1, 1])),
            _pair(op.attr('paddings', [0, 0])),
            op.attr('pooling_type', 'max'),
            op.attr('exclusive', True), op.attr('adaptive', False),
            op.attr('global_pooling', False), op.attr('ceil_mode', False))
    if ctx.has_nhwc(op, 'X'):
        ctx.out_nhwc(op, 'Out', _pool(ctx.in_nhwc(op, 'X'), *args,
                                      channels_last=True))
    else:
        ctx.out(op, 'Out', _pool(ctx.in1(op, 'X'), *args))


@register_op('pool3d')
def _pool3d(ctx, op):
    x = ctx.in1(op, 'X')
    out = _pool(x, _pair(op.attr('ksize'), 3),
                _pair(op.attr('strides', [1, 1, 1]), 3),
                _pair(op.attr('paddings', [0, 0, 0]), 3),
                op.attr('pooling_type', 'max'),
                op.attr('exclusive', True), op.attr('adaptive', False),
                op.attr('global_pooling', False), op.attr('ceil_mode', False))
    ctx.out(op, 'Out', out)


@register_op('max_pool2d_with_index')
def _max_pool2d_with_index(ctx, op):
    """reference pool_with_index_op.cc: Mask carries real flat argmax
    positions into H*W (consumed by unpool)."""
    from .misc_ops import _pool_with_index
    x = ctx.in1(op, 'X')
    ksize = _pair(op.attr('ksize'))
    strides = _pair(op.attr('strides', [1, 1]))
    pads = _pair(op.attr('paddings', [0, 0]))
    if op.attr('global_pooling', False):
        ksize = x.shape[-2:]
        strides = (1, 1)
        pads = (0, 0)
    vals, mask = _pool_with_index(x, ksize, strides, pads,
                                  adaptive=op.attr('adaptive', False))
    ctx.out(op, 'Out', vals)
    ctx.out(op, 'Mask', mask)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------

@register_op('batch_norm')
def _batch_norm(ctx, op):
    # layout-twin path: when the producer left an NHWC twin (conv/pool),
    # normalize channels-minor — stats reduce over leading axes and the
    # affine broadcasts on the minor dim, so the conv stack never
    # materializes NCHW between ops
    twin = ctx.has_nhwc(op, 'X') and ctx.get(op.input('X')[0]).ndim == 4 \
        and op.attr('data_layout', 'NCHW') == 'NCHW'
    x = ctx.in_nhwc(op, 'X') if twin else ctx.in1(op, 'X')
    scale = ctx.in1(op, 'Scale')
    bias = ctx.in1(op, 'Bias')
    mean = ctx.in1(op, 'Mean')
    var = ctx.in1(op, 'Variance')
    x = amp.cast_compute(op, x)
    momentum = op.attr('momentum', 0.9)
    eps = op.attr('epsilon', 1e-5)
    is_test = op.attr('is_test', False)
    layout = 'NHWC' if twin else op.attr('data_layout', 'NCHW')
    use_global = op.attr('use_global_stats', False) or is_test

    if layout == 'NCHW':
        axes = (0,) + tuple(range(2, x.ndim))
        bshape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        axes = tuple(range(x.ndim - 1))
        bshape = (1,) * (x.ndim - 1) + (-1,)

    if use_global:
        m, v = mean, var
        ctx.out(op, 'MeanOut', mean)
        ctx.out(op, 'VarianceOut', var)
    else:
        # statistics ALWAYS accumulate in f32 (a bf16 mean over ~1e5
        # elements loses precision); running stats stay f32 state.
        # Two-pass mean/var (jnp.var): the one-pass E[x^2]-E[x]^2 form
        # cancels catastrophically for channels with large mean and tiny
        # variance (|m|^2*eps swamps the true variance)
        xf = x.astype(jnp.float32)
        m = jnp.mean(xf, axis=axes)
        v = jnp.var(xf, axis=axes)
        ctx.out(op, 'MeanOut',
                momentum * mean + (1.0 - momentum) * lax.stop_gradient(m))
        ctx.out(op, 'VarianceOut',
                momentum * var + (1.0 - momentum) * lax.stop_gradient(v))
    ctx.out(op, 'SavedMean', m)
    ctx.out(op, 'SavedVariance', 1.0 / jnp.sqrt(v + eps))
    xn = (x - m.reshape(bshape)) / jnp.sqrt(v.reshape(bshape) + eps)
    y = xn * scale.reshape(bshape) + bias.reshape(bshape)
    if twin:
        ctx.out_nhwc(op, 'Y', y.astype(x.dtype))
    else:
        ctx.out(op, 'Y', y.astype(x.dtype))


@register_op('layer_norm')
def _layer_norm(ctx, op):
    x = ctx.in1(op, 'X')
    scale = ctx.in1(op, 'Scale')
    bias = ctx.in1(op, 'Bias')
    eps = op.attr('epsilon', 1e-5)
    bna = op.attr('begin_norm_axis', 1)
    axes = tuple(range(bna, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    y = (x - m) / jnp.sqrt(v + eps)
    tail = x.shape[bna:]
    if scale is not None:
        y = y * scale.reshape((1,) * bna + tail)
    if bias is not None:
        y = y + bias.reshape((1,) * bna + tail)
    ctx.out(op, 'Y', y)
    ctx.out(op, 'Mean', m.reshape(x.shape[:bna]).reshape(-1))
    ctx.out(op, 'Variance', v.reshape(x.shape[:bna]).reshape(-1))


# ---------------------------------------------------------------------------
# Fused LayerNorm + residual-add — the 4th kernel-tier unit
# (ops/kernel_tier.py). The pre-norm transformer block pays this pair
# twice per layer (residual add feeding the next norm); fusing them keeps
# the summed row in VMEM across both (one HBM pass), the fwd computes
# mean/rstddev in that same sweep, and the bwd recomputes x_hat from the
# saved O(N) stats instead of residualizing any normalized [N, D] tensor.
# ---------------------------------------------------------------------------

def ln_res_shapes_ok(n, d):
    """Tiling rule: d fills whole lanes; the row count tiles a
    power-of-two block bn whose (1, bn) row-statistics block Mosaic
    accepts (whole 128-lane tiles, or all n rows at once); and the four
    (bn, d) f32 row blocks either kernel streams, double-buffered, stay
    under 12 MB of the 16 MB scoped VMEM limit (bn 128: d <= 3072 — at
    d 4096 Mosaic's own accounting reads 16.03 MB and refuses)."""
    from .ce_ops import _pick_block
    bn = _pick_block(n, 128, 8)
    return d % 128 == 0 and bn in (128, n) and \
        8 * bn * d * 4 <= 12 * 1024 * 1024


def ln_res_spmd_ok(mesh, n, d):
    """Per-shard rule under a mesh: rows partition over 'data'."""
    from .kernel_tier import mesh_axis
    ax = mesh_axis(mesh, 'data', n)
    n_loc = n // mesh.shape[ax] if ax else n
    return ln_res_shapes_ok(n_loc, d)


def _ln_res_fwd_kernel(eps, x_ref, r_ref, sc_ref, b_ref,
                       s_ref, y_ref, m_ref, rs_ref):
    s = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    m = jnp.mean(s, axis=-1, keepdims=True)
    c = s - m
    rstd = 1.0 / jnp.sqrt(jnp.mean(c * c, axis=-1, keepdims=True) + eps)
    s_ref[...] = s.astype(s_ref.dtype)
    y_ref[...] = (c * rstd * sc_ref[...] + b_ref[...]).astype(y_ref.dtype)
    m_ref[0] = m[:, 0]
    rs_ref[0] = rstd[:, 0]


def _ln_res_fwd_pallas(x, r, scale, bias, eps, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ce_ops import _pick_block
    n, d = x.shape
    bn = _pick_block(n, 128, 8)
    row = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat = pl.BlockSpec((1, bn), lambda i: (0, i))
    s, y, m, rs = pl.pallas_call(
        functools.partial(_ln_res_fwd_kernel, float(eps)),
        grid=(n // bn,),
        in_specs=[row, row, vec, vec],
        out_specs=[row, row, stat, stat],
        out_shape=[jax.ShapeDtypeStruct((n, d), x.dtype),
                   jax.ShapeDtypeStruct((n, d), x.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='fused_ln_residual_fwd',
    )(x, r, scale.reshape(1, d), bias.reshape(1, d))
    return s, y, m[0], rs[0]


def _ln_res_bwd_kernel(s_ref, m_ref, rs_ref, sc_ref, dy_ref, ds_ref,
                       dx_ref):
    s = s_ref[...].astype(jnp.float32)
    m = m_ref[0][:, None]
    rstd = rs_ref[0][:, None]
    xhat = (s - m) * rstd
    dyw = dy_ref[...].astype(jnp.float32) * sc_ref[...]
    mean1 = jnp.mean(dyw, axis=-1, keepdims=True)
    mean2 = jnp.mean(dyw * xhat, axis=-1, keepdims=True)
    dx = rstd * (dyw - mean1 - xhat * mean2)
    dx_ref[...] = (dx + ds_ref[...].astype(jnp.float32)).astype(
        dx_ref.dtype)


def _ln_res_bwd_pallas(s, m, rs, scale, dy, ds, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ce_ops import _pick_block
    n, d = s.shape
    bn = _pick_block(n, 128, 8)
    row = pl.BlockSpec((bn, d), lambda i: (i, 0))
    vec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat = pl.BlockSpec((1, bn), lambda i: (0, i))
    return pl.pallas_call(
        _ln_res_bwd_kernel,
        grid=(n // bn,),
        in_specs=[row, stat, stat, vec, row, row],
        out_specs=[row],
        out_shape=[jax.ShapeDtypeStruct((n, d), s.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='fused_ln_residual_bwd',
    )(s, m[None, :], rs[None, :], scale.reshape(1, d), dy, ds)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_ln_residual(x, r, scale, bias, eps, impl):
    """(y, s) for rows x, r [N, D]: s = x + r, y = LN(s) * scale + bias.
    ``impl`` in 'xla' | 'pallas' | 'interpret' (the 'off' tier lowers the
    legacy composition and never reaches here). Both outputs are consumed
    (y feeds the next sublayer, s carries the residual stream), so the
    bwd merges both cotangents; x_hat is recomputed from (s, mean, rstd)
    — O(N) residual stats, no [N, D] normalized tensor saved."""
    return _ln_res_fwd(x, r, scale, bias, eps, impl)[0]


def _ln_res_fwd(x, r, scale, bias, eps, impl):
    if impl in ('pallas', 'interpret'):
        s, y, m, rs = _ln_res_fwd_pallas(x, r, scale, bias, eps,
                                         impl == 'interpret')
    else:
        s = x + r
        sf = s.astype(jnp.float32)
        m = jnp.mean(sf, axis=-1)
        c = sf - m[:, None]
        rs = 1.0 / jnp.sqrt(jnp.mean(c * c, axis=-1) + eps)
        y = (c * rs[:, None] * scale + bias).astype(x.dtype)
    return (y, s), (s, m, rs, scale)


def _ln_res_bwd(eps, impl, res, cts):
    dy, ds = cts
    s, m, rs, scale = res
    if impl in ('pallas', 'interpret'):
        dx = _ln_res_bwd_pallas(s, m, rs, scale, dy, ds,
                                impl == 'interpret')
    else:
        sf = s.astype(jnp.float32)
        xhat = (sf - m[:, None]) * rs[:, None]
        dyw = dy.astype(jnp.float32) * scale
        mean1 = jnp.mean(dyw, axis=-1, keepdims=True)
        mean2 = jnp.mean(dyw * xhat, axis=-1, keepdims=True)
        dx = (rs[:, None] * (dyw - mean1 - xhat * mean2)
              + ds.astype(jnp.float32)).astype(s.dtype)
    # scale/bias grads: plain jnp reductions over the recomputed x_hat —
    # XLA fuses them into one pass over s; nothing [N, D] is saved
    xhat_f = (s.astype(jnp.float32) - m[:, None]) * rs[:, None]
    dscale = jnp.sum(dy.astype(jnp.float32) * xhat_f,
                     axis=0).astype(scale.dtype)
    dbias = jnp.sum(dy.astype(jnp.float32), axis=0).astype(scale.dtype)
    return dx, dx, dscale, dbias


fused_ln_residual.defvjp(_ln_res_fwd, _ln_res_bwd)


def fused_ln_residual_spmd(x, r, scale, bias, mesh, eps, impl):
    """Mesh-partitioned LN+residual: rows over 'data' via
    kernel_tier.partitioned_call — normalization is per-row, so the
    partitioned kernel needs no comms at all; scale/bias ride replicated
    and their cotangents psum through shard_map's transpose."""
    from jax.sharding import PartitionSpec as P
    from .kernel_tier import partitioned_call, mesh_axis
    data_ax = mesh_axis(mesh, 'data', x.shape[0])
    rowp = P(data_ax, None)

    def inner(xl, rl, sc, b):
        return fused_ln_residual(xl, rl, sc, b, eps, impl)

    return partitioned_call(inner, mesh, (rowp, rowp, P(), P()),
                            (rowp, rowp))(x, r, scale, bias)


@register_op('fused_ln_residual')
def _fused_ln_residual_op(ctx, op):
    """Program-level op: Y = layer_norm(X + Residual) * Scale + Bias,
    ResidualOut = X + Residual (both consumed: Y feeds the next sublayer,
    ResidualOut carries the residual stream). Attrs epsilon,
    begin_norm_axis (the normalized tail must be the LAST axis — the
    transformer wiring's case; anything else falls to 'off'). The 'off'
    tier reproduces elementwise_add + layer_norm BITWISE."""
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    x = ctx.in1(op, 'X')
    r = ctx.in1(op, 'Residual')
    scale = ctx.in1(op, 'Scale')
    bias = ctx.in1(op, 'Bias')
    eps = op.attr('epsilon', 1e-5)
    bna = op.attr('begin_norm_axis', x.ndim - 1)
    fusable = scale is not None and bias is not None and \
        bna == x.ndim - 1 and x.ndim >= 2
    n = int(np.prod(x.shape[:-1])) if fusable else 0
    d = x.shape[-1] if fusable else 0
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    if fusable:
        pallas_ok = ln_res_spmd_ok(mesh, n, d) if meshed \
            else ln_res_shapes_ok(n, d)
    else:
        pallas_ok = False
    impl = kernel_tier.dispatch(
        'fused_ln_residual', pallas_ok=pallas_ok, xla_ok=fusable,
        mesh=mesh, count=getattr(ctx, 'sparse_mode', None) != 'scout')
    if impl == 'off':
        # bitwise legacy: exactly the elementwise_add + layer_norm
        # lowerings composed (the parity anchor)
        s = x + r
        axes = tuple(range(bna, x.ndim))
        m = jnp.mean(s, axis=axes, keepdims=True)
        v = jnp.var(s, axis=axes, keepdims=True)
        y = (s - m) / jnp.sqrt(v + eps)
        tail = s.shape[bna:]
        if scale is not None:
            y = y * scale.reshape((1,) * bna + tail)
        if bias is not None:
            y = y + bias.reshape((1,) * bna + tail)
        ctx.out(op, 'Y', y)
        ctx.out(op, 'ResidualOut', s)
        return
    lead = x.shape[:-1]
    x2 = x.reshape(n, d)
    r2 = r.reshape(n, d)
    if meshed and impl in ('pallas', 'interpret'):
        y2, s2 = fused_ln_residual_spmd(x2, r2, scale, bias, mesh, eps,
                                        impl)
    else:
        y2, s2 = fused_ln_residual(x2, r2, scale, bias, eps, impl)
    ctx.out(op, 'Y', y2.reshape(lead + (d,)))
    ctx.out(op, 'ResidualOut', s2.reshape(lead + (d,)))


@register_op('group_norm')
def _group_norm(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    scale = ctx.in1(op, 'Scale')
    bias = ctx.in1(op, 'Bias')
    eps = op.attr('epsilon', 1e-5)
    groups = op.attr('groups')
    n, c = x.shape[:2]
    sp = x.shape[2:]
    xg = x.reshape((n, groups, c // groups) + sp)
    axes = tuple(range(2, xg.ndim))
    m = jnp.mean(xg, axis=axes, keepdims=True)
    v = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - m) / jnp.sqrt(v + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * len(sp)
    if scale is not None:
        y = y * scale.reshape(bshape)
    if bias is not None:
        y = y + bias.reshape(bshape)
    ctx.out(op, 'Y', y)
    ctx.out(op, 'Mean', m.reshape(n, groups))
    ctx.out(op, 'Variance', v.reshape(n, groups))


@register_op('data_norm')
def _data_norm(ctx, op):
    x = ctx.in1(op, 'X')
    sizes = ctx.in1(op, 'BatchSize')
    sums = ctx.in1(op, 'BatchSum')
    sqs = ctx.in1(op, 'BatchSquareSum')
    means = sums / sizes
    scales = jnp.sqrt(sizes / (sqs - sums * means + 1e-4))
    ctx.out(op, 'Means', means)
    ctx.out(op, 'Scales', scales)
    ctx.out(op, 'Y', (x - means) * scales)


@register_op('lrn')
def _lrn(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    n_ = op.attr('n', 5)
    k = op.attr('k', 2.0)
    alpha = op.attr('alpha', 1e-4)
    beta = op.attr('beta', 0.75)
    sq = x * x
    half = n_ // 2
    acc = lax.reduce_window(sq, 0.0, lax.add, (1, n_, 1, 1), (1, 1, 1, 1),
                            [(0, 0), (half, n_ - 1 - half), (0, 0), (0, 0)])
    mid = (k + alpha * acc) ** beta
    ctx.out(op, 'MidOut', mid)
    ctx.out(op, 'Out', x / mid)


@register_op('affine_channel')
def _affine_channel(ctx, op):
    x = ctx.in1(op, 'X')
    scale = ctx.in1(op, 'Scale')
    bias = ctx.in1(op, 'Bias')
    layout = op.attr('data_layout', 'NCHW')
    if layout == 'NCHW':
        bshape = (1, -1) + (1,) * (x.ndim - 2)
    else:
        bshape = (1,) * (x.ndim - 1) + (-1,)
    ctx.out(op, 'Out', x * scale.reshape(bshape) + bias.reshape(bshape))


# ---------------------------------------------------------------------------
# Resize / interpolate
# ---------------------------------------------------------------------------

def _interp_sizes(op, x):
    out_h = op.attr('out_h', -1)
    out_w = op.attr('out_w', -1)
    scale = op.attr('scale', 0.0)
    if scale and (not out_h or out_h <= 0):
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    return out_h, out_w


@register_op('bilinear_interp')
def _bilinear_interp(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    out_h, out_w = _interp_sizes(op, x)
    align = op.attr('align_corners', True)
    h, w = x.shape[2], x.shape[3]

    def src_idx(out_sz, in_sz):
        if align and out_sz > 1:
            return jnp.arange(out_sz) * ((in_sz - 1.0) / (out_sz - 1.0))
        ratio = in_sz / out_sz
        return jnp.maximum((jnp.arange(out_sz) + 0.5) * ratio - 0.5, 0.0) \
            if not align else jnp.zeros(out_sz)

    ys = src_idx(out_h, h)
    xs = src_idx(out_w, w)
    y0 = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, h - 1)
    x0 = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, w - 1)
    y1 = jnp.clip(y0 + 1, 0, h - 1)
    x1 = jnp.clip(x0 + 1, 0, w - 1)
    wy = (ys - y0).reshape(1, 1, -1, 1)
    wx = (xs - x0).reshape(1, 1, 1, -1)
    g = lambda yy, xx: x[:, :, yy, :][:, :, :, xx]
    out = (g(y0, x0) * (1 - wy) * (1 - wx) + g(y1, x0) * wy * (1 - wx) +
           g(y0, x1) * (1 - wy) * wx + g(y1, x1) * wy * wx)
    ctx.out(op, 'Out', out.astype(x.dtype))


@register_op('nearest_interp')
def _nearest_interp(ctx, op):
    x = ctx.in1(op, 'X')
    out_h, out_w = _interp_sizes(op, x)
    align = op.attr('align_corners', True)
    h, w = x.shape[2], x.shape[3]
    if align and out_h > 1:
        ys = jnp.round(jnp.arange(out_h) * ((h - 1.0) / (out_h - 1.0)))
        xs = jnp.round(jnp.arange(out_w) * ((w - 1.0) / (out_w - 1.0)))
    else:
        ys = jnp.floor(jnp.arange(out_h) * (h / out_h))
        xs = jnp.floor(jnp.arange(out_w) * (w / out_w))
    ys = jnp.clip(ys.astype(jnp.int32), 0, h - 1)
    xs = jnp.clip(xs.astype(jnp.int32), 0, w - 1)
    ctx.out(op, 'Out', x[:, :, ys, :][:, :, :, xs])


# ---------------------------------------------------------------------------
# Sampled / hierarchical losses
# ---------------------------------------------------------------------------

@register_op('nce')
def _nce(ctx, op):
    # Noise-contrastive estimation: full-softmax equivalent computation on
    # TPU (dense matmul beats gather-sampling on MXU for moderate vocab);
    # sampling path kept for parity (reference operators/nce_op.cc).
    x = ctx.in1(op, 'Input')          # (N, D)
    label = ctx.in1(op, 'Label')      # (N, num_true)
    w = ctx.in1(op, 'Weight')         # (V, D)
    b = ctx.in1(op, 'Bias')           # (V,)
    num_neg = op.attr('num_neg_samples', 10)
    key = ctx.rng()
    n = x.shape[0]
    v = w.shape[0]
    neg = jax.random.randint(key, (n, num_neg), 0, v)
    lab = label[:, :1].reshape(-1).astype(jnp.int32)
    ids = jnp.concatenate([lab[:, None], neg], axis=1)       # (N, 1+num_neg)
    wg = w[ids]                                              # (N, S, D)
    logits = jnp.einsum('nd,nsd->ns', x, wg)
    if b is not None:
        logits = logits + b[ids]
    p_noise = 1.0 / v
    logits = logits - jnp.log(num_neg * p_noise)
    labels01 = jnp.concatenate(
        [jnp.ones((n, 1)), jnp.zeros((n, num_neg))], axis=1)
    loss = jnp.maximum(logits, 0) - logits * labels01 + \
        jnp.log1p(jnp.exp(-jnp.abs(logits)))
    ctx.out(op, 'Cost', jnp.sum(loss, axis=1, keepdims=True))
    ctx.out(op, 'SampleLogits', logits)
    ctx.out(op, 'SampleLabels', ids.astype(jnp.int64))


@register_op('hierarchical_sigmoid')
def _hsigmoid(ctx, op):
    # Default (complete binary tree) mode of reference hsigmoid
    # (operators/hierarchical_sigmoid_op.cc + math/matrix_bit_code.h).
    x = ctx.in1(op, 'X')              # (N, D)
    w = ctx.in1(op, 'W')              # (num_classes-1, D)
    label = ctx.in1(op, 'Label')      # (N, 1)
    bias = ctx.in1(op, 'Bias')
    num_classes = op.attr('num_classes')
    code_len = int(np.ceil(np.log2(num_classes)))
    lab = label.reshape(-1).astype(jnp.int32) + num_classes  # leaf index
    losses = []
    node = lab
    for _ in range(code_len):
        parent = node // 2
        sign = (node % 2).astype(x.dtype)          # 1 if right child
        idx = jnp.clip(parent - 1, 0, w.shape[0] - 1)
        valid = (parent >= 1) & (parent - 1 < w.shape[0])
        logit = jnp.einsum('nd,nd->n', x, w[idx])
        if bias is not None:
            logit = logit + bias.reshape(-1)[idx]
        l = jnp.maximum(logit, 0) - logit * sign + \
            jnp.log1p(jnp.exp(-jnp.abs(logit)))
        losses.append(jnp.where(valid, l, 0.0))
        node = parent
    ctx.out(op, 'Out', jnp.stack(losses, 1).sum(1, keepdims=True))
    ctx.out(op, 'PreOut', jnp.zeros((x.shape[0], code_len), dtype=x.dtype))


@register_op('sample_logits')
def _sample_logits(ctx, op):
    logits = ctx.in1(op, 'Logits')
    labels = ctx.in1(op, 'Labels')
    num_samples = op.attr('num_samples')
    key = ctx.rng()
    n, v = logits.shape
    neg = jax.random.randint(key, (n, num_samples), 0, v)
    ids = jnp.concatenate([labels.astype(jnp.int32), neg], axis=1)
    out = jnp.take_along_axis(logits, ids, axis=1)
    ctx.out(op, 'SampledLogits', out)
    ctx.out(op, 'Samples', ids.astype(jnp.int64))
    ctx.out(op, 'SampledLabels',
            jnp.zeros((n, labels.shape[1]), dtype=jnp.int64))
    ctx.out(op, 'Probabilities', jnp.full_like(out, 1.0 / v))


@register_op('im2sequence', share_lod=False)
def _im2sequence(ctx, op):
    x = ctx.in1(op, 'X')  # NCHW
    kernels = op.attr('kernels')
    strides = op.attr('strides', [1, 1])
    paddings = op.attr('paddings', [0, 0, 0, 0])
    n, c, h, w = x.shape
    kh, kw = kernels
    xp = jnp.pad(x, [(0, 0), (0, 0), (paddings[0], paddings[2]),
                     (paddings[1], paddings[3])])
    oh = (xp.shape[2] - kh) // strides[0] + 1
    ow = (xp.shape[3] - kw) // strides[1] + 1
    patches = []
    for i in range(kh):
        for j in range(kw):
            patches.append(
                xp[:, :, i:i + oh * strides[0]:strides[0],
                   j:j + ow * strides[1]:strides[1]])
    out = jnp.stack(patches, axis=2).reshape(n, c * kh * kw, oh * ow)
    out = out.transpose(0, 2, 1).reshape(n * oh * ow, c * kh * kw)
    ctx.out(op, 'Out', out)
