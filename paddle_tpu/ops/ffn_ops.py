"""Fused FFN tail: matmul + bias + gelu + matmul + bias (+ dropout) as
one kernel-tier unit — MFU push round 4 (BENCH_r06 top_offenders rank
``dropout``/``gelu``/the residual ``layer_norm`` rows as the remaining
unfused tail of the flagship LM; the reference collapses exactly this
composition in operators/fused/fused_feedforward_op).

The unit covers the transformer block's whole FFN sublayer:

    y = dropout(gelu(x @ W1 + b1) @ W2 + b2)

Tiers (ops/kernel_tier.py):
- off:       the mul -> elementwise_add -> gelu -> mul ->
             elementwise_add -> dropout lowerings composed, expression
             for expression (the bitwise parity anchor, amp casts
             included);
- xla:       one fused emission under a custom_vjp: the backward saves
             (x, pre1) and recomputes gelu(pre1) instead of keeping the
             [N, d_ff] activation as a residual — one fewer d_ff-wide
             tensor in HBM than jax AD of the unfused chain;
- pallas:    a tiled matmul-epilogue kernel: each row block runs
             x @ W1 + b1, gelu, @ W2 + b2 (and the dropout multiply)
             without the [bn, d_ff] intermediate ever visiting HBM;
             backward shares the xla tier's recompute emission (its
             gradient is three MXU matmuls XLA already schedules well);
- interpret: the pallas kernel through the interpreter (CPU tests).

Dropout RNG: the op draws ONE key from the program's counted stream
(core/lowering.py ctx.rng(): run counter + op index), so masks replay
exactly across checkpoint save/restore and are identical across tiers
within one program build. Because the fused op replaces six ops with
one, op indices downstream SHIFT relative to the unfused build — masks
therefore differ between fused and unfused program STRUCTURES (the same
precedent fused_ln_residual set in PR 11); bitwise off-tier parity is
asserted for dropout-free/is_test trajectories, which is also the only
regime the pre-PR trajectory tests pin.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..core import amp
from ..core.registry import register_op
from .common import broadcast_y_to, flatten_to_2d


def ffn_shapes_ok(n, d_in, d_ff, d_out):
    """Tiling rule for the pallas kernel: every matmul axis fills whole
    128-lane tiles, the row count tiles a power-of-two block, and both
    weight panels (+ one row block of every operand) fit VMEM together
    (f32 budget ~12 MB of the 16 MB scoped limit). Mosaic keeps ONE copy
    of a block whose index never changes — the weight panels — and
    double-buffers the row blocks, which the doubled d_ff/d_out row
    terms stand for; at d512 ff2048 (admitted) its own accounting reads
    ~11 MB, at d640 ff2560 (refused here) 16.25 MB."""
    from .ce_ops import _pick_block
    if d_in % 128 or d_ff % 128 or d_out % 128:
        return False
    bn = _pick_block(n, 128, 8)
    if bn is None:
        return False
    weights = (d_in * d_ff + d_ff * d_out) * 4
    rows = bn * (d_in + 2 * d_ff + 2 * d_out) * 4
    return weights + rows <= 12 * 1024 * 1024


def ffn_spmd_ok(mesh, n, d_in, d_ff, d_out):
    """Per-shard rule under a mesh: rows partition over 'data', weights
    ride replicated (tensor-parallel FFN sharding stays on the unfused
    path — parallel/api.py's column/row split of ffn1/ffn2)."""
    from .kernel_tier import mesh_axis
    ax = mesh_axis(mesh, 'data', n)
    n_loc = n // mesh.shape[ax] if ax else n
    return ffn_shapes_ok(n_loc, d_in, d_ff, d_out)


# ---------------------------------------------------------------------------
# pallas forward kernel: one row block through both matmuls per program
# ---------------------------------------------------------------------------

# Mosaic lowers neither lax.erf nor erfc (jax 0.9.0), and exact gelu needs
# one: the kernel expands erf into the mul/add/div rational approximation
# XLA itself uses for f32 (|err| < 4e-7 against math.erf).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08,
          -2.10102402082508e-06, -5.69250639462346e-05,
          -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04,
          -1.68282697438203e-03, -7.37332916720468e-03,
          -1.42647390514189e-02)


def _erf_rational(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p = jnp.float32(_ERF_P[0])
    for c in _ERF_P[1:]:
        p = p * x2 + jnp.float32(c)
    q = jnp.float32(_ERF_Q[0])
    for c in _ERF_Q[1:]:
        q = q * x2 + jnp.float32(c)
    return x * p / q


def _ffn_fwd_kernel(has_mask, *refs):
    if has_mask:
        (x_ref, w1_ref, b1_ref, w2_ref, b2_ref, mk_ref,
         y_ref, p1_ref) = refs
    else:
        x_ref, w1_ref, b1_ref, w2_ref, b2_ref, y_ref, p1_ref = refs
    x = x_ref[...]
    pre1 = jnp.dot(x, w1_ref[...],
                   preferred_element_type=jnp.float32) + b1_ref[...]
    cdf = 0.5 * (1.0 + _erf_rational(
        pre1 * np.float32(1.0 / np.sqrt(2.0))))
    h = (pre1 * cdf).astype(x.dtype)
    y = jnp.dot(h, w2_ref[...],
                preferred_element_type=jnp.float32) + b2_ref[...]
    y = y.astype(y_ref.dtype)
    if has_mask:
        y = y * mk_ref[...]
    y_ref[...] = y
    # pre1 is the ONLY saved d_ff-wide residual (bwd recomputes gelu)
    p1_ref[...] = pre1.astype(p1_ref.dtype)


def _ffn_fwd_pallas(x, w1, b1, w2, b2, mask, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .ce_ops import _pick_block
    n, d_in = x.shape
    d_ff = w1.shape[1]
    d_out = w2.shape[1]
    bn = _pick_block(n, 128, 8)
    row_in = pl.BlockSpec((bn, d_in), lambda i: (i, 0))
    row_out = pl.BlockSpec((bn, d_out), lambda i: (i, 0))
    row_ff = pl.BlockSpec((bn, d_ff), lambda i: (i, 0))

    def full(a, b):
        return pl.BlockSpec((a, b), lambda i: (0, 0))
    in_specs = [row_in,
                full(d_in, d_ff), full(1, d_ff),
                full(d_ff, d_out), full(1, d_out)]
    args = [x, w1, b1.reshape(1, d_ff), w2, b2.reshape(1, d_out)]
    if mask is not None:
        in_specs.append(row_out)
        args.append(mask)
    y, pre1 = pl.pallas_call(
        functools.partial(_ffn_fwd_kernel, mask is not None),
        grid=(n // bn,),
        in_specs=in_specs,
        out_specs=[row_out, row_ff],
        out_shape=[jax.ShapeDtypeStruct((n, d_out), x.dtype),
                   jax.ShapeDtypeStruct((n, d_ff), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='fused_ffn_tail_fwd',
    )(*args)
    return y, pre1


# ---------------------------------------------------------------------------
# custom_vjp core: both fused tiers share the recompute backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def fused_ffn_core(x, w1, b1, w2, b2, mask, impl):
    """y [N, d_out] for rows x [N, d_in]:
    ``y = (gelu(x @ w1 + b1) @ w2 + b2) * mask`` (``mask`` is the
    pre-scaled keep mask, or None when dropout is inactive). ``impl`` in
    'xla' | 'pallas' | 'interpret' — the 'off' tier lowers the legacy
    composition and never reaches here. The backward saves (x, pre1)
    and recomputes gelu, so no [N, d_ff] activation residual exists."""
    return _ffn_fwd(x, w1, b1, w2, b2, mask, impl)[0]


def _ffn_fwd(x, w1, b1, w2, b2, mask, impl):
    if impl in ('pallas', 'interpret'):
        y, pre1 = _ffn_fwd_pallas(x, w1, b1, w2, b2, mask,
                                  impl == 'interpret')
        cdf = None            # TPU trade: recompute erf, save HBM
    else:
        pre1 = jnp.dot(x, w1, preferred_element_type=jnp.float32) + b1
        # gelu expanded so the erf factor (cdf) is a named value: the
        # backward reuses it for BOTH the recomputed activation
        # (h = pre1 * cdf) and the gelu derivative — zero erf calls in
        # the backward instead of the two a naive recompute costs (erf
        # dominates the epilogue on CPU)
        cdf = _gelu_cdf(pre1)
        h = (pre1 * cdf).astype(x.dtype)
        y = (jnp.dot(h, w2, preferred_element_type=jnp.float32)
             + b2).astype(x.dtype)
        if mask is not None:
            y = y * mask
    return y, (x, w1, w2, pre1, cdf, mask)


def _gelu_cdf(pre1):
    """Phi(x) — the erf factor of exact gelu, f32."""
    return 0.5 * (1.0 + jax.lax.erf(pre1 * np.float32(1.0 / np.sqrt(2.0))))


def _ffn_bwd(impl, res, dy):
    x, w1, w2, pre1, cdf, mask = res
    dyf = dy.astype(jnp.float32)
    if mask is not None:
        dyf = dyf * mask.astype(jnp.float32)
    if cdf is None:                 # pallas tiers saved pre1 only
        cdf = _gelu_cdf(pre1)
    h = pre1 * cdf                  # gelu recomputed from cdf: no erf
    db2 = jnp.sum(dyf, axis=0).astype(w2.dtype)
    dh = jnp.dot(dyf, w2.T.astype(jnp.float32),
                 preferred_element_type=jnp.float32)
    dw2 = jnp.dot(h.T, dyf,
                  preferred_element_type=jnp.float32).astype(w2.dtype)
    phi = jnp.exp(-0.5 * pre1 * pre1) * np.float32(
        1.0 / np.sqrt(2.0 * np.pi))
    dpre1 = dh * (cdf + pre1 * phi)
    db1 = jnp.sum(dpre1, axis=0).astype(w1.dtype)
    dx = jnp.dot(dpre1, w1.T.astype(jnp.float32),
                 preferred_element_type=jnp.float32).astype(x.dtype)
    dw1 = jnp.dot(x.T.astype(jnp.float32), dpre1,
                  preferred_element_type=jnp.float32).astype(w1.dtype)
    dmask = None if mask is None else jnp.zeros_like(mask)
    return dx, dw1, db1, dw2, db2, dmask


fused_ffn_core.defvjp(_ffn_fwd, _ffn_bwd)


def fused_ffn_spmd(x, w1, b1, w2, b2, mask, mesh, impl):
    """Mesh-partitioned FFN tail: rows over 'data' via
    kernel_tier.partitioned_call — the kernel is row-independent, so the
    partitioned call needs no comms; weights ride replicated and their
    cotangents psum through shard_map's transpose. The dropout mask is
    drawn ONCE on the global shape and sharded like the rows, so masks
    are identical with and without a mesh."""
    from jax.sharding import PartitionSpec as P
    from .kernel_tier import partitioned_call, mesh_axis
    data_ax = mesh_axis(mesh, 'data', x.shape[0])
    rowp = P(data_ax, None)
    if mask is None:
        def inner(xl, a1, c1, a2, c2):
            return fused_ffn_core(xl, a1, c1, a2, c2, None, impl)
        return partitioned_call(inner, mesh,
                                (rowp, P(), P(), P(), P()),
                                rowp)(x, w1, b1, w2, b2)

    def inner_m(xl, a1, c1, a2, c2, mk):
        return fused_ffn_core(xl, a1, c1, a2, c2, mk, impl)
    return partitioned_call(inner_m, mesh,
                            (rowp, P(), P(), P(), P(), rowp),
                            rowp)(x, w1, b1, w2, b2, mask)


# ---------------------------------------------------------------------------
# the program-level op
# ---------------------------------------------------------------------------

def _ffn_rng_active(op):
    """Static RNG predicate for executor.bind's needs_rng scan: only a
    TRAIN-mode op with a live dropout probability draws a key — decode
    towers (is_test, prob 0) keep the RNG-free single-PRNGKey fast
    path."""
    return (not op.attr('is_test', False)
            and op.attr('dropout_prob', 0.0) > 0.0)


def _dropout_mask(ctx, op, shape, dtype):
    """The keep mask of the legacy dropout lowering (random_ops._dropout),
    pre-scaled for 'upscale_in_train': key from the counted stream (or
    the op's explicit seed attr, same override rule)."""
    prob = op.attr('dropout_prob', 0.5)
    seed = op.attr('seed', 0)
    key = ctx.rng()
    if seed:
        key = jax.random.PRNGKey(seed)
        key = jax.random.fold_in(key, ctx.op_index)
    keep = jax.random.bernoulli(key, 1.0 - prob, shape)
    return keep.astype(dtype)


@register_op('fused_ffn_tail', needs_rng=_ffn_rng_active)
def _fused_ffn_tail_op(ctx, op):
    """Out = dropout(gelu(X @ W1 + B1) @ W2 + B2): the transformer FFN
    sublayer as one unit. Attrs: x_num_col_dims (the mul flatten rule),
    dropout_prob / is_test / seed / dropout_implementation (the dropout
    op's contract; 'upscale_in_train' is the fused fast path). The 'off'
    tier reproduces the six-op composition BITWISE (amp casts
    included)."""
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    x = ctx.in1(op, 'X')
    w1 = ctx.in1(op, 'W1')
    b1 = ctx.in1(op, 'B1')
    w2 = ctx.in1(op, 'W2')
    b2 = ctx.in1(op, 'B2')
    xnc = op.attr('x_num_col_dims', 1)
    prob = op.attr('dropout_prob', 0.0)
    is_test = op.attr('is_test', False)
    dimpl = op.attr('dropout_implementation', 'upscale_in_train')
    drop_active = bool(prob) and not is_test

    d_in = w1.shape[0]
    d_ff = w1.shape[1]
    d_out = w2.shape[1]
    n = int(np.prod(x.shape[:xnc])) if xnc > 0 else 1
    amp_dt = op.attr(amp.AMP_ATTR, None)
    # the fused emissions assume the standard tail: trailing-axis matmuls,
    # f32 row streams, upscale dropout — anything else takes the off tier
    fusable = (x.shape[xnc:] == w1.shape[:1] and x.ndim == xnc + 1
               and x.dtype == jnp.dtype(jnp.float32)
               and (not drop_active or (dimpl == 'upscale_in_train'
                                        and prob < 1.0)))
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    # AMP-marked instances run the xla tier (the casts wrap the fused
    # emission the way mul's lowering wraps each dot); the pallas kernel
    # is written for f32 row tiles, so it stands down under amp
    if fusable and not amp_dt:
        pallas_ok = ffn_spmd_ok(mesh, n, d_in, d_ff, d_out) if meshed \
            else ffn_shapes_ok(n, d_in, d_ff, d_out)
    else:
        pallas_ok = False
    impl = kernel_tier.dispatch(
        'fused_ffn_tail', pallas_ok=pallas_ok, xla_ok=fusable,
        mesh=mesh, count=getattr(ctx, 'sparse_mode', None) != 'scout')

    if impl == 'off':
        # bitwise legacy: mul + elementwise_add + gelu + mul +
        # elementwise_add + dropout lowerings composed (the parity anchor)
        x2 = flatten_to_2d(x, xnc)
        w1_2 = flatten_to_2d(w1, 1)
        x2, w1_2 = amp.cast_compute(op, x2, w1_2)
        h = jnp.dot(x2, w1_2, preferred_element_type=jnp.float32)
        h = h.astype(x.dtype).reshape(x.shape[:xnc] + w1.shape[1:])
        h = h + broadcast_y_to(h, b1, xnc)
        h = jax.nn.gelu(h, approximate=False)
        h2 = flatten_to_2d(h, xnc)
        w2_2 = flatten_to_2d(w2, 1)
        h2, w2_2 = amp.cast_compute(op, h2, w2_2)
        y = jnp.dot(h2, w2_2, preferred_element_type=jnp.float32)
        y = y.astype(h.dtype).reshape(h.shape[:xnc] + w2.shape[1:])
        y = y + broadcast_y_to(y, b2, xnc)
        if drop_active:
            keep = _dropout_mask(ctx, op, y.shape, y.dtype)
            if dimpl == 'upscale_in_train':
                y = jnp.where(prob < 1.0, y * keep / (1.0 - prob),
                              jnp.zeros_like(y))
            else:
                y = y * keep
        elif is_test and bool(prob) and dimpl == 'downgrade_in_infer':
            y = y * (1.0 - prob)
        ctx.out(op, 'Out', y)
        return

    lead = x.shape[:xnc]
    x2 = x.reshape(n, d_in)
    w1c, w2c = w1, w2
    if amp_dt:
        x2, w1c, w2c = amp.cast_compute(op, x2, w1, w2)
    mask = None
    if drop_active:
        # mask on the GLOBAL row shape, pre-scaled, f32: identical across
        # fused tiers and across mesh layouts for one program build
        mask = _dropout_mask(ctx, op, (n, d_out),
                             jnp.float32) / np.float32(1.0 - prob)
    if meshed and impl in ('pallas', 'interpret'):
        y2 = fused_ffn_spmd(x2, w1c, b1, w2c, b2, mask, mesh, impl)
    else:
        y2 = fused_ffn_core(x2, w1c, b1, w2c, b2, mask, impl)
    ctx.out(op, 'Out', y2.astype(x.dtype).reshape(lead + (d_out,)))
