"""Fused softmax-cross-entropy over logits: the V=32000 lm_head tail as one
blocked kernel (softmax + label gather + NLL in a single pass).

Motivation (BENCH r03-r05 + PADDLE_PROFILE_OPS attribution): the lm_*
rows' flat MFU sits in the loss tail — ``softmax_with_cross_entropy`` over
``[B*L, 32000]`` logits. The unfused lowering materializes a full
probability/one-hot intermediate on the backward pass; this kernel streams
vocab blocks through VMEM keeping only per-row running max / running
denominator / picked-logit scratch (FlashAttention's online-softmax trick
applied to the loss), and the backward recomputes the probability TILE
from (logits, LSE) — O(N) residuals, no ``[N, V]`` one-hot ever exists.

Tiers (ops/kernel_tier.py):
- off:       nn_ops._ce_hard (bit-identical legacy path);
- xla:       one-hot-free jnp emission (scatter-subtract backward), XLA
             fuses the forward reduction chain;
- pallas:    the blocked kernels below;
- interpret: the same kernels through the Pallas interpreter (CPU tests).

Both fused tiers keep the ``ignore_index`` contract: ignored rows emit 0
loss and 0 gradient.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30


def _pick_block(n, pref, mult):
    """Largest power-of-two tile <= pref that divides n and is a multiple
    of mult; None when no such tile exists (caller falls back a tier)."""
    b = pref
    while b >= mult:
        if n % b == 0:
            return b
        b //= 2
    return None


def pallas_shapes_ok(n, v):
    """Can the kernels tile [n, v] logits? (the per-op fallback rule)"""
    return _pick_block(n, 256, 128) is not None and \
        _pick_block(v, 2048, 128) is not None


# --------------------------------------------------------------------------
# forward kernel: loss + lse in one sweep over vocab blocks
# --------------------------------------------------------------------------

def _fwd_kernel(nj, ignore_index, *refs):
    import jax.experimental.pallas as pl
    (x_ref, lab_ref, loss_ref, lse_ref, m_scr, l_scr, pick_scr) = refs
    j = pl.program_id(1)
    bn, bv = x_ref.shape

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        pick_scr[...] = jnp.zeros(pick_scr.shape, jnp.float32)

    s = x_ref[...].astype(jnp.float32)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    l_scr[...] = jnp.broadcast_to(
        l_scr[:, :1] * jnp.exp(m_prev - m_new)
        + jnp.sum(jnp.exp(s - m_new), axis=-1, keepdims=True), l_scr.shape)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    cols = j * bv + lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    lab = lab_ref[0]                                   # [bn] int32
    hit = cols == lab[:, None]
    # each row's label lands in exactly one vocab block, so += accumulates
    # one real value (ignore_index never matches: it is outside [0, V))
    pick_scr[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        pick_scr.shape)

    @pl.when(j == nj - 1)
    def _finish():
        lse = m_scr[:, 0] + jnp.log(jnp.maximum(l_scr[:, 0], 1e-30))
        lse_ref[0] = lse
        loss = lse - pick_scr[:, 0]
        loss_ref[0] = jnp.where(lab_ref[0] != ignore_index, loss, 0.0)


def _fused_ce_fwd_pallas(logits, labels, ignore_index, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, v = logits.shape
    bn = _pick_block(n, 256, 128)
    bv = _pick_block(v, 2048, 128)
    nj = v // bv
    lab2 = labels.astype(jnp.int32)[None, :]
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, nj, int(ignore_index)),
        grid=(n // bn, nj),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, i))],
        out_specs=[pl.BlockSpec((1, bn), lambda i, j: (0, i)),
                   pl.BlockSpec((1, bn), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bn, 128), jnp.float32),
                        pltpu.VMEM((bn, 128), jnp.float32),
                        pltpu.VMEM((bn, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name='softmax_ce_fwd',
    )(logits, lab2)
    return loss[0], lse[0]


# --------------------------------------------------------------------------
# backward kernel: dlogits tile recomputed from (logits, lse) — no
# [N, V] softmax/one-hot residual
# --------------------------------------------------------------------------

def _bwd_kernel(ignore_index, x_ref, lab_ref, lse_ref, ct_ref, dx_ref):
    import jax.experimental.pallas as pl
    j = pl.program_id(1)
    bn, bv = x_ref.shape
    s = x_ref[...].astype(jnp.float32)
    lab = lab_ref[0]
    ct = jnp.where(lab != ignore_index, ct_ref[0], 0.0)    # [bn]
    p = jnp.exp(s - lse_ref[0][:, None])
    cols = j * bv + lax.broadcasted_iota(jnp.int32, (bn, bv), 1)
    hit = cols == lab[:, None]
    dx_ref[...] = ((p - jnp.where(hit, 1.0, 0.0))
                   * ct[:, None]).astype(dx_ref.dtype)


def _fused_ce_bwd_pallas(logits, labels, lse, ct, ignore_index, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, v = logits.shape
    bn = _pick_block(n, 256, 128)
    bv = _pick_block(v, 2048, 128)
    lab2 = labels.astype(jnp.int32)[None, :]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, int(ignore_index)),
        grid=(n // bn, v // bv),
        in_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, i)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, i)),
                  pl.BlockSpec((1, bn), lambda i, j: (0, i))],
        out_specs=[pl.BlockSpec((bn, bv), lambda i, j: (i, j))],
        out_shape=[jax.ShapeDtypeStruct((n, v), logits.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name='softmax_ce_bwd',
    )(logits, lab2, lse[None, :], ct.astype(jnp.float32)[None, :])[0]


# --------------------------------------------------------------------------
# xla tier: one-hot-free jnp emission
# --------------------------------------------------------------------------

def _ce_fwd_xla(logits, labels, ignore_index):
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = (m[:, 0] + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1)))
    safe = jnp.clip(labels, 0, x.shape[-1] - 1)
    picked = jnp.take_along_axis(x, safe[:, None], axis=-1)[:, 0]
    loss = jnp.where(labels != ignore_index, lse - picked, 0.0)
    return loss, lse


def _ce_bwd_xla(logits, labels, lse, ct, ignore_index):
    x = logits.astype(jnp.float32)
    ct_eff = jnp.where(labels != ignore_index, ct, 0.0)
    g = jnp.exp(x - lse[:, None]) * ct_eff[:, None]
    safe = jnp.clip(labels, 0, x.shape[-1] - 1)
    # scatter-subtract at the label column instead of building a [N, V]
    # one-hot (the memory the fused tier exists to avoid)
    g = g.at[jnp.arange(g.shape[0]), safe].add(-ct_eff)
    return g.astype(logits.dtype)


# --------------------------------------------------------------------------
# custom_vjp wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_softmax_ce(logits, labels, ignore_index, impl):
    """loss [N] for logits [N, V], int labels [N]. ``impl`` in
    'xla' | 'pallas' | 'interpret' (the 'off' tier never reaches here)."""
    return _fused_fwd(logits, labels, ignore_index, impl)[0]


def _fused_fwd(logits, labels, ignore_index, impl):
    labels = labels.astype(jnp.int32)
    if impl in ('pallas', 'interpret'):
        loss, lse = _fused_ce_fwd_pallas(logits, labels, ignore_index,
                                         impl == 'interpret')
    else:
        loss, lse = _ce_fwd_xla(logits, labels, ignore_index)
    return loss, (logits, labels, lse)


def _fused_ce_bwd(ignore_index, impl, res, ct):
    logits, labels, lse = res
    if impl in ('pallas', 'interpret'):
        g = _fused_ce_bwd_pallas(logits, labels, lse, ct, ignore_index,
                                 impl == 'interpret')
    else:
        g = _ce_bwd_xla(logits, labels, lse, ct, ignore_index)
    return g, None


fused_softmax_ce.defvjp(_fused_fwd, _fused_ce_bwd)


# --------------------------------------------------------------------------
# SPMD: mesh-partitioned fused CE (ops/kernel_tier.partitioned_call)
#
# Batch rows shard over 'data' (each shard runs the whole kernel on its
# rows — no comms at all); a vocab-sharded 'model' axis runs the kernel on
# partial vocab blocks and combines with an lse-aware all-reduce:
# lse_g = pmax + log(psum(exp(lse_l - pmax))), pick_g = psum(pick_l) — the
# online-softmax merge rule applied across shards instead of vocab blocks.
# --------------------------------------------------------------------------

# kernel-level ignore sentinel for the vocab-sharded partial passes: the
# locally-shifted label is -1 for rows whose label lives on another shard
# (misses every column >= 0), so the kernel's own ignore masking must be a
# no-op — -2 never equals a shifted label
_NO_IGNORE = -2


def spmd_shapes_ok(mesh, n, v):
    """Per-SHARD tiling rule under a mesh: each shard's [n_local, v_local]
    logits block must tile for the kernels (the per-op fallback rule,
    evaluated on the post-partitioning shapes)."""
    from .kernel_tier import mesh_axis
    data_ax = mesh_axis(mesh, 'data', n)
    model_ax = mesh_axis(mesh, 'model', v)
    n_loc = n // mesh.shape[data_ax] if data_ax else n
    v_loc = v // mesh.shape[model_ax] if model_ax else v
    return pallas_shapes_ok(n_loc, v_loc)


def _partial_stats(logits, lab_l, impl):
    """Per-shard (lse_local, pick_local) over a partial vocab block.
    ``lab_l`` is already shifted into the local column space (-1 = label
    lives on another shard -> pick contribution 0)."""
    if impl in ('pallas', 'interpret'):
        loss_l, lse_l = _fused_ce_fwd_pallas(logits, lab_l, _NO_IGNORE,
                                             impl == 'interpret')
        # the kernel emits loss = lse - pick (ignore masking defused via
        # the sentinel), so the picked logit inverts exactly
        return lse_l, lse_l - loss_l
    x = logits.astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    lse_l = m[:, 0] + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1))
    safe = jnp.clip(lab_l, 0, x.shape[-1] - 1)
    picked = jnp.take_along_axis(x, safe[:, None], axis=-1)[:, 0]
    return lse_l, jnp.where(lab_l >= 0, picked, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _sharded_vocab_ce(logits, labels, ignore_index, impl, vocab_axis):
    """Per-shard body under shard_map when the VOCAB axis is sharded:
    logits [n_loc, v_loc] local block, labels [n_loc] GLOBAL ids.
    Returns this shard's PARTIAL loss (partials psum to the true loss):
    an output the transpose treats as genuinely sharded — claiming a
    replicated [n] loss instead makes shard_map's reverse rule (with
    replication checking off) average the cotangent over the vocab
    axis, silently scaling dlogits by 1/axis_size."""
    return _sharded_vocab_ce_fwd(logits, labels, ignore_index, impl,
                                 vocab_axis)[0]


def _shift_labels(labels, vloc, vocab_axis):
    off = lax.axis_index(vocab_axis).astype(jnp.int32) * vloc
    shifted = labels - off
    in_rng = (shifted >= 0) & (shifted < vloc)
    return jnp.where(in_rng, shifted, -1)


def _sharded_vocab_ce_fwd(logits, labels, ignore_index, impl, vocab_axis):
    labels = labels.astype(jnp.int32)
    lab_l = _shift_labels(labels, logits.shape[1], vocab_axis)
    lse_l, pick_l = _partial_stats(logits, lab_l, impl)
    mx = lax.pmax(lse_l, vocab_axis)
    lse_g = mx + jnp.log(lax.psum(jnp.exp(lse_l - mx), vocab_axis))
    # decompose loss = lse_g - pick_g into per-shard partials that sum
    # exactly once across the axis: share_i = exp(lse_l - lse_g) is this
    # shard's softmax mass (psums to 1), pick lives on one shard only
    partial = jnp.exp(lse_l - lse_g) * lse_g - pick_l
    partial = jnp.where(labels != ignore_index, partial, 0.0)
    # residuals: O(N) lse_g instead of any [n, v] intermediate; the
    # backward is comms-free (each shard owns its dlogits block)
    return partial, (logits, labels, lab_l, lse_g)


def _sharded_vocab_ce_bwd(ignore_index, impl, vocab_axis, res, ct):
    logits, labels, lab_l, lse_g = res
    ct_eff = jnp.where(labels != ignore_index, ct, 0.0).astype(jnp.float32)
    if impl in ('pallas', 'interpret'):
        g = _fused_ce_bwd_pallas(logits, lab_l, lse_g, ct_eff, _NO_IGNORE,
                                 impl == 'interpret')
    else:
        x = logits.astype(jnp.float32)
        gmat = jnp.exp(x - lse_g[:, None]) * ct_eff[:, None]
        safe = jnp.clip(lab_l, 0, x.shape[-1] - 1)
        gmat = gmat.at[jnp.arange(x.shape[0]), safe].add(
            -jnp.where(lab_l >= 0, ct_eff, 0.0))
        g = gmat.astype(logits.dtype)
    return g, None


_sharded_vocab_ce.defvjp(_sharded_vocab_ce_fwd, _sharded_vocab_ce_bwd)


def fused_softmax_ce_spmd(logits, labels, mesh, ignore_index, impl):
    """Mesh-partitioned fused CE: loss [N] for logits [N, V] under an
    active mesh. Rows shard over 'data', vocab over 'model' (each only
    when present, >1 and dividing); kernel per shard via
    kernel_tier.partitioned_call. Batch-only sharding is comms-free;
    a sharded vocab axis pays one pmax + two psums of [n_loc] vectors."""
    from jax.sharding import PartitionSpec as P
    from .kernel_tier import partitioned_call, mesh_axis
    n, v = logits.shape
    data_ax = mesh_axis(mesh, 'data', n)
    model_ax = mesh_axis(mesh, 'model', v)
    lab = labels.astype(jnp.int32)
    if model_ax is None:
        def inner(xl, ll):
            return fused_softmax_ce(xl, ll, ignore_index, impl)
        return partitioned_call(inner, mesh,
                                (P(data_ax, None), P(data_ax)),
                                P(data_ax))(logits, lab)

    # each vocab shard emits a [1, n_loc] PARTIAL row (see
    # _sharded_vocab_ce: a replicated-loss claim mis-transposes); the
    # stacked [msize, n] partials sum to the loss outside the shard_map
    def inner_sharded(xl, ll):
        return _sharded_vocab_ce(xl, ll, ignore_index, impl,
                                 model_ax)[None, :]
    parts = partitioned_call(inner_sharded, mesh,
                             (P(data_ax, model_ax), P(data_ax)),
                             P(model_ax, data_ax))(logits, lab)
    return jnp.sum(parts, axis=0)
