"""Blocked flash attention: the pallas kernel tier (SURVEY §2.4: the TPU
analog of the reference's operators/jit/ runtime-codegen kernels
(jit/kernel_base.h:24-52), with the same refer-vs-optimized cross-checking
discipline of operators/jit/test.cc — see tests/test_attention.py).

Forward: FlashAttention-2 style. Grid (batch*head, q_block, k_block); the
k dimension is innermost+sequential so f32 scratch (running max, running
denominator, output accumulator) carries across k blocks — scores for one
(q_block, k_block) tile live in VMEM only and never round-trip through HBM.
Matmuls feed the MXU in the input dtype (bf16 under AMP) with f32
accumulation via preferred_element_type; causal tiles below the diagonal
are skipped with predication. Alongside O it emits per-row LSE
(logsumexp), the residual the backward needs.

Backward: two pallas kernels (the FlashAttention-2 split):
  - dQ:    grid (bh, q_block, k_block), accumulates dQ across k blocks;
  - dK/dV: grid (bh, k_block, q_block), accumulates dK and dV across
           q blocks.
Both recompute the probability tile from (Q, K, LSE) instead of storing it
— O(L) memory, O(L^2) recompute, the standard trade on HBM-bound hardware.
delta = rowsum(dO * O) is precomputed outside the kernels (XLA fuses it).

Under SPMD (an active MeshRunner mesh) the op no longer falls back to
einsum: it wraps the kernel in shard_map over the (data, model) axes —
batch and heads are embarrassingly parallel — and when the sequence axis
itself is sharded it dispatches to the ring-attention path
(parallel/ring_attention.py), making ring the long-context execution mode
of this same op rather than a parallel universe.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op

_NEG_INF = -1e30


def _attention_ref(q, k, v, scale, causal):
    """Plain jnp reference ([BH, L, dh] each) — the 'refer' tier."""
    s = jnp.einsum('bqd,bkd->bqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        ln = q.shape[1]
        mask = jnp.tril(jnp.ones((ln, ln), bool))
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bqk,bkd->bqd', p.astype(v.dtype), v)


def _pick_block(ln, pref):
    """Largest power-of-two tile (<= pref) dividing the sequence length."""
    b = pref
    while b > 128:
        if ln % b == 0:
            return b
        b //= 2
    return b if ln % b == 0 else ln


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------

def _fwd_kernel(scale, causal, nk, has_bias, *refs):
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            # per-key additive bias (padding masks: 0 keep / -1e9 drop)
            s = s + bias_ref[0, 0][None, :]
        if causal:
            rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = rows >= cols
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if causal:
            # rows whose tile slice is fully masked have m_new == _NEG_INF
            # and exp(_NEG_INF - _NEG_INF) == 1; force masked entries to 0
            p = jnp.where(mask, p, 0.0)
        if bias_ref is not None:
            # exact zero for dropped keys (-1e8 or lower — covers the
            # documented -1e9 pad convention), independent of underflow
            p = jnp.where(bias_ref[0, 0][None, :] > -1e8, p, 0.0)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv

    if causal:
        # tile visible iff its first key column <= last query row
        pl.when(j * bk <= i * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, 0] + jnp.log(
            jnp.maximum(l_scr[:, 0], 1e-30))


def _flash_fwd_pallas(q, k, v, scale, causal, interpret, block_q, block_k,
                      bias=None, n_heads=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, ln, dh = q.shape
    bq = _pick_block(ln, block_q)
    bk = _pick_block(ln, block_k)
    nq, nk = ln // bq, ln // bk
    has_bias = bias is not None
    kernel = functools.partial(_fwd_kernel, scale, causal, nk, has_bias)
    qspec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0))
    ins = [q, k, v]
    in_specs = [qspec, kspec, kspec]
    if has_bias:
        # bias [B, L]: each (batch*head) row b maps to batch b // n_heads
        ins.append(bias.astype(jnp.float32)[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j: (b // n_heads, 0, j)))
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, ln), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, 128), jnp.float32),
                        pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name='flash_attention_fwd',
    )(*ins)
    return o, lse[:, 0]


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------

def _bwd_dq_kernel(scale, causal, nk, has_bias, *refs):
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0][None, :]
        if causal:
            rows = i * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = j * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])   # masked entries underflow
        if bias_ref is not None:
            # all-padded rows have lse = log(1e-30); without the forward's
            # exact zeroing p explodes to ~e^69 and poisons dQ
            p = jnp.where(bias_ref[0, 0][None, :] > -1e8, p, 0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_scr[...] += lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(j * bk <= i * bq + bq - 1)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(scale, causal, nq, has_bias, *refs):
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)      # i: k block, j: q block
    bk, bq = k_ref.shape[1], q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0][None, :]
        if causal:
            rows = j * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = i * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, None])            # [bq, bk]
        if bias_ref is not None:
            p = jnp.where(bias_ref[0, 0][None, :] > -1e8, p, 0.0)
        dv_scr[...] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_scr[...] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # tile visible iff its last query row >= first key column
        pl.when(j * bq + bq - 1 >= i * bk)(_compute)
    else:
        _compute()

    @pl.when(j == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, interpret,
                      block_q, block_k, bias=None, n_heads=1):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, ln, dh = q.shape
    bq = _pick_block(ln, block_q)
    bk = _pick_block(ln, block_k)
    nq, nk = ln // bq, ln // bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]
    has_bias = bias is not None
    bias3 = bias.astype(jnp.float32)[:, None, :] if has_bias else None

    qspec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kspec_j = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    ins = [q, k, v, do, lse3, delta3]
    in_specs = [qspec, kspec_j, kspec_j, qspec, rowspec, rowspec]
    if has_bias:
        ins.append(bias3)
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j: (b // n_heads, 0, j)))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale, causal, nk, has_bias),
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name='flash_attention_bwd_dq',
    )(*ins)[0]

    # k-major grid: q blocks stream innermost
    qspec_j = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, j, 0))
    kspec_i = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0))
    rowspec_j = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j))
    ins2 = [q, k, v, do, lse3, delta3]
    in_specs2 = [qspec_j, kspec_i, kspec_i, qspec_j, rowspec_j, rowspec_j]
    if has_bias:
        ins2.append(bias3)
        in_specs2.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j: (b // n_heads, 0, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale, causal, nq, has_bias),
        grid=(bh, nk, nq),
        in_specs=in_specs2,
        out_specs=[kspec_i, kspec_i],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), k.dtype),
                   jax.ShapeDtypeStruct((bh, ln, dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name='flash_attention_bwd_dkv',
    )(*ins2)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp wrapper ([BH, L, dh] level)
# --------------------------------------------------------------------------

# default tile sizes; the round-3 sweep measured 512x512 optimal at
# d_head 64 (256/128 tiles 1.5-2.5x slower). Env-overridable so a sweep
# can re-measure without editing source; nothing in the repo sets either.
import os as _os
_DEF_BQ = int(_os.environ.get('PADDLE_FLASH_BQ', '512'))
_DEF_BK = int(_os.environ.get('PADDLE_FLASH_BK', '512'))


def _fwd_impl(q, k, v, scale, causal, impl):
    if impl in ('pallas', 'interpret'):
        return _flash_fwd_pallas(q, k, v, scale, causal,
                                 impl == 'interpret', _DEF_BQ, _DEF_BK)
    return _attention_ref(q, k, v, scale, causal), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, scale, causal, impl):
    return _fwd_impl(q, k, v, scale, causal, impl)[0]


def _flash_fwd(q, k, v, scale, causal, impl):
    o, lse = _fwd_impl(q, k, v, scale, causal, impl)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, impl, res, ct):
    q, k, v, o, lse = res
    if impl in ('pallas', 'interpret'):
        return _flash_bwd_pallas(q, k, v, o, lse, ct, scale, causal,
                                 impl == 'interpret', _DEF_BQ, _DEF_BK)
    _, vjp = jax.vjp(lambda a, b, c: _attention_ref(a, b, c, scale, causal),
                     q, k, v)
    return vjp(ct)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attention_ref_biased(q, k, v, bias, scale, causal, n_heads):
    """jnp reference with per-key additive bias [B, L] (row b of the
    [BH, L, dh] inputs belongs to batch b // n_heads)."""
    s = jnp.einsum('bqd,bkd->bqk', q, k,
                   preferred_element_type=jnp.float32) * scale
    s = s + jnp.repeat(bias.astype(jnp.float32), n_heads, axis=0)[:, None, :]
    if causal:
        ln = q.shape[1]
        mask = jnp.tril(jnp.ones((ln, ln), bool))
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bqk,bkd->bqd', p.astype(v.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_biased(q, k, v, bias, scale, causal, impl, n_heads):
    return _fwd_impl_biased(q, k, v, bias, scale, causal, impl,
                            n_heads)[0]


def _fwd_impl_biased(q, k, v, bias, scale, causal, impl, n_heads):
    if impl in ('pallas', 'interpret'):
        return _flash_fwd_pallas(q, k, v, scale, causal,
                                 impl == 'interpret', _DEF_BQ, _DEF_BK,
                                 bias=bias, n_heads=n_heads)
    return _attention_ref_biased(q, k, v, bias, scale, causal,
                                 n_heads), None


def _flash_biased_fwd(q, k, v, bias, scale, causal, impl, n_heads):
    o, lse = _fwd_impl_biased(q, k, v, bias, scale, causal, impl, n_heads)
    return o, (q, k, v, bias, o, lse)


def _flash_biased_bwd(scale, causal, impl, n_heads, res, ct):
    q, k, v, bias, o, lse = res
    # bias is a padding mask: treated as non-differentiable (zero grad)
    if impl in ('pallas', 'interpret'):
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, o, lse, ct, scale, causal, impl == 'interpret',
            _DEF_BQ, _DEF_BK, bias=bias, n_heads=n_heads)
        return dq, dk, dv, jnp.zeros_like(bias)
    _, vjp = jax.vjp(
        lambda a, b, c: _attention_ref_biased(a, b, c, bias, scale,
                                              causal, n_heads), q, k, v)
    dq, dk, dv = vjp(ct)
    return dq, dk, dv, jnp.zeros_like(bias)


_flash_biased.defvjp(_flash_biased_fwd, _flash_biased_bwd)


def flash_shapes_ok(ln):
    """Tiling rule for the kernels: a 128-multiple tile divides L, or L is
    short enough (<= 1024) to ride as one full-L VMEM tile. The
    flash_attention op consults it through kernel_tier.dispatch; direct
    callers of the functions below get the kernel they ask for."""
    return ln % 128 == 0 or ln <= 1024


def _resolve_impl(use_pallas):
    if use_pallas is None:
        return 'pallas' if jax.default_backend() == 'tpu' else 'ref'
    if use_pallas == 'interpret':
        return 'interpret'
    return 'pallas' if use_pallas else 'ref'


def flash_attention(q, k, v, scale=None, causal=True, use_pallas=None,
                    key_padding_bias=None, num_heads=1):
    """q/k/v: [B, H, L, dh] (or [BH, L, dh]). On TPU lowers to the blocked
    pallas kernels (fwd + dq/dkv bwd); elsewhere to the jnp reference
    (use_pallas='interpret' forces the kernels through the pallas
    interpreter for cross-checking). key_padding_bias: optional [B, L]
    additive per-key bias (0 keep / -1e9 drop — BERT-style padding masks),
    fused into the kernel; treated as non-differentiable."""
    shape4 = q.ndim == 4
    if shape4:
        b, h, ln, dh = q.shape
        num_heads = h
        q = q.reshape(b * h, ln, dh)
        k = k.reshape(b * h, ln, dh)
        v = v.reshape(b * h, ln, dh)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    impl = _resolve_impl(use_pallas)
    if key_padding_bias is not None:
        out = _flash_biased(q, k, v, key_padding_bias, float(scale),
                            bool(causal), impl, int(num_heads))
    else:
        out = _flash(q, k, v, float(scale), bool(causal), impl)
    if shape4:
        out = out.reshape(b, h, ln, dh)
    return out


# --------------------------------------------------------------------------
# SPMD: shard_map over (data, model); ring dispatch for a sharded seq axis
# --------------------------------------------------------------------------

def _shard_map(fn, mesh, in_specs, out_specs):
    # shared fused-tier wrapper (ops/kernel_tier.partitioned_call) — this
    # module's original helper, extracted so CE/adam/embedding/layernorm
    # partition the same way
    from .kernel_tier import partitioned_call
    return partitioned_call(fn, mesh, in_specs, out_specs)


def _mesh_axis(mesh, name, dim_size):
    """Axis name if present, >1, and divides dim_size; else None."""
    from .kernel_tier import mesh_axis
    return mesh_axis(mesh, name, dim_size)


def flash_attention_spmd(q, k, v, mesh, scale=None, causal=True,
                         use_pallas=None, ring_zigzag=False,
                         key_padding_bias=None):
    """[B, H, L, dh] under an active mesh: batch sharded over 'data', heads
    over 'model', kernel per shard via shard_map. If the 'seq' axis shards
    L, dispatches to ring attention (the long-context mode); ring_zigzag
    uses the balanced causal layout (parallel/ring_attention.py)."""
    from jax.sharding import PartitionSpec as P
    b, h, ln, dh = q.shape
    if scale is None:
        scale = dh ** -0.5
    data_ax = _mesh_axis(mesh, 'data', b)
    model_ax = _mesh_axis(mesh, 'model', h)
    seq_ax = _mesh_axis(mesh, 'seq', ln)
    if seq_ax is not None:
        if key_padding_bias is not None:
            # ring + bias would need the bias rotating with K/V blocks;
            # the partitionable einsum reference covers this case
            return _flash_biased(
                q.reshape(b * h, ln, dh), k.reshape(b * h, ln, dh),
                v.reshape(b * h, ln, dh), key_padding_bias,
                float(scale), bool(causal), 'ref',
                h).reshape(b, h, ln, dh)
        from ..parallel.ring_attention import ring_attention
        zz = (bool(ring_zigzag) and causal
              and ln % (2 * mesh.shape[seq_ax]) == 0)
        return ring_attention(q, k, v, mesh, axis_name=seq_ax,
                              scale=scale, causal=causal,
                              batch_axis=data_ax, head_axis=model_ax,
                              zigzag=zz)
    impl = _resolve_impl(use_pallas)
    spec = P(data_ax, model_ax, None, None)

    if key_padding_bias is None:
        def inner(ql, kl, vl):
            lb, lh = ql.shape[0], ql.shape[1]
            o = _flash(ql.reshape(lb * lh, ln, dh),
                       kl.reshape(lb * lh, ln, dh),
                       vl.reshape(lb * lh, ln, dh), float(scale),
                       bool(causal), impl)
            return o.reshape(lb, lh, ln, dh)

        return _shard_map(inner, mesh, (spec, spec, spec), spec)(q, k, v)

    # the [B, L] bias shards along the batch axis like Q/K/V
    bspec = P(data_ax, None)

    def inner_biased(ql, kl, vl, bl):
        lb, lh = ql.shape[0], ql.shape[1]
        o = _flash_biased(ql.reshape(lb * lh, ln, dh),
                          kl.reshape(lb * lh, ln, dh),
                          vl.reshape(lb * lh, ln, dh), bl, float(scale),
                          bool(causal), impl, lh)
        return o.reshape(lb, lh, ln, dh)

    return _shard_map(inner_biased, mesh, (spec, spec, spec, bspec),
                      spec)(q, k, v, key_padding_bias)


@register_op('flash_attention')
def _flash_attention_op(ctx, op):
    """Program-level op: inputs Q, K, V [B, H, L, dh]; attrs scale (float,
    default dh^-0.5) and causal (bool). Under bf16 AMP the kernel's matmuls
    run bf16 on the MXU with f32 accumulation (preferred_element_type) and
    f32 softmax state. Under an active SPMD mesh the kernel runs per shard
    via shard_map (ring attention when the sequence axis is sharded)."""
    from ..core import amp
    q = ctx.in1(op, 'Q')
    k = ctx.in1(op, 'K')
    v = ctx.in1(op, 'V')
    out_dtype = q.dtype
    q, k, v = amp.cast_compute(op, q, k, v)
    bias = ctx.in1(op, 'KeyPaddingBias')       # optional [B, L]
    if bias is not None and q.ndim != 4:
        raise NotImplementedError(
            "flash_attention KeyPaddingBias needs 4-d [B, H, L, dh] Q "
            "(the bias row maps to batch via the head dim)")
    # missing attr -> kernel default dh**-0.5; a present value (incl. 0.0)
    # is literal. Legacy programs that stored 0.0 meaning "default" keep
    # that behavior.
    scale = op.attr('scale', None)
    scale = None if scale is None or scale == 0.0 else float(scale)
    causal = op.attr('causal', True)
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    # the same tier knob as every other fused unit picks the lowering
    # (pallas | interpret -> the kernels, anything else -> the einsum
    # reference, counted as 'off': there is no distinct xla emission).
    # Under a mesh the kernel needs batch/head axes to shard_map over
    # (the XLA partitioner cannot split a pallas custom call), and a
    # sharded sequence axis takes the ring path, which has no kernel.
    ring = meshed and q.ndim == 4 and \
        _mesh_axis(mesh, 'seq', q.shape[2]) is not None
    pallas_ok = flash_shapes_ok(q.shape[-2]) and not ring and \
        (q.ndim == 4 or not meshed)
    impl = kernel_tier.dispatch(
        'flash_attention', pallas_ok=pallas_ok, xla_ok=False, mesh=mesh,
        count=getattr(ctx, 'sparse_mode', None) != 'scout')
    use_pallas = {'pallas': True, 'interpret': 'interpret'}.get(impl, False)
    if meshed and q.ndim == 4:
        out = flash_attention_spmd(
            q, k, v, mesh, scale=scale, causal=causal,
            use_pallas=use_pallas,
            ring_zigzag=op.attr('ring_zigzag', False),
            key_padding_bias=bias)
    else:
        out = flash_attention(q, k, v, scale=scale, causal=causal,
                              use_pallas=use_pallas,
                              key_padding_bias=bias)
    ctx.out(op, 'Out', out.astype(out_dtype))
