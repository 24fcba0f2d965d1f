"""Blocked flash attention: the pallas kernel tier (SURVEY §2.4: the TPU
analog of the reference's operators/jit/ runtime-codegen kernels
(jit/kernel_base.h:24-52), with the same refer-vs-optimized cross-checking
discipline of operators/jit/test.cc — see tests/test_attention.py).

Forward: FlashAttention-2 style. Grid (batch*head, q tile, key block): a
step holds bq queries and the block of K and V that its BlockSpec fetched
-- the whole key axis where the VMEM budget holds it (L 2048: one block,
fetched once a (batch*head)) -- and WALKS it bk keys a trip with a trip
count that ends at the diagonal: a tile above it takes no grid step and no
DMA, and only the trips ON the diagonal build and apply the causal mask.
Where the key axis takes several blocks, a step past the diagonal names
the block the diagonal's step held, so nothing is fetched for it. A trip's
scores are [bk, bq], queries along the lanes: the running max, the running
denominator, the rescale and LSE are [1, bq] rows (a [bq, 1] column costs a
vreg every 8 rows, an exp of it as much as an exp of 8 x 128 scores: that
shape, not the mask or the second select, was what made round 3's forward
tile cost 1.7-2.1 x a dQ tile), both reductions run down the sublanes, and
the f32 accumulator is [dh, bq], turned once a q tile. Scores live in VMEM
only. Matmuls feed the MXU in the input dtype (bf16 under AMP) with f32
accumulation via preferred_element_type; a power-of-two scale (head size
64: 2^-3) multiplies the [bq, dh] operand once, exactly, any other the f32
scores. Alongside O it emits per-row LSE (logsumexp), the residual the
backward needs, in the [1, L] layout both backward kernels read.

Backward: two pallas kernels (the FlashAttention-2 split), the same walk:
  - dQ:    a step holds a q tile and walks the keys up to the diagonal;
  - dK/dV: a step holds a k tile and walks the queries FROM the diagonal.
Both recompute the probability tile from (Q, K, LSE) instead of storing it
— O(L) memory, O(L^2) recompute, the standard trade on HBM-bound hardware.
delta = rowsum(dO * O) is precomputed outside the kernels (XLA fuses it).
The tile sizes are `flash_attention_tiling`'s, a function of (L, head size,
dtype, kernel) with the sweep that set its constants beside it; a program's
`flash_attention_tiling_total{kernel,bq,bk}` says which schedule it got.

Under SPMD (an active MeshRunner mesh) the op no longer falls back to
einsum: it wraps the kernel in shard_map over the (data, model) axes —
batch and heads are embarrassingly parallel — and when the sequence axis
itself is sharded it dispatches to the ring-attention path
(parallel/ring_attention.py), making ring the long-context execution mode
of this same op rather than a parallel universe.
"""
import functools
import math

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
# the tile schedule
# --------------------------------------------------------------------------

# The tile, from `tools/kernbench.py --cases flash_attention --size bench
# --tilings ...` on one v5e (2026-10-02, jax 0.9.0, libtpu 0.0.34; PERF.md,
# PR 52), ms a call forward / dQ / dKV:
#   bh 64, L 2048, dh 64, bfloat16 (fd355m-train-2k):
#     512 x 512 0.79 / 0.91 / 1.20   1024 x 1024 0.83 / 0.88 / 1.31
#     1024 x 512 0.87 / 0.91 / 1.34  512 x 256   0.95 / 1.07 / 1.32
#     256 x 512 1.19 / 1.14 / 1.54   256 x 256   1.43 / 1.33 / 1.84
#     512 x 128 1.23 / 1.37 / 1.63   128 x 128   3.30 / 3.02 / 3.08
#   (round 3's kernels at 512 x 512: 1.89 / 1.14 / 1.52)
#   dh 128 bfloat16 (bh 32): 512 x 512 0.54 / 0.58 / 0.66, 256 x 256 0.93 /
#   0.77 / 0.96, 1024 x 1024 0.58 / 0.57 / 0.73; float32 dh 64 (bh 64):
#   512 x 512 0.91 / 1.05 / 1.56, 256 x 256 1.54 / 1.46 / 2.12, 1024 x
#   1024 0.97 / 1.03 / dKV out of VMEM.
# The three kernels, both head sizes and both dtypes want the same tile: a
# trip's fixed cost (the state read and written, the matmuls' fill) is
# large beside 256 x 256 scores, and 1024 x 1024 wastes more of the four
# tiles on the diagonal than it saves in trips.
_TILE = 512
# the K and V (dKV: Q and dO) blocks a grid step holds, double-buffered,
# of the 16 MB a kernel has on v5e; a trip's float32 scores, their exp and
# the backward's dP and dS (4 x 1 MB at 512 x 512) take most of the rest.
# At L 2048 x dh 64 the whole of K and V is 1 MB of it in bfloat16 (a row
# of 64 lies in a 128-lane tile), 2 MB in float32: one block.
_WALK_VMEM_BYTES = 4 << 20


def flash_attention_tiling(ln, dh, dtype, kernel):
    """(bq, bk, block) of `kernel` ('fwd', 'bwd_dq', 'bwd_dkv') at sequence
    length `ln`, head size `dh`, operands of `dtype`: a grid step holds bq
    query rows (dKV: bk keys) and WALKS the other axis bk keys (dKV: bq
    queries) a trip, inside the block of `block` rows of it that the step's
    BlockSpec fetched -- the whole axis where `_WALK_VMEM_BYTES` holds it.
    Short or odd L (BERT's 128 / 512, `flash_shapes_ok`) is one tile. A
    function of its arguments alone: no process sees another schedule."""
    bq = bk = _pick_block(ln, _TILE)
    walk = bq if kernel == 'bwd_dkv' else bk
    # two operands, two buffers each, a row padded to the 128 lanes
    row = 4 * -(-dh // 128) * 128 * jnp.dtype(dtype).itemsize
    n = ln // walk
    fit = [d for d in range(1, n + 1)
           if n % d == 0 and d * walk * row <= _WALK_VMEM_BYTES]
    return bq, bk, walk * max(fit or [1])


def _count_tiling(kernel, bq, bk):
    """`flash_attention_tiling_total{kernel,bq,bk}`: + 1 a kernel a lowering
    (trace time), so a program's counters say which schedule it got."""
    from .. import monitor
    monitor.inc('flash_attention_tiling_total',
                labels={'kernel': kernel, 'bq': str(bq), 'bk': str(bk)})


def _folds(scale):
    """A power of two times a float is that float scaled: the scale then
    multiplies the [tile, dh] operand once, not every score."""
    return math.frexp(scale)[0] == 0.5


def _trips(lo, hi, tile):
    """tile(t) for t in [lo, hi): straight code where the bounds are known
    and one trip, else a loop whose trip count the grid step computes."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        if hi > lo:
            tile(lo)
        return
    lax.fori_loop(lo, hi, lambda t, c: tile(t) or c, 0)


def _at(t, size):
    """pl.ds of sub-tile `t` of `size` rows."""
    import jax.experimental.pallas as pl
    if isinstance(t, int):
        return pl.ds(t * size, size)
    return pl.ds(pl.multiple_of(t * size, size), size)


def _visible(q0, k0, bq, bk):
    """q0 + (column of the [bk, bq] tile) >= k0 + (row): the causal mask
    of the tile whose first query is q0 and first key k0."""
    qi = lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    ki = lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    return qi - ki >= k0 - q0


def _key_walk(causal, i, j, bq, bk, spm):
    """The trips of query tile i over the sub-tiles of key block j, as
    (first masked, end): [0, masked) lie wholly under the diagonal,
    [masked, end) on it; what lies above it takes no trip."""
    if not causal:
        return spm, spm
    full = jnp.clip((i * bq) // bk - j * spm, 0, spm)
    end = jnp.clip(((i + 1) * bq + bk - 1) // bk - j * spm, 0, spm)
    return full, end


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _dot(a, b, dims):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------

def _fwd_kernel(scale, causal, bk, has_bias, *refs):
    """A grid step holds bq queries and walks the keys of its K / V block
    bk a trip, up to the diagonal. The scores of a trip are [bk, bq]: a
    row's max, sum, `alpha` and `lse` are [1, bq] rows along the lanes (4
    vregs at 512 where a [bq, 1] column is 64), the two reductions run
    down the sublanes, and the accumulator is [dh, bq], turned once at
    the end."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    spm = k_ref.shape[1] // bk
    fold = _folds(scale)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0]
    if fold:
        q = q * scale

    def tile(t, masked):
        k = k_ref[0, _at(t, bk), :]
        v = v_ref[0, _at(t, bk), :]
        s = _dot(k, q, _NT)                                 # [bk, bq]
        if not fold:
            s = s * scale
        if bias_ref is not None:
            # per-key additive bias (padding masks: 0 keep / -1e9 drop)
            bias = bias_ref[0, t].reshape(bk, 1)
            s = s + bias
        if masked:
            # keys are walked from column 0, so a row's m is finite after
            # its first tile and exp(-1e30 - m) is an exact 0.0
            s = jnp.where(_visible(i * bq, (j * spm + t) * bk, bq, bk),
                          s, _NEG_INF)
        m_prev = m_scr[...]                                 # [1, bq]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if bias_ref is not None:
            # exact zero for dropped keys (-1e8 or lower -- covers the
            # documented -1e9 pad convention), independent of underflow
            p = jnp.where(bias > -1e8, p, 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=0, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha + _dot(
            v, p.astype(v.dtype), _TN)                      # [dh, bq]

    masked, end = _key_walk(causal, i, j, bq, bk, spm)
    _trips(0, masked, lambda t: tile(t, False))
    _trips(masked, end, lambda t: tile(t, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).T.astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


def _key_specs(causal, bq, bk, block, dh, n_heads):
    """BlockSpecs of a step's K / V block ([L, dh], `block` rows) and of its
    padding bias ([B, L] as a sub-tile a row: batch*head row b is batch
    b // n_heads), for a step that holds q tile i: under the causal mask
    a step past the diagonal names the block the diagonal's step held, so
    nothing is fetched for it."""
    import jax.experimental.pallas as pl
    if causal:
        def held(i, j):
            return jnp.minimum(j, ((i + 1) * bq - 1) // block)
    else:
        def held(i, j):
            return j
    return (pl.BlockSpec((1, block, dh),
                         lambda b, i, j: (b, held(i, j), 0)),
            pl.BlockSpec((1, block // bk, 1, bk),
                         lambda b, i, j: (b // n_heads, held(i, j), 0, 0)))


def _flash_fwd_pallas(q, k, v, scale, causal, interpret, bias=None,
                      n_heads=1, tiling=None):
    """(O, LSE) of [BH, L, dh] operands by the forward kernel under the
    rule's schedule (or `tiling`, the sweep's). The call goes through
    `jax.jit`: the 24 layers of a program trace and lower the kernel ONCE
    (set-up, not the step: XLA inlines the calls)."""
    ln, dh = q.shape[1:]
    tiling = tiling or flash_attention_tiling(ln, dh, q.dtype, 'fwd')
    _count_tiling('fwd', *tiling[:2])
    return _fwd_call(q, k, v, bias, scale, causal, interpret, n_heads,
                     tiling)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _fwd_call(q, k, v, bias, scale, causal, interpret, n_heads, tiling):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, ln, dh = q.shape
    bq, bk, block = tiling
    has_bias = bias is not None
    kernel = functools.partial(_fwd_kernel, scale, causal, bk, has_bias)
    qspec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kspec, bias_spec = _key_specs(causal, bq, bk, block, dh, n_heads)
    ins = [q, k, v]
    in_specs = [qspec, kspec, kspec]
    if has_bias:
        ins.append(bias.astype(jnp.float32).reshape(-1, ln // bk, 1, bk))
        in_specs.append(bias_spec)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, ln // bq, ln // block),
        in_specs=in_specs,
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), q.dtype),
                   jax.ShapeDtypeStruct((bh, 1, ln), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((dh, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name='flash_attention_fwd',
    )(*ins)
    return o, lse[:, 0]


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------

def _bwd_dq_kernel(scale, causal, bk, has_bias, *refs):
    """The forward's walk and its [bk, bq] scores; dQ sums as [dh, bq]."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)
    bq = q_ref.shape[1]
    spm = k_ref.shape[1] // bk
    fold = _folds(scale)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q, do = q_ref[0], do_ref[0]
    if fold:
        q = q * scale
    lse, delta = lse_ref[0], delta_ref[0]                   # [1, bq]

    def tile(t, masked):
        k = k_ref[0, _at(t, bk), :]
        v = v_ref[0, _at(t, bk), :]
        s = _dot(k, q, _NT)                                 # [bk, bq]
        if not fold:
            s = s * scale
        if bias_ref is not None:
            bias = bias_ref[0, t].reshape(bk, 1)
            s = s + bias
        if masked:
            s = jnp.where(_visible(i * bq, (j * spm + t) * bk, bq, bk),
                          s, _NEG_INF)
        p = jnp.exp(s - lse)                  # masked entries underflow
        if bias_ref is not None:
            # all-padded rows have lse = log(1e-30); without the forward's
            # exact zeroing p explodes to ~e^69 and poisons dQ
            p = jnp.where(bias > -1e8, p, 0.0)
        ds = p * (_dot(v, do, _NT) - delta)
        # the scale of dS multiplies the float32 sum once, at the end
        dq_scr[...] += _dot(k, ds.astype(k.dtype), _TN)     # [dh, bq]

    masked, end = _key_walk(causal, i, j, bq, bk, spm)
    _trips(0, masked, lambda t: tile(t, False))
    _trips(masked, end, lambda t: tile(t, True))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(scale, causal, bq, has_bias, *refs):
    """A grid step holds bk keys and walks the queries of its Q / dO block
    bq a trip, FROM the diagonal. Scores as [bk, bq] here too: the walked
    queries lie along the lanes, as lse and delta are stored, and dV = P^T
    dO, dK = dS^T Q are plain products."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    i, j = pl.program_id(1), pl.program_id(2)      # i: k tile, j: q block
    bk, dh = k_ref.shape[1:]
    spm = q_ref.shape[1] // bq
    fold = _folds(scale)

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    k, v = k_ref[0], v_ref[0]
    if fold:
        k = k * scale
    bias = bias_ref[0].reshape(bk, 1) if has_bias else None

    def tile(t, masked):
        q = q_ref[0, _at(t, bq), :]
        do = do_ref[0, _at(t, bq), :]
        s = _dot(k, q, _NT)                                 # [bk, bq]
        if not fold:
            s = s * scale
        if bias is not None:
            s = s + bias
        if masked:
            s = jnp.where(_visible((j * spm + t) * bq, i * bk, bq, bk),
                          s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, t])
        if bias is not None:
            p = jnp.where(bias > -1e8, p, 0.0)
        dv_scr[...] += _dot(p.astype(do.dtype), do, _NN)
        dp = _dot(v, do, _NT)
        ds = p * (dp - delta_ref[0, t])
        dk_scr[...] += _dot(ds.astype(q.dtype), q, _NN)

    # the walk over queries STARTS at the diagonal: [first, full) on it,
    # [full, spm) wholly under it
    if causal:
        first = jnp.clip((i * bk) // bq - j * spm, 0, spm)
        full = jnp.clip(((i + 1) * bk + bq - 2) // bq - j * spm, 0, spm)
    else:
        first = full = 0
    _trips(first, full, lambda t: tile(t, True))
    _trips(full, spm, lambda t: tile(t, False))

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, interpret,
                      bias=None, n_heads=1, tiling_dq=None,
                      tiling_dkv=None):
    """(dQ, dK, dV) by the two backward kernels, each under the rule's
    schedule (or the sweep's); through `jax.jit` as the forward is."""
    ln, dh = q.shape[1:]
    tiling_dq = tiling_dq or flash_attention_tiling(ln, dh, q.dtype,
                                                    'bwd_dq')
    tiling_dkv = tiling_dkv or flash_attention_tiling(ln, dh, q.dtype,
                                                      'bwd_dkv')
    _count_tiling('bwd_dq', *tiling_dq[:2])
    _count_tiling('bwd_dkv', *tiling_dkv[:2])
    return _bwd_call(q, k, v, o, lse, do, bias, scale, causal, interpret,
                     n_heads, tiling_dq, tiling_dkv)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12))
def _bwd_call(q, k, v, o, lse, do, bias, scale, causal, interpret, n_heads,
              tiling_dq, tiling_dkv):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bh, ln, dh = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    has_bias = bias is not None
    bias32 = bias.astype(jnp.float32) if has_bias else None
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))

    bq, bk, block = tiling_dq
    qspec = pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0))
    kspec, bias_spec = _key_specs(causal, bq, bk, block, dh, n_heads)
    rowspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    ins = [q, k, v, do, lse[:, None, :], delta[:, None, :]]
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    if has_bias:
        ins.append(bias32.reshape(-1, ln // bk, 1, bk))
        in_specs.append(bias_spec)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale, causal, bk, has_bias),
        grid=(bh, ln // bq, ln // block),
        in_specs=in_specs,
        out_specs=[qspec],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), q.dtype)],
        scratch_shapes=[pltpu.VMEM((dh, bq), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name='flash_attention_bwd_dq',
    )(*ins)[0]

    # k-major grid: a step holds a K / V tile and walks the queries
    bq, bk, block = tiling_dkv
    if causal:
        # a step before the diagonal names the block the diagonal's holds
        def held(i, j):
            return jnp.maximum(j, (i * bk) // block)
    else:
        def held(i, j):
            return j
    qspec = pl.BlockSpec((1, block, dh), lambda b, i, j: (b, held(i, j), 0))
    kspec = pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, i, 0))
    # lse and delta a sub-tile a row, so a trip reads its row by index
    rowspec = pl.BlockSpec((1, block // bq, 1, bq),
                           lambda b, i, j: (b, held(i, j), 0, 0))
    ins = [q, k, v, do, lse.reshape(bh, ln // bq, 1, bq),
           delta.reshape(bh, ln // bq, 1, bq)]
    in_specs = [qspec, kspec, kspec, qspec, rowspec, rowspec]
    if has_bias:
        ins.append(bias32[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, i, j: (b // n_heads, 0, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale, causal, bq, has_bias),
        grid=(bh, ln // bk, ln // block),
        in_specs=in_specs,
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, ln, dh), k.dtype),
                   jax.ShapeDtypeStruct((bh, ln, dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, dh), jnp.float32),
                        pltpu.VMEM((bk, dh), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name='flash_attention_bwd_dkv',
    )(*ins)
    return dq, dk, dv


# --------------------------------------------------------------------------
# custom_vjp wrapper ([BH, L, dh] level)
# --------------------------------------------------------------------------


def _fwd_impl(q, k, v, scale, causal, impl):
    if impl in ('pallas', 'interpret'):
        return _flash_fwd_pallas(q, k, v, scale, causal,
                                 impl == 'interpret')
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
                                 impl == 'interpret')
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
                                 impl == 'interpret', bias=bias, n_heads=n_heads)
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
            bias=bias, n_heads=n_heads)
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
