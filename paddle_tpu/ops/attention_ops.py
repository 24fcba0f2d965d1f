"""Blocked flash attention: the pallas kernel tier (SURVEY §2.4: the TPU
analog of the reference's operators/jit/ runtime-codegen kernels
(jit/kernel_base.h:24-52), with the same refer-vs-optimized cross-checking
discipline of operators/jit/test.cc — see tests/test_attention.py).

Two operand views, one set of three kernel bodies. Head-major: Q, K, V
``[BH, L, dh]`` arrays, one head a lane block (any head size; a head of 64
lies in half of a 128-lane tile, so HBM holds and every DMA moves a
half-empty row). PACKED (PR 57): the fused QKV product ``[B, L, 3 * H *
dh]`` itself, columns ``[3, H, dh]`` as `layers.fc` leaves it, the context
out ``[B, L, H * dh]`` as `attn.proj`'s `fc` reads it and the gradient ONE
array in the product's column order -- no transpose on either side of any
kernel. A lane block there is 128 columns of the product: two heads of 64
side by side (heads 2p and 2p + 1 of Q are column block p, K's block H *
dh / 128 + p, V's twice that + p) or one head of 128; BlockSpecs pick the
three out of the one array, every DMA and every store is lane-dense, and a
kernel body runs its heads one after the other on static lane slices of
the tiles it holds. The packed view applies to heads of 64 or 128 in whole
lane blocks, without a padding bias, and under a mesh only where nothing
shards the heads or L (`_flash_attention_op` decides, from shapes and the
mesh alone); everything else -- `layers.flash_attention`, BERT's biased
attention, ring attention, odd head sizes -- is head-major.

Forward: FlashAttention-2 style. Grid (batch, lane block, q tile, key
block) -- head-major: (batch*head, 1, ..) -- : a step holds bq queries and
the block of K and V that its BlockSpec fetched -- the whole key axis
where the VMEM budget holds it (L 2048: one block, fetched once a (batch,
lane block)) -- and WALKS it bk keys a trip with a trip count that ends at
the diagonal: a tile above it takes no grid step and no DMA, and only the
trips ON the diagonal build and apply the causal mask. Where the key axis
takes several blocks, a step past the diagonal names the block the
diagonal's step held, so nothing is fetched for it. A trip's scores are
[bk, bq] a head, queries along the lanes: the running max, the running
denominator, the rescale and LSE are [1, bq] rows (a [bq, 1] column costs
a vreg every 8 rows, an exp of it as much as an exp of 8 x 128 scores:
that shape, not the mask or the second select, was what made round 3's
forward tile cost 1.7-2.1 x a dQ tile), both reductions run down the
sublanes, and the f32 accumulator is [heads * dh, bq] (a head's rows an
aligned sublane slice), turned once a q tile. Scores live in VMEM only.
Matmuls feed the MXU in the input dtype (bf16 under AMP) with f32
accumulation via preferred_element_type; a power-of-two scale (head size
64: 2^-3) multiplies the [bq, dh] operand once, exactly, any other the f32
scores. Alongside O it emits per-row LSE (logsumexp), the residual the
backward needs, as [.., heads, 1, L] rows, the layout both backward
kernels read.

Backward: two pallas kernels (the FlashAttention-2 split), the same walk:
  - dQ:    a step holds a q tile and walks the keys up to the diagonal;
  - dK/dV: a step holds a k tile and walks the queries FROM the diagonal.
Both recompute the probability tile from (Q, K, LSE) instead of storing it
— O(L) memory, O(L^2) recompute, the standard trade on HBM-bound hardware.
delta = rowsum(dO * O) a head is precomputed outside the kernels (XLA
fuses it). Packed, dQ, dK and dV come lane-dense, [B, L, H * dh] each, and
one concatenate makes the product's cotangent (one buffer filled by both
kernels would need dKV to write two column blocks of one array a step,
which one BlockSpec cannot name); the residuals are the product, the
context and LSE. The tile sizes are `flash_attention_tiling`'s, a function
of (L, head size, dtype, kernel, heads a block) with the sweep that set
its constants beside it; a program's
`flash_attention_tiling_total{kernel,bq,bk}` says which schedule it got
and `flash_attention_layout_total{layout}` which operand view.

Under SPMD (an active MeshRunner mesh) the op no longer falls back to
einsum: it wraps the kernel in shard_map over the (data, model) axes —
batch and heads are embarrassingly parallel; the packed view over 'data'
alone, its columns cannot be sharded by head — and when the sequence axis
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
# The operand view, same tool and chip (2026-10-03, jax 0.9.0, libtpu
# 0.0.34; PERF.md, PR 57), at 512 x 512, ms a call forward / dQ / dKV, then
# 'whole': everything between the fused QKV product and `attn.proj`, both
# ways (head-major pays its two transposes each way, packed its one
# concatenate):
#   bh 64, dh 64, bfloat16:  head-major 0.78 / 0.89 / 1.18, whole 3.38;
#                            packed     0.74 / 0.83 / 1.15, whole 2.61
#   bh 32, dh 64, bfloat16:  head-major 0.41 / 0.48 / 0.63, whole 1.63;
#                            packed     0.40 / 0.46 / 0.62, whole 1.37
#   bh 32, dh 128, bfloat16: head-major 0.49 / 0.54 / 0.62, whole 2.22;
#                            packed     0.52 / 0.60 / 0.69, whole 1.70
#   bh 64, dh 64, float32:   head-major 0.81 / 0.96 / 1.39, whole 4.72;
#                            packed     0.74 / 0.84 / 1.17, whole 2.79
# Two heads of 64 a block cost LESS a head than one padded head (the DMAs
# halve); one head of 128 a block costs 6-11 % more a kernel than the same
# body on head-major arrays (the same bytes, read as 256-byte row pieces of
# a 3 x wider array) and the whole is still 23 % less.
_TILE = 512
# the K and V (dKV: Q and dO) blocks a grid step holds, double-buffered,
# of the 16 MB a kernel has on v5e; a trip's float32 scores, their exp and
# the backward's dP and dS (4 x 1 MB at 512 x 512) take most of the rest.
# At L 2048 x dh 64 the whole of K and V is 1 MB of it in bfloat16 (a row
# of 64 lies in a 128-lane tile), 2 MB in float32: one block.
_WALK_VMEM_BYTES = 4 << 20


def flash_attention_tiling(ln, dh, dtype, kernel, heads=1):
    """(bq, bk, block) of `kernel` ('fwd', 'bwd_dq', 'bwd_dkv') at sequence
    length `ln`, head size `dh`, operands of `dtype`, `heads` heads a lane
    block (2 where the packed layout holds two heads of 64 in 128 lanes):
    a grid step holds bq query rows (dKV: bk keys) and WALKS the other axis
    bk keys (dKV: bq queries) a trip, inside the block of `block` rows of
    it that the step's BlockSpec fetched -- the whole axis where
    `_WALK_VMEM_BYTES` holds it. Short or odd L (BERT's 128 / 512,
    `flash_shapes_ok`) is one tile. A function of its arguments alone: no
    process sees another schedule."""
    bq = bk = _pick_block(ln, _TILE)
    walk = bq if kernel == 'bwd_dkv' else bk
    # two operands, two buffers each; a row of one head-major head is padded
    # to the 128 lanes, a packed row of two heads of 64 fills them
    row = 4 * -(-dh * heads // 128) * 128 * jnp.dtype(dtype).itemsize
    n = ln // walk
    fit = [d for d in range(1, n + 1)
           if n % d == 0 and d * walk * row <= _WALK_VMEM_BYTES]
    return bq, bk, walk * max(fit or [1])


def _count_tiling(kernel, bq, bk, packed):
    """`flash_attention_tiling_total{kernel,bq,bk}` and
    `flash_attention_layout_total{layout}`: + 1 a kernel a lowering (trace
    time), so a program's counters say which schedule and which operand
    layout ('packed': the fused QKV product; 'heads': head-major) each of
    its attention ops got."""
    from .. import monitor
    monitor.inc('flash_attention_tiling_total',
                labels={'kernel': kernel, 'bq': str(bq), 'bk': str(bk)})
    monitor.inc('flash_attention_layout_total',
                labels={'layout': 'packed' if packed else 'heads'})


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
# heads a lane block. A kernel's blocks are [rows, heads * dh] wide: one
# head (the head-major view, and packed heads of 128) or two heads of 64
# side by side in the 128 lanes of the fused QKV product's column block.
# A head's operand is a static lane slice of the block (`_head`), its
# [dh, bq] accumulator an aligned sublane slice of the [heads * dh, bq]
# scratch, its softmax state a [1, bq] row of its own. The other way --
# the other head's lanes of the held operand zeroed once a step and every
# contraction over all 128 lanes -- was swept beside it (kernbench, one
# v5e, 2026-10-03, PR 57; ms a call forward / dQ / dKV at fd355m-train-2k):
# slices 0.74 / 0.81 / 1.12, zeroed lanes 0.85 / 0.91 / 1.12 (that call's).
# --------------------------------------------------------------------------

def _span(h, heads, width):
    """Head h's share of an axis of `width` (the lanes of a tile, the rows
    of the turned accumulator): all of it where the block holds one head."""
    dh = width // heads
    return slice(h * dh, (h + 1) * dh)


def _head(x, h, heads):
    """Head h's [rows, dh] of the [rows, heads * dh] tile `x`."""
    return x if heads == 1 else x[:, _span(h, heads, x.shape[1])]


def _scores(k, q, scale, bias, visible):
    """One head's [bk, bq] scores of a trip: k @ q.T, times the scale where
    it was not folded into an operand, plus the per-key padding bias [bk,
    1] (0 keep / -1e9 drop), the tile's causal mask applied."""
    s = _dot(k, q, _NT)
    if not _folds(scale):
        s = s * scale
    if bias is not None:
        s = s + bias
    if visible is not None:
        s = jnp.where(visible, s, _NEG_INF)
    return s


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------

def _fwd_kernel(scale, causal, bk, has_bias, heads, *refs):
    """A grid step holds bq queries of one lane block (`heads` heads) and
    walks the keys of its K / V block bk a trip, up to the diagonal. The
    scores of a trip are [bk, bq] a head: a row's max, sum, `alpha` and
    `lse` are [1, bq] rows along the lanes (4 vregs at 512 where a [bq, 1]
    column is 64), the two reductions run down the sublanes, and the
    accumulator is [heads * dh, bq], turned once at the end."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
        bias_ref = None
    i, j = pl.program_id(2), pl.program_id(3)
    bq, width = q_ref.shape[1:]
    spm = k_ref.shape[1] // bk

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0]
    if _folds(scale):
        q = q * scale
    qs = [_head(q, h, heads) for h in range(heads)]

    def tile(t, masked):
        k = k_ref[0, _at(t, bk), :]
        v = v_ref[0, _at(t, bk), :]
        bias = None if bias_ref is None else bias_ref[0, t].reshape(bk, 1)
        # keys are walked from column 0, so a row's m is finite after its
        # first tile and exp(-1e30 - m) of a masked score is an exact 0.0
        visible = _visible(i * bq, (j * spm + t) * bk, bq, bk) \
            if masked else None
        for h, qh in enumerate(qs):
            s = _scores(_head(k, h, heads), qh, scale, bias, visible)
            m_prev = m_scr[h]                                   # [1, bq]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if bias is not None:
                # exact zero for dropped keys (-1e8 or lower -- covers the
                # documented -1e9 pad convention), independent of underflow
                p = jnp.where(bias > -1e8, p, 0.0)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=0, keepdims=True)
            m_scr[h] = m_new
            rows = _span(h, heads, width)
            acc_scr[rows] = acc_scr[rows] * alpha + _dot(
                _head(v, h, heads), p.astype(v.dtype), _TN)     # [dh, bq]

    masked, end = _key_walk(causal, i, j, bq, bk, spm)
    _trips(0, masked, lambda t: tile(t, False))
    _trips(masked, end, lambda t: tile(t, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        for h in range(heads):
            l = jnp.maximum(l_scr[h], 1e-30)
            rows = _span(h, heads, width)
            acc_scr[rows] = acc_scr[rows] / l
            lse_ref[0, h] = m_scr[h] + jnp.log(l)
        o_ref[0] = acc_scr[...].T.astype(o_ref.dtype)


def _view(q, heads):
    """(batches, lane blocks, heads a block, lanes a block, the column
    blocks at which Q, K and V start) of a kernel's operands: head-major
    ``[BH, L, dh]`` arrays (`heads` 0: one block of one head, dh lanes,
    three arrays) or the packed ``[B, L, 3 * heads * dh]`` product, whose
    columns are ``[3, heads, dh]`` -- Q's lane block p is its column block
    p, K's is heads * dh / 128 + p, V's twice that + p."""
    if not heads:
        return q.shape[0], 1, 1, q.shape[2], (0, 0, 0)
    dh = q.shape[2] // (3 * heads)
    blocks = heads * dh // 128
    return q.shape[0], blocks, 128 // dh, 128, (0, blocks, 2 * blocks)


def _key_specs(causal, bq, bk, block, width, n_heads, k_at, v_at):
    """BlockSpecs of a step's K and V blocks (`block` rows of lane block
    p, from column block `k_at` / `v_at` on) and of its padding bias
    ([B, L] as a sub-tile a row: batch*head row b of the head-major view
    is batch b // n_heads), for a step that holds q tile i: under the
    causal mask a step past the diagonal names the block the diagonal's
    step held, so nothing is fetched for it."""
    import jax.experimental.pallas as pl
    if causal:
        def held(i, j):
            return jnp.minimum(j, ((i + 1) * bq - 1) // block)
    else:
        def held(i, j):
            return j
    return (pl.BlockSpec((1, block, width),
                         lambda b, p, i, j: (b, held(i, j), k_at + p)),
            pl.BlockSpec((1, block, width),
                         lambda b, p, i, j: (b, held(i, j), v_at + p)),
            pl.BlockSpec((1, block // bk, 1, bk),
                         lambda b, p, i, j: (b // n_heads, held(i, j), 0,
                                             0)))


def _flash_fwd_pallas(q, k, v, scale, causal, interpret, bias=None,
                      n_heads=1, tiling=None, heads=0):
    """(O, LSE) by the forward kernel under the rule's schedule (or
    `tiling`, the sweep's): of head-major [BH, L, dh] operands ([BH, L,
    dh], [BH, L]), or with `heads` of the packed [B, L, 3 * heads * dh]
    product `q` (k and v None: [B, L, heads * dh], [B, heads, L]). The call
    goes through `jax.jit`: the 24 layers of a program trace and lower the
    kernel ONCE (set-up, not the step: XLA inlines the calls)."""
    ln = q.shape[1]
    _, _, per, width, _ = _view(q, heads)
    tiling = tiling or flash_attention_tiling(ln, width // per, q.dtype,
                                              'fwd', per)
    _count_tiling('fwd', *tiling[:2], packed=bool(heads))
    return _fwd_call(q, k, v, bias, scale, causal, interpret, n_heads,
                     tiling, heads)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9))
def _fwd_call(q, k, v, bias, scale, causal, interpret, n_heads, tiling,
              heads):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ln = q.shape[1]
    nbatch, blocks, per, width, (q_at, k_at, v_at) = _view(q, heads)
    bq, bk, block = tiling
    has_bias = bias is not None
    kernel = functools.partial(_fwd_kernel, scale, causal, bk, has_bias,
                               per)
    qspec = pl.BlockSpec((1, bq, width),
                         lambda b, p, i, j: (b, i, q_at + p))
    kspec, vspec, bias_spec = _key_specs(causal, bq, bk, block, width,
                                         n_heads, k_at, v_at)
    ins = [q, q, q] if heads else [q, k, v]
    in_specs = [qspec, kspec, vspec]
    if has_bias:
        ins.append(bias.astype(jnp.float32).reshape(-1, ln // bk, 1, bk))
        in_specs.append(bias_spec)
    o, lse = pl.pallas_call(
        kernel,
        grid=(nbatch, blocks, ln // bq, ln // block),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, bq, width),
                                lambda b, p, i, j: (b, i, p)),
                   pl.BlockSpec((1, per, 1, bq),
                                lambda b, p, i, j: (b, p, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((nbatch, ln, blocks * width),
                                        q.dtype),
                   jax.ShapeDtypeStruct((nbatch, blocks * per, 1, ln),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((per, 1, bq), jnp.float32),
                        pltpu.VMEM((per, 1, bq), jnp.float32),
                        pltpu.VMEM((width, bq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name='flash_attention_fwd',
    )(*ins)
    return o, (lse[:, :, 0] if heads else lse[:, 0, 0])


# --------------------------------------------------------------------------
# backward kernels
# --------------------------------------------------------------------------

def _bwd_dq_kernel(scale, causal, bk, has_bias, heads, *refs):
    """The forward's walk and its [bk, bq] scores a head; dQ sums as
    [heads * dh, bq]."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dq_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        bias_ref = None
    i, j = pl.program_id(2), pl.program_id(3)
    bq, width = q_ref.shape[1:]
    spm = k_ref.shape[1] // bk

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    q = q_ref[0]
    if _folds(scale):
        q = q * scale
    qs = [_head(q, h, heads) for h in range(heads)]
    dos = [_head(do_ref[0], h, heads) for h in range(heads)]

    def tile(t, masked):
        k = k_ref[0, _at(t, bk), :]
        v = v_ref[0, _at(t, bk), :]
        bias = None if bias_ref is None else bias_ref[0, t].reshape(bk, 1)
        visible = _visible(i * bq, (j * spm + t) * bk, bq, bk) \
            if masked else None
        for h in range(heads):
            kh = _head(k, h, heads)
            s = _scores(kh, qs[h], scale, bias, visible)
            p = jnp.exp(s - lse_ref[0, h])    # masked entries underflow
            if bias is not None:
                # all-padded rows have lse = log(1e-30); without the
                # forward's exact zeroing p explodes to ~e^69 and poisons dQ
                p = jnp.where(bias > -1e8, p, 0.0)
            ds = p * (_dot(_head(v, h, heads), dos[h], _NT)
                      - delta_ref[0, h])
            # the scale of dS multiplies the float32 sum once, at the end
            dq_scr[_span(h, heads, width)] += _dot(
                kh, ds.astype(k.dtype), _TN)                    # [dh, bq]

    masked, end = _key_walk(causal, i, j, bq, bk, spm)
    _trips(0, masked, lambda t: tile(t, False))
    _trips(masked, end, lambda t: tile(t, True))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).T.astype(dq_ref.dtype)


def _bwd_dkv_kernel(scale, causal, bq, has_bias, heads, *refs):
    """A grid step holds bk keys of one lane block and walks the queries
    of its Q / dO block bq a trip, FROM the diagonal. Scores as [bk, bq]
    here too: the walked queries lie along the lanes, as lse and delta are
    stored, and dV = P^T dO, dK = dS^T Q are plain products -- [bk, heads
    * dh], of which a head's lanes are its own."""
    import jax.experimental.pallas as pl
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    i, j = pl.program_id(2), pl.program_id(3)      # i: k tile, j: q block
    bk, width = k_ref.shape[1:]
    spm = q_ref.shape[1] // bq

    @pl.when(j == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    k = k_ref[0]
    if _folds(scale):
        k = k * scale
    ks = [_head(k, h, heads) for h in range(heads)]
    vs = [_head(v_ref[0], h, heads) for h in range(heads)]
    bias = bias_ref[0].reshape(bk, 1) if has_bias else None

    def tile(t, masked):
        q = q_ref[0, _at(t, bq), :]
        do = do_ref[0, _at(t, bq), :]
        visible = _visible((j * spm + t) * bq, i * bk, bq, bk) \
            if masked else None
        for h in range(heads):
            qh, doh = _head(q, h, heads), _head(do, h, heads)
            s = _scores(ks[h], qh, scale, bias, visible)
            p = jnp.exp(s - lse_ref[0, h, t])
            if bias is not None:
                p = jnp.where(bias > -1e8, p, 0.0)
            cols = _span(h, heads, width)
            dv_scr[:, cols] += _dot(p.astype(do.dtype), doh, _NN)
            ds = p * (_dot(vs[h], doh, _NT) - delta_ref[0, h, t])
            dk_scr[:, cols] += _dot(ds.astype(q.dtype), qh, _NN)

    # the walk over queries STARTS at the diagonal: [first, full) on it,
    # [full, spm) wholly under it
    if causal:
        first = jnp.clip((i * bk) // bq - j * spm, 0, spm)
        full = jnp.clip(((i + 1) * bk + bq - 2) // bq - j * spm, 0, spm)
    else:
        first = full = 0
    _trips(first, full, lambda t: tile(t, True))
    _trips(full, spm, lambda t: tile(t, False))

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal, interpret,
                      bias=None, n_heads=1, tiling_dq=None,
                      tiling_dkv=None, heads=0):
    """(dQ, dK, dV) by the two backward kernels, each under the rule's
    schedule (or the sweep's); through `jax.jit` as the forward is. With
    `heads`, `q` is the packed product (k, v None), `o` and `do` are [B,
    L, heads * dh], `lse` [B, heads, L], and the three gradients come
    lane-dense, [B, L, heads * dh] each, in the product's column order."""
    ln = q.shape[1]
    _, _, per, width, _ = _view(q, heads)
    dh = width // per
    tiling_dq = tiling_dq or flash_attention_tiling(ln, dh, q.dtype,
                                                    'bwd_dq', per)
    tiling_dkv = tiling_dkv or flash_attention_tiling(ln, dh, q.dtype,
                                                      'bwd_dkv', per)
    _count_tiling('bwd_dq', *tiling_dq[:2], packed=bool(heads))
    _count_tiling('bwd_dkv', *tiling_dkv[:2], packed=bool(heads))
    return _bwd_call(q, k, v, o, lse, do, bias, scale, causal, interpret,
                     n_heads, tiling_dq, tiling_dkv, heads)


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12, 13))
def _bwd_call(q, k, v, o, lse, do, bias, scale, causal, interpret, n_heads,
              tiling_dq, tiling_dkv, heads):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ln = q.shape[1]
    nbatch, blocks, per, width, (q_at, k_at, v_at) = _view(q, heads)
    n_rows = blocks * per                   # heads a batch row: [.., L] rows
    # delta = rowsum(dO * O) a head, [batches, heads, L] as LSE is
    delta = do.astype(jnp.float32) * o.astype(jnp.float32)
    if heads:
        # a head's columns summed by a 0 / 1 matrix on the MXU, exactly
        # (`highest`): XLA reduces a 64-lane piece of a row only behind a
        # transposing copy of dO and of O (two 33 us copies a layer at
        # fd355m-train-2k; PERF.md, PR 57)
        own = jnp.arange(n_rows * width // per)[:, None] // (width // per) \
            == jnp.arange(n_rows)[None, :]
        delta = jnp.einsum('blw,wh->bhl', delta, own.astype(jnp.float32),
                           precision=lax.Precision.HIGHEST)
    else:
        delta = jnp.sum(delta, axis=-1)[:, None, :]
    lse = lse.reshape(nbatch, n_rows, ln)
    has_bias = bias is not None
    bias32 = bias.astype(jnp.float32) if has_bias else None
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    qkv = [q, q, q] if heads else [q, k, v]
    out = jax.ShapeDtypeStruct((nbatch, ln, blocks * width), q.dtype)

    bq, bk, block = tiling_dq
    qspec = pl.BlockSpec((1, bq, width),
                         lambda b, p, i, j: (b, i, q_at + p))
    ospec = pl.BlockSpec((1, bq, width), lambda b, p, i, j: (b, i, p))
    kspec, vspec, bias_spec = _key_specs(causal, bq, bk, block, width,
                                         n_heads, k_at, v_at)
    rowspec = pl.BlockSpec((1, per, 1, bq), lambda b, p, i, j: (b, p, 0, i))
    ins = qkv + [do, lse[:, :, None, :], delta[:, :, None, :]]
    in_specs = [qspec, kspec, vspec, ospec, rowspec, rowspec]
    if has_bias:
        ins.append(bias32.reshape(-1, ln // bk, 1, bk))
        in_specs.append(bias_spec)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale, causal, bk, has_bias, per),
        grid=(nbatch, blocks, ln // bq, ln // block),
        in_specs=in_specs,
        out_specs=[ospec],
        out_shape=[out],
        scratch_shapes=[pltpu.VMEM((width, bq), jnp.float32)],
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
    qspec = pl.BlockSpec((1, block, width),
                         lambda b, p, i, j: (b, held(i, j), q_at + p))
    dospec = pl.BlockSpec((1, block, width),
                          lambda b, p, i, j: (b, held(i, j), p))
    kspec = pl.BlockSpec((1, bk, width),
                         lambda b, p, i, j: (b, i, k_at + p))
    vspec = pl.BlockSpec((1, bk, width),
                         lambda b, p, i, j: (b, i, v_at + p))
    ospec = pl.BlockSpec((1, bk, width), lambda b, p, i, j: (b, i, p))
    # lse and delta a sub-tile a row, so a trip reads its row by index
    rowspec = pl.BlockSpec((1, per, block // bq, 1, bq),
                           lambda b, p, i, j: (b, p, held(i, j), 0, 0))
    ins = qkv + [do, lse.reshape(nbatch, n_rows, ln // bq, 1, bq),
                 delta.reshape(nbatch, n_rows, ln // bq, 1, bq)]
    in_specs = [qspec, kspec, vspec, dospec, rowspec, rowspec]
    if has_bias:
        ins.append(bias32[:, None, :])
        in_specs.append(pl.BlockSpec(
            (1, 1, bk), lambda b, p, i, j: (b // n_heads, 0, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale, causal, bq, has_bias, per),
        grid=(nbatch, blocks, ln // bk, ln // block),
        in_specs=in_specs,
        out_specs=[ospec, ospec],
        out_shape=[out, out],
        scratch_shapes=[pltpu.VMEM((bk, width), jnp.float32),
                        pltpu.VMEM((bk, width), jnp.float32)],
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


# --------------------------------------------------------------------------
# the packed operand view: the fused QKV product in, the context out
# --------------------------------------------------------------------------

def packed_shapes_ok(heads, dh):
    """Whether heads of `dh` lie in aligned 128-lane column blocks of the
    fused product: one head of 128 a block, or two of 64."""
    return dh in (64, 128) and (heads * dh) % 128 == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_packed(qkv, heads, scale, causal, interpret):
    """Attention of the fused product ``qkv`` [B, L, 3 * heads * dh]
    (columns ``[3, heads, dh]``, as `layers.fc` leaves it): the context
    [B, L, heads * dh], as `attn.proj`'s `fc` reads it. The kernels pick
    Q, K and V lane blocks out of the one array and write lane-dense; the
    cotangent is ONE [B, L, 3 * heads * dh] array in the product's column
    order. Residuals: the product, the context and LSE."""
    return _flash_fwd_pallas(qkv, None, None, scale, causal, interpret,
                             heads=heads)[0]


def _flash_packed_fwd(qkv, heads, scale, causal, interpret):
    o, lse = _flash_fwd_pallas(qkv, None, None, scale, causal, interpret,
                               heads=heads)
    return o, (qkv, o, lse)


def _flash_packed_bwd(heads, scale, causal, interpret, res, ct):
    qkv, o, lse = res
    return (jnp.concatenate(_flash_bwd_pallas(
        qkv, None, None, o, lse, ct, scale, causal, interpret, heads=heads),
        axis=-1),)


_flash_packed.defvjp(_flash_packed_fwd, _flash_packed_bwd)


def flash_attention_packed(qkv, heads, mesh=None, scale=None, causal=True,
                           interpret=False):
    """`_flash_packed` of ``qkv`` [B, L, 3 * heads * dh]; under `mesh` (a
    'data' axis over the batch and nothing over the heads or L: the packed
    columns cannot be sharded by head) the call is shard_mapped over the
    batch."""
    from jax.sharding import PartitionSpec as P
    if scale is None:
        scale = (qkv.shape[2] // (3 * heads)) ** -0.5

    def inner(x):
        return _flash_packed(x, int(heads), float(scale), bool(causal),
                             bool(interpret))
    if mesh is None or mesh.size == 1:
        return inner(qkv)
    spec = P(_mesh_axis(mesh, 'data', qkv.shape[0]), None, None)
    return _shard_map(inner, mesh, (spec,), spec)(qkv)


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
    """Program-level op, two input forms. Head-major: Q, K, V [B, H, L,
    dh] in, Out [B, H, L, dh]. Fused: QKV [B, L, 3 * H * dh], the fused
    projection's product as `layers.fc` leaves it (columns ``[3, H, dh]``;
    attr `num_heads`), Out the context [B, L, H * dh] as `attn.proj`'s `fc`
    reads it -- no transpose op on either side. Attrs scale (float, default
    dh^-0.5) and causal (bool). Under bf16 AMP the kernel's matmuls run
    bf16 on the MXU with f32 accumulation (preferred_element_type) and f32
    softmax state. Under an active SPMD mesh the kernel runs per shard via
    shard_map (ring attention when the sequence axis is sharded).

    The fused form lowers to the PACKED kernels (`_flash_packed`: they read
    the product's own lane blocks, two heads of 64 or one of 128 a block)
    where that layout applies, decided from the shapes and the mesh alone:
    head size 64 or 128 with H * dh a multiple of 128, the kernels' tier
    and `flash_shapes_ok(L)`, no KeyPaddingBias (the packed form stands
    down for a bias), and under a mesh no 'seq' axis over L and no 'model'
    axis over the heads. Anything else turns the product head-major inside
    the lowering and takes the head-major path below;
    `flash_attention_layout_total{layout}` says which a program got."""
    from ..core import amp
    from . import kernel_tier
    from ..parallel.api import get_active_mesh
    qkv = ctx.in1(op, 'QKV')
    if qkv is not None:
        out_dtype = qkv.dtype
        qkv = amp.cast_compute(op, qkv)
        heads = int(op.attr('num_heads'))
        b, ln, dh = qkv.shape[0], qkv.shape[1], qkv.shape[2] // (3 * heads)
        four = True
    else:
        q = ctx.in1(op, 'Q')
        k = ctx.in1(op, 'K')
        v = ctx.in1(op, 'V')
        out_dtype = q.dtype
        q, k, v = amp.cast_compute(op, q, k, v)
        ln, four = q.shape[-2], q.ndim == 4
    bias = ctx.in1(op, 'KeyPaddingBias')       # optional [B, L]
    if bias is not None and not four:
        raise NotImplementedError(
            "flash_attention KeyPaddingBias needs 4-d [B, H, L, dh] Q "
            "(the bias row maps to batch via the head dim)")
    # missing attr -> kernel default dh**-0.5; a present value (incl. 0.0)
    # is literal. Legacy programs that stored 0.0 meaning "default" keep
    # that behavior.
    scale = op.attr('scale', None)
    scale = None if scale is None or scale == 0.0 else float(scale)
    causal = op.attr('causal', True)
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    # the same tier knob as every other fused unit picks the lowering
    # (pallas | interpret -> the kernels, anything else -> the einsum
    # reference, counted as 'off': there is no distinct xla emission).
    # Under a mesh the kernel needs batch/head axes to shard_map over
    # (the XLA partitioner cannot split a pallas custom call), and a
    # sharded sequence axis takes the ring path, which has no kernel.
    ring = meshed and four and _mesh_axis(mesh, 'seq', ln) is not None
    pallas_ok = flash_shapes_ok(ln) and not ring and (four or not meshed)
    impl = kernel_tier.dispatch(
        'flash_attention', pallas_ok=pallas_ok, xla_ok=False, mesh=mesh,
        count=getattr(ctx, 'sparse_mode', None) != 'scout')
    use_pallas = {'pallas': True, 'interpret': 'interpret'}.get(impl, False)
    if qkv is not None:
        if use_pallas and bias is None and packed_shapes_ok(heads, dh) \
                and not (meshed
                         and _mesh_axis(mesh, 'model', heads) is not None):
            out = flash_attention_packed(
                qkv, heads, mesh=mesh if meshed else None, scale=scale,
                causal=causal, interpret=use_pallas == 'interpret')
            ctx.out(op, 'Out', out.astype(out_dtype))
            return
        q, k, v = jnp.transpose(qkv.reshape(b, ln, 3, heads, dh),
                                (2, 0, 3, 1, 4))
    if meshed and four:
        out = flash_attention_spmd(
            q, k, v, mesh, scale=scale, causal=causal,
            use_pallas=use_pallas,
            ring_zigzag=op.attr('ring_zigzag', False),
            key_padding_bias=bias)
    else:
        out = flash_attention(q, k, v, scale=scale, causal=causal,
                              use_pallas=use_pallas,
                              key_padding_bias=bias)
    if qkv is not None:
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, ln, heads * dh)
    ctx.out(op, 'Out', out.astype(out_dtype))
