"""The Gated DeltaNet mixer's recurrence and its state, a row a slot
(the linear-attention layers of Qwen3-Next and of Olmo-Hybrid: models/
transformer.py, LMConfig(layer_types=...) ``'gdn'``).

Between the mixer's projections (``[q | k | v | z] = h W_in``, ``[b | a] =
h W_ba`` and ``out = o W_out``, ordinary `fc`s in models/transformer.py) a
Gated DeltaNet layer is (arXiv:2412.06464; HF `modeling_qwen3_next.py`
`Qwen3NextGatedDeltaNet`; ``Hk`` key heads of ``dk``, ``Hv`` value heads of
``dv``, value head ``h`` reads key head ``h // (Hv / Hk)``)

    [q | k | v] = silu(conv([q | k | v]))   causal depthwise, K taps, no
                                            bias, over all 2 Hk dk + Hv dv
    q = q / |q| / sqrt(dk)    k = k / |k|   eps 1e-6 inside the root
    beta = sigmoid(b)   [Hv]                (0, 1); with the ops' attribute
                                            `allow_neg_eigval` 2 sigmoid(b)
    g = -exp(A_log) softplus(a + dt_bias)   [Hv], float32
    S_t[h] = e^g S_{t-1}[h]                 S [dk, dv], keys x values
    u = beta (v - S^T k)                    the delta rule: what k already
    S_t[h] = S_t[h] + k u^T                 reads of S is taken out of v
    o = S^T q
    o = RMSNorm_w(o) * silu(z)              over each head's dv numbers, ONE
                                            weight [dv]; the norm first

The state is multiplied by ``e^g (I - beta k k^T)``, no diagonal: a Mamba-2
block (ops/ssd_ops.py) has no such term, and its chunked form does not
carry over.

What a token leaves behind is ``S`` after it, ``Hv x dk x dv`` numbers a
layer (2 MB at 32 heads of 128 x 128, 2.2 MB at 30 of 96 x 192), and the
convolution's last ``K - 1`` inputs.
Both live A ROW A SLOT in two pools of their own (models/transformer.py
`GDN_STATE` ``[slots + 1, gdn layers, dk, Hv dv]`` and `GDN_TAIL` ``[slots
+ 1, gdn layers, 8, 2 Hk dk + Hv dv]``; row 0 is the trash row), addressed
through the feed 'gen_srow' as the Mamba layers' are (ops/ssm_ops.py, whose
tail kernel `decode_conv` and `_taps` serve this kind too: the convolution
is the same operation at another width, its bias zeros).

THE STATE LIES ``[dk, Hv dv]``: the keys on the sublanes, a head's values
side by side on the lanes. So ``v``, the decay, ``beta``, ``u`` and ``o`` of
a row are lane vectors as the projections give and take them, ``k`` and
``q`` are COLUMNS ``[dk, 1]`` that broadcast over their heads' lanes, and
the two read-outs ``S^T k`` and ``S^T q`` are sums over sublanes.

- ``gdn_decode``: every slot's one new row. The tail kernel, then
  `decode_update`: a grid of (slots, strips of whole heads); a step moves a
  ``[dk, strip]`` block of the slot's state HBM -> VMEM -> HBM IN PLACE
  (the block is named by the prefetched row ids and the layer: no gather,
  no scatter, no copy of the pool) and walks it twice a head: decayed and
  read against ``k``, then corrected by ``k u^T`` and read against ``q``.
  ``e^g`` and ``beta`` are scalars a head, computed outside on ``[S, Hv]``
  and handed over as lane vectors. A row fed 0 reads zeros and writes the
  trash row. HEADS WHOSE VALUES ARE NO WHOLE VREGS (192: a vreg and a
  half) are walked a RUN at a time -- the fewest heads whose values side
  by side are whole vregs, two of 192 = three vregs -- so every load and
  store of the state stays lane-aligned; what the run's heads do not share,
  their key heads' ``k`` and ``q`` columns, is put over each head's lanes
  by one select a further head (the middle vreg of three takes half of
  each), two selects a vreg on top of the walk's eight operations: the
  kernel moves its strip HBM -> VMEM -> HBM all the same.
- ``gdn_prefill``: one prompt suffix or chunk of ``T`` rows from position
  ``off = Positions[0]`` on, THE CHUNKED FORM (the WY representation of
  arXiv:2406.06484 with the decay of arXiv:2412.06464), not the recurrence
  a position. In blocks of ``C = chunk`` rows, a head; ``gamma_i`` the sum
  of ``g`` from the block's first row to row ``i``, ``D_ij = e^(gamma_i -
  gamma_j)`` for ``i >= j``:

      T  = (I + tril(diag(beta) K K^T * D, -1))^-1
      U  = T diag(beta) V          W = T diag(beta e^gamma) K
      V' = U - W S                 the rows' ``u``, all at once
      O  = (Q * e^gamma) S + tril(Q K^T * D) V'
      S  = e^gamma_C S + (K * e^(gamma_C - gamma))^T V'

  an identity of the recurrence, which stays the definition (tests/
  test_qwen3next_serving.py holds every tier to it). No decay is ever
  divided by. THE TRIANGULAR INVERSE is taken where it is stable: the
  diagonal blocks of 16 rows by forward substitution a row (15 steps on
  the VPU, the four blocks independent), and the rest by the blocks'
  nilpotency -- with ``Xd`` the inverted diagonal blocks and ``N = Xd
  A_off`` (block strictly lower: ``N^(C/16) = 0``), ``Y = Xd R - N Y``
  reaches ``(I + A)^-1 R`` in ``C/16 - 1`` rounds of one matmul. Every sum
  over positions or keys is a matmul on the MXU at `Precision.HIGHEST`.
  History: zeros if ``off == 0`` -- whatever the row's last tenant left is
  never read -- else the row as an earlier chunk left it (or as a snapshot
  row copied into it did: serving/kv_blocks.py `SlotRows`). Heads of whole
  vregs are read where the projections left them; others are laid a head
  first around the kernel (`prefill_chunks`). A PAD ROW'S ``g``
  AND ``beta`` ARE SET TO 0: its decay is 1 and its ``u`` 0, the identity
  on the state; the state and the tail are written as of the last real
  row.

Lowerings behind `kernel_tier.dispatch`: ``pallas`` / ``interpret`` are
the kernels (device operations ``mosaic:gdn_decode_update``,
``mosaic:gdn_prefill_chunk`` and the shared ``mosaic:ssm_decode_conv``);
``xla`` / ``off`` gather and scatter the rows and write the chunked form as
einsums round `solve_triangular`. Everything of both ops lies under the
named scope ``paddle_tpu:gdn_chunk``.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op
from . import ssm_ops
# the row-a-slot kernels' shared helpers: a strip's vectors as columns, a
# head's number over its lanes, a matmul at `Precision.HIGHEST`
from .ssd_ops import _columns, _dot, _over_heads

SCOPE = 'paddle_tpu:gdn_chunk'
_LANES = 128
_PRECISION = lax.Precision.HIGHEST
# bytes of a slot's state a step of the decode grid holds at most
_STRIP_BYTES = 1 << 20
# rows of a diagonal block the prefill inverts by forward substitution
_DIAG = 16
# inside the root of the l2-norm of q and k (the family's kernels')
_L2_EPS = 1e-6


def shapes_ok(key_dim, value_dim, key_heads, value_heads, rows=None,
              chunk=None):
    """The kernels' tiling rule: a head's keys are whole sublane tiles, the
    value heads whole groups a key head, and the value heads come in whole
    RUNS whose values side by side are whole vregs of lanes (`_heads_a_run`:
    one head of 128 or 256 values, two of 192, eight of 48) -- the decode
    update walks a run at a time; for the prefill the prompt is whole chunks
    of whole diagonal blocks (its heads may be any width: `prefill_chunks`
    lays heads that are no whole vregs a head first)."""
    ok = key_dim % 8 == 0 and value_dim % 8 == 0 \
        and value_heads % key_heads == 0 \
        and value_heads % _heads_a_run(value_dim) == 0
    if rows is not None:
        ok = ok and rows % chunk == 0 and chunk % _DIAG == 0
    return ok


def _heads_a_run(value_dim):
    """The fewest value heads whose values side by side are whole vregs of
    lanes."""
    return math.lcm(value_dim, _LANES) // value_dim


def _heads_a_strip(key_dim, value_dim, value_heads, rep):
    """Value heads a step of the decode grid holds: whole groups of `rep`
    (the heads of one key head) and whole runs (`_heads_a_run`), the most
    that keep its block of the state within `_STRIP_BYTES`."""
    head = key_dim * value_dim * 4
    unit = math.lcm(rep, _heads_a_run(value_dim))
    return max(h for h in range(unit, value_heads + 1, unit)
               if value_heads % h == 0
               and (h == unit or h * head <= _STRIP_BYTES))


# ---------------------------------------------------------------------------
# the decode update


def _decode_update_kernel(rows_ref, layer_ref, decay_ref, beta_ref, v_ref,
                          q_ref, k_ref, s_ref, o_ref, out_ref, *, size):
    import jax.experimental.pallas as pl
    del layer_ref
    per = decay_ref.shape[2] // size            # value heads of the strip
    rep = per // k_ref.shape[3]                 # value heads a key head
    run = _heads_a_run(size)                    # heads walked together
    dk = k_ref.shape[2]
    live = rows_ref[pl.program_id(0)] > 0
    lane = lax.broadcasted_iota(jnp.int32, (dk, run * size), 1)

    def columns(ref, j):
        """The run's key heads' vectors, each over its heads' lanes: ONE
        column ``[dk, 1]`` where the run has one key head (it broadcasts),
        else ``[dk, run x size]`` by a select a further key head."""
        col = ref[0, 0, :, j // rep:j // rep + 1]
        for i in range(1, run):
            if (j + i) // rep != (j + i - 1) // rep:
                col = jnp.where(lane >= i * size, ref[
                    0, 0, :, (j + i) // rep:(j + i) // rep + 1], col)
        return col
    for j in range(0, per, run):
        at = pl.ds(j * size, run * size)
        kc, qc = columns(k_ref, j), columns(q_ref, j)   # [dk, 1 | lanes]
        # first pass: the decayed state, read against k
        s = jnp.where(live, s_ref[0, 0, :, at], 0.0) * decay_ref[0, :, at]
        u = beta_ref[0, :, at] * (
            v_ref[0, :, at] - jnp.sum(s * kc, axis=0, keepdims=True))
        # second pass: the rank-one correction, read against q
        s = s + kc * u
        o_ref[0, :, at] = jnp.sum(s * qc, axis=0, keepdims=True)
        out_ref[0, 0, :, at] = s


@functools.partial(jax.jit, static_argnames=('value_heads', 'interpret'))
def decode_update(state, rows, layer, decay, beta, v, q, k, *, value_heads,
                  interpret=False):
    """One step of the recurrence for every slot, the pool updated IN
    PLACE: ``state [R, L, dk, Hv dv]``, ``rows [S]`` int32 (0: no row),
    ``layer`` an int32 scalar, ``decay = e^g`` and ``beta`` ``[S, Hv dv]``
    (a head's scalar on each of its lanes), ``v [S, Hv dv]``, ``q`` / ``k``
    ``[S, Hk, dk]`` (normed, q scaled). Returns (``o [S, Hv dv]`` with
    ``o[h] = S[h]^T q``, the pool). Jitted, with `layer` an operand: the
    layers of a program share one traced kernel."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, vd = v.shape
    dk, hk = state.shape[2], k.shape[1]
    hv = value_heads
    size = vd // hv
    per = _heads_a_strip(dk, size, hv, hv // hk)
    kper = per // (hv // hk)
    strips = hv // per
    width = per * size
    row = pl.BlockSpec((1, 1, width), lambda i, j, *_: (i, 0, j))
    cols = pl.BlockSpec((1, 1, dk, kper), lambda i, j, *_: (i, j, 0, 0))
    block = pl.BlockSpec(
        (1, 1, dk, width), lambda i, j, rows, layer: (rows[i], layer[0], 0, j))
    o, state = pl.pallas_call(
        functools.partial(_decode_update_kernel, size=size),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S, strips),
            in_specs=[row, row, row, cols, cols, block],
            out_specs=[row, block]),
        out_shape=[jax.ShapeDtypeStruct((S, 1, vd), v.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool (the last operand, the prefetched scalars counted) IS
        # the second output
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret, name='gdn_decode_update',
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      decay.reshape(S, 1, vd), beta.reshape(S, 1, vd), v.reshape(S, 1, vd),
      _columns(q, kper), _columns(k, kper), state)
    return o.reshape(S, vd), state


def _decode_update_xla(state, rows, layer, decay, beta, v, q, k, *,
                       value_heads):
    """`decode_update` as a gather, the step and a scatter."""
    S, vd = v.shape
    dk, rep = state.shape[2], value_heads // k.shape[1]

    def heads(x):                               # [S, 1, Hv, dv]
        return x.reshape(S, 1, value_heads, -1)

    def cols(x):                                # [S, dk, Hv, 1]
        return jnp.repeat(x, rep, axis=1).transpose(0, 2, 1)[..., None]
    s = jnp.where((rows > 0)[:, None, None], state[rows, layer], 0.0)
    s = s.reshape(S, dk, value_heads, -1) * heads(decay)
    u = heads(beta) * (heads(v) - jnp.sum(s * cols(k), axis=1,
                                          keepdims=True))
    s = s + cols(k) * u
    return jnp.sum(s * cols(q), axis=1).reshape(S, vd), \
        state.at[rows, layer].set(s.reshape(S, dk, vd))


# ---------------------------------------------------------------------------
# the prefill, chunked


def _prefill_chunk_kernel(q_ref, k_ref, kt_ref, v_ref, gx_ref, bx_ref,
                          rt_ref, s0_ref, o_ref, last_ref, s_scr, xd_scr, *,
                          joint=True):
    import jax.experimental.pallas as pl
    C, dv = v_ref.shape
    blocks = C // _DIAG

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_scr[...] = s0_ref[...]

    q, k, kt, v = q_ref[...], k_ref[...], kt_ref[0, 0], v_ref[...]
    gcol, bcol = gx_ref[:, :1], bx_ref[:, :1]               # [C, 1]
    grow, brow = rt_ref[0, 0, 0:1, :], rt_ref[0, 0, 1:2, :]  # [1, C]
    s = s_scr[...]                                          # [dk, dv]
    ri = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    own = ri // _DIAG == ci // _DIAG                        # a diagonal block
    # D[i, j] = e^(gamma_i - gamma_j) at or under the diagonal, and its
    # transpose: decays, never over 1 (the difference is taken first)
    d_lo = jnp.exp(jnp.minimum(gcol - grow, 0.0))
    d_up = jnp.exp(jnp.minimum(grow - gcol, 0.0))
    kk = _dot(k, kt)                                        # [C, C]
    a = jnp.where(ri > ci, bcol * d_lo * kk, 0.0)           # A
    at = jnp.where((ri < ci) & own, brow * d_up * kk, 0.0)  # A^T, its blocks
    # the diagonal blocks of (I + A), inverted a row at a time: row i of a
    # block's inverse is e_i - sum_{r < i} A[i, r] (row r), and A[i, r] for
    # all r is column i of the block's A^T
    r16 = lax.broadcasted_iota(jnp.int32, (_DIAG, C), 0)
    c16 = lax.broadcasted_iota(jnp.int32, (_DIAG, C), 1)
    for b in range(blocks):
        mine = at[b * _DIAG:(b + 1) * _DIAG, :]             # [16, C]
        x = jnp.where(c16 == r16 + b * _DIAG, 1.0, 0.0)
        for i in range(1, _DIAG):
            col = mine[:, b * _DIAG + i:b * _DIAG + i + 1]  # [16, 1]
            x = jnp.where(r16 == i,
                          x - jnp.sum(col * x, axis=0, keepdims=True), x)
        xd_scr[b * _DIAG:(b + 1) * _DIAG, :] = x
    xd = xd_scr[...]
    # the blocks under the diagonal: N = Xd A_off is nilpotent over the
    # blocks, so Y = Xd R - N Y is exact after blocks - 1 rounds
    n = _dot(xd, jnp.where(own, 0.0, a))
    # U's and W's right-hand sides: side by side through one solve where
    # both are whole vregs of lanes (`joint`), else one after the other
    rhs = [bcol * v, (bcol * jnp.exp(gcol)) * k]
    ys = []
    for r in [jnp.concatenate(rhs, axis=1)] if joint else rhs:
        y = p = _dot(xd, r)
        for _ in range(blocks - 1):
            y = p - _dot(n, y)
        ys.append(y)
    u, w = (ys[0][:, :dv], ys[0][:, dv:]) if joint else ys
    vp = u - _dot(w, s)                                     # the rows' u
    o_ref[...] = _dot(q * jnp.exp(gcol), s) + _dot(
        jnp.where(ri >= ci, d_lo * _dot(q, kt), 0.0), vp)
    # e^gamma_C over the state's lanes, and D's last row: e^(gamma_C -
    # gamma_j) over the block's
    s = jnp.exp(gx_ref[C - 1:C, :]) * s + _dot(kt * d_lo[C - 1:C, :], vp)
    s_scr[...] = s
    last_ref[...] = s


def _chunked(q, k, v, g, beta, chunk):
    """What both lowerings take, a block of `chunk` rows first: q, k ``[n,
    C, Hk, dk]``, v ``[n, C, Hv, dv]``, beta and ``gamma`` (the sums of g
    from each block's first row on) ``[n, C, Hv]``."""
    n = q.shape[0] // chunk
    q, k, v, g, beta = [x.reshape((n, chunk) + x.shape[1:])
                        for x in (q, k, v, g, beta)]
    return q, k, v, jnp.cumsum(g, axis=1), beta


@functools.partial(jax.jit, static_argnames=('chunk', 'interpret'))
def prefill_chunks(q, k, v, g, beta, s0, *, chunk, interpret=False):
    """The recurrence over one prompt's ``T`` rows from the state ``s0
    [dk, Hv dv]``, in blocks of ``chunk`` rows: ``q`` / ``k`` ``[T, Hk,
    dk]`` (normed, q scaled), ``v [T, Hv, dv]``, ``g`` / ``beta`` ``[T,
    Hv]`` (a pad row's both 0). Returns (``o [T, Hv dv]``, the state after
    the last row). The grid is (value heads, blocks of rows): a head's
    ``[dk, dv]`` state stays in VMEM between its blocks. Heads whose keys
    and values are whole vregs of lanes are read where the projections left
    them, a head a block of lanes; any other width (96 keys by 192 values)
    is laid A HEAD FIRST around the call -- ``[heads, T, width]``, a block
    the head's whole rows -- which costs a transpose of the chunk's q, k, v
    and o (``T x (2 Hk dk + 2 Hv dv)`` numbers, against the layer's
    projections of ``T x d_model`` by as many) and of the state."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, hk, dk = q.shape
    hv, dv = v.shape[1:]
    rep, n = hv // hk, T // chunk
    _, kc, _, gamma, bc = _chunked(q, k, v, g, beta, chunk)
    tiled = dv % _LANES == 0 and (dk % _LANES == 0 or hk == 1)
    if tiled:
        keys = pl.BlockSpec((chunk, dk), lambda h, j: (j, h // rep))
        vals = pl.BlockSpec((chunk, dv), lambda h, j: (j, h))
        head = pl.BlockSpec((dk, dv), lambda h, j: (0, h))
        shapes = (T, hv * dv), (dk, hv * dv)

        def lay(x):                             # [T, H, w] as it lies
            return x.reshape(T, -1)
        gx, bx = [_over_heads(x.reshape(T, hv), hv * dv)
                  for x in (gamma, beta)]
    else:
        keys = pl.BlockSpec((None, chunk, dk), lambda h, j: (h // rep, j, 0))
        vals = pl.BlockSpec((None, chunk, dv), lambda h, j: (h, j, 0))
        head = pl.BlockSpec((None, dk, dv), lambda h, j: (h, 0, 0))
        shapes = (hv, T, dv), (hv, dk, dv)

        def lay(x):                             # [T, H, w] -> [H, T, w]
            return x.transpose(1, 0, 2)
        gx, bx = [jnp.broadcast_to(x.reshape(T, hv).T[:, :, None],
                                   (hv, T, dv)) for x in (gamma, beta)]
        s0 = lay(s0.reshape(dk, hv, dv))
    o, last = pl.pallas_call(
        functools.partial(_prefill_chunk_kernel, joint=tiled),
        grid=(hv, n),
        in_specs=[keys, keys,
                  pl.BlockSpec((1, 1, dk, chunk),
                               lambda h, j: (h // rep, j, 0, 0)),
                  vals, vals, vals,
                  pl.BlockSpec((1, 1, 2, chunk), lambda h, j: (h, j, 0, 0)),
                  head],
        out_specs=[vals, head],
        out_shape=[jax.ShapeDtypeStruct(shapes[0], v.dtype),
                   jax.ShapeDtypeStruct(shapes[1], s0.dtype)],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name='gdn_prefill_chunk',
    )(lay(q), lay(k),
      kc.transpose(2, 0, 3, 1),                             # [Hk, n, dk, C]
      lay(v), gx, bx,
      # a head's gamma and beta as ROWS, a block apart: [Hv, n, 2, C]
      jnp.stack([gamma, bc], axis=2).transpose(3, 0, 2, 1), s0)
    if tiled:
        return o, last
    return lay(o).reshape(T, hv * dv), lay(last).reshape(dk, hv * dv)


def _prefill_chunks_xla(q, k, v, g, beta, s0, chunk):
    """`prefill_chunks` as einsums over ``[blocks, heads, chunk, ...]``
    round one batched `solve_triangular`, the state carried from block to
    block in a `lax.scan`."""
    from jax.scipy.linalg import solve_triangular
    T, hk, dk = q.shape
    hv, dv = v.shape[1:]
    rep = hv // hk
    ein = functools.partial(jnp.einsum, precision=_PRECISION)
    q, k, v, gamma, beta = _chunked(q, k, v, g, beta, chunk)
    # a head first: [n, Hv, C, ...]
    q, k = [jnp.repeat(x, rep, axis=2).transpose(0, 2, 1, 3) for x in (q, k)]
    v = v.transpose(0, 2, 1, 3)
    gamma, beta = [x.transpose(0, 2, 1)[..., None] for x in (gamma, beta)]
    row = jnp.arange(chunk)
    decays = jnp.exp(jnp.minimum(gamma - jnp.swapaxes(gamma, 2, 3), 0.0))
    a = jnp.where(row[:, None] > row[None, :],
                  beta * decays * ein('nhid,nhjd->nhij', k, k), 0.0)
    y = solve_triangular(
        a + jnp.eye(chunk, dtype=a.dtype),
        jnp.concatenate([beta * v, beta * jnp.exp(gamma) * k], axis=-1),
        lower=True, unit_diagonal=True)
    u, w = y[..., :dv], y[..., dv:]
    inner = jnp.where(row[:, None] >= row[None, :],
                      decays * ein('nhid,nhjd->nhij', q, k), 0.0)
    total = gamma[:, :, -1:, :]                             # [n, Hv, 1, 1]

    def carry(s, xs):
        u_c, w_c, q_c, k_c, inner_c, gamma_c, total_c = xs
        vp = u_c - ein('hid,hde->hie', w_c, s)
        o = ein('hid,hde->hie', q_c * jnp.exp(gamma_c), s) \
            + ein('hij,hje->hie', inner_c, vp)
        s = jnp.exp(total_c) * s + ein(
            'hjd,hje->hde', k_c * jnp.exp(total_c - gamma_c), vp)
        return s, o

    s0 = s0.reshape(dk, hv, dv).transpose(1, 0, 2)          # [Hv, dk, dv]
    last, o = lax.scan(carry, s0, (u, w, q, k, inner, gamma, total))
    return o.transpose(0, 2, 1, 3).reshape(T, hv * dv), \
        last.transpose(1, 0, 2).reshape(dk, hv * dv)


# ---------------------------------------------------------------------------
# the IR ops

_WEIGHTS = ('ConvW', 'ALog', 'DtBias', 'NormW')


def _operands(ctx, op):
    p = {name: ctx.in1(op, name).astype(jnp.float32) for name in _WEIGHTS}
    return (p, ctx.in1(op, 'State'), ctx.in1(op, 'Tail'),
            ctx.in1(op, 'Rows').reshape(-1).astype(jnp.int32),
            int(op.attr('layer')), float(op.attr('epsilon')),
            int(op.attr('key_heads')))


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def _split(c, hk, hv, dv):
    """The convolved rows ``[rows, 2 Hk dk + Hv dv]`` as q and k ``[rows,
    Hk, dk]``, normed (q scaled by ``dk^-1/2``), and v ``[rows, Hv, dv]``."""
    rows = c.shape[0]
    kd = (c.shape[1] - hv * dv) // 2
    q, k = [_l2norm(x.reshape(rows, hk, kd // hk))
            for x in (c[:, :kd], c[:, kd:2 * kd])]
    return q * (kd // hk) ** -0.5, k, c[:, 2 * kd:].reshape(rows, hv, dv)


def _gates(b, a, p, op):
    """(``g = -exp(A_log) softplus(a + dt_bias)``, ``beta = sigmoid(b)``),
    float32, ``[rows, Hv]``; with the op's ``allow_neg_eigval`` ``beta = 2
    sigmoid(b)``: the transition ``e^g (I - beta k k^T)`` then has its one
    eigenvalue off ``e^g`` in (-1, 1) and not in (0, 1)."""
    wide = 2.0 if op.attr('allow_neg_eigval', False) else 1.0
    return -jnp.exp(p['ALog']) * jax.nn.softplus(a + p['DtBias']), \
        wide * jax.nn.sigmoid(b)


def _gated_norm(o, z, w, eps):
    """``RMSNorm_w(o) * silu(z)`` over each head's ``dv`` numbers: the norm
    first, then the gate, one weight ``[dv]`` for all heads."""
    dv = w.shape[0]
    o = o.reshape(o.shape[0], -1, dv)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) * w
    return o.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))


@register_op('gdn_decode', share_lod=False)
def _gdn_decode(ctx, op):
    x = ctx.in1(op, 'X')                        # [S, 2 Hk dk + Hv dv]
    z = ctx.in1(op, 'Z')                        # [S, Hv dv]
    b = ctx.in1(op, 'B').astype(jnp.float32)    # [S, Hv]
    a = ctx.in1(op, 'A').astype(jnp.float32)
    p, state, tails, rows, layer, eps, hk = _operands(ctx, op)
    vd, hv = z.shape[1], b.shape[1]
    dk, dv = state.shape[2], vd // hv
    impl = ssm_ops.tier(
        'gdn_decode', shapes_ok(dk, dv, hk, hv)
        and ssm_ops.shapes_ok(x.shape[1], 8))
    conv, update = ssm_ops._decode_conv_xla, _decode_update_xla
    if impl in ('pallas', 'interpret'):
        conv, update = [functools.partial(f, interpret=impl == 'interpret')
                        for f in (ssm_ops.decode_conv, decode_update)]
    with jax.named_scope(SCOPE):
        c, tails = conv(tails, rows, layer, x.astype(tails.dtype),
                        p['ConvW'], jnp.zeros(x.shape[1], tails.dtype))
        q, k, v = _split(c, hk, hv, dv)
        g, beta = _gates(b, a, p, op)
        o, state = update(state, rows, layer, _over_heads(jnp.exp(g), vd),
                          _over_heads(beta, vd), v.reshape(-1, vd), q, k,
                          value_heads=hv)
        out = _gated_norm(o, z, p['NormW'], eps)
    ctx.out(op, 'Out', out.astype(z.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)


@register_op('gdn_prefill', share_lod=False)
def _gdn_prefill(ctx, op):
    x = ctx.in1(op, 'X')                        # [1, T, 2 Hk dk + Hv dv]
    z = ctx.in1(op, 'Z')
    b = ctx.in1(op, 'B')[0].astype(jnp.float32)             # [T, Hv]
    a = ctx.in1(op, 'A')[0].astype(jnp.float32)
    p, state, tails, rows, layer, eps, hk = _operands(ctx, op)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)  # [T]
    length = ctx.in1(op, 'Length').reshape(-1).astype(jnp.int32)[0]
    T, K = x.shape[1], p['ConvW'].shape[1]
    vd, hv = z.shape[2], b.shape[1]
    dk, dv = state.shape[2], vd // hv
    chunk = min(int(op.attr('chunk')), T)
    impl = ssm_ops.tier('gdn_prefill',
                        shapes_ok(dk, dv, hk, hv, T, chunk))
    row, resumes = rows[0], pos[0] > 0
    with jax.named_scope(SCOPE):
        hist = jnp.where(resumes, tails[row, layer, :K - 1], 0.0)
        ext = jnp.concatenate([hist, x[0].astype(tails.dtype)], axis=0)
        window = jnp.stack([ext[j:j + T] for j in range(K)], axis=1)
        q, k, v = _split(jax.nn.silu(ssm_ops._taps(window, p['ConvW'], 0.0)),
                         hk, hv, dv)
        # a pad row leaves the state as it is: e^0 = 1, and u = 0 x (..)
        real = (jnp.arange(T) < length)[:, None]
        g, beta = [jnp.where(real, t, 0.0) for t in _gates(b, a, p, op)]
        pad = -T % chunk                        # the xla tier's odd bucket
        if pad:
            q, k, v = [jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
                       for t in (q, k, v)]
            g, beta = [jnp.pad(t, ((0, pad), (0, 0))) for t in (g, beta)]
        s0 = jnp.where(resumes, state[row, layer], 0.0)     # [dk, Hv dv]
        if impl in ('pallas', 'interpret'):
            o, last = prefill_chunks(q, k, v, g, beta, s0, chunk=chunk,
                                     interpret=impl == 'interpret')
        else:
            o, last = _prefill_chunks_xla(q, k, v, g, beta, s0, chunk)
        out = _gated_norm(o[:T], z[0], p['NormW'], eps)
        state = state.at[row, layer].set(last)
        # ext[length + j] is the convolution's input K - 1 - j rows before
        # the last real one's successor: its last K - 1 inputs
        tails = tails.at[row, layer, :K - 1].set(
            lax.dynamic_slice_in_dim(ext, length, K - 1, axis=0))
    ctx.out(op, 'Out', out[None].astype(z.dtype))
    ctx.out(op, 'StateOut', state)
    ctx.out(op, 'TailOut', tails)
