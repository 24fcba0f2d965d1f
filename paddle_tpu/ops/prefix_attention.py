"""The attention of a prefill's rows against its slot's keys as a Pallas TPU
kernel: blockwise, with a running softmax, so that no ``[heads, rows,
keys]`` value ever stands in HBM, and the key tiles no row can see are
neither read nor computed.

`kv_prefix_attention` (ops/kv_cache_ops.py) hands it what its plain
composition works on: the queries ``[H, T, dh]`` with their positions
``[T]``, and the keys and values ``[Hkv, M, dh]`` with the position each
key holds, ``at [M]`` (negative: no key there). A global layer's keys are
the slot's pages in table order, taken out of the pool by ONE gather
(`kv_cache_ops.pool_pages`: two page-sized copies a layer, where the scores
crossed HBM ten times at ``H * T`` rows a key), so ``at`` is ``0 .. M -
1``; a window layer's are the rows its ring holds from before the chunk and
the chunk's own. Query row ``t`` sees key ``i`` iff ``0 <= at[i] <=
pos[t]`` and, under ``window``, ``at[i] > pos[t] - window``: the op's own
mask, and the only one.

Grid ``(K/V head, query tile, key tile)``, the key tiles innermost. The
``G = H // Hkv`` query heads of a K/V head are rows of ONE matmul against
its keys (``[G * tq, dh] x [tk, dh]``), never repeated keys. Max, sum and
accumulator of a query row live in VMEM scratch across the key tiles
(float32); the two products take their float32 operands as XLA's einsums
of the plain composition take them on this chip, at the default matmul
precision.

Keys and values need not be one width, and a part of every key may be
ONE row that all heads share: `mla_prefix_attention` (ops/mla_ops.py) is
this kernel with a query head a K/V head, 128 key lanes of a head's own,
64 rotary lanes behind them against the one rotary key ``[M, 64]`` (a
second product a key tile, against a block whose index ignores the head)
and values of 128 lanes, under its own Mosaic name. A call with one width
and no shared part traces to the kernel it was before
(tests/fixtures/prefix_attention_kernel_parent_pr61.json).

What is skipped. ``seen[q]``, a scalar prefetched a query tile, counts the
leading keys that some row of the tile sees (a prompt's first chunk of 512
rows against a table of 5 120 keys: 512). A key tile wholly past it is not
computed, and not read either: its block index is held at the last tile
that counts, and the pipeline copies no block twice in a row.

Masked keys get score ``-1e30`` and weight exactly 0 (the decode kernels'
contract), so a NaN among such keys changes no bit of the output; the V
rows at or past ``seen[q]`` — the tail of the tile that straddles it, the
ragged end of a table that is no whole number of tiles — are zeroed before
the product, so whatever stands there adds exactly 0 too. A row that sees
no key at all (none does in a prefill: a row sees itself) gives zeros, as
the plain composition does.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_NO_KEY = jnp.iinfo(jnp.int32).max
# keys a tile, the columns of one scores matmul, and the query rows (of
# all the queries of one K/V head) that one tile may hold
_KEY_TILE = 512
_ROWS = 1024
_VMEM_BYTES = 64 << 20
# the scores' bytes from which a call takes the kernel. Measured in the
# serve cells' own prefill programs on the v5e (PERF.md, PR 44), XLA's
# composition | the kernel, ms a program: fairseq-dense 355M (24 layers, 16
# heads of 64, 768 keys) at 64 / 128 / 256 / 512 rows = 3 / 6 / 13 / 25 MB
# of scores 2.31 / 2.99 / 4.24 / 7.53 | 2.92 / 3.42 / 4.68 / 8.20; OLMoE (6
# layers, 16 heads of 128, 1 280 keys) at 128 / 256 / 512 rows = 10 / 21 /
# 42 MB 11.0 / 13.2 / 16.1 | 11.1 / 13.25 / 16.2; fairseq-dense 1.3B (24
# layers, 32 heads of 64, 1 056 keys) at 768 / 1 024 rows = 104 / 138 MB
# 41.5 / 52.5 | 28.1 / 37.5
_MIN_SCORES_BYTES = 64 << 20


def shapes_ok(n_head, n_kv_head, rows, head_dim, keys, v_dim=None,
              shared_dim=0):
    """Whether a call takes the kernel. The tiling rule: whole sublanes of
    query rows, heads (and values, ``v_dim`` where it differs) of whole or
    half vregs, the query heads divided evenly over the K/V heads; under a
    shared key part of ``shared_dim`` lanes, a head's own lanes whole vregs
    (the shared lanes of q begin where they end) and that part half a vreg
    or a whole one. And the call's size: the float32 scores of all its
    heads, which the plain composition would form, are `_MIN_SCORES_BYTES`
    at least — under that XLA keeps them on the chip itself and the
    kernel's fixed cost a call (its grid steps, the K and V copies in the
    layout it reads) is the larger (see there)."""
    widths = (64, 128, 256)
    return rows % 8 == 0 and head_dim in widths and \
        (v_dim or head_dim) in widths and \
        (not shared_dim or (head_dim % 128 == 0 and shared_dim in (64, 128))) \
        and n_head % n_kv_head == 0 and \
        n_head * rows * keys * 4 >= _MIN_SCORES_BYTES


def query_tile(rows, group, most=_ROWS):
    """Rows of one query head in a tile: the largest divisor of ``rows``
    of whole sublanes that keeps the group's rows within ``most``."""
    return max(t for t in range(8, rows + 1, 8)
               if rows % t == 0 and (t * group <= most or t == 8))


def _kernel(seen_ref,                               # scalar prefetch
            q_ref, pos_ref, k_ref, v_ref, at_ref,   # inputs
            *rest,          # [the shared key part,] output, three scratch
            scale, window, n_key_tiles):
    import jax.experimental.pallas as pl
    *shared, o_ref, m_scr, l_scr, acc_scr = rest
    qi, j = pl.program_id(1), pl.program_id(2)
    G, tq, dq = q_ref.shape[1:]
    tk, dk = k_ref.shape[1:]
    dv = v_ref.shape[2]
    seen = seen_ref[qi]

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j * tk < seen)
    def _():
        q = q_ref[0].reshape(G * tq, dq)
        pos = pos_ref[0]                                    # [G * tq, 1]
        at = at_ref[...]                                    # [1, tk]
        at = jnp.where(at >= 0, at, _NO_KEY)
        live = at <= pos
        if window is not None:
            live &= at > pos - window
        def product(x, y):
            return lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        if shared:
            # a head's own lanes against its own keys, the lanes behind
            # them against the key part that every head shares
            s = product(q[:, :dk], k_ref[0]) + product(q[:, dk:],
                                                       shared[0][...])
        else:
            s = product(q, k_ref[0])
        s = s * scale
        s = jnp.where(live, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a key the row does not see: weight exactly 0, whatever its
        # score was (a NaN, or -1e30 against a maximum of -1e30)
        p = jnp.where(live, jnp.exp(s - m_new), 0.0)
        key = j * tk + lax.broadcasted_iota(jnp.int32, (tk, dv), 0)
        v = jnp.where(key < seen, v_ref[0], 0.0)
        l_scr[...] = jnp.broadcast_to(
            alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        acc_scr[...] = alpha * acc_scr[...] + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == n_key_tiles - 1)
    def _():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).reshape(
            G, tq, dv).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'window',
                                             'interpret', 'name', 'rows'))
def prefix_attention(q, k, v, at, pos, k_shared=None, *, scale, window=None,
                     interpret=False, name=None, rows=_ROWS):
    """q ``[H, T, dk]`` at positions ``pos [T]``; k ``[Hkv, M, dk]`` and v
    ``[Hkv, M, dv]``, key ``i`` at position ``at[i]`` (negative: none).
    Returns ``[H, T, dv]``: row ``t``'s softmax over the keys with ``at <=
    pos[t]`` (and ``> pos[t] - window``), query head ``h`` against K/V head
    ``h // (H // Hkv)``.

    ``k_shared [M, dr]`` (latent attention's one rotary key, ops/mla_ops.py):
    a key part that every head shares, q then ``[H, T, dk + dr]`` — a score
    is the sum of two products, the second against a block whose index
    ignores the head, and no key ``dk + dr`` wide is laid out anywhere.
    ``rows``: the most query rows a tile may hold (`query_tile`).

    Jitted: the layers of a prefill program call ONE traced function, so
    the kernel is lowered to Mosaic once a program and not once a layer."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    H, T, dq = q.shape
    Hkv, M = k.shape[:2]
    dv = v.shape[2]
    G = H // Hkv
    tq = query_tile(T, G, rows)
    nq = T // tq
    pos = pos.astype(jnp.int32)
    at = at.astype(jnp.int32)
    shared = [] if k_shared is None else [k_shared]
    if M < _KEY_TILE:
        # one tile, of whole vregs of scores: the added keys are none
        tk = -(-M // 128) * 128
        k, v = (jnp.pad(x, ((0, 0), (0, tk - M), (0, 0))) for x in (k, v))
        shared = [jnp.pad(x, ((0, tk - M), (0, 0))) for x in shared]
    else:
        tk = _KEY_TILE
    nk = -(-M // tk)
    at = jnp.pad(at, (0, nk * tk - at.shape[0]), constant_values=-1)

    # the leading keys that some row of a query tile sees
    tiles = pos.reshape(nq, tq)
    sees = (at >= 0) & (at <= tiles.max(axis=1)[:, None])
    if window is not None:
        sees &= at > tiles.min(axis=1)[:, None] - window
    seen = jnp.max(jnp.where(sees, jnp.arange(nk * tk) + 1, 0),
                   axis=1).astype(jnp.int32)                   # [nq]

    def tile(qi, j, seen):
        # past the last tile that counts, that tile again: no copy
        return jnp.minimum(j, jnp.maximum(seen[qi] - 1, 0) // tk)

    def queries(d):
        return pl.BlockSpec((1, G, tq, d),
                            lambda h, qi, j, seen: (h, 0, qi, 0))

    def keys(d):
        return pl.BlockSpec((1, tk, d),
                            lambda h, qi, j, seen: (h, tile(qi, j, seen), 0))
    stat = pltpu.VMEM((G * tq, 128), jnp.float32)
    if name is None:
        name = 'kv_prefix_attention' if window is None \
            else 'kv_prefix_window_attention'
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, window=window,
                          n_key_tiles=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Hkv, nq, nk),
            in_specs=[
                queries(dq),
                pl.BlockSpec((1, G * tq, 1),
                             lambda h, qi, j, seen: (qi, 0, 0)),
                keys(k.shape[2]), keys(dv),
                pl.BlockSpec((1, tk),
                             lambda h, qi, j, seen: (0, tile(qi, j, seen))),
            ] + [pl.BlockSpec((tk, x.shape[1]),
                              lambda h, qi, j, seen: (tile(qi, j, seen), 0))
                 for x in shared],
            out_specs=queries(dv),
            scratch_shapes=[stat, stat,
                            pltpu.VMEM((G * tq, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Hkv, G, T, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=name,
    )(seen, q.reshape(Hkv, G, T, dq),
      jnp.tile(tiles[:, None, :], (1, G, 1)).reshape(nq, G * tq, 1),
      k, v, at[None], *shared)
    return out.reshape(H, T, dv)
