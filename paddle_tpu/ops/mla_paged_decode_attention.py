"""Absorbed latent-attention (MLA) decode as a Pallas TPU kernel: every
slot's ``H`` absorbed queries attend the slot's latent rows WHERE THEY LIE
in the block pool, and only the pages at or below the slot's position are
read.

Pool layout (ops/kv_cache_ops.py, one pool, no V): ``[num_blocks, layers,
block_size, W]``, ``W`` the ``kv_lora_rank + qk_rope_dim`` numbers of a
row in whole 128-lane tiles (`LMConfig.kv_width`) — a page is ``[bs, W]``
float32, shared by ALL heads: lanes ``[0, V)`` (``V = kv_lora_rank``) are
the normed latent ``c_kv``, the next ``qk_rope_dim`` the rotated shared
key ``k_r``, the rest zeros. The absorbed query of a head is ``[q_nope
W_uk^T | q_r | 0]`` (``W`` lanes), so

    score = q' . row          over all W lanes
    out   = sum p * row[:V]   the VALUES are the first V lanes of the
                              same row; W_uv is applied by the caller

and a page is read once for both. Per page that is a ``[H, W] x [W, bs]``
and a ``[H, bs] x [bs, V]`` matmul — MXU work, ~2 H FLOP a byte — where
the per-head kernel (ops/paged_decode_attention.py) has none.

Pipeline: the ring of page DMAs and the scalar-prefetched tables,
positions and layer of ops/paged_decode_attention.py. The ring is cut
into GROUPS of ``G = _GROUP_KEYS // bs`` pages, the columns of one scores
matmul (256 keys: on the v5e 21 % faster than 128, and than 512; PERF.md
PR 32); the prefetch cursor runs ahead of the compute across slot
boundaries and starts every slot on a group boundary (a slot's last group
may be short: its unused ring places keep what an earlier page left
there, finite, masked to weight exactly 0). The two matmuls take their
float32 operands at the TPU's default precision, as every other matmul
of the float32 serving programs does (Mosaic and XLA alike round them to
bfloat16 for one MXU pass: casting them by hand gave the same bits and
the same time); sums and the online softmax are float32.

Slot independence is bitwise, as there: the pages a slot visits and the
operations on them depend on its own position, table row and queries
only; a masked key has weight exactly 0 and adds exactly 0.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_LANES = 128
# keys a group of pages holds: the columns of one scores matmul
_GROUP_KEYS = 256
# groups of pages the ring holds (VMEM: groups x keys x W lanes)
_RING_GROUPS = 4


def shapes_ok(n_head, width, v_width, block_size):
    """The kernel's tiling rule: a group of pages is whole (8, 128) tiles,
    the values are whole lane tiles of the row, and the heads fill whole
    sublanes."""
    return block_size % 8 == 0 and _GROUP_KEYS % block_size == 0 \
        and v_width % _LANES == 0 and v_width <= width and n_head % 8 == 0


def _kernel(tables_ref, pos_ref, layer_ref,          # scalar prefetch
            q_ref, pool_hbm,                         # inputs
            o_ref,                                   # output
            buf, sems, m_scr, l_scr, acc_scr, cur,
            *, scale, v_width, block_size, max_blocks, slots, group,
            ring):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s = pl.program_id(0)
    bs, G = block_size, group
    H = q_ref.shape[1]
    keys = G * bs
    layer = layer_ref[0]
    # cur: [0] groups consumed, [1] ring places handed out (pages issued
    # and the places skipped to a group boundary), [2]/[3] the prefetch
    # cursor's slot and page

    def n_pages(slot):
        return jnp.clip(pos_ref[slot] // bs, 0, max_blocks - 1) + 1

    def copy(block, place):
        return pltpu.make_async_copy(
            pool_hbm.at[block, layer],
            buf.at[pl.ds(pl.multiple_of(place * bs, bs), bs)],
            sems.at[place])

    def issue():
        """Start the DMA of the cursor's page into the next ring place and
        advance the cursor; a slot's last page rounds the place up to the
        next group. Nothing once the cursor has run past the last slot or
        the ring is full."""
        ps, pp = cur[2], cur[3]

        @pl.when((ps < slots) & (cur[1] < cur[0] * G + ring))
        def _():
            copy(tables_ref[ps * max_blocks + pp], cur[1] % ring).start()
            last = pp + 1 == n_pages(ps)
            nxt = cur[1] + 1
            cur[1] = jnp.where(last, (nxt + G - 1) // G * G, nxt)
            cur[2] = jnp.where(last, ps + 1, ps)
            cur[3] = jnp.where(last, 0, pp + 1)

    @pl.when(s == 0)
    def _():
        cur[0] = 0
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0
        # a ring place a short group leaves unused is read (and masked):
        # it has to hold finite numbers from the start
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        lax.fori_loop(0, ring, lambda i, c: (issue(), c)[1], 0)

    q = q_ref[0] * scale                                     # [H, W]
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[s]
    n = n_pages(s)
    key = lax.broadcasted_iota(jnp.int32, (H, keys), 1)

    def page_group(g, carry):
        base = (cur[0] * G) % ring
        have = jnp.minimum(G, n - g * G)
        for j in range(G):
            @pl.when(j < have)
            def _():
                copy(0, base + j).wait()
        rows = buf[pl.ds(pl.multiple_of(base * bs, keys), keys), :]
        sc = lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        # keys past the position — the rest of the slot's last page and
        # the unused places of its last group — get weight exactly 0
        live = g * keys + key <= pos
        sc = jnp.where(live, sc, _NEG_INF)
        m_prev = m_scr[...]                                  # [H, 128]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        w = jnp.where(live, jnp.exp(sc - m_new[:, :1]), 0.0)
        l_scr[...] = alpha * l_scr[...] \
            + jnp.sum(w, axis=1, keepdims=True)
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + jnp.dot(
            w, rows[:, :v_width], preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        cur[0] = cur[0] + 1
        for _ in range(G):
            issue()
        return carry

    lax.fori_loop(0, (n + G - 1) // G, page_group, 0)
    o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'v_width',
                                             'interpret'))
def mla_paged_decode_attention(q, pool, tables, pos, layer, *, scale,
                               v_width, interpret=False):
    """q ``[S, H, W]`` absorbed queries; pool ``[NB, Ln, bs, W]``; tables
    ``[S, MB]`` and pos ``[S]`` int32; layer an int32 scalar. Returns
    ``[S, H, v_width]``: softmax(scale q . row[0..pos]) row[0..pos, :V]
    per slot and head — the attention output still in the latent space.

    Jitted with `layer` an operand, so the layers of a decode program
    share one traced and lowered kernel (ops/paged_decode_attention.py)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, W = q.shape
    bs = pool.shape[2]
    MB = tables.shape[1]
    group = _GROUP_KEYS // bs
    ring = _RING_GROUPS * group
    kernel = functools.partial(
        _kernel, scale=scale, v_width=v_width, block_size=bs,
        max_blocks=MB, slots=S, group=group, ring=ring)
    stat = pltpu.VMEM((H, _LANES), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, v_width),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((ring * bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((ring,)),
                stat, stat,
                pltpu.VMEM((H, v_width), jnp.float32),
                pltpu.SMEM((4,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='mla_paged_decode_attention',
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, pool)
