"""Paged one-query decode attention as a Pallas TPU kernel: every slot's
query attends its K/V pages WHERE THEY LIE in the block pool, and only the
pages at or below the slot's position are read.

Pool layout (ops/kv_cache_ops.py): ``[num_blocks, layers, block_size,
heads * head_dim]``. A page — one block of one layer — is ``[bs, H*dh]``
float32, one contiguous run of HBM whose rows are tokens and whose lanes
are (head, feature). The pools stay in HBM (``memory_space=ANY``); block
tables, positions and the layer index arrive by scalar prefetch, so the
24 call sites of a decode program share one kernel body.

Pipeline. The grid walks the slots in order. A ring of ``R`` page buffers
in VMEM is kept full by a prefetch cursor that runs AHEAD of the compute
across slot boundaries — the pages of slot ``s + 1`` are already in
flight while slot ``s`` computes — so a DMA's latency is exposed once
per call, not once per slot. Each page DMA (K and V) signals its own
semaphore; the consumer waits for exactly the page it is about to read.
The cursor and the consumed-page count live in SMEM scratch, which
persists across the sequential grid.

Arithmetic (all float32, on the VPU: a one-row query has no use for the
MXU). Lanes carry ``128 // dh`` heads per vreg, so ``q . k`` is a
segmented lane sum. Every (token-row-in-page, head) pair keeps its OWN
online-softmax stream (running max, sum, accumulator, lane-replicated
over the head's ``dh`` lanes): a page updates ``bs`` streams per head
elementwise, with no cross-sublane traffic, and the streams are merged
once per slot. Rows past the position — they exist only in a slot's
LAST page — get score ``-1e30``, weight exactly 0, and leave max, sum and
accumulator as they were: the exact-zero contract of the gather
formulation.

Grouped queries (``n_kv_head < n_head``): the pool holds the K/V heads
only, a page is ``[bs, n_kv_head * dh]``, and query head ``h`` reads K/V
head ``h // G`` (``G = n_head // n_kv_head``). A page is still copied
ONCE; the ``G`` queries of each of its heads are laid over its lanes one
after the other (query ``g`` of every K/V head at a time, a lane layout
like the page's own), each with its own ``bs`` streams. With ``G == 1``
this is the kernel it was, operation for operation.

Slot independence is bitwise: the pages a slot visits, and the sequence
of operations on them, depend on its own position, table row and query
only. Which ring buffer a page lands in depends on the neighbours; the
values do not.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -1e30
_LANES = 128
# VMEM the page ring may take (K + V); the ring is as deep as this allows
_RING_BYTES = 2 << 20
_RING_MAX = 32


def shapes_ok(n_head, head_dim, block_size, n_kv_head=None):
    """The kernel's tiling rule: pages (of the K/V heads) are whole
    (8, 128) tiles, a head's lanes never straddle a vreg, and the query
    heads divide evenly over the K/V heads."""
    n_kv_head = n_kv_head or n_head
    return (n_kv_head * head_dim) % _LANES == 0 and \
        _LANES % head_dim == 0 and block_size % 8 == 0 and \
        n_head % n_kv_head == 0


def ring_depth(n_head, head_dim, block_size):
    """Pages in flight: what `_RING_BYTES` holds of K + V pages, at
    least 2 and at most `_RING_MAX`."""
    page = block_size * n_head * head_dim * 4
    return int(max(2, min(_RING_MAX, _RING_BYTES // (2 * page))))


def _segment_sum(x, heads):
    """Sum each head's lanes of a [rows, 128] tile, the sum replicated
    over that head's lanes. `heads`: one lane mask per head of the tile
    (None when a head takes all 128 lanes)."""
    if heads is None:
        return jnp.broadcast_to(jnp.sum(x, axis=-1, keepdims=True), x.shape)
    out = jnp.zeros_like(x)
    for mine in heads:
        tot = jnp.sum(jnp.where(mine, x, 0.0), axis=-1, keepdims=True)
        out = jnp.where(mine, tot, out)
    return out


def _kernel(tables_ref, pos_ref, layer_ref,          # scalar prefetch
            q_ref, k_hbm, v_hbm,                     # inputs
            o_ref,                                   # output
            k_buf, v_buf, sems, qb, m_scr, l_scr, acc_scr, cur,
            *, scale, head_dim, block_size, max_blocks, slots, ring, group):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s = pl.program_id(0)
    bs, hd = block_size, q_ref.shape[-1]
    layer = layer_ref[0]
    # cur: [0] pages consumed, [1] pages issued, [2]/[3] the prefetch
    # cursor's slot and page

    def n_pages(slot):
        return jnp.clip(pos_ref[slot] // bs, 0, max_blocks - 1) + 1

    def copies(block, slot_in_ring):
        return [pltpu.make_async_copy(
            hbm.at[block, layer], buf.at[slot_in_ring],
            sems.at[i, slot_in_ring])
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                            (v_hbm, v_buf)))]

    def issue():
        """Start the DMA of the cursor's page and advance the cursor;
        nothing once the cursor has run past the last slot."""
        ps, pp = cur[2], cur[3]

        @pl.when(ps < slots)
        def _():
            for c in copies(tables_ref[ps * max_blocks + pp],
                            cur[1] % ring):
                c.start()
            cur[1] = cur[1] + 1
            last = pp + 1 == n_pages(ps)
            cur[2] = jnp.where(last, ps + 1, ps)
            cur[3] = jnp.where(last, 0, pp + 1)

    @pl.when(s == 0)
    def _():
        cur[0] = 0
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0
        lax.fori_loop(0, ring, lambda i, c: (issue(), c)[1], 0)

    # query g of every K/V head over the page's lanes, in rows g*bs..
    for g in range(group):
        qb[pl.ds(g * bs, bs), :] = jnp.broadcast_to(
            q_ref[0, pl.ds(g, 1), :] * scale, (bs, hd))
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[s]
    n = n_pages(s)
    row = lax.broadcasted_iota(jnp.int32, (bs, _LANES), 0)
    heads = None
    if head_dim < _LANES:
        lane_head = lax.broadcasted_iota(jnp.int32, (bs, _LANES),
                                         1) // head_dim
        heads = [lane_head == g for g in range(_LANES // head_dim)]

    def page(p, carry):
        r = cur[0] % ring
        for c in copies(0, r):
            c.wait()
        # only the slot's last page has rows past its position: every
        # other page compares against a limit no row reaches, so one body
        # serves both (traced and lowered once)
        live = p * bs + row <= jnp.where(p == n - 1, pos,
                                         jnp.iinfo(jnp.int32).max)
        for g, c0 in [(g, c0) for g in range(group)
                      for c0 in range(0, hd, _LANES)]:
            rs, sl = pl.ds(g * bs, bs), pl.ds(c0, _LANES)
            sc = _segment_sum(k_buf[r, :, sl] * qb[rs, sl], heads)
            sc = jnp.where(live, sc, _NEG_INF)
            m_prev = m_scr[rs, sl]
            m_new = jnp.maximum(m_prev, sc)
            alpha = jnp.exp(m_prev - m_new)
            # a row past the position: weight exactly 0, whatever the
            # page holds there
            w = jnp.where(live, jnp.exp(sc - m_new), 0.0)
            l_scr[rs, sl] = alpha * l_scr[rs, sl] + w
            acc_scr[rs, sl] = alpha * acc_scr[rs, sl] + w * v_buf[r, :, sl]
            m_scr[rs, sl] = m_new
        cur[0] = cur[0] + 1
        issue()
        return carry

    lax.fori_loop(0, n, page, 0)

    # merge the bs streams of every head
    for g, c0 in [(g, c0) for g in range(group)
                  for c0 in range(0, hd, _LANES)]:
        rs, sl = pl.ds(g * bs, bs), pl.ds(c0, _LANES)
        m = m_scr[rs, sl]
        w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
        den = jnp.sum(l_scr[rs, sl] * w, axis=0, keepdims=True)
        num = jnp.sum(acc_scr[rs, sl] * w, axis=0, keepdims=True)
        o_ref[0, pl.ds(g, 1), sl] = (num / den).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'interpret'))
def paged_decode_attention(q, k_pool, v_pool, tables, pos, layer, *,
                           scale, interpret=False):
    """q ``[S, H, dh]``; pools ``[NB, Ln, bs, Hkv*dh]``; tables ``[S,
    MB]`` and pos ``[S]`` int32; layer an int32 scalar. Returns ``[S, H,
    dh]``: softmax(q . K[0..pos]) V[0..pos] per slot and head, query head
    h against K/V head ``h // (H // Hkv)``.

    Jitted, with `layer` an operand: the layers of a decode program call
    ONE traced function, so the kernel is traced and lowered to Mosaic
    once per program and not once per layer — per layer, a 24-layer
    engine's warm start took 17 s longer (PERF.md, PR 26)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    S, H, dh = q.shape
    bs, hd = k_pool.shape[2], k_pool.shape[3]
    MB = tables.shape[1]
    Hkv = hd // dh
    G = H // Hkv
    ring = ring_depth(Hkv, dh, bs)
    kernel = functools.partial(
        _kernel, scale=scale, head_dim=dh, block_size=bs,
        max_blocks=MB, slots=S, ring=ring, group=G)
    # rows of one slot: query g of every K/V head, laid out as a page's
    # lanes are (K/V head, feature)
    row = pl.BlockSpec((1, G, hd), lambda s, *_: (s, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    state = pltpu.VMEM((G * bs, hd), jnp.float32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[row, pool, pool],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((ring, bs, hd), k_pool.dtype),
                pltpu.VMEM((ring, bs, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, ring)),
                state, state, state, state,
                pltpu.SMEM((4,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name='paged_decode_attention',
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.swapaxes(q.reshape(S, Hkv, G, dh), 1, 2).reshape(S, G, hd),
      k_pool, v_pool)
    return jnp.swapaxes(out.reshape(S, G, Hkv, dh), 1, 2).reshape(S, H, dh)
