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
semaphore; the consumer waits for exactly the pages it is about to read.
The cursor and the consumed count live in SMEM scratch, which persists
across the sequential grid.

Two bodies share that pipeline and no arithmetic. ``G = n_head //
n_kv_head``, read from the call's shapes, chooses: the VPU's cost grows
with ``G`` and the MXU's does not, while a block-diagonal query matrix
wastes a factor ``n_kv_head`` of the MXU, which at ``G == 1`` buys nothing.

Arithmetic, ``G == 1`` (`_kernel`; all float32, on the VPU: a one-row
query has no use for the MXU, and the work fits under the page's DMA).
Lanes carry ``128 // dh`` heads per vreg, so ``q . k`` is a segmented
lane sum. Every (token-row-in-page, head) pair keeps its OWN
online-softmax stream (running max, sum, accumulator, lane-replicated
over the head's ``dh`` lanes): a page updates ``bs`` streams per head
elementwise, with no cross-sublane traffic, and the streams are merged
once per slot. Rows past the position — they exist only in a slot's
LAST page — get score ``-1e30``, weight exactly 0, and leave max, sum and
accumulator as they were: the exact-zero contract of the gather
formulation.

Grouped queries, ``G > 1`` (`_grouped_kernel`: the pool holds the K/V
heads only, a page is ``[bs, n_kv_head * dh]``, and query head ``h``
reads K/V head ``h // G``). One stream a lane would walk a page once per
query of the group, ``G`` times the VPU's work for the same bytes (LFM2,
``G = 4``: 35 % of its bytes' time; PERF.md, PR 35). Here the ``H``
queries of a slot are ONE block-diagonal matrix ``Qbd [H, n_kv_head *
dh]`` — row ``(g, j)`` holds ``scale * q`` of query ``g`` of K/V head
``j`` in that head's ``dh`` lanes, zeros elsewhere — and the ring is cut
into WINDOWS of ``_WINDOW_KEYS // bs`` pages, the columns of one scores
matmul: ``scores [H, keys] = Qbd . Kwindow^T`` and ``acc [H, n_kv_head *
dh] = alpha * acc + P . Vwindow`` on the MXU, the online softmax once a
query ROW (``[H, keys]``, max and sum ``[H, 1]``, all float32). Row ``(g,
j)`` of ``acc`` is wanted in head ``j``'s lanes only; the rest is the
block-diagonal form's waste and is dropped at the end. The cursor starts
every slot on a window boundary (a slot's last window may be short: its
unused ring places keep what an earlier page left there). Keys past the
position — the tail of the last page and those places — get score
``-1e30`` and weight exactly 0, and their V rows are zeroed in the ring
before the product, so whatever they hold (an earlier tenant's rows, a
NaN) adds exactly 0. The two products take their float32 operands at
``_PRECISION``; see there.

A bounded call (``span``, a window layer's: the slot's query sees the
``span`` keys up to its position and no other) walks the same pipeline
from the page of the FIRST key seen, ``(pos - span + 1) // bs``, so it
reads at most ``ceil(span / bs) + 1`` pages a slot whatever the context.
Its tables are rings (logical page ``p`` in column ``p % MB``,
models/transformer.py `window_ring`), and the keys before the first one
seen — the head of the first page — get the tail's treatment: score
``-1e30``, weight exactly 0, V rows zeroed in the grouped body. It
lowers under an operation name of its own, so a trace tells the window
layers' calls from the global layers'. (The grouped body's "window" is a
group of pages in one scores matmul and has nothing to do with it.)

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
# grouped queries: the keys a window of pages holds, the columns of one
# scores matmul (v5e, LFM2's shape: 512 keys 3.41 ms, 256 3.64, 128 4.08,
# 1 024 3.48); the VMEM the ring of windows may take (K + V); and the ring
# places it may have: each has two DMA semaphores, and a core has 512
_WINDOW_KEYS = 512
_WINDOW_RING_BYTES = 8 << 20
_RING_PLACES = 128
# grouped queries: the MXU takes the float32 operands of the two products
# whole (six bfloat16 passes), as the VPU body multiplies them whole. On
# the v5e the pages' DMA hides nearly all of it: one pass or a three-pass
# split 3.31 ms, this 3.41 (PERF.md, PR 36).
_PRECISION = lax.Precision.HIGHEST


def shapes_ok(n_head, head_dim, block_size, n_kv_head=None):
    """The kernel's tiling rule: pages (of the K/V heads) are whole
    (8, 128) tiles, a head's lanes never straddle a vreg -- or, under
    grouped queries alone, fill whole vregs (heads of 256: the MXU body
    contracts over the page's lanes whatever a head's share of them; the
    VPU body sums a head's lanes inside one vreg) --, and the query
    heads divide evenly over the K/V heads. Grouped queries besides: whole
    pages fill a window, and the ring holds two windows (the query rows
    fill whole sublanes by `padded_group`)."""
    n_kv_head = n_kv_head or n_head
    return (n_kv_head * head_dim) % _LANES == 0 and \
        (_LANES % head_dim == 0 or (head_dim % _LANES == 0
                                    and n_head != n_kv_head)) and \
        block_size % 8 == 0 and \
        n_head % n_kv_head == 0 and \
        (n_head == n_kv_head
         or (_WINDOW_KEYS % block_size == 0
             and ring_depth(n_kv_head, head_dim, block_size,
                            _WINDOW_KEYS // block_size)
             >= 2 * (_WINDOW_KEYS // block_size)))


def padded_group(group, n_kv_head):
    """Queries a K/V head as the MXU body is given them: ``group``, or
    the next count at which the query rows fill whole sublanes (Jamba's 20
    queries on one K/V head run as 24; the added rows are zero queries
    whose output is dropped). LFM2's 4 x 8 and K-EXAONE's 8 x 8 stand."""
    while (group * n_kv_head) % 8:
        group += 1
    return group


def form(n_head, n_kv_head=None):
    """Which body a call of these head counts takes: ``'mxu'`` where
    several queries share a K/V head, ``'vpu'`` where each has its own."""
    return 'mxu' if n_head != (n_kv_head or n_head) else 'vpu'


def ring_depth(n_head, head_dim, block_size, window=1):
    """Pages in flight. One query a head: what `_RING_BYTES` holds of
    K + V pages, at least 2 and at most `_RING_MAX`. Grouped queries:
    the whole windows of `window` pages that `_WINDOW_RING_BYTES` and
    `_RING_PLACES` hold."""
    page = block_size * n_head * head_dim * 4
    if window > 1:
        return int(min(_WINDOW_RING_BYTES // (2 * page * window),
                       _RING_PLACES // window)) * window
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


def _page_ring(tables_ref, pos_ref, layer_ref, k_hbm, v_hbm, k_buf, v_buf,
               sems, cur, *, block_size, max_blocks, slots, ring, window=1,
               span=None):
    """The DMA side both bodies share: `first_page(slot)` (0 unless the
    call is bounded by ``span``), `n_pages(slot)`, `copies(block, place)`
    (the K and the V copy of one page into one ring place) and `issue()`;
    at the first slot the ring is filled. ``cur``: [0]
    windows consumed (pages, at ``window == 1``), [1] ring places handed
    out, [2]/[3] the prefetch cursor's slot and page. With ``window >
    1`` a slot's last page rounds the place up to the next window, so
    every slot starts on a window boundary, and a place is handed out
    only once the window that held it before is consumed."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bs = block_size
    layer = layer_ref[0]

    def first_page(slot):
        if span is None:
            return 0
        return jnp.maximum(pos_ref[slot] - (span - 1), 0) // bs

    def n_pages(slot):
        if span is None:
            return jnp.clip(pos_ref[slot] // bs, 0, max_blocks - 1) + 1
        return jnp.maximum(pos_ref[slot], 0) // bs - first_page(slot) + 1

    def column(slot, page):
        """Where the slot's table names its `page`-th page read: a
        bounded call's table is a ring."""
        if span is None:
            return page
        return (first_page(slot) + page) % max_blocks

    def copies(block, place):
        return [pltpu.make_async_copy(
            hbm.at[block, layer], buf.at[place], sems.at[i, place])
            for i, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                            (v_hbm, v_buf)))]

    def issue():
        """Start the DMA of the cursor's page and advance the cursor;
        nothing once the cursor has run past the last slot (or, with
        windows, while the ring is full)."""
        ps, pp = cur[2], cur[3]
        room = ps < slots
        if window > 1:
            room = room & (cur[1] < cur[0] * window + ring)

        @pl.when(room)
        def _():
            for c in copies(tables_ref[ps * max_blocks + column(ps, pp)],
                            cur[1] % ring):
                c.start()
            last = pp + 1 == n_pages(ps)
            nxt = cur[1] + 1
            if window > 1:
                nxt = jnp.where(last, (nxt + window - 1) // window * window,
                                nxt)
            cur[1] = nxt
            cur[2] = jnp.where(last, ps + 1, ps)
            cur[3] = jnp.where(last, 0, pp + 1)

    @pl.when(pl.program_id(0) == 0)
    def _():
        cur[0] = 0
        cur[1] = 0
        cur[2] = 0
        cur[3] = 0
        lax.fori_loop(0, ring, lambda i, c: (issue(), c)[1], 0)

    return first_page, n_pages, copies, issue


def _kernel(tables_ref, pos_ref, layer_ref,          # scalar prefetch
            q_ref, k_hbm, v_hbm,                     # inputs
            o_ref,                                   # output
            k_buf, v_buf, sems, qb, m_scr, l_scr, acc_scr, cur,
            *, scale, head_dim, block_size, max_blocks, slots, ring,
            span=None):
    """One query a K/V head (``G == 1``): the VPU body."""
    import jax.experimental.pallas as pl
    s = pl.program_id(0)
    bs, hd = block_size, q_ref.shape[-1]
    first_page, n_pages, copies, issue = _page_ring(
        tables_ref, pos_ref, layer_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
        cur, block_size=bs, max_blocks=max_blocks, slots=slots, ring=ring,
        span=span)

    qb[...] = jnp.broadcast_to(q_ref[0] * scale, (bs, hd))
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
        if span is None:
            live = p * bs + row <= jnp.where(p == n - 1, pos,
                                             jnp.iinfo(jnp.int32).max)
        else:
            # a bounded call: the rows ahead of the first key seen too
            at = (first_page(s) + p) * bs + row
            live = (at <= pos) & (at > pos - span)
        for c0 in range(0, hd, _LANES):
            sl = pl.ds(c0, _LANES)
            sc = _segment_sum(k_buf[r, :, sl] * qb[:, sl], heads)
            sc = jnp.where(live, sc, _NEG_INF)
            m_prev = m_scr[:, sl]
            m_new = jnp.maximum(m_prev, sc)
            alpha = jnp.exp(m_prev - m_new)
            # a row past the position: weight exactly 0, whatever the
            # page holds there
            w = jnp.where(live, jnp.exp(sc - m_new), 0.0)
            l_scr[:, sl] = alpha * l_scr[:, sl] + w
            acc_scr[:, sl] = alpha * acc_scr[:, sl] + w * v_buf[r, :, sl]
            m_scr[:, sl] = m_new
        cur[0] = cur[0] + 1
        issue()
        return carry

    lax.fori_loop(0, n, page, 0)

    # merge the bs streams of every head
    for c0 in range(0, hd, _LANES):
        sl = pl.ds(c0, _LANES)
        m = m_scr[:, sl]
        w = jnp.exp(m - jnp.max(m, axis=0, keepdims=True))
        den = jnp.sum(l_scr[:, sl] * w, axis=0, keepdims=True)
        num = jnp.sum(acc_scr[:, sl] * w, axis=0, keepdims=True)
        o_ref[0, :, sl] = (num / den).astype(o_ref.dtype)


def _scores(q, k):
    """``[H, hd] x [keys, hd] -> [H, keys]`` on the MXU."""
    return lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                           precision=_PRECISION,
                           preferred_element_type=jnp.float32)


def _weighted(p, v):
    """``[H, keys] x [keys, hd] -> [H, hd]`` on the MXU."""
    return lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                           precision=_PRECISION,
                           preferred_element_type=jnp.float32)


def _grouped_kernel(tables_ref, pos_ref, layer_ref,  # scalar prefetch
                    q_ref, k_hbm, v_hbm,             # inputs
                    o_ref,                           # output
                    k_buf, v_buf, sems, qbd, m_scr, l_scr, acc_scr, cur,
                    *, scale, head_dim, block_size, max_blocks, slots, ring,
                    group, window, span=None):
    """``G`` queries a K/V head (``G > 1``): the MXU body."""
    import jax.experimental.pallas as pl
    s = pl.program_id(0)
    bs, hd = block_size, q_ref.shape[-1]
    H = qbd.shape[0]
    n_kv = H // group
    keys = window * bs
    first_page, n_pages, copies, issue = _page_ring(
        tables_ref, pos_ref, layer_ref, k_hbm, v_hbm, k_buf, v_buf, sems,
        cur, block_size=bs, max_blocks=max_blocks, slots=slots, ring=ring,
        window=window, span=span)

    # row (g, j) = g * n_kv + j is query g of K/V head j: `mine[g]` says
    # where a row of the group g sits in its own head's lanes
    r_ = lax.broadcasted_iota(jnp.int32, (H, hd), 0)
    lane_head = lax.broadcasted_iota(jnp.int32, (H, hd), 1) // head_dim
    mine = [r_ - g * n_kv == lane_head for g in range(group)]
    q = jnp.zeros((H, hd), jnp.float32)
    for g in range(group):
        q = jnp.where(mine[g], jnp.broadcast_to(q_ref[0, pl.ds(g, 1), :],
                                                (H, hd)), q)
    qbd[...] = q * scale
    m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos = pos_ref[s]
    n = n_pages(s)
    n_win = (n + window - 1) // window
    key = lax.broadcasted_iota(jnp.int32, (H, keys), 1)

    def seen(at):
        """Whether the key at place `at` of the slot's pages read (0 the
        first row of the first page read) is one the query sees."""
        if span is None:
            return at <= pos
        at = first_page(s) * bs + at
        return (at <= pos) & (at > pos - span)

    def pages(w, carry):
        base = pl.multiple_of((cur[0] * window) % ring, window)
        have = jnp.minimum(window, n - w * window)
        for j in range(window):
            @pl.when(j < have)
            def _():
                for c in copies(0, base + j):
                    c.wait()
        here = pl.ds(base, window)

        # only the slot's last window has keys past its position: the
        # tail of its last page and the ring places no page came to. Their
        # weight is exactly 0; their V rows are zeroed here, so that 0
        # times whatever they hold (a NaN) adds 0 too
        # (a bounded call's first window besides: the head of its first
        # page)
        @pl.when((w == n_win - 1) if span is None
                 else (w == n_win - 1) | (w == 0))
        def _():
            v_row = lax.broadcasted_iota(jnp.int32, (keys, hd), 0)
            v = v_buf[here].reshape(keys, hd)
            v_buf[here] = jnp.where(seen(w * keys + v_row), v,
                                    0.0).reshape(window, bs, hd)

        sc = _scores(qbd[...], k_buf[here].reshape(keys, hd))
        live = seen(w * keys + key)
        sc = jnp.where(live, sc, _NEG_INF)
        m_prev = m_scr[...]                                  # [H, 128]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(sc - m_new[:, :1]), 0.0)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + _weighted(
            p, v_buf[here].reshape(keys, hd))
        m_scr[...] = m_new
        cur[0] = cur[0] + 1
        for _ in range(window):
            issue()
        return carry

    lax.fori_loop(0, n_win, pages, 0)

    # row (g, j) keeps head j's lanes; the others are exact zeros in the sum
    out = acc_scr[...] / l_scr[:, :1]
    for g in range(group):
        o_ref[0, pl.ds(g, 1), :] = jnp.sum(
            jnp.where(mine[g], out, 0.0), axis=0,
            keepdims=True).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=('scale', 'interpret',
                                             'attention_span', 'part'))
def paged_decode_attention(q, k_pool, v_pool, tables, pos, layer, *,
                           scale, interpret=False, attention_span=None,
                           part=None):
    """q ``[S, H, dh]``; pools ``[NB, Ln, bs, Hkv*dh]``; tables ``[S,
    MB]`` and pos ``[S]`` int32; layer an int32 scalar. Returns ``[S, H,
    dh]``: softmax(q . K[0..pos]) V[0..pos] per slot and head, query head
    h against K/V head ``h // (H // Hkv)``. ``attention_span`` (a window
    layer's call): keys ``pos - attention_span + 1 .. pos`` alone, through
    tables that are rings. ``part`` (a looped model's pass, ``loop_pass_
    <t>``): the operation's name ends in it -- one trace of the kernel a
    pass, not one a program.

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
    # said only where there is one: an unbounded call's kernel is built
    # from the arguments it always had
    bound = {} if attention_span is None else {'span': int(attention_span)}
    # rows of one slot: query g of every K/V head, laid out as a page's
    # lanes are (K/V head, feature)
    rows = jnp.swapaxes(q.reshape(S, Hkv, G, dh), 1, 2).reshape(S, G, hd)
    Gp = G
    if G == 1:
        ring = ring_depth(Hkv, dh, bs)
        kernel = functools.partial(
            _kernel, scale=scale, head_dim=dh, block_size=bs,
            max_blocks=MB, slots=S, ring=ring, **bound)
        # bs online-softmax streams a head
        state = pltpu.VMEM((bs, hd), jnp.float32)
        scratch = [state, state, state, state]
    else:
        Gp = padded_group(G, Hkv)
        if Gp != G:
            rows = jnp.pad(rows, ((0, 0), (0, Gp - G), (0, 0)))
        H = Gp * Hkv
        window = _WINDOW_KEYS // bs
        ring = ring_depth(Hkv, dh, bs, window)
        kernel = functools.partial(
            _grouped_kernel, scale=scale, head_dim=dh, block_size=bs,
            max_blocks=MB, slots=S, ring=ring, group=Gp, window=window,
            **bound)
        # one online-softmax stream a query row
        stat = pltpu.VMEM((H, _LANES), jnp.float32)
        scratch = [pltpu.VMEM((H, hd), jnp.float32), stat, stat,
                   pltpu.VMEM((H, hd), jnp.float32)]
    row = pl.BlockSpec((1, Gp, hd), lambda s, *_: (s, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
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
                pltpu.SemaphoreType.DMA((2, ring))] + scratch + [
                pltpu.SMEM((4,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((S, Gp, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=('paged_window_decode_attention' if bound
              else 'paged_decode_attention') + ('_' + part if part else ''),
    )(tables.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows, k_pool, v_pool)
    return jnp.swapaxes(out[:, :G].reshape(S, G, Hkv, dh), 1,
                        2).reshape(S, G * Hkv, dh)
