"""Device-resident KV-cache ops for the generative decode engine.

The serving-side decode path (serving/generate.py) keeps one pair of
persistable cache buffers per engine — a pool of fixed-size blocks, laid
out

    [num_blocks, layers, block_size, heads * head_dim]

and compiles exactly TWO program shapes per engine: a per-prompt-bucket
prefill and a single-token decode step. The cache vars are read-AND-written
persistables, so the executor's donation path (PR 1) aliases each step's
updated cache onto the previous buffer — the whole multi-hundred-MB cache
never doubles in HBM and never crosses the host.

Every slot addresses the pool through a runtime-fed BLOCK TABLE — logical
position ``p`` lives at ``(table[p // block_size], p % block_size)``:
``kv_cache_prefill_paged`` writes a prompt suffix's K (or V) rows,
``kv_cache_update_paged`` is the decode-step write (every slot deposits
its new token's row at its OWN position, one scatter for the whole
in-flight batch) and ``kv_decode_attention_paged`` the one-query
attention of every slot against its cached keys/values, masked at each
slot's current length. Positions past a slot's write head carry stale
garbage from earlier tenants of the block; the mask zeroes their weights
EXACTLY (post-softmax ``where``): a masked (stale / trash / other-tenant)
position contributes ``0 * garbage = 0`` bit-exactly, so a slot's output
is bit-identical whatever previously occupied the cache — the property
the continuous batcher's parity contract (tests/test_generate.py) rests
on. All ops are slot-row-independent: no op mixes data across the slot
axis, which is what makes admitting/evicting requests at token boundaries
safe while other slots are mid-sequence.

A PAGE (one block of one layer) is ``[block_size, heads * head_dim]``:
rows are tokens, lanes are (head, feature). The minor dimension has to
be a multiple of 128: then the layout XLA gives the pool on a TPU is the
row-major one and a page is ONE contiguous run of HBM (64 KB at
16 x 16 x 64 float32). A pool whose minor dimension is ``head_dim`` = 64
is NOT laid out as declared — XLA:TPU will not pad 64 lanes to 128 and
makes ``num_blocks`` the minor dimension instead, so a page is 16 K
single elements a pool-stride apart and every page read or write is an
element gather (measured on the v5e, PERF.md PR 26).
The table is an ordinary feed, so ONE compiled program serves any
allocation pattern (the fixed-signature / zero-recompile contract is
untouched); HBM is committed block-by-block as sequences actually grow,
and requests with a common prompt prefix can point their leading table
entries at the SAME physical blocks (serving/kv_blocks.py refcounts
them, copy-on-write on the first divergent write). Physical block 0 is
reserved as the TRASH block: table filler entries and redirected
pad-row writes land there, so an idle slot's garbage computation can
never scribble over a live block.

``kv_decode_attention_paged`` — the op every decode step spends its
time in — has three lowerings behind ``kernel_tier.dispatch``: ``off``
gathers each slot's whole table row into a dense ``[S, H, MB*bs, dh]``
K and V and runs plain einsums on them (the tests'
reference); ``xla`` does the same without the transpose; ``pallas`` /
``interpret`` is the kernel of ops/paged_decode_attention.py, which
reads each slot's LIVE pages in place from the pool — no pool slice, no
table-wide gather, no dense copy.

``kv_prefix_attention`` is what makes prefix sharing pay: a prefill
whose leading ``P`` positions are already cached computes only the
SUFFIX rows (queries at global positions ``P..P+T-1``) and attends them
against the block-table cache — prefix K/V are read, never recomputed,
so shared-prefix traffic buckets by suffix length and skips the shared
prefill compute entirely.

A WINDOW layer's calls (PR 41: attribute ``window`` on the two attention
ops and on the prefill's write, ``ring`` on the decode step's) go through
pools and tables of their own: the table is the slot's RING of
`models.transformer.window_ring` blocks, logical block ``b`` in column ``b
% ring``, so a page behind the window is written over and those pools do
not grow with the context. The decode attention sees keys ``p - window +
1 .. p`` (the kernel starts at the first one's page; the gather takes the
whole ring and masks by the position each place holds); the prefix
attention takes the suffix's own K and V as inputs beside the ``window -
1`` rows the ring holds from before it, and the suffix is written BEHIND
it, its last ``window - 1`` real rows only. They lower under the named
scope ``paddle_tpu:window_attention``.

``sample_next_token`` is the sampling leg: temperature / top-k / top-p
over the step logits, driven by a HOST-FED per-slot uniform (the
engine owns one PRNG stream per request), so the op is deterministic,
``needs_rng``-free (bind's single-PRNGKey fast path still applies), and
``temperature == 0`` rows return the bitwise argmax — greedy stays the
bitwise default. It is the op every decode step and every prefill ends
in, so it adapts to what it is fed, on the device: a step with no
sampled row takes ``argmax`` and nothing else (a ``lax.cond`` on
``any(Temp > 0)``), and a step with one sorts the vocabulary once and
lets that sort carry the values — neither gathers ``[S, V]`` (that
gather was 15.5 ms of a 22.4 ms decode step at 32 x 50264 on the v5e,
PERF.md PR 26 / PR 29).

SPECULATIVE-DECODE ops (PR 13) widen the per-slot decode step from one
token to a window of ``W = spec_k + 1`` tokens so a target model can
VERIFY a draft model's K proposals in one batched dispatch:

- ``kv_cache_update_span_paged``: every slot deposits W new K (or V)
  rows at its own W positions through its block table — the wide
  sibling of ``kv_cache_update_paged``. A per-row ``Valid`` feed
  redirects rows the host has not budgeted (idle slots, positions at or
  past ``max_len``, positions past the slot's allocated blocks) to the
  trash block: a speculative write may be THROWN AWAY later, but it
  must never be able to scribble a live block it doesn't own.
- ``kv_verify_attention_paged``: W-query attention of every slot
  against its block-table cache, each query row (s, t) masked to
  positions ``<= Positions[s, t]`` — row t attends the cached history
  plus the window rows at or before it (deposited by the span write
  just above), exactly the causal view the plain decode step would have
  had at that position. The exact-zero post-softmax mask keeps the
  bitwise contract: verify logits for position p equal the plain
  decode step's logits at p, which is what lets the engine accept draft
  tokens with NO numeric drift from non-speculative greedy decode.

Speculative ROLLBACK needs no op at all: rejected rows sit at positions
strictly past the slot's accepted write head, where the position mask
already zeroes them, and the engine returns their tail blocks to the
allocator (serving/generate.py) — the block table is the rollback
mechanism, no cache bytes are copied or cleared.
"""
import contextlib

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register_op

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# paged (block-table) variants


WINDOW_SCOPE = 'paddle_tpu:window_attention'


def _block_of(table, pos, block_size, ring=False):
    """(block id, in-block offset) of logical position(s) `pos` through a
    1-D block table. Out-of-range table indices clip to the last entry;
    unallocated entries hold 0 — the trash block — so a wild position can
    only ever touch trash. ``ring``: the table is a window layer's ring,
    logical block ``b`` in column ``b % len(table)``."""
    idx = (pos // block_size) % table.shape[0] if ring \
        else jnp.clip(pos // block_size, 0, table.shape[0] - 1)
    return table[idx].astype(jnp.int32), (pos % block_size).astype(jnp.int32)


def _window_scope(window):
    """The named scope a window layer's ops lower under, so that a device
    trace tells them from the global layers'; nothing for those."""
    return contextlib.nullcontext() if window is None \
        else jax.named_scope(WINDOW_SCOPE)


def _ring_positions(pos, ring, block_size):
    """``[S, ring * block_size]``: the position whose row each place of a
    slot's gathered ring holds, for slots writing at ``pos`` ``[S]`` —
    column ``c`` holds the newest logical block ``b <= pos // block_size``
    with ``b % ring == c``. Negative: no block of the slot came there
    yet."""
    newest = (pos // block_size)[:, None]
    col = jnp.arange(ring)[None, :]
    block = newest - (newest - col) % ring                    # [S, ring]
    return (block[:, :, None] * block_size
            + jnp.arange(block_size)).reshape(pos.shape[0], -1)


def pool_pages(cache, layer, tables):
    """The pages `tables` names ([..., MB] block ids) of one layer of a
    pool ``[num_blocks, layers, block_size, W]``, as ``[..., MB, bs, W]``.
    ONE gather indexed by (block, layer), so that it reads the table's
    ``MB`` pages out of the pool where it lies. The other spelling,
    ``cache[:, layer][tables]``, gives the same values, but XLA:TPU does
    not fold the slice into the gather: it first copies the layer's share
    of the WHOLE pool (``num_blocks`` pages) and gathers from the copy —
    for K and for V in every layer of every prefill, whatever the prompt's
    length (chat: 48 copies of 67 MB, 4.2 ms of a 7 ms prefill; PERF.md,
    PR 42). Every op that reads pages outside a Pallas kernel takes them
    through here."""
    return cache[tables, layer]


def _gather_pages(cache, layer, tables, n_head):
    """The pages `tables` names ([..., MB] block ids) of one layer, as
    ``[..., MB*bs, H, dh]`` in logical position order. The index is (block,
    layer) in one gather (`pool_pages`): a slice of the layer first costs
    a copy of its ``num_blocks`` pages to read ``MB`` of them."""
    g = pool_pages(cache, layer, tables)        # [..., MB, bs, H*dh]
    lead = tables.shape[:-1]
    return g.reshape(lead + (-1, n_head, g.shape[-1] // n_head))


def _gather_heads(cache, layer, tables, n_head):
    """`_gather_pages` with heads ahead of positions: the dense
    ``[..., H, MB*bs, dh]`` K or V of a slot."""
    return jnp.moveaxis(_gather_pages(cache, layer, tables, n_head), -2, -3)


@register_op('kv_cache_prefill_paged', share_lod=False)
def _kv_cache_prefill_paged(ctx, op):
    """Cache[table[(P+t)//bs], layer, :, (P+t)%bs, :] = New[0, :, t, :] for
    suffix rows t < Length; rows at or past the real suffix length are
    REDIRECTED to the trash block (a slot owns no span of its own, so
    pad garbage must never land in a real block). With ``window`` the
    table is the slot's ring in a window layer's pool, and of the suffix
    only the ``window - 1`` last real rows are written: the keys a query
    behind the suffix can still see, which a ring of
    `models.transformer.window_ring` blocks holds without one landing on
    another."""
    cache = ctx.in1(op, 'Cache')                # [NB, Ln, bs, H*dh]
    new = ctx.in1(op, 'New')                    # [1, H, T, dh]
    table = ctx.in1(op, 'BlockTable').reshape(-1).astype(jnp.int32)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)  # [T]
    length = ctx.in1(op, 'Length').reshape(-1).astype(jnp.int32)
    layer = int(op.attr('layer'))
    bs = int(op.attr('block_size'))
    window = op.attr('window', None)
    rows = jnp.transpose(new[0], (1, 0, 2)).astype(cache.dtype)  # [T,H,dh]
    rows = rows.reshape(rows.shape[0], -1)                       # [T,H*dh]
    blk, off = _block_of(table, pos, bs, ring=window is not None)
    real = jnp.arange(rows.shape[0]) < length[0]
    if window is not None:
        real &= pos > pos[0] + length[0] - int(window)
    blk = jnp.where(real, blk, 0)
    off = jnp.where(real, off, 0)
    out = cache.at[blk, layer, off, :].set(rows)
    ctx.out(op, 'Out', out)


@register_op('kv_cache_update_paged', share_lod=False)
def _kv_cache_update_paged(ctx, op):
    """Cache[tables[s][Positions[s]//bs], layer, :, Positions[s]%bs, :]
    = New[s] for every slot s. Idle slots feed position 0 against an
    all-zero table row, so their garbage row lands in the trash block.
    An optional per-slot ``Valid`` input ([S] or [S, 1]; nonzero = keep)
    redirects invalid rows to the trash block explicitly — the drafter's
    unrolled steps use it for positions at or past ``max_len``, where
    the clipped table lookup would otherwise target a LIVE block.
    ``ring``: the tables are the slots' rings in a window layer's pool
    (`_block_of`), and the row lands on the oldest block's."""
    cache = ctx.in1(op, 'Cache')                # [NB, Ln, bs, H*dh]
    new = ctx.in1(op, 'New')                    # [S, H, dh]
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)  # [S, MB]
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)
    valid = ctx.in1(op, 'Valid')                # optional [S]/[S, 1]
    layer = int(op.attr('layer'))
    bs = int(op.attr('block_size'))
    idx = (pos // bs) % tables.shape[1] if op.attr('ring', False) \
        else jnp.clip(pos // bs, 0, tables.shape[1] - 1)
    blk = jnp.take_along_axis(tables, idx[:, None], axis=1)[:, 0]
    off = (pos % bs).astype(jnp.int32)
    if valid is not None:
        keep = valid.reshape(-1) != 0
        blk = jnp.where(keep, blk, 0)
        off = jnp.where(keep, off, 0)
    rows = new.reshape(new.shape[0], -1).astype(cache.dtype)  # [S, H*dh]
    out = cache.at[blk, layer, off, :].set(rows)
    ctx.out(op, 'Out', out)


@register_op('kv_cache_update_span_paged', share_lod=False)
def _kv_cache_update_span_paged(ctx, op):
    """Wide decode-step write: every slot deposits W rows —
    Cache[tables[s][Positions[s,t]//bs], layer, :, Positions[s,t]%bs, :]
    = New[s, :, t, :] for t < W. Rows with ``Valid[s, t] == 0`` (idle
    slots, positions past max_len or past the slot's allocated blocks)
    are redirected to the trash block: a speculative row may later be
    rolled back, but it must never be able to touch a live block the
    slot doesn't own."""
    cache = ctx.in1(op, 'Cache')                # [NB, Ln, bs, H*dh]
    new = ctx.in1(op, 'New')                    # [S, H, W, dh]
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)  # [S, MB]
    pos = ctx.in1(op, 'Positions').astype(jnp.int32)       # [S, W]
    valid = ctx.in1(op, 'Valid')                # [S, W]
    layer = int(op.attr('layer'))
    bs = int(op.attr('block_size'))
    idx = jnp.clip(pos // bs, 0, tables.shape[1] - 1)
    blk = jnp.take_along_axis(tables, idx, axis=1)         # [S, W]
    off = (pos % bs).astype(jnp.int32)
    keep = valid.astype(jnp.int32) != 0
    blk = jnp.where(keep, blk, 0)
    off = jnp.where(keep, off, 0)
    rows = jnp.transpose(new, (0, 2, 1, 3)).astype(cache.dtype)  # [S,W,H,dh]
    S, W = pos.shape
    out = cache.at[blk.reshape(-1), layer, off.reshape(-1), :].set(
        rows.reshape(S * W, -1))
    ctx.out(op, 'Out', out)


@register_op('kv_verify_attention_paged', share_lod=False)
def _kv_verify_attention_paged(ctx, op):
    """W-query attention per slot over its block-table-gathered K/V:
    query row (s, t) sits at global position Positions[s, t] and attends
    every cached position <= Positions[s, t] — the slot's accepted
    history plus the verify window's own rows at or before t (the span
    write above deposited them). Per-row masking makes each row's
    output IDENTICAL to what the single-query decode attention would
    compute at that position, which is the bitwise foundation of
    speculative acceptance; masked (stale / trash / rolled-back) rows
    contribute exact 0."""
    q = ctx.in1(op, 'Q')                        # [S, H, W, dh]
    kc = ctx.in1(op, 'KCache')                  # [NB, Ln, bs, H*dh]
    vc = ctx.in1(op, 'VCache')
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)  # [S, MB]
    pos = ctx.in1(op, 'Positions')              # [S, W]
    layer = int(op.attr('layer'))
    scale = op.attr('scale', 1.0)
    bs = int(op.attr('block_size'))
    MB = tables.shape[1]
    k = _gather_heads(kc, layer, tables, q.shape[1])   # [S, H, MB*bs, dh]
    v = _gather_heads(vc, layer, tables, q.shape[1])
    scores = jnp.einsum('shtd,shmd->shtm', q, k,
                        preferred_element_type=jnp.float32) * scale
    m = jnp.arange(MB * bs)[None, None, None, :] <= \
        pos[:, None, :, None]                   # [S, 1, W, M]
    scores = jnp.where(m, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(m, w, 0.0)
    ctx.out(op, 'Out',
            jnp.einsum('shtm,shmd->shtd', w.astype(v.dtype), v))


@register_op('kv_decode_attention_paged', share_lod=False)
def _kv_decode_attention_paged(ctx, op):
    """One-query attention per slot over the pages its BLOCK TABLE names,
    masked to each slot's positions 0..Positions[s] (inclusive: the
    step's own token was just deposited there by kv_cache_update_paged):
    masked (stale / trash / shared-beyond-prefix) rows contribute exact
    0. ``pallas`` / ``interpret``: the kernel of
    ops/paged_decode_attention.py, which reads pages 0..Positions[s]//bs
    of each slot in place. ``xla``: the table-wide gather, einsums on
    the gathered ``[S, MB*bs, H, dh]``. ``off``: that gather moved to
    ``[S, H, MB*bs, dh]``, heads ahead of positions (the tests'
    reference). Pools of fewer K/V heads than Q has (grouped queries):
    query head h reads K/V head ``h // (H // Hkv)``; the kernel copies a
    page once for its heads' queries and takes its MXU body (counted in
    ``paged_decode_attention_form_total{form=mxu|vpu}``, once a call site
    lowered to the kernel), and ``xla`` / ``off`` are ONE gather
    formulation over the ``Hkv`` gathered heads, none repeated. A
    >1-device mesh has no kernel here (the pool is not
    sharded): it takes ``xla``. ``window`` (a window layer's call): the
    tables are the slots' rings, the keys seen are ``Positions[s] - window
    + 1 .. Positions[s]``; the kernel starts at the page of the first of
    them under an operation name of its own, and the gather takes the
    whole ring and masks by the position each place holds
    (`_ring_positions`). ``trace_scope`` (a looped model's pass): the
    kernel's operation name ends in it, ``paged_decode_attention_loop_
    pass_<t>``, so that a device trace's per-name sums tell the passes
    apart."""
    from . import kernel_tier, paged_decode_attention as pda
    from ..parallel.api import get_active_mesh
    q = ctx.in1(op, 'Q')                        # [S, H, dh]
    kc = ctx.in1(op, 'KCache')                  # [NB, Ln, bs, Hkv*dh]
    vc = ctx.in1(op, 'VCache')
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)  # [S, MB]
    pos = ctx.in1(op, 'Positions').reshape(-1)  # [S]
    bs = int(op.attr('block_size'))
    window = op.attr('window', None)
    H, dh = q.shape[1], q.shape[2]
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    impl = kernel_tier.dispatch(
        'kv_decode_attention_paged',
        pallas_ok=pda.shapes_ok(H, dh, bs, kc.shape[3] // dh) and not meshed,
        mesh=mesh)
    with _window_scope(window):
        ctx.out(op, 'Out', _decode_attention(
            impl, q, kc, vc, tables, pos, int(op.attr('layer')),
            op.attr('scale', 1.0), bs, window, op.attr('trace_scope')))


def _decode_attention(impl, q, kc, vc, tables, pos, layer, scale, bs,
                      window, part=None):
    """`kv_decode_attention_paged` under the tier `impl`; ``window`` None
    for a layer that sees every key; ``part`` the op's ``trace_scope`` (a
    looped model's pass), which the kernel's operation name then ends
    in."""
    from . import paged_decode_attention as pda
    from .. import monitor
    MB = tables.shape[1]
    H, dh = q.shape[1], q.shape[2]
    Hkv = kc.shape[3] // dh
    if impl in ('pallas', 'interpret'):
        # which of the kernel's two bodies this call site lowered to: the
        # head counts decide, at trace time
        monitor.inc('paged_decode_attention_form_total',
                    labels={'form': pda.form(H, Hkv)})
        return pda.paged_decode_attention(
            q, kc, vc, tables, pos, jnp.int32(layer), scale=float(scale),
            interpret=impl == 'interpret', attention_span=window,
            part=part)
    if window is None:
        m = jnp.arange(MB * bs)[None, None, :] <= pos[:, None, None]
    else:
        at = _ring_positions(pos.astype(jnp.int32), MB, bs)[:, None, :]
        m = (at >= 0) & (at <= pos[:, None, None]) \
            & (at > pos[:, None, None] - window)
    if Hkv != H:
        # grouped queries: the H // Hkv query heads of a K/V head against
        # its gathered pages, which are not repeated
        k = _gather_pages(kc, layer, tables, Hkv)           # [S, M, Hkv, dh]
        v = _gather_pages(vc, layer, tables, Hkv)
        qg = q.reshape(q.shape[0], Hkv, H // Hkv, dh)
        scores = jnp.einsum('skgd,smkd->skgm', qg, k,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(m[:, None], scores, _NEG_INF)
        w = jnp.where(m[:, None], jax.nn.softmax(scores, axis=-1), 0.0)
        return jnp.einsum('skgm,smkd->skgd', w.astype(v.dtype),
                          v).reshape(q.shape)
    # xla keeps the gathered [S, M, H, dh]; off moves it to
    # [S, H, M, dh]
    gather, qk, wv = \
        (_gather_pages, 'shd,smhd->shm', 'shm,smhd->shd') if impl == 'xla' \
        else (_gather_heads, 'shd,shmd->shm', 'shm,shmd->shd')
    k = gather(kc, layer, tables, H)
    v = gather(vc, layer, tables, H)
    scores = jnp.einsum(qk, q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(m, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(m, w, 0.0)
    return jnp.einsum(wv, w.astype(v.dtype), v)


def _prefix_attention_scores(q, k, v, at, pos, scale, window):
    """`kv_prefix_attention` as plain XLA (the `off` / `xla` tier, and the
    tests' reference of the kernel): q ``[H, T, dh]`` at positions ``pos
    [T]`` against k, v ``[Hkv, M, dh]``, key ``i`` at position ``at[i]``.
    The scores of every head stand whole in HBM, ``[H, T, M]`` float32
    twice over (K-EXAONE's 64 heads x 512 rows x 5120 keys: 1.34 GB, were
    such a call to come here: on the chip it is the kernel's, whose
    `shapes_ok` takes every call of 64 MB or more that tiles)."""
    H, T, dh = q.shape
    Hkv = k.shape[0]
    m = at[None, :] <= pos[:, None]                        # [T, M]
    if window is not None:
        m &= (at[None, :] >= 0) & (at[None, :] > pos[:, None] - window)
    if Hkv != H:
        # grouped queries: a K/V head's H // Hkv query heads are rows
        # of ONE matmul against it; the gathered keys are not repeated
        qg = q.reshape(Hkv, (H // Hkv) * T, dh)
        mg = jnp.tile(m, (H // Hkv, 1))[None]
    else:
        qg, mg = q, m[None]
    scores = jnp.einsum('htd,hmd->htm', qg, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mg, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(mg, w, 0.0)
    return jnp.einsum('htm,hmd->htd', w.astype(v.dtype),
                      v).reshape(H, T, dh)


@register_op('kv_prefix_attention', share_lod=False)
def _kv_prefix_attention(ctx, op):
    """Multi-query causal attention of one slot's prefill SUFFIX against
    its block-table cache: query row t sits at global position
    Positions[t] and attends every cached position <= Positions[t] —
    the shared prefix (cached by an earlier request) plus the suffix
    rows the surrounding program just deposited. With no shared prefix
    (Positions starting at 0) this is exactly the causal prefill
    attention, computed from the cache instead of a local K/V copy.

    ``window`` (a window layer's call; the table is the slot's ring):
    query row t sees key j iff ``0 <= Positions[t] - j < window``. The
    ring cannot hold a suffix, so the suffix's own ``K`` and ``V`` ([1,
    Hkv, T, dh], the rows behind ``Length`` masked) are inputs and the
    cache gives the ``window - 1`` rows before Positions[0] alone — the
    program writes the suffix behind this op.

    Two lowerings behind ``kernel_tier.dispatch``, for both calls.
    ``pallas`` / ``interpret``: the blockwise kernel of
    ops/prefix_attention.py — the scores stay on the chip, and the keys
    past the last query's position are neither read nor computed. ``off``
    / ``xla`` (the CPU, a >1-device mesh, shapes the kernel does not tile,
    calls whose scores are too few bytes to pay for it —
    `prefix_attention.shapes_ok`; the tests' reference):
    `_prefix_attention_scores`, whose scores stand in HBM. Either way a
    masked key has weight exactly 0."""
    from . import kernel_tier, prefix_attention as pfa
    from ..parallel.api import get_active_mesh
    q = ctx.in1(op, 'Q')                        # [1, H, T, dh]
    kc = ctx.in1(op, 'KCache')                  # [NB, Ln, bs, Hkv*dh]
    vc = ctx.in1(op, 'VCache')
    table = ctx.in1(op, 'BlockTable').reshape(-1).astype(jnp.int32)
    pos = ctx.in1(op, 'Positions').reshape(-1)  # [T] global query positions
    layer = int(op.attr('layer'))
    scale = op.attr('scale', 1.0)
    bs = int(op.attr('block_size'))
    window = op.attr('window', None)
    MB = table.shape[0]
    H, T, dh = q.shape[1:]
    Hkv = kc.shape[3] // dh
    if window is None:
        k = _gather_heads(kc, layer, table, Hkv)       # [Hkv, MB*bs, dh]
        v = _gather_heads(vc, layer, table, Hkv)
        at = jnp.arange(MB * bs)               # the position a key holds
    else:
        length = ctx.in1(op, 'Length').reshape(-1)[0]
        before = pos[0] - (window - 1) + jnp.arange(window - 1)
        blk, off = _block_of(table, jnp.maximum(before, 0), bs, ring=True)

        def keys(cache, own):
            held = cache[blk, layer, off].reshape(window - 1, Hkv, dh)
            return jnp.concatenate([jnp.moveaxis(held, 0, 1),
                                    own[0].astype(cache.dtype)], axis=1)
        k = keys(kc, ctx.in1(op, 'K'))                 # [Hkv, W-1+T, dh]
        v = keys(vc, ctx.in1(op, 'V'))
        # -1: no key (before position 0, behind the suffix's real rows)
        at = jnp.concatenate([before, jnp.where(jnp.arange(T) < length,
                                                pos, -1)])

    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    impl = kernel_tier.dispatch(
        'kv_prefix_attention',
        pallas_ok=pfa.shapes_ok(H, Hkv, T, dh, at.shape[0]) and not meshed,
        mesh=mesh)
    with _window_scope(window):
        if impl in ('pallas', 'interpret'):
            out = pfa.prefix_attention(
                q[0], k, v, at, pos, scale=float(scale), window=window,
                interpret=impl == 'interpret')
        else:
            out = _prefix_attention_scores(q[0], k, v, at, pos, scale,
                                           window)
    ctx.out(op, 'Out', out.reshape(1, H, T, dh))           # [1, H, T, dh]


@register_op('sample_next_token', share_lod=False)
def _sample_next_token(ctx, op):
    """Per-row temperature / top-k / top-p sampling driven by a host-fed
    uniform U[s] in [0, 1): sort the distribution descending, intersect
    the top-k and top-p (nucleus) keep sets, renormalize, inverse-CDF
    sample with U. Rows with Temp <= 0 return the bitwise argmax (the
    greedy default); TopK <= 0 disables top-k, TopP <= 0 or >= 1
    disables nucleus. Deterministic given U — the engine owns one host
    PRNG stream per request, so co-resident slots sample independently
    and a (seed, prompt) pair replays exactly.

    The op branches ON THE DEVICE on what it can see in its input
    (``lax.cond`` on ``any(Temp > 0)``; the host does not choose and
    there is one program): a step whose rows are all greedy takes the
    argmax and nothing else — no sort, no softmax, no pass over the
    vocabulary but the one. A step with at least one sampled row runs
    the sampled branch for every row and its greedy rows still take the
    argmax through the final ``where``. Neither branch gathers ``[S, V]``:
    the one sort carries the negated logits along with their indices,
    so the sorted distribution is its first result (negation is exact)
    and the only gather left reads the drawn token out of the order."""
    logits = ctx.in1(op, 'Logits').astype(jnp.float32)     # [S, V]
    temp = ctx.in1(op, 'Temp').reshape(-1)                 # [S]
    topk = ctx.in1(op, 'TopK').reshape(-1).astype(jnp.int32)
    topp = ctx.in1(op, 'TopP').reshape(-1)
    u = ctx.in1(op, 'U').reshape(-1)
    V = logits.shape[1]

    def greedy_step():
        return jnp.argmax(logits, axis=1).astype(jnp.int64)

    def sampled_step():
        t = jnp.where(temp > 0, temp, 1.0)[:, None]
        # stable, so ties keep the vocabulary's order: jnp.argsort(-logits)
        # is this very sort with its first result thrown away
        neg_sorted, order = lax.sort_key_val(
            -logits, lax.broadcasted_iota(jnp.int32, logits.shape, 1),
            dimension=1, is_stable=True)
        probs = jax.nn.softmax(-neg_sorted / t, axis=1)
        ranks = jnp.arange(V)[None, :]
        k_eff = jnp.where(topk > 0, topk, V)[:, None]
        p_on = (topp > 0) & (topp < 1.0)
        p_eff = jnp.where(p_on, topp, 1.0)[:, None]
        cum = jnp.cumsum(probs, axis=1)
        # nucleus keeps the smallest head with mass >= p (the first token
        # always survives); top-k keeps ranks < k; the sets intersect
        keep = (ranks < k_eff) & ((cum - probs < p_eff) | (ranks == 0))
        masked = jnp.where(keep, probs, 0.0)
        mcum = jnp.cumsum(masked, axis=1)
        total = mcum[:, -1:]
        # smallest kept index with cumulative mass > u * total
        j = jnp.sum(mcum <= u[:, None] * total, axis=1)
        j = jnp.minimum(j, jnp.sum(keep, axis=1) - 1)
        sampled = jnp.take_along_axis(order, j[:, None], axis=1)[:, 0]
        return jnp.where(temp > 0, sampled.astype(jnp.int64), greedy_step())

    ctx.out(op, 'Out', lax.cond(jnp.any(temp > 0), sampled_step, greedy_step))
