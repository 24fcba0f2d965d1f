"""Latent attention (MLA, the DeepSeek-V2/V3 layer) against the paged
cache: two ops for a block whose cached state is ONE row a token a layer,
shared by all heads (models/transformer.py, LMConfig(attention='mla')).

The cached row is ``[c_kv | k_r]``: the normed latent (``kv_lora_rank``
lanes) and the rotated shared rotary key (``qk_rope_dim`` lanes), ``W``
lanes in all. It lies in the K pool of ops/kv_cache_ops.py, written by the
same ``kv_cache_update_paged`` / ``kv_cache_prefill_paged`` (a row is a
"head" of width ``W``); there is NO V pool. With ``W_uk [H, nope, R]`` and
``W_uv [H, R, v]`` the two halves of the published up-projection
(``kv_b_proj``: ``k_nope = c_kv W_uk^T``, ``v = c_kv W_uv`` per head):

    score(h, t) = (q_nope_h . k_nope_h,t + q_r_h . k_r,t) * scale
    out_h       = sum_t softmax(score)(h, t) v_h,t

Two forms of it, one result:

- ``mla_prefix_attention`` — EXPANDED, the prefill: the table's rows are
  gathered, ``k_nope`` and ``v`` rebuilt from their latents for every
  head, and the suffix's queries attend them causally. ``pallas`` /
  ``interpret``: the blockwise kernel of ops/prefix_attention.py at this
  layer's widths — a head's ``nope`` lanes against its own keys, its
  ``rope`` lanes against the one rotary key all heads share, values of
  ``v`` lanes — so no score stands in HBM and the key tiles past a query
  tile's last position are neither read nor computed. ``off`` / ``xla``:
  the composition below (queries in chunks, so the scores of a 2048-row
  bucket against a 2816-row table never stand whole), the CPU path and
  the kernel's parity oracle.
- ``mla_decode_attention_paged`` — ABSORBED, the decode step: ``q' =
  [q_nope W_uk | q_r]`` (``W`` lanes a head) scores against the cached
  rows as they lie, ``o_latent = sum p c_kv`` (``R`` lanes a head), then
  ``out = o_latent W_uv``. ``pallas`` / ``interpret``: the kernel of
  ops/mla_paged_decode_attention.py on the pool in place; ``off`` /
  ``xla``: the gather formulation below, the CPU path and the kernel's
  parity oracle.

A masked (stale / trash / other-tenant) position has weight exactly 0, as
in every cache op: a slot's output is bit-identical whatever else the
pool holds.
"""
import jax
import jax.numpy as jnp

from ..core.registry import register_op
from .kv_cache_ops import pool_pages

_NEG_INF = -1e30
# query rows of a prefill that attend at once in the plain composition (the
# scores are [H, rows, table positions] float32)
_QUERY_CHUNK = 256
# query rows a tile of the kernel: with one query head a K/V head, tiles of
# 512 rows skip more of the causal half than the kernel's own 1 024 at twice
# the K/V reads. Measured on the v5e at JoyAI's shape (32 heads of 128 + 64 |
# 128, 2 816 keys; tools/kernbench.py --cases mla_prefix_attention; PERF.md,
# PR 61), ms a call at 1 024 | 512 | 256 rows a tile: 512 rows 0.189 | 0.190
# | 0.225; 1 024 rows 0.441 | 0.380 | 0.476; 2 048 rows 1.304 | 1.188 | 1.587
_KERNEL_QUERY_ROWS = 512


def absorbed_decode_reference(q, pool, tables, pos, layer, scale, v_width):
    """The gather formulation of the absorbed decode: q ``[S, H, W]``,
    pool ``[NB, Ln, bs, W]``, tables ``[S, MB]``, pos ``[S]`` ->
    ``[S, H, v_width]``."""
    rows = pool_pages(pool, layer, tables)          # [S, MB, bs, W]
    rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])   # [S, M, W]
    scores = jnp.einsum('shw,smw->shm', q, rows,
                        preferred_element_type=jnp.float32) * scale
    m = jnp.arange(rows.shape[1])[None, None, :] <= pos[:, None, None]
    scores = jnp.where(m, scores, _NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(m, w, 0.0)
    return jnp.einsum('shm,smv->shv', w.astype(rows.dtype),
                      rows[..., :v_width])


@register_op('mla_decode_attention_paged', share_lod=False)
def _mla_decode_attention_paged(ctx, op):
    """Absorbed one-query latent attention per slot over the pages its
    block table names, masked to positions 0..Positions[s] (the step's own
    row was just deposited there). Q ``[S, H, nope + rope]`` (the rotary
    part rotated), Out ``[S, H, v]``."""
    from . import kernel_tier, mla_paged_decode_attention as kern
    from ..parallel.api import get_active_mesh
    q = ctx.in1(op, 'Q')                        # [S, H, nope + rope]
    pool = ctx.in1(op, 'Cache')                 # [NB, Ln, bs, W]
    w_uk = ctx.in1(op, 'UpK')                   # [H, nope, R]
    w_uv = ctx.in1(op, 'UpV')                   # [H, R, v]
    tables = ctx.in1(op, 'BlockTables').astype(jnp.int32)   # [S, MB]
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)
    layer = int(op.attr('layer'))
    scale = float(op.attr('scale', 1.0))
    nope, rank = w_uk.shape[1], w_uk.shape[2]
    S, H, bs = q.shape[0], q.shape[1], pool.shape[2]
    # [S, H, W], lane for lane the cached row: the latent, the rotary
    # key, zeros up to whole lane tiles
    fill = pool.shape[3] - rank - (q.shape[2] - nope)
    absorbed = jnp.concatenate(
        [jnp.einsum('shn,hnr->shr', q[..., :nope], w_uk), q[..., nope:],
         jnp.zeros((S, H, fill), q.dtype)], axis=-1)
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    impl = kernel_tier.dispatch(
        'mla_decode_attention_paged', mesh=mesh,
        pallas_ok=kern.shapes_ok(H, pool.shape[3], rank, bs)
        and not meshed)
    if impl in ('pallas', 'interpret'):
        latent = kern.mla_paged_decode_attention(
            absorbed, pool, tables, pos, jnp.int32(layer), scale=scale,
            v_width=rank, interpret=impl == 'interpret')
    else:
        latent = absorbed_decode_reference(absorbed, pool, tables, pos,
                                           layer, scale, rank)
    ctx.out(op, 'Out', jnp.einsum('shr,hrv->shv', latent, w_uv))


def _expanded_attention_scores(q, k_nope, k_rope, value, pos, scale):
    """`mla_prefix_attention` as plain XLA (the `off` / `xla` tier, and the
    tests' reference of the kernel): q ``[T, H, nope + rope]`` at positions
    ``pos [T]`` against k_nope ``[H, M, nope]``, k_rope ``[M, rope]`` and
    value ``[H, M, v]``, key ``i`` at position ``i``. The scores stand in
    HBM against the table's whole width, `_QUERY_CHUNK` rows at a time."""
    nope = k_nope.shape[2]
    key_at = jnp.arange(k_nope.shape[1])

    def attend(args):
        qc, pc = args                           # [C, H, nope + rope], [C]
        scores = (jnp.einsum('thn,hmn->htm', qc[..., :nope], k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum('thr,mr->htm', qc[..., nope:], k_rope,
                               preferred_element_type=jnp.float32)) * scale
        m = (key_at[None, :] <= pc[:, None])[None]            # [1, C, M]
        scores = jnp.where(m, scores, _NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        w = jnp.where(m, w, 0.0)
        return jnp.einsum('htm,hmv->thv', w.astype(value.dtype), value)

    T = q.shape[0]
    chunk = _QUERY_CHUNK if T % _QUERY_CHUNK == 0 else T
    out = jax.lax.map(attend, (q.reshape((T // chunk, chunk) + q.shape[1:]),
                               pos.reshape(T // chunk, chunk)))
    return out.reshape((T,) + out.shape[2:])


@register_op('mla_prefix_attention', share_lod=False)
def _mla_prefix_attention(ctx, op):
    """Expanded causal latent attention of one slot's prefill SUFFIX
    against its block-table cache: query row t sits at global position
    Positions[t] and attends every cached position <= Positions[t] — the
    shared prefix plus the suffix rows just deposited. Q ``[1, T, H, nope
    + rope]``, Out ``[1, T, H, v]``.

    ``k_nope`` and ``v`` are two einsums of the gathered latent rows either
    way; the tiers differ from the scores on. ``pallas`` / ``interpret``:
    the blockwise kernel of ops/prefix_attention.py (Mosaic name
    `mla_prefix_attention`), one query head a K/V head, the rotary key its
    shared key part — no score stands in HBM, and the key tiles past a
    query tile's last position are neither read nor computed. ``off`` /
    ``xla`` (the CPU, a >1-device mesh, what `prefix_attention.shapes_ok`
    refuses; the tests' reference): `_expanded_attention_scores`."""
    from . import kernel_tier, prefix_attention as pfa
    from ..parallel.api import get_active_mesh
    q = ctx.in1(op, 'Q')[0]                     # [T, H, nope + rope]
    pool = ctx.in1(op, 'Cache')                 # [NB, Ln, bs, W]
    w_uk = ctx.in1(op, 'UpK')                   # [H, nope, R]
    w_uv = ctx.in1(op, 'UpV')                   # [H, R, v]
    table = ctx.in1(op, 'BlockTable').reshape(-1).astype(jnp.int32)
    pos = ctx.in1(op, 'Positions').reshape(-1).astype(jnp.int32)   # [T]
    layer = int(op.attr('layer'))
    scale = float(op.attr('scale', 1.0))
    nope, rank = w_uk.shape[1], w_uk.shape[2]
    rope = q.shape[-1] - nope
    rows = pool_pages(pool, layer, table).reshape(-1, pool.shape[3])  # [M, W]
    # behind the rotary key the row is zeros up to whole lane tiles
    latent = rows[:, :rank]
    k_rope = rows[:, rank:rank + rope]
    k_nope = jnp.einsum('mr,hnr->hmn', latent, w_uk)          # [H, M, nope]
    value = jnp.einsum('mr,hrv->hmv', latent, w_uv)           # [H, M, v]
    (T, H), M = q.shape[:2], rows.shape[0]
    mesh = get_active_mesh()
    meshed = mesh is not None and mesh.size > 1
    impl = kernel_tier.dispatch(
        'mla_prefix_attention', mesh=mesh,
        pallas_ok=pfa.shapes_ok(H, H, T, nope, M, v_dim=value.shape[2],
                                shared_dim=rope) and not meshed)
    if impl in ('pallas', 'interpret'):
        out = pfa.prefix_attention(
            jnp.swapaxes(q, 0, 1), k_nope, value, jnp.arange(M), pos,
            k_rope, scale=scale, interpret=impl == 'interpret',
            name='mla_prefix_attention', rows=_KERNEL_QUERY_ROWS)
        out = jnp.swapaxes(out, 0, 1)
    else:
        out = _expanded_attention_scores(q, k_nope, k_rope, value, pos,
                                         scale)
    ctx.out(op, 'Out', out[None])
