"""kv_prefix_attention (ops/kv_cache_ops.py) across its tiers: the blockwise
Pallas kernel of ops/prefix_attention.py, through the interpreter, against
the plain composition (`_prefix_attention_scores`, the `off` tier, whose
scores stand whole) — the head groupings and head sizes of the served
models; a prompt's first chunk, a later one, a suffix behind a shared
prefix; a table that is no whole number of key tiles; a bucket's pad rows;
a repeated page and the trash block; keys past the chunk's end that hold
garbage; a window layer's call; and what falls to the composition.

The op is lowered directly (a stand-in ctx/op pair around the registered
lowering, test_paged_decode_attention.py's): the tiers differ only inside
it.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.core.registry import get_op
from paddle_tpu.ops import prefix_attention as pfa

from test_paged_decode_attention import _Ctx, _Op, _pools

BS = 16
LAYER = 1


def _attend(tier, monkeypatch, q, kc, vc, table, pos, lands_on=None,
            any_size=True, **more):
    """The op under `tier`; its one dispatch must land on `lands_on` (the
    tier itself unless a shape makes it fall). `more`: a window layer's
    ``window`` attribute and ``K``, ``V``, ``Length`` inputs. The kernel
    takes a call of any size here (``any_size``): the cases are small, for
    the interpreter's sake, and the size it starts from is a matter of
    speed."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    if any_size:
        monkeypatch.setattr(pfa, '_MIN_SCORES_BYTES', 0)
    before = monitor.counters()
    attrs = {k: more.pop(k) for k in ('window',) if k in more}
    ctx = _Ctx(Q=jnp.asarray(q)[None], KCache=jnp.asarray(kc),
               VCache=jnp.asarray(vc), BlockTable=jnp.asarray(table)[None],
               Positions=jnp.asarray(pos)[None],
               **{k: jnp.asarray(x) for k, x in more.items()})
    get_op('kv_prefix_attention').lower(ctx, _Op(
        layer=LAYER, scale=q.shape[-1] ** -0.5, block_size=BS, **attrs))
    assert monitor.counter_delta(before) == {
        'fused_kernel_dispatch_total{impl=%s,mesh=1,op=kv_prefix_attention}'
        % (lands_on or tier): 1}
    return np.asarray(ctx.outs['Out'])[0]


def _case(seed, H, Hkv, dh, T, MB, nb=None):
    """Queries, pools of `nb` blocks (two layers) and a table of `MB`
    distinct blocks, none the trash block."""
    rng = np.random.RandomState(seed)
    nb = nb or MB + 8
    kc, vc = _pools(rng, nb, 2, BS, Hkv * dh)
    q = rng.randn(H, T, dh).astype('float32')
    table = (1 + rng.permutation(nb - 1)[:MB]).astype('int32')
    return q, kc, vc, table


def _close(got, want):
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# (H, Hkv, dh): fairseq-dense's heads; OLMoE's; LFM2's 4 queries a K/V head;
# K-EXAONE's 8 at heads of 128; Jamba2's 20 queries on ONE K/V head. At
# (start, T, MB): a prompt's first chunk (rows 0..127 of a table of 640 keys:
# the second key tile is never read), a later chunk from 512 (two query
# tiles at 8 queries a head), a suffix behind a shared prefix of 4 096.
HEADS = {'g1-dh64': (4, 4, 64), 'g1-dh128': (2, 2, 128),
         'g4-dh64': (8, 2, 64), 'g8-dh128': (16, 2, 128),
         'g20-one-kv-head': (20, 1, 128)}
CHUNKS = {'first-chunk': (0, 128, 40), 'chunk-from-512': (512, 256, 64),
          'suffix-behind-4096': (4096, 64, 264)}


@pytest.mark.parametrize('chunk', CHUNKS)
@pytest.mark.parametrize('heads', HEADS)
def test_interpret_tier_matches_the_plain_composition(monkeypatch, heads,
                                                      chunk):
    H, Hkv, dh = HEADS[heads]
    start, T, MB = CHUNKS[chunk]
    q, kc, vc, table = _case(H + T, H, Hkv, dh, T, MB)
    pos = start + np.arange(T)
    args = (q, kc, vc, table, pos)
    _close(_attend('interpret', monkeypatch, *args),
           _attend('off', monkeypatch, *args))


def test_a_table_of_no_whole_number_of_key_tiles(monkeypatch):
    """doc's table: 66 pages = 1 056 keys, two tiles of 512 and 32 keys of
    a third; the chunk ends on the table's last key."""
    q, kc, vc, table = _case(7, 4, 4, 64, 128, 66)
    pos = 1056 - 128 + np.arange(128)
    args = (q, kc, vc, table, pos)
    _close(_attend('interpret', monkeypatch, *args),
           _attend('off', monkeypatch, *args))


def test_a_buckets_pad_rows_and_a_tables_filler(monkeypatch):
    """A suffix of 37 rows in a bucket of 64 behind 100 cached positions,
    as the engine feeds it: the pad rows' positions run on (clipped at the
    context's end), the table's entries past the prompt are the trash block,
    and a page is shared with itself (a repeated entry)."""
    q, kc, vc, table = _case(11, 8, 2, 64, 64, 10)
    table[3] = table[1]
    table[9:] = 0
    pos = np.clip(100 + np.arange(64), 0, 10 * BS - 1)
    args = (q, kc, vc, table, pos)
    _close(_attend('interpret', monkeypatch, *args),
           _attend('off', monkeypatch, *args))


@pytest.mark.parametrize('planted', [1e30, np.nan], ids=['1e30', 'nan'])
@pytest.mark.parametrize('heads', ['g1-dh64', 'g8-dh128'])
def test_garbage_past_the_chunks_end_changes_no_bit(monkeypatch, heads,
                                                    planted):
    """Rows 512..639 of a table of 1 280 keys. The pages past the chunk's
    end — the rest of the second key tile, which is read and masked, and the
    third, which is not read — hold an earlier tenant's rows: whatever
    stands in them, in K or in V, the output is bit for bit the same."""
    H, Hkv, dh = HEADS[heads]
    q, kc, vc, table = _case(13, H, Hkv, dh, 128, 80)
    pos = 512 + np.arange(128)
    clean = _attend('interpret', monkeypatch, q, kc, vc, table, pos)
    kd, vd = kc.copy(), vc.copy()
    kd[table[40:], LAYER] = planted
    vd[table[40:], LAYER] = planted
    np.testing.assert_array_equal(
        _attend('interpret', monkeypatch, q, kd, vd, table, pos), clean)
    assert np.isfinite(clean).all()
    if np.isfinite(planted):
        # the plain composition's contract is the same where 0 * x is 0
        _close(_attend('off', monkeypatch, q, kd, vd, table, pos), clean)


def test_a_slot_is_independent_of_the_tables_later_entries(monkeypatch):
    """The same rows against two tables that agree on the pages up to the
    chunk's end and on none behind it: the same bits."""
    q, kc, vc, table = _case(17, 8, 2, 64, 64, 48, nb=120)
    pos = 300 + np.arange(64)
    other = table.copy()
    other[23:] = np.setdiff1d(np.arange(1, 120), table)[:48 - 23]
    np.testing.assert_array_equal(
        _attend('interpret', monkeypatch, q, kc, vc, table, pos),
        _attend('interpret', monkeypatch, q, kc, vc, other, pos))


@pytest.mark.parametrize('start,T,length', [(0, 64, 64), (40, 64, 50),
                                            (1000, 512, 509)],
                         ids=['first-chunk', 'inside-the-window',
                              'k-exaone-b512'])
def test_a_window_layers_call_takes_the_same_kernel(monkeypatch, start, T,
                                                    length):
    """A window of 128 keys through a ring of 9 pages: the 127 rows the
    ring holds from before the chunk (those before position 0 are no key)
    beside the chunk's own K and V (those behind `Length` are no key)."""
    H, Hkv, dh, W, ring = 8, 2, 64, 128, 9
    rng = np.random.RandomState(T + start)
    kc, vc = _pools(rng, 24, 2, BS, Hkv * dh)
    q = rng.randn(H, T, dh).astype('float32')
    own = {n: rng.randn(1, Hkv, T, dh).astype('float32') for n in 'KV'}
    table = (1 + rng.permutation(23)[:ring]).astype('int32')
    args = (q, kc, vc, table, start + np.arange(T))
    more = dict(own, window=W, Length=np.array([[length]], 'int64'))
    _close(_attend('interpret', monkeypatch, *args, **more),
           _attend('off', monkeypatch, *args, **more))


def test_shapes_the_kernel_refuses_fall_to_the_composition(monkeypatch):
    # five rows fill no sublanes; heads of 24 no vreg; 3 queries on 2 heads
    big = 1 << 20
    assert not pfa.shapes_ok(4, 4, 5, 64, big)
    assert not pfa.shapes_ok(4, 4, 64, 24, big)
    assert not pfa.shapes_ok(3, 2, 64, 64, big)
    assert pfa.shapes_ok(4, 4, 64, 64, big)
    rng = np.random.RandomState(0)
    kc, vc = _pools(rng, 6, 2, BS, 2 * 24)
    q = rng.randn(2, 8, 24).astype('float32')
    args = (q, kc, vc, np.array([1, 2, 0], 'int32'), 20 + np.arange(8))
    _close(_attend('interpret', monkeypatch, *args, lands_on='xla'),
           _attend('off', monkeypatch, *args))


# (H, Hkv, T, dh, keys) of the serve cells' calls, and whether the kernel
# takes them: the scores the composition would form are 64 MB at least
CELLS = {
    'kexaone-global-b128': ((64, 8, 128, 128, 5120), True),
    'kexaone-window-b512': ((64, 8, 512, 128, 639), True),
    'kexaone-window-b256': ((64, 8, 256, 128, 383), False),
    'doc-b768': ((32, 32, 768, 64, 1056), True),
    'lfm2-b128': ((32, 8, 128, 64, 5120), True),
    'jamba2-b512': ((20, 1, 512, 128, 3072), True),
    'jamba2-b256': ((20, 1, 256, 128, 3072), False),
    'olmoe-b768': ((16, 16, 768, 128, 1280), False),
    'chat-b512': ((16, 16, 512, 64, 768), False),
    'chat-b64': ((16, 16, 64, 64, 768), False)}


@pytest.mark.parametrize('cell', CELLS)
def test_a_small_call_stays_with_the_composition(cell):
    shape, taken = CELLS[cell]
    assert pfa.shapes_ok(*shape) is taken


def test_a_small_call_lands_on_xla(monkeypatch):
    q, kc, vc, table = _case(3, 4, 4, 64, 64, 10)
    args = (q, kc, vc, table, np.arange(64))
    _close(_attend('interpret', monkeypatch, *args, lands_on='xla',
                   any_size=False),
           _attend('off', monkeypatch, *args))


@pytest.mark.parametrize('T,G,tq', [(512, 8, 128), (128, 8, 128),
                                    (1024, 1, 1024), (768, 1, 768),
                                    (512, 4, 256), (512, 20, 32),
                                    (64, 1, 64), (8, 256, 8)])
def test_query_tiles_divide_the_bucket(T, G, tq):
    """A tile holds the rows of all the queries of one K/V head, `_ROWS`
    at most (unless eight rows of each already pass it)."""
    assert pfa.query_tile(T, G) == tq


# (H, Hkv, T, dh, keys, window): doc's widest bucket, and K-EXAONE's window
# layers' call at theirs
PARENT_KERNELS = {'doc-b1024': (32, 32, 1024, 64, 1056, None),
                  'kexaone-window-b512': (64, 8, 512, 128, 639, 128)}


def traced_kernel(H, Hkv, T, dh, M, window):
    """`prefix_attention` traced at a shape, as text: the jaxpr of the
    whole call — what XLA computes before the kernel, the `pallas_call`
    with its grid, block shapes, compiler parameters, name and BODY —
    and each operand's index map behind it."""
    import functools
    import jax

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    jaxpr = jax.make_jaxpr(functools.partial(
        pfa.prefix_attention, scale=dh ** -0.5, window=window))(
        sds((H, T, dh)), sds((Hkv, M, dh)), sds((Hkv, M, dh)),
        sds((M,), jnp.int32), sds((T,), jnp.int32))
    inner, = (e for e in jaxpr.eqns if e.primitive.name == 'jit')
    call, = (e for e in inner.params['jaxpr'].eqns
             if e.primitive.name == 'pallas_call')
    maps = [str(b.index_map_jaxpr)
            for b in call.params['grid_mapping'].block_mappings]
    return '\n'.join([str(jaxpr)] + maps)


@pytest.mark.parametrize('shape', PARENT_KERNELS)
def test_the_kernel_of_equal_widths_is_the_parents(shape):
    """A call with keys and values of one width and no shared key part
    traces to the kernel it was before the latent attention's widths came
    (fixtures/prefix_attention_kernel_parent_pr61.json, recorded on PR
    60's tree): the cells that run `kv_prefix_attention` keep their
    Mosaic programs, and their compile-cache keys."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'fixtures',
                           'prefix_attention_kernel_parent_pr61.json')) as f:
        want = json.load(f)['kernels'][shape]
    assert traced_kernel(*PARENT_KERNELS[shape]) == want
