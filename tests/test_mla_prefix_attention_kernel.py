"""mla_prefix_attention (ops/mla_ops.py) across its tiers: the blockwise
kernel of ops/prefix_attention.py at latent attention's widths — a head's
own key lanes beside ONE rotary key that all heads share, values narrower
than the keys — through the interpreter, against the plain composition
(`_expanded_attention_scores`, the `off` tier, whose scores stand in HBM):
a prompt's start, a suffix behind a shared prefix, the table's end; a table
that is no whole number of key tiles; a bucket's pad rows and the trash
block; garbage past the chunk's end; and what falls to the composition.

The op is lowered directly (test_olmoe_serving.py's stand-in ctx/op pair):
the tiers differ only inside it. Few heads and rows, for the interpreter's
sake; the widths of a head are the kernel's own.
"""
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.ops import prefix_attention as pfa

from test_olmoe_serving import lower

BS = 16
LAYER = 1
RANK = 64


def _attend(tier, monkeypatch, c, table, pos, lands_on=None, any_size=True,
            mesh='1'):
    """The op under `tier`; its one dispatch must land on `lands_on` (the
    tier itself unless a shape, the call's size or a mesh makes it fall)
    and count under `mesh`."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    if any_size:
        monkeypatch.setattr(pfa, '_MIN_SCORES_BYTES', 0)
    before = monitor.counters()
    out = lower('mla_prefix_attention',
                {'layer': LAYER, 'scale': c['q'].shape[-1] ** -0.5},
                Q=c['q'][None], Cache=c['pool'], UpK=c['w_uk'],
                UpV=c['w_uv'], Positions=np.asarray(pos)[None],
                BlockTable=np.asarray(table)[None])['Out']
    assert monitor.counter_delta(before) == {
        'fused_kernel_dispatch_total{impl=%s,mesh=%s,op=mla_prefix_attention}'
        % (lands_on or tier, mesh): 1}
    return np.asarray(out)[0]


def _case(seed, H, nope, rope, v, T, MB, nb=None):
    """Queries, a pool of `nb` blocks of latent rows (two layers; zeros
    behind the rotary lanes, up to whole lane tiles), the two halves of
    the up-projection, and a table of `MB` distinct blocks, none the trash
    block."""
    rng = np.random.RandomState(seed)
    nb = nb or MB + 8
    width = -(-(RANK + rope) // 128) * 128
    pool = np.zeros((nb, 2, BS, width), 'float32')
    pool[..., :RANK + rope] = rng.randn(nb, 2, BS, RANK + rope)
    c = dict(pool=pool, q=rng.randn(T, H, nope + rope).astype('float32'),
             w_uk=rng.randn(H, nope, RANK).astype('float32') * 0.2,
             w_uv=rng.randn(H, RANK, v).astype('float32') * 0.2)
    table = (1 + rng.permutation(nb - 1)[:MB]).astype('int32')
    return c, table


def _close(got, want):
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# (H, nope, rope, v): JoyAI's (DeepSeek-V3's) head, values still narrower,
# a rotary part of a whole vreg. At (start, T, MB): a prompt's start (rows
# 0..127 of a table of 640 keys: the second key tile is never read), a
# suffix behind a shared prefix of 1 024, the table's end, and a bucket of
# two query tiles (the op's tiles hold 512 rows) whose first skips the
# second key tile
WIDTHS = {'128+64|128': (4, 128, 64, 128), '128+64|64': (2, 128, 64, 64),
          '128+128|128': (2, 128, 128, 128)}
CHUNKS = {'prompt-start': (0, 128, 40), 'suffix-behind-1024': (1024, 64, 72),
          'table-end': (640 - 256, 256, 40), 'two-query-tiles': (0, 1024, 64)}


@pytest.mark.parametrize('chunk', CHUNKS)
@pytest.mark.parametrize('widths', WIDTHS)
def test_interpret_tier_matches_the_plain_composition(monkeypatch, widths,
                                                      chunk):
    start, T, MB = CHUNKS[chunk]
    c, table = _case(T + MB, *WIDTHS[widths], T, MB)
    pos = start + np.arange(T)
    got = _attend('interpret', monkeypatch, c, table, pos)
    assert got.shape == (T, WIDTHS[widths][0], WIDTHS[widths][3])
    _close(got, _attend('off', monkeypatch, c, table, pos))


def test_a_table_of_no_whole_number_of_key_tiles(monkeypatch):
    """JoyAI's table is 176 pages = 2 816 keys, five tiles of 512 and half
    a sixth; here 66 pages = 1 056 keys, two tiles and 32 keys of a third,
    the chunk ending on the table's last key."""
    c, table = _case(7, 4, 128, 64, 128, 128, 66)
    pos = 1056 - 128 + np.arange(128)
    _close(_attend('interpret', monkeypatch, c, table, pos),
           _attend('off', monkeypatch, c, table, pos))


def test_a_buckets_pad_rows_and_a_tables_filler(monkeypatch):
    """A suffix of 37 rows in a bucket of 64 behind 100 cached positions,
    as the engine feeds it: the pad rows' positions run on (clipped at the
    context's end), the table's entries past the prompt are the trash
    block, and a page is shared with itself (a repeated entry)."""
    c, table = _case(11, 4, 128, 64, 128, 64, 10)
    table[3] = table[1]
    table[9:] = 0
    pos = np.clip(100 + np.arange(64), 0, 10 * BS - 1)
    _close(_attend('interpret', monkeypatch, c, table, pos),
           _attend('off', monkeypatch, c, table, pos))


@pytest.mark.parametrize('planted', [1e30, np.nan], ids=['1e30', 'nan'])
def test_garbage_past_the_chunks_end_changes_no_bit(monkeypatch, planted):
    """Rows 512..639 of a table of 1 280 keys. The pages past the chunk's
    end — the rest of the second key tile, which is read and masked, and
    the third, which is not read — hold an earlier tenant's latent rows:
    whatever stands in them (and so in their k_nope, their rotary key and
    their values), the output is bit for bit the same."""
    c, table = _case(13, 4, 128, 64, 128, 128, 80)
    pos = 512 + np.arange(128)
    clean = _attend('interpret', monkeypatch, c, table, pos)
    dirty = dict(c, pool=c['pool'].copy())
    dirty['pool'][table[40:], LAYER] = planted
    np.testing.assert_array_equal(
        _attend('interpret', monkeypatch, dirty, table, pos), clean)
    assert np.isfinite(clean).all()
    if np.isfinite(planted):
        # the plain composition's contract is the same where 0 * x is 0
        _close(_attend('off', monkeypatch, dirty, table, pos), clean)


def test_a_slot_is_independent_of_the_tables_later_entries(monkeypatch):
    """The same rows against two tables that agree on the pages up to the
    chunk's end and on none behind it: the same bits."""
    c, table = _case(17, 4, 128, 64, 128, 64, 48, nb=120)
    pos = 300 + np.arange(64)
    other = table.copy()
    other[23:] = np.setdiff1d(np.arange(1, 120), table)[:48 - 23]
    np.testing.assert_array_equal(
        _attend('interpret', monkeypatch, c, table, pos),
        _attend('interpret', monkeypatch, c, other, pos))


# (heads, rows, nope, rope, v, keys) and whether the kernel takes the call
CALLS = {
    'joyai-b512': ((32, 512, 128, 64, 128, 2816), True),       # 184 MB
    'joyai-b1024': ((32, 1024, 128, 64, 128, 2816), True),     # 369 MB
    'joyai-b2048': ((32, 2048, 128, 64, 128, 2816), True),     # 738 MB
    'under-the-size-floor': ((32, 128, 128, 64, 128, 2816), False),
    'five-rows': ((32, 2045, 128, 64, 128, 2816), False),
    'own-lanes-of-no-whole-vreg': ((32, 2048, 96, 32, 128, 2816), False),
    'a-rotary-part-of-24': ((32, 2048, 128, 24, 128, 2816), False),
    'values-of-48': ((32, 2048, 128, 64, 48, 2816), False),
    'the-tests-toy': ((8, 32, 16, 8, 16, 64), False)}


@pytest.mark.parametrize('call', CALLS)
def test_the_shape_rule(call):
    (H, T, nope, rope, v, M), taken = CALLS[call]
    assert pfa.shapes_ok(H, H, T, nope, M, v_dim=v, shared_dim=rope) is taken


def test_shapes_the_kernel_refuses_fall_to_the_composition(monkeypatch):
    """The toy model's head (16 + 8 | 16) under `interpret`, whatever the
    size floor: one dispatch, on `xla`."""
    c, table = _case(3, 8, 16, 8, 16, 32, 6)
    pos = 20 + np.arange(32)
    _close(_attend('interpret', monkeypatch, c, table, pos, lands_on='xla'),
           _attend('off', monkeypatch, c, table, pos))


def test_under_a_mesh_the_call_stays_with_the_composition(monkeypatch):
    """A Pallas call cannot be partitioned by XLA: with a mesh of more than
    one device active, the same call lands on `xla` and counts `mesh=n`."""
    from paddle_tpu.parallel import api, make_mesh
    c, table = _case(19, 4, 128, 64, 128, 64, 10)
    pos = np.arange(64)
    want = _attend('off', monkeypatch, c, table, pos)
    monkeypatch.setattr(api, '_ACTIVE_MESH', make_mesh([('data', 2)]))
    _close(_attend('interpret', monkeypatch, c, table, pos, lands_on='xla',
                   mesh='n'), want)


def test_a_small_call_lands_on_xla(monkeypatch):
    c, table = _case(5, 4, 128, 64, 128, 64, 10)
    pos = np.arange(64)
    _close(_attend('interpret', monkeypatch, c, table, pos, lands_on='xla',
                   any_size=False),
           _attend('off', monkeypatch, c, table, pos))


def test_kernbench_mla_prefix_attention_case(capsys):
    """tools/kernbench.py's `mla_prefix_attention` case at its toy shape
    through the interpreter: a form a column beside the composition, a
    stated query tile, each form the composition's result, and the JSON
    line the CLI prints."""
    import json
    import sys
    from tools import kernbench
    argv = sys.argv
    sys.argv = ['kernbench.py', '--cases', 'mla_prefix_attention', '--size',
                'small', '--rounds', '1', '--k', '1', '--tilings', '32']
    try:
        kernbench.main()
    finally:
        sys.argv = argv
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    (shape, row), = res['mla_prefix_attention'].items()
    assert shape == 'toy [2, 64, 128 + 64 | 128] x 160 keys'
    forms = {'composition', 'two products', 'one wide key', 'padded key'}
    assert set(row) == forms | {'kernel alone', 'two products, rows 32'}
    for name, col in row.items():
        assert col['ms'] > 0 and 'error' not in col
        if name in forms:
            assert col['max_err'] < 1e-5
