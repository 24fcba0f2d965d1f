"""kv_decode_attention_paged (ops/kv_cache_ops.py) across its tiers: the
Pallas kernel of ops/paged_decode_attention.py, through the interpreter,
against the `off` tier's table-wide gather — on the op alone at a toy, a
355M-shaped and a 1.3B-shaped case and, for the grouped-query (MXU) body,
at 2, 4 and 8 queries a K/V head; bitwise slot independence; dead rows
that hold NaN; the one-query (VPU) body bit for bit what it was before
the MXU body came (a recorded fixture); and a toy paged engine whose
greedy tokens must not depend on the tier.

The op is lowered directly (a stand-in ctx/op pair around the registered
lowering): the tiers differ only inside it, and a program around it would
test the executor again.
"""
import re

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.core.registry import get_op
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.serving import GenerateConfig, GenerateEngine


class _Op(object):
    def __init__(self, **attrs):
        self.attrs = attrs

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


class _Ctx(object):
    def __init__(self, **ins):
        self.ins, self.outs = ins, {}

    def in1(self, op, slot):
        return self.ins.get(slot)

    def out(self, op, slot, value):
        self.outs[slot] = value


def _attend(tier, monkeypatch, q, kc, vc, tables, pos, layer, bs,
            lands_on=None):
    """The op under `tier`; its one dispatch must land on `lands_on`
    (the tier itself unless a shape makes it fall)."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    before = monitor.counters()
    ctx = _Ctx(Q=jnp.asarray(q), KCache=jnp.asarray(kc),
               VCache=jnp.asarray(vc), BlockTables=jnp.asarray(tables),
               Positions=jnp.asarray(pos)[:, None])
    op = _Op(layer=layer, scale=q.shape[-1] ** -0.5, block_size=bs)
    get_op('kv_decode_attention_paged').lower(ctx, op)
    moved = monitor.counter_delta(before)
    landed = lands_on or tier
    want = {'fused_kernel_dispatch_total{impl=%s,mesh=1,'
            'op=kv_decode_attention_paged}' % landed: 1}
    if landed in ('pallas', 'interpret'):
        # the kernel's body, chosen by the head counts alone
        Hkv = kc.shape[3] // q.shape[2]
        want['paged_decode_attention_form_total{form=%s}'
             % ('mxu' if q.shape[1] > Hkv else 'vpu')] = 1
    assert moved == want, moved
    return np.asarray(ctx.outs['Out'])


def _pools(rng, nb, ln, bs, hd):
    return (rng.randn(nb, ln, bs, hd).astype('float32'),
            rng.randn(nb, ln, bs, hd).astype('float32'))


# (S, H, Hkv, bs, dh, MB): toy; fairseq-dense 355M's heads, block and table
# at fewer slots; 1.3B's; OLMoE's 16 heads of 128 (a head takes a whole
# vreg: the kernel's `heads is None` branch) at a shorter table; then
# grouped queries, the MXU body: LFM2's 32 on 8 heads of 64 in pages of 32
# (a table of 20 = 640 keys, a window of 512 and a short one), 2 queries a
# K/V head, and 8 with heads of 128. Pools of 40 blocks, 2 layers.
SHAPES = [(6, 2, 2, 8, 64, 4), (6, 16, 16, 16, 64, 48),
          (6, 32, 32, 16, 64, 66), (6, 16, 16, 16, 128, 12),
          (8, 32, 8, 32, 64, 20), (8, 16, 8, 16, 64, 40),
          (8, 16, 2, 16, 128, 36)]


@pytest.mark.parametrize('S,H,Hkv,bs,dh,MB', SHAPES,
                         ids=['toy', 'fd355m', 'fd1.3b', 'olmoe-head128',
                              'lfm2-g4', 'g2', 'g8-head128'])
def test_interpret_tier_matches_off_tier(monkeypatch, S, H, Hkv, bs, dh, MB):
    """One tolerance for both bodies: the MXU body's two products are
    committed at `Precision.HIGHEST` (float32 operands whole, as the VPU
    body multiplies them; the interpreter's are float32 too), so what is
    left against the gather is summation order, here as there."""
    assert pda.shapes_ok(H, dh, bs, Hkv)
    assert pda.form(H, Hkv) == ('mxu' if H > Hkv else 'vpu')
    rng = np.random.RandomState(S * H + MB)
    nb, ln, layer = 40, 2, 1
    kc, vc = _pools(rng, nb, ln, bs, Hkv * dh)
    q = rng.randn(S, H, dh).astype('float32')
    tables = rng.randint(1, nb, size=(S, MB)).astype('int32')
    # slot 0 at position 0; 1 on a page's last row; 2 on the next page's
    # first row; 3 fills the table; 4 is idle (all-zero row -> the trash
    # block, position 0); 5 shares slot 3's leading pages; grouped
    # queries: 6 on a window's last key, 7 on the next window's first
    pos = np.array([0, bs - 1, bs, MB * bs - 1, 0, 2 * bs + 3,
                    pda._WINDOW_KEYS - 1, pda._WINDOW_KEYS][:S], 'int32')
    assert pos.max() < MB * bs
    tables[4] = 0
    tables[5, :2] = tables[3, :2]
    args = (q, kc, vc, tables, pos, layer, bs)
    off = _attend('off', monkeypatch, *args)
    got = _attend('interpret', monkeypatch, *args)
    np.testing.assert_allclose(got, off, rtol=2e-5, atol=2e-6)
    if (S, H, Hkv, bs, dh, MB) == SHAPES[0]:
        np.testing.assert_allclose(_attend('xla', monkeypatch, *args), off,
                                   rtol=2e-5, atol=2e-6)


def test_shapes_the_kernel_refuses_fall_to_xla(monkeypatch):
    # a head's 24 lanes would straddle vregs; 4-row pages are no tile
    assert not pda.shapes_ok(2, 24, 8) and not pda.shapes_ok(2, 64, 4)
    # grouped queries: 12 query rows run as 16 (`padded_group`: whole
    # sublanes), but pages of 24 rows fill no window, and two windows of 16
    # K/V heads of 128 no ring
    assert pda.shapes_ok(32, 64, 32, 8) and pda.shapes_ok(12, 64, 16, 4)
    assert not pda.shapes_ok(32, 64, 24, 8)
    assert pda.shapes_ok(64, 128, 16, 8) and not pda.shapes_ok(64, 128, 16, 16)
    rng = np.random.RandomState(0)
    kc, vc = _pools(rng, 6, 1, 4, 2 * 16)
    q = rng.randn(2, 2, 16).astype('float32')
    tables = np.array([[1, 2], [3, 0]], 'int32')
    pos = np.array([5, 2], 'int32')
    args = (q, kc, vc, tables, pos, 0, 4)
    np.testing.assert_allclose(
        _attend('interpret', monkeypatch, *args, lands_on='xla'),
        _attend('off', monkeypatch, *args), rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize('H,Hkv,bs,MB,pos2', [
    (4, 4, 16, 6, 2 * 16 + 5), (32, 8, 32, 20, 17 * 32 + 5)],
    ids=['one-query', 'grouped-g4-two-windows'])
def test_a_slot_is_bitwise_independent_of_its_neighbours(monkeypatch, H, Hkv,
                                                         bs, MB, pos2):
    S, dh = 5, 64
    rng = np.random.RandomState(3)
    nb = 30
    kc, vc = _pools(rng, nb, 2, bs, Hkv * dh)
    q = rng.randn(S, H, dh).astype('float32')
    tables = rng.randint(1, nb, size=(S, MB)).astype('int32')
    tables[2] = 1 + rng.permutation(nb - 1)[:MB]        # no page twice
    pos = np.array([7, 40, pos2, 95, 16], 'int32')
    full = _attend('interpret', monkeypatch, q, kc, vc, tables, pos, 0, bs)

    # slot 2 alone, in another row of the batch, its unused table entries
    # on other blocks, and every block it does not read filled with
    # garbage: not a bit of its output may move
    n_used = pos2 // bs + 1
    used = tables[2, :n_used]
    kc2, vc2 = kc.copy(), vc.copy()
    spare = np.setdiff1d(np.arange(nb), used)
    kc2[spare] = 1e30
    vc2[spare] = -1e30
    q1 = np.zeros_like(q[:2])
    q1[1] = q[2]
    t1 = np.zeros((2, MB), 'int32')
    t1[1, :n_used] = used
    t1[1, n_used:] = spare[:MB - n_used]
    p1 = np.array([0, pos[2]], 'int32')
    alone = _attend('interpret', monkeypatch, q1, kc2, vc2, t1, p1, 0, bs)
    np.testing.assert_array_equal(alone[1], full[2])


@pytest.mark.parametrize('H,Hkv,bs,planted', [
    (4, 4, 16, 'k'), (32, 8, 32, 'k-and-v')],
    ids=['one-query-k', 'grouped-g4-k-and-v'])
def test_a_row_past_the_position_has_weight_exactly_zero(monkeypatch, H, Hkv,
                                                         bs, planted):
    """NaN in the rows past the position of a slot's last page: the output
    is, bit for bit, what it is with zeros there. The MXU body zeroes the
    dead V rows in its ring before the product (0 x NaN would be NaN);
    the VPU body multiplies a weight of exactly 0 by the row, as it always
    did, so only its keys may hold anything."""
    S, dh, MB, nb = 3, 64, 4, 16
    rng = np.random.RandomState(11)
    kc, vc = _pools(rng, nb, 2, bs, Hkv * dh)
    q = rng.randn(S, H, dh).astype('float32')
    tables = (1 + np.arange(S * MB).reshape(S, MB) % (nb - 1)).astype('int32')
    pos = np.array([5, bs + 3, 3 * bs + bs // 2], 'int32')
    clean, dirty = [], []
    for fill, into in ((0.0, clean), (np.nan, dirty)):
        k2, v2 = kc.copy(), vc.copy()
        for s_ in range(S):
            last = tables[s_, pos[s_] // bs]
            k2[last, :, pos[s_] % bs + 1:] = fill
            if planted == 'k-and-v':
                v2[last, :, pos[s_] % bs + 1:] = fill
        into.append(_attend('interpret', monkeypatch, q, k2, v2, tables, pos,
                            1, bs))
    assert np.isfinite(dirty[0]).all()
    np.testing.assert_array_equal(dirty[0], clean[0])


# (S, H, bs, dh, MB, seed): heads of 64 (two a vreg) and of 128 (the
# `heads is None` branch)
G1_FIXTURE = [(5, 4, 16, 64, 6, 36), (4, 2, 8, 128, 5, 37)]


def _g1_fixture_inputs(S, H, bs, dh, MB, seed):
    rng = np.random.RandomState(seed)
    nb = 24
    kc, vc = _pools(rng, nb, 2, bs, H * dh)
    q = rng.randn(S, H, dh).astype('float32')
    tables = rng.randint(1, nb, size=(S, MB)).astype('int32')
    pos = rng.randint(0, MB * bs, size=S).astype('int32')
    pos[0], pos[-1] = 0, MB * bs - 1
    return q, kc, vc, tables, pos


@pytest.mark.parametrize('case', range(len(G1_FIXTURE)),
                         ids=['head64', 'head128'])
def test_the_one_query_body_is_bit_for_bit_the_parents(monkeypatch, case):
    """`G == 1` keeps the body it had, operation for operation:
    fixtures/paged_decode_attention_g1_pr35.json holds the outputs of PR
    35's kernel (interpreted, on these seeded inputs) as float32 bytes."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'fixtures',
                           'paged_decode_attention_g1_pr35.json')) as f:
        rec = json.load(f)['cases'][case]
    shape = G1_FIXTURE[case]
    assert rec['shape'] == list(shape)
    q, kc, vc, tables, pos = _g1_fixture_inputs(*shape)
    got = _attend('interpret', monkeypatch, q, kc, vc, tables, pos, 1,
                  shape[2])
    want = np.frombuffer(bytes.fromhex(rec['out_f32_hex']),
                         '<f4').reshape(got.shape)
    np.testing.assert_array_equal(got, want)


def _toy_engine():
    model = LMConfig(vocab_size=64, seq_len=32, d_model=128, n_head=2,
                     n_layer=2, d_ff=64, dropout=0.0, attn_dropout=0.0,
                     use_flash_attention=False)
    return GenerateEngine(GenerateConfig(
        model=model, slots=4, max_len=48, prompt_buckets=[16],
        eos_id=None, seed=0, block_size=8))


def _serve(eng, prompts, n_new):
    eng.warmup()
    warm = monitor.counters()
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    eng._admit()
    while any(r.finish_reason is None and r._error is None for r in reqs):
        eng._step()
        eng._admit()
    moved = monitor.counter_delta(warm)
    return [list(r.result(timeout=5)) for r in reqs], moved


def test_engine_tokens_do_not_depend_on_the_tier(monkeypatch):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(2, 64, size=n).astype('int64')
               for n in (5, 16, 9, 12, 3, 8)]
    n_new = [12, 20, 7, 30, 16, 9]
    out = {}
    for tier in ('off', 'interpret'):
        monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
        before = monitor.counters()
        eng = _toy_engine()
        out[tier], moved = _serve(eng, prompts, n_new)
        assert not any(k.startswith('compile_cache_miss') for k in moved), \
            moved
        built = monitor.counter_delta(before)
        # one decision per layer of the decode program, all on the tier
        assert built['fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=kv_decode_attention_paged}' % tier] == 2
        # the feed phase's page counters: 6 pages a table row, and of them
        # what lies at or below each slot's position
        live = moved['kv_decode_pages_live_total']
        table = moved['kv_decode_pages_table_total']
        assert table % 6 == 0 and 0 < live < table
        share = eng.stats()['blocks']['decode_live_page_share']
        assert 0.0 < share < 1.0
    assert out['interpret'] == out['off']
    assert [len(t) for t in out['off']] == n_new


# ---------------------------------------------------------------------------
# Mosaic, without a chip: the TPU compiler against a described v5e. The
# topology is described inside a fixture, never at import (one process at a
# time may load libtpu: under xdist only this file's worker does).

@pytest.fixture(scope='module')
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for k, v in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
                 ('TPU_WORKER_HOSTNAMES', 'localhost'),
                 ('TPU_SKIP_MDS_QUERY', '1')):
        os.environ.setdefault(k, v)
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('S,H,MB,NB,dh,ln', [
    (32, 16, 48, 1024, 64, 24), (4, 32, 66, 265, 64, 24),
    (16, 16, 80, 1280, 128, 6)],
    ids=['fd355m-serve-chat', 'fd1.3b-serve-doc', 'olmoe-serve-chat16'])
def test_mosaic_accepts_the_kernel_at_the_cells_shapes(one_chip, S, H, MB,
                                                       NB, dh, ln):
    import jax
    bs, layer = 16, 3

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kc, vc, q, new, tables, pos):
        # the layer's row scatter into the donated pool, then the kernel's
        # read of it: a pool the custom call could not take in place would
        # show as a pool-sized temp
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        kc = kc.at[blk, layer, pos % bs, :].set(new)
        return kc, pda.paged_decode_attention(q, kc, vc, tables, pos, layer,
                                              scale=dh ** -0.5)

    pool = sds((NB, ln, bs, H * dh))
    c = jax.jit(step, donate_argnums=0).lower(
        pool, pool, sds((S, H, dh)), sds((S, H * dh)),
        sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()
    text = c.as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_attention' in text
    # row-major pool: a page is one contiguous run
    assert 'f32[%d,%d,%d,%d]{3,2,1,0:T(8,128)}' % (NB, ln, bs, H * dh) in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_accepts_the_grouped_query_kernel_at_its_cells_shapes(
        one_chip):
    """lfm2-serve-agent64: 64 slots, 32 query heads on 8 K/V heads of 64,
    pages of 32 rows x 512 lanes, tables of 160 entries (the kernel's
    largest so far), a pool of 4 096 blocks over the 2 attention layers.
    A page is copied once for the four queries of each of its heads, and
    the body is the MXU's: a block-diagonal [32, 512] query matrix against
    windows of 16 pages (PR 36)."""
    import jax
    S, H, Hkv, dh, MB, NB, ln, bs, layer = 64, 32, 8, 64, 160, 4096, 2, 32, 1

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kc, vc, q, new, tables, pos):
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        kc = kc.at[blk, layer, pos % bs, :].set(new)
        return kc, pda.paged_decode_attention(q, kc, vc, tables, pos, layer,
                                              scale=dh ** -0.5)

    assert pda.shapes_ok(H, dh, bs, Hkv) and pda.form(H, Hkv) == 'mxu'
    assert not pda.shapes_ok(H, dh, bs, 5) and not pda.shapes_ok(2, dh, bs, 1)
    pool = sds((NB, ln, bs, Hkv * dh))
    c = jax.jit(step, donate_argnums=0).lower(
        pool, pool, sds((S, H, dh)), sds((S, Hkv * dh)),
        sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()
    text = c.as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_attention' in text
    assert 'f32[%d,%d,%d,%d]{3,2,1,0:T(8,128)}' % (NB, ln, bs, Hkv * dh) \
        in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_accepts_the_grouped_query_kernel_at_heads_of_256(one_chip):
    """qwen3next-serve-longmix64 (PR 55): 64 slots, 16 query heads on 2 K/V
    heads of 256 -- a head two vregs of lanes --, pages of 32 rows x 512
    lanes, tables of 288 entries, a pool of 18 432 blocks over the 2
    attention layers. The MXU body: a block-diagonal [16, 512] query matrix
    against windows of 16 pages; the row's scatter ahead of it costs no
    copy of the pool."""
    import jax
    S, H, Hkv, dh, MB, NB, ln, bs, layer = 64, 16, 2, 256, 288, 18432, 2, \
        32, 1

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(kc, vc, q, new, tables, pos):
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        kc = kc.at[blk, layer, pos % bs, :].set(new)
        return kc, pda.paged_decode_attention(q, kc, vc, tables, pos, layer,
                                              scale=dh ** -0.5)

    assert pda.shapes_ok(H, dh, bs, Hkv) and pda.form(H, Hkv) == 'mxu'
    # one query a head of 256 is the VPU body's, which sums a head's lanes
    # inside one vreg: refused, as it was
    assert not pda.shapes_ok(Hkv, dh, bs, Hkv)
    pool = sds((NB, ln, bs, Hkv * dh))
    c = jax.jit(step, donate_argnums=0).lower(
        pool, pool, sds((S, H, dh)), sds((S, Hkv * dh)),
        sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()
    text = c.as_text()
    assert 'tpu_custom_call' in text and 'paged_decode_attention' in text
    assert 'f32[%d,%d,%d,%d]{3,2,1,0:T(8,128)}' % (NB, ln, bs, Hkv * dh) \
        in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20


def test_mosaic_accepts_the_delta_rule_kernels_at_the_cells_shapes(one_chip):
    """qwen3next-serve-longmix64's Gated DeltaNet layers (ops/gdn_ops.py, PR
    55): 16 key heads and 32 value heads of 128. The decode update moves a
    slot's [128, 2048] strip of the state pool in place (no temporary as
    long as the pool), the tails' kernel serves 8 192 channels, and the
    chunked prefill compiles at every bucket in blocks of 64 rows."""
    import jax
    from paddle_tpu.ops import gdn_ops, ssm_ops
    S, hk, hv, dk, dv, layers = 64, 16, 32, 128, 128, 6
    cw, vd = 2 * hk * dk + hv * dv, hv * dv

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, tails, rows, x, w, decay, beta, q, k):
        c, tails = ssm_ops.decode_conv(tails, rows, 3, x, w,
                                       jnp.zeros((cw,), jnp.float32))
        o, state = gdn_ops.decode_update(state, rows, 3, decay, beta,
                                         c[:, 2 * hk * dk:], q, k,
                                         value_heads=hv)
        return o, state, tails
    assert gdn_ops.shapes_ok(dk, dv, hk, hv) and ssm_ops.shapes_ok(cw, 8)
    c = jax.jit(step, donate_argnums=(0, 1)).lower(
        sds((S + 1, layers, dk, vd)), sds((S + 1, layers, 8, cw)),
        sds((S,), jnp.int32), sds((S, cw)), sds((cw, 4)), sds((S, vd)),
        sds((S, vd)), sds((S, hk, dk)), sds((S, hk, dk))).compile()
    text = c.as_text()
    assert 'gdn_decode_update' in text and 'ssm_decode_conv' in text
    assert c.memory_analysis().temp_size_in_bytes < 8 << 20
    for rows in (128, 256, 512):
        assert gdn_ops.shapes_ok(dk, dv, hk, hv, rows, 64)

        def prefill(q, k, v, g, beta, s0):
            return gdn_ops.prefill_chunks(q, k, v, g, beta, s0, chunk=64)
        c = jax.jit(prefill).lower(
            sds((rows, hk, dk)), sds((rows, hk, dk)), sds((rows, hv, dv)),
            sds((rows, hv)), sds((rows, hv)), sds((dk, vd))).compile()
        assert 'gdn_prefill_chunk' in c.as_text()
        assert c.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize('NB,ln,bs,W,MB', [
    (1024, 24, 16, 1024, 48),   # fd355m-serve-chat: the K (or V) pool
    (8192, 7, 16, 640, 176)])   # joyai-serve-longchat64: the latent pool
def test_a_tables_pages_are_gathered_from_the_pool_where_it_lies(
        one_chip, NB, ln, bs, W, MB):
    """`kv_cache_ops.pool_pages` at two cells' sizes: XLA:TPU reads the
    table's MB pages and no more. Spelled ``pool[:, layer][table]`` it
    copies the layer's share of the whole pool first (PR 42: 73 MB
    accessed for chat's 6, 348 for JoyAI's 12) and gathers from that."""
    import jax
    from paddle_tpu.ops.kv_cache_ops import pool_pages

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def scores(pool, table, q):
        keys = pool_pages(pool, 3, table).reshape(-1, W)
        return jnp.einsum('td,md->tm', q, keys)

    c = jax.jit(scores).lower(sds((NB, ln, bs, W)), sds((MB,), jnp.int32),
                              sds((64, W))).compile()
    share = NB * bs * W * 2         # one layer of the pool, as bfloat16
    assert c.cost_analysis()['bytes accessed'] < share // 2
    assert c.memory_analysis().temp_size_in_bytes < share // 8
    assert not re.search(r'= \w+\[%d,(1,)?%d,%d\]' % (NB, bs, W),
                         c.as_text())


def test_mosaic_accepts_the_latent_kernel_at_its_cells_shapes(one_chip):
    """ops/mla_paged_decode_attention.py at joyai-serve-longchat64's size:
    64 slots x 32 absorbed queries of 640 lanes (576 numbers in whole
    tiles) against ONE pool of latent rows, the values its first 512
    lanes. A pool declared 576 wide is stored 640 wide by the TPU anyway,
    and Mosaic refuses a page of it (a slice of the minor dimension has to
    be whole 128-lane tiles): LMConfig.kv_width says 640."""
    import jax
    from paddle_tpu.ops import mla_paged_decode_attention as mla
    S, H, W, V, MB, NB, ln, bs, layer = 64, 32, 640, 512, 176, 8192, 7, 16, 3

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(pool, q, new, tables, pos):
        blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
        pool = pool.at[blk, layer, pos % bs, :].set(new)
        return pool, mla.mla_paged_decode_attention(
            q, pool, tables, pos, layer, scale=192 ** -0.5, v_width=V)

    assert mla.shapes_ok(H, W, V, bs)
    c = jax.jit(step, donate_argnums=0).lower(
        sds((NB, ln, bs, W)), sds((S, H, W)), sds((S, W)),
        sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()
    text = c.as_text()
    assert 'tpu_custom_call' in text
    assert 'mla_paged_decode_attention' in text
    assert 'f32[%d,%d,%d,%d]{3,2,1,0:T(8,128)}' % (NB, ln, bs, W) in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
    with pytest.raises(Exception, match='aligned to tiling'):
        jax.jit(step, donate_argnums=0).lower(
            sds((NB, ln, bs, 576)), sds((S, H, 576)), sds((S, 576)),
            sds((S, MB), jnp.int32), sds((S,), jnp.int32)).compile()


def _serving_program(build, fetch, rows, sharding=None):
    """A serving program (`build()` -> its vars) lowered by
    core.lowering.build_fn: the raw function and its arguments as shapes,
    `rows` rows a feed, the cache pools the written state."""
    import jax
    from paddle_tpu import unique_name
    from paddle_tpu.core.lowering import build_fn
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import (CONV_CACHE, KV_CACHE_K,
                                               KV_CACHE_V)
    main = Program()
    with program_guard(main, Program()), unique_name.guard():
        v = build()
    block = main.global_block()

    def sds(var):
        dt = jnp.dtype(str(var.dtype))
        return jax.ShapeDtypeStruct(
            tuple(rows if s < 0 else s for s in var.shape),
            jnp.int32 if dt == jnp.int64 else dt, sharding=sharding)
    state = [x.name for x in block.vars.values() if x.persistable]
    fn, ro, rw = build_fn(main, [v[fetch].name], state,
                          [KV_CACHE_K, KV_CACHE_V, CONV_CACHE])
    written = {n for op in block.ops for ns in op.outputs.values()
               for n in ns}
    feeds = {n: x for n, x in block.vars.items()
             if n.startswith('gen_') and not x.persistable
             and n not in written}
    return fn, ({n: sds(x) for n, x in feeds.items()},
                {n: sds(block.var(n)) for n in ro},
                {n: sds(block.var(n)) for n in rw},
                jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=sharding))


def _compile_serving_program(build, fetch, rows, one_chip):
    """`_serving_program` compiled for the described chip, the cache pools
    donated: shapes in, nothing executed."""
    import jax
    fn, args = _serving_program(build, fetch, rows, one_chip)
    return jax.jit(fn, donate_argnums=2).lower(*args).compile()


@pytest.mark.parametrize('program', ['decode_step', 'prefill_b64'])
def test_no_serving_program_re_lays_the_embedding_table(one_chip,
                                                        monkeypatch,
                                                        program):
    """The lookup reads its rows from `tok_emb.w` [V, D] as it is stored.
    Until PR 33 a Pallas gather took the table as `w.reshape(V, 1, D)`,
    which on the TPU is a copy of the whole table ((8, 128) tiles to
    (1, 128) tiles) in EVERY dispatch: 134 MB of temporaries here, 1.06 GB
    in the largest cell. No instruction but a parameter may have the
    table's element count (shapes [D, V] are the untied head's, inside
    its matmul's fusion), and the program's temporaries stay far under the
    table's bytes."""
    import re
    from paddle_tpu.models import transformer as T
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')
    V, D, slots, bs, max_len, NB = 32768, 1024, 8, 16, 128, 64
    cfg = LMConfig(vocab_size=V, seq_len=max_len, d_model=D, n_head=16,
                   n_layer=2, d_ff=2048, dropout=0.0, attn_dropout=0.0)
    before = monitor.counters()
    if program == 'decode_step':
        c = _compile_serving_program(
            lambda: T.build_lm_decode_step(cfg, slots, max_len,
                                           block_size=bs, num_blocks=NB),
            'next_tokens', slots, one_chip)
    else:
        c = _compile_serving_program(
            lambda: T.build_lm_prefill_paged(cfg, 64, NB, bs,
                                             max_len // bs),
            'first_token', 1, one_chip)
    # the lookup counts the one lowering it has, whatever the tier
    assert monitor.counter_delta(before)[
        'fused_kernel_dispatch_total{impl=off,mesh=1,op=lookup_table}'] == 1
    text = c.as_text()
    assert 'f32[%d,%d]{1,0:T(8,128)}' % (V, D) in text       # in place
    table_sized = []
    for m in re.finditer(r'^\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]+)\]\S* '
                         r'([\w\-]+)\(', text, re.M):
        dims = [int(d) for d in m.group(1).split(',')]
        if int(np.prod(dims)) == V * D and dims != [D, V] \
                and m.group(2) != 'parameter':
            table_sized.append(m.group(0).strip())
    assert not table_sized, table_sized
    assert c.memory_analysis().temp_size_in_bytes < V * D * 4 // 4


@pytest.mark.parametrize('program', ['decode_step', 'prefill_b64'])
def test_a_tied_head_and_the_tails_pool_cost_no_copy(one_chip, monkeypatch,
                                                     program):
    """An LFM2-shaped block (a convolution layer, grouped queries, the head
    tied to the table): the head contracts against `tok_emb.w` [V, D]
    where it lies — no transposed copy, no `lm_head.w` —, the tails' pool
    is updated in place beside the K/V pools (all three aliased), and the
    decode step's attention is the kernel."""
    from paddle_tpu.models import transformer as T
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')
    V, D, slots, bs, max_len, NB = 32768, 1024, 8, 16, 128, 64
    cfg = LMConfig(vocab_size=V, seq_len=max_len, d_model=D, n_head=16,
                   n_kv_head=4, n_layer=2, layer_types=['conv', 'attention'],
                   d_ff=2048, dropout=0.0, attn_dropout=0.0, norm='rms_norm',
                   position='rope', qk_norm='head', bias=False,
                   tie_embeddings=True, ffn='moe', n_dense_layers=2,
                   n_experts=4, experts_per_token=2, expert_width=64)
    if program == 'decode_step':
        c = _compile_serving_program(
            lambda: T.build_lm_decode_step(cfg, slots, max_len,
                                           block_size=bs, num_blocks=NB),
            'next_tokens', slots, one_chip)
    else:
        c = _compile_serving_program(
            lambda: T.build_lm_prefill_paged(cfg, 64, NB, bs,
                                             max_len // bs),
            'first_token', 1, one_chip)
    text = c.as_text()
    assert 'f32[%d,%d]{1,0:T(8,128)}' % (V, D) in text        # in place
    assert 'f32[%d,%d]' % (D, V) not in text                 # no [D, V]
    ma = c.memory_analysis()
    assert ma.temp_size_in_bytes < V * D * 4 // 4
    pools = 2 * NB * 1 * bs * 4 * 64 * 4 + NB * 1 * 2 * D * 4
    assert ma.alias_size_in_bytes == pools
    if program == 'decode_step':
        assert 'tpu_custom_call' in text
        assert 'paged_decode_attention' in text


def test_the_block_copy_takes_the_pool_in_place(one_chip):
    """serving/generate.py `block_copy_fn`: the copy-on-write block copy
    that warmup() compiles donates the pool on the TPU. Undonated it held
    a second pool while it ran, and that was every serve cell's memory
    peak (JoyAI: 15.20 GB over a 12.76 GB state; PERF.md, PR 33)."""
    import jax
    from paddle_tpu.serving.generate import block_copy_fn
    pool = jax.ShapeDtypeStruct((1024, 6, 16, 2048), jnp.float32,
                                sharding=one_chip)
    idx = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ma = block_copy_fn('tpu').lower(pool, idx, idx).compile() \
        .memory_analysis()
    nbytes = 1024 * 6 * 16 * 2048 * 4
    assert ma.alias_size_in_bytes == nbytes
    assert ma.temp_size_in_bytes < nbytes // 4


# (H, Hkv, T, dh, keys, window): K-EXAONE's global layer at its widest and
# narrowest bucket and its window layers' call (127 ring rows + the chunk);
# doc's two buckets against 1 056 keys (no whole number of key tiles); LFM2;
# OLMoE; Jamba2's 20 queries on one K/V head; chat's narrowest bucket
PREFIX_SHAPES = {
    'kexaone-b512': (64, 8, 512, 128, 5120, None),
    'kexaone-b128': (64, 8, 128, 128, 5120, None),
    'kexaone-window-b512': (64, 8, 512, 128, 639, 128),
    'kexaone-window-b128': (64, 8, 128, 128, 255, 128),
    'doc-b1024': (32, 32, 1024, 64, 1056, None),
    'doc-b768': (32, 32, 768, 64, 1056, None),
    'lfm2-b512': (32, 8, 512, 64, 5120, None),
    'olmoe-b768': (16, 16, 768, 128, 1280, None),
    'jamba2-b512': (20, 1, 512, 128, 3072, None),
    'chat-b64': (16, 16, 64, 64, 768, None)}


@pytest.mark.parametrize('shape', PREFIX_SHAPES)
def test_mosaic_accepts_the_prefix_kernel_at_the_cells_shapes(one_chip,
                                                              shape):
    """ops/prefix_attention.py at every shape class a serve cell compiles:
    one custom call under its own name (a window layer's under another),
    and nothing beside it as long as the scores."""
    import functools
    import jax
    from paddle_tpu.ops import prefix_attention as pfa
    H, Hkv, T, dh, M, window = PREFIX_SHAPES[shape]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = jax.jit(functools.partial(
        pfa.prefix_attention, scale=dh ** -0.5, window=window)).lower(
        sds((H, T, dh)), sds((Hkv, M, dh)), sds((Hkv, M, dh)),
        sds((M,), jnp.int32), sds((T,), jnp.int32)).compile()
    text = c.as_text()
    name = 'kv_prefix_window_attention' if window else 'kv_prefix_attention'
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r'%%?%s[.\d]* = ' % name, text)
    assert c.memory_analysis().temp_size_in_bytes < H * T * M * 4 // 4


@pytest.mark.parametrize('T', [512, 1024, 2048])
def test_mosaic_accepts_the_prefix_kernel_at_latent_attentions_widths(
        one_chip, T):
    """ops/prefix_attention.py as `mla_prefix_attention` calls it, at
    joyai-serve-longchat64's three buckets: 32 query heads of 128 + 64
    lanes, each against its own 128-lane keys and 128-lane values, and ONE
    rotary key of 64 lanes that the 32 share — a second, 64-deep product a
    key tile against a block whose index ignores the head, query tiles of
    the op's 512 rows. One custom call under the op's own name, and
    nothing beside it as long as the scores."""
    import functools
    import jax
    from paddle_tpu.ops import mla_ops, prefix_attention as pfa
    H, nope, rope, v, M = 32, 128, 64, 128, 2816
    assert pfa.shapes_ok(H, H, T, nope, M, v_dim=v, shared_dim=rope)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    c = jax.jit(functools.partial(
        pfa.prefix_attention, scale=(nope + rope) ** -0.5,
        name='mla_prefix_attention',
        rows=mla_ops._KERNEL_QUERY_ROWS)).lower(
        sds((H, T, nope + rope)), sds((H, M, nope)), sds((H, M, v)),
        sds((M,), jnp.int32), sds((T,), jnp.int32),
        sds((M, rope))).compile()
    text = c.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r'%?mla_prefix_attention[.\d]* = ', text)
    assert c.memory_analysis().temp_size_in_bytes < H * T * M * 4 // 4


@pytest.mark.parametrize('config,traffic,program,keys,kernel,gb', [
    ('k-exaone-236b-a23b-ep16-l5', 'mixed64-closed', 'prefill_b512',
     (5120, 639), 'kv_prefix_attention', 27.5),
    ('fairseq-dense-1.3b', 'doc-closed', 'prefill_b1024', (1056,),
     'kv_prefix_attention', 34.0),
    ('joyai-llm-flash-ep4', 'longchat64-closed', 'prefill_b2048', (2816,),
     'mla_prefix_attention', 52.5)],
    ids=['kexaone-b512', 'doc-b1024', 'joyai-b2048'])
def test_a_prefill_program_holds_no_attention_scores(one_chip, monkeypatch,
                                                     config, traffic,
                                                     program, keys, kernel,
                                                     gb):
    """The widest prefill programs of the benchmark, whole, at their real
    size (`tools/poolscan.py`'s device-less compile). Until PR 44 the
    scores of every attention layer stood in HBM — ``f32[8,2048,5120]``,
    336 MB, twice a chunk in K-EXAONE; ``f32[32,1024,1056]``, 138 MB, 48
    times in doc; until PR 61 JoyAI's ``f32[32,256,2816]``, 92 MB a chunk
    of 256 rows, eight chunks a layer — now no float32 value is a row of
    keys long for as many rows as the bucket has. XLA's `bytes accessed`
    counts such a value once a fusion that writes it (not once a pass the
    chip makes over it): it read 29.61, 39.86 and 53.12 GB at the
    parent."""
    import os
    from tools import poolscan
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), '..',
                        'benchmark')
    (key, e, cfg, compiled), = poolscan.compiled_programs(
        os.path.join(root, 'configs', config + '.json'),
        os.path.join(root, 'traffic', traffic + '.json'), only=[program])
    text = compiled.as_text()
    rows = int(program.rsplit('b', 1)[1])
    scores = [(name, dims)
              for name, _, dtype, dims in poolscan._outputs(text)
              if dtype == 'f32' and dims and dims[-1] in keys
              and np.prod(dims[:-1]) >= rows]
    assert not scores, scores
    assert re.search(r'%%?%s[.\d]* = ' % kernel, text)
    assert compiled.cost_analysis()['bytes accessed'] < gb * 1e9


@pytest.mark.parametrize('width,relaid', [(1856, True), (1920, False),
                                          (1024, False)],
                         ids=['nemotron-1856', 'whole-lanes-1920',
                              'olmoe-1024'])
def test_a_bound_entry_asks_for_the_up_matrices_the_kernel_takes(
        one_chip, width, relaid):
    """`Executor.bind`'s entry (`StateCallable.lower_bound`: every
    read-only leaf's layout left to the compiler) of one ungated
    `moe_ffn`, 16 held experts of 128 over 2688, compiled for the
    described chip (`tools/boundlayouts.py`). At Nemotron's width, 1856 =
    14.5 lane tiles, the chip's default for ``[16, 2688, 1856]`` puts
    2688 on the lanes and the grouped matmul's custom call wants 1856
    there: until PR 49 a transpose of 319 MB stood in front of it in
    every dispatch of every expert layer. The bound entry asks
    ``major_to_minor == (0, 1, 2)`` for the up matrix and holds no copy
    of a parameter; at 1920 and at OLMoE's 1024 every leaf keeps the
    default."""
    import paddle_tpu as fluid
    from tools import boundlayouts

    def build():
        x = fluid.layers.data(name='gen_x', shape=[128, 2688],
                              dtype='float32', append_batch_size=False)
        out, _, _ = fluid.layers.moe_ffn(
            x, 128, width, 6, experts_held=(0, 16), form='relu2',
            up_param_attr=fluid.ParamAttr(name='moe.up.w'))
        return {'out': out}
    fn, leaves, compiled = boundlayouts.lower_bound(
        build, 'out', 128, one_chip._device, [])
    assert 'moe.up.w' in fn.ro_names and len(fn.ro_names) == 3
    want = boundlayouts.relaid(fn, leaves, compiled)
    text = compiled.as_text()
    assert text.count('ragged-dot') >= 2
    assert boundlayouts.weight_copies(text, leaves) == {}
    if relaid:
        assert list(want) == ['moe.up.w']
        assert want['moe.up.w']['default'] == [0, 2, 1]
        assert want['moe.up.w']['chosen'] == [0, 1, 2]
        assert want['moe.up.w']['bytes'] == 16 * 2688 * 1856 * 4
        at = fn.ro_names.index('moe.up.w')
        assert compiled.input_formats[0][1][at].layout.major_to_minor \
            == (0, 1, 2)
    else:
        assert want == {}
