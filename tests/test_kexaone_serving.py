"""The K-EXAONE block in the Program path (ISSUE 41): window layers of a few
keys beside a global one, each kind with pools of its own — the window
layers' a ring of blocks a slot —, rotary positions on the window layers
alone: the bounded kernel against the gather, prefill (whole and in
chunks) then decode through both pools against the plain reference's FULL
forward pass (logits, not tokens), the ring's accounting, the controls,
the counters, the Mosaic compile at the cell's shapes and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-kexaone.json):
d 64, 8 query heads on 2 K/V heads of 8, 5 layers (window window window
global window, 1 dense), a window of 12 keys, 4 of 16 experts of width 32
held, top-2, a shared expert, seeded weights.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import Scope, monitor
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.serving import GenerateConfig, GenerateEngine
from paddle_tpu.serving.kv_blocks import WindowRings

from benchmark.models import kexaone
from benchmark.reference import kexaone_control, kexaone_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import lower, serve_five, tap_logits
from test_paged_decode_attention import _Ctx, _Op, _pools
from paddle_tpu.core.registry import get_op

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-kexaone.json')) as _f:
    TOY = json.load(_f)

# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 3e-7 to 1e-6 over every comparison below); the controls move the
# logits by 3e-3 (a window one key off) to 0.5 (the norm weights left out).
TOLERANCE = 1e-4


def _scope(m=TOY, seed=5):
    """Seeded weights; the experts four times larger, so that a wrong
    choice of expert or weight moves the logits (test_joyai_serving.py)."""
    scope = Scope()
    for name, value in kexaone.init_params(m, seed).items():
        big = '.moe.' in name and 'router' not in name
        scope.set(name, value * (4.0 if big else 1.0))
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=96, **kw):
    kw.setdefault('block_size', 8)
    kw.setdefault('prefix_sharing', False)
    return GenerateEngine(GenerateConfig(
        model=kexaone.lm_config(TOY, max_len, False), slots=4,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope())


# ---- 1. the bounded kernel and the ops --------------------------------------

def _window_attend(tier, monkeypatch, q, kc, vc, tables, pos, layer, bs,
                   window):
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    before = monitor.counters()
    ctx = _Ctx(Q=jnp.asarray(q), KCache=jnp.asarray(kc),
               VCache=jnp.asarray(vc), BlockTables=jnp.asarray(tables),
               Positions=jnp.asarray(pos)[:, None])
    get_op('kv_decode_attention_paged').lower(ctx, _Op(
        layer=layer, scale=q.shape[-1] ** -0.5, block_size=bs,
        window=window))
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=kv_decode_attention_paged}' % tier) == 1, moved
    return np.asarray(ctx.outs['Out'])


def _ring_tables(S, ring):
    return (1 + np.arange(S * ring)).reshape(S, ring).astype('int32')


def _plain_window_attention(q, kc, vc, tables, pos, layer, bs, window):
    """Key by key through the ring: position j of slot s lies at
    (tables[s, (j // bs) % ring], j % bs)."""
    S, H, dh = q.shape
    Hkv = kc.shape[3] // dh
    out = np.zeros_like(q)
    for s in range(S):
        js = np.arange(max(0, pos[s] - window + 1), pos[s] + 1)
        blk = tables[s, (js // bs) % tables.shape[1]]
        K = kc[blk, layer, js % bs].reshape(len(js), Hkv, dh)
        V = vc[blk, layer, js % bs].reshape(len(js), Hkv, dh)
        for h in range(H):
            sc = K[:, h // (H // Hkv)] @ q[s, h] * dh ** -0.5
            w = np.exp(sc - sc.max())
            out[s, h] = (w / w.sum()) @ V[:, h // (H // Hkv)]
    return out


# (H, Hkv, bs, dh, window): K-EXAONE's 64 on 8 heads of 128 in pages of 32
# with its window of 128; a block that does not divide the window (the
# grouped body, then the one-query body); a window inside one page
WINDOWED = [(64, 8, 32, 128, 128), (16, 2, 16, 64, 40), (8, 8, 8, 16, 20),
            (16, 2, 16, 64, 8)]


@pytest.mark.parametrize('H,Hkv,bs,dh,window', WINDOWED,
                         ids=['kexaone', 'grouped-40-of-16', 'one-query',
                              'inside-a-page'])
def test_the_bounded_kernel_matches_the_gather(monkeypatch, H, Hkv, bs, dh,
                                               window):
    """Positions inside the window, on its last key, one past it (the
    first key seen lies mid-page), many rings further, at a block's first
    row; every tier against the ring read key by key."""
    rng = np.random.RandomState(0)
    S = 6
    ring = -(-window // bs) + 2
    kc, vc = _pools(rng, S * ring + 1, 2, bs, Hkv * dh)
    tables = _ring_tables(S, ring)
    pos = np.array([0, 5, window - 1, window, 3 * window + 7,
                    40 * bs], 'int32')
    q = rng.randn(S, H, dh).astype('float32')
    want = _plain_window_attention(q, kc, vc, tables, pos, 1, bs, window)
    for tier in ('off', 'xla', 'interpret'):
        got = _window_attend(tier, monkeypatch, q, kc, vc, tables, pos, 1,
                             bs, window)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=tier)


@pytest.mark.parametrize('H,Hkv,planted', [(16, 2, 'k-and-v'), (8, 8, 'k')],
                         ids=['grouped-k-and-v', 'one-query-k'])
def test_a_row_behind_the_window_has_weight_exactly_zero(monkeypatch, H, Hkv,
                                                         planted):
    """A NaN planted in every row of the ring that the query does not see
    — behind the window in its first page, past the position in its last,
    in the ring's other places — adds exactly 0: the output is bit for bit
    what zeros there give. (As for the tail of an unbounded call: the MXU
    body zeroes the V rows it does not see, the VPU body multiplies a
    weight of exactly 0 by the row, so only its keys may hold anything.)"""
    rng = np.random.RandomState(1)
    S, bs, dh, window = 3, 16, 64, 40
    ring = -(-window // bs) + 2
    kc, vc = _pools(rng, S * ring + 1, 1, bs, Hkv * dh)
    tables = _ring_tables(S, ring)
    pos = np.array([7, 59, 200], 'int32')
    q = rng.randn(S, H, dh).astype('float32')
    seen = np.zeros((kc.shape[0], bs), bool)
    for s in range(S):
        js = np.arange(max(0, pos[s] - window + 1), pos[s] + 1)
        seen[tables[s, (js // bs) % ring], js % bs] = True
    assert 0 < seen.sum() == 8 + 40 + 40
    out = []
    for fill in (0.0, np.nan):
        k2, v2 = kc.copy(), vc.copy()
        k2[:, 0][~seen] = fill
        if planted == 'k-and-v':
            v2[:, 0][~seen] = fill
        out.append(_window_attend('interpret', monkeypatch, q, k2, v2,
                                  tables, pos, 0, bs, window))
    assert np.isfinite(out[1]).all()
    np.testing.assert_array_equal(out[1], out[0])


def test_an_unbounded_call_builds_the_kernel_it_always_built(monkeypatch):
    """The bound is a static of the call site: without it the kernel's
    name and its arguments are the parent's, with it the call lowers under
    a name of its own."""
    import jax
    rng = np.random.RandomState(2)
    kc, vc = _pools(rng, 9, 1, 16, 128)
    args = (jnp.asarray(rng.randn(2, 16, 64), jnp.float32), jnp.asarray(kc),
            jnp.asarray(vc), jnp.asarray(_ring_tables(2, 4)),
            jnp.asarray([3, 40], jnp.int32), jnp.int32(0))

    def text(**kw):
        return str(jax.make_jaxpr(lambda *a: pda.paged_decode_attention(
            *a, scale=0.125, interpret=True, **kw))(*args))
    plain, bounded = text(), text(attention_span=24)
    assert 'paged_window_decode_attention' not in plain
    assert 'paged_decode_attention' in plain
    assert 'paged_window_decode_attention' in bounded


@pytest.mark.parametrize('chunks', [(21,), (16, 5), (8, 8, 5), (3, 8, 10)],
                         ids=['whole', 'block-edge', 'three', 'unaligned'])
def test_a_prefill_in_chunks_attends_what_the_ring_holds(chunks):
    """21 rows, a window of 6 in blocks of 4 (ring 4), in one dispatch or
    several, each padded to its bucket: every row attends its own chunk's
    rows and the 5 before them out of the ring, and leaves the chunk's
    last 5 behind."""
    rng = np.random.RandomState(3)
    Hkv, H, dh, bs, W, T = 2, 4, 8, 4, 6, 24
    ring = 4
    k = rng.randn(21, Hkv, dh).astype('float32')
    v = rng.randn(21, Hkv, dh).astype('float32')
    q = rng.randn(21, H, dh).astype('float32')
    kc = rng.randn(6, 2, bs, Hkv * dh).astype('float32')   # stale everywhere
    vc = rng.randn(6, 2, bs, Hkv * dh).astype('float32')
    table = np.array([[3, 1, 4, 2]], 'int32')
    want = np.zeros((21, H, dh), 'float32')
    for i in range(21):
        js = np.arange(max(0, i - W + 1), i + 1)
        for h in range(H):
            sc = k[js, h // 2] @ q[i, h] * dh ** -0.5
            w = np.exp(sc - sc.max())
            want[i, h] = (w / w.sum()) @ v[js, h // 2]
    off, got = 0, []
    attrs = {'layer': 1, 'block_size': bs, 'window': W}
    for n in chunks:
        def padded(x):
            out = np.zeros((1, x.shape[1], T, dh), 'float32')
            out[0, :, :n] = np.swapaxes(x[off:off + n], 0, 1)
            return out
        pos, length = (off + np.arange(T))[None], np.array([[n]])
        out = lower('kv_prefix_attention', dict(attrs, scale=dh ** -0.5),
                    Q=padded(q), KCache=kc, VCache=vc, K=padded(k),
                    V=padded(v), Positions=pos, BlockTable=table,
                    Length=length)['Out']
        got.append(np.swapaxes(out[0, :, :n], 0, 1))
        kc = lower('kv_cache_prefill_paged', attrs, Cache=kc, New=padded(k),
                   Positions=pos, BlockTable=table, Length=length)['Out']
        vc = lower('kv_cache_prefill_paged', attrs, Cache=vc, New=padded(v),
                   Positions=pos, BlockTable=table, Length=length)['Out']
        off += n
    np.testing.assert_allclose(np.concatenate(got), want, rtol=2e-5,
                               atol=2e-6)
    # the ring holds the last 5 rows, each at (table[(j // 4) % 4], j % 4)
    for j in range(16, 21):
        np.testing.assert_array_equal(
            kc[table[0, (j // bs) % ring], 1, j % bs], k[j].reshape(-1))
    # layer 0 of every block is as it was
    assert np.abs(kc[:, 0]).min() > 0


# ---- 2. through the engine, against the reference ---------------------------

@pytest.fixture(scope='module')
def served():
    """`serve_five` (test_olmoe_serving.py) on the toy K-EXAONE block:
    prompts of 5 to 23, inside the window of 12 and past it."""
    eng = _engine(max_len=64)
    eng.warmup()
    return serve_five(eng, TOY['vocab_size'])


def _serve_one(eng, log, prompt, n):
    """One request admitted and stepped by hand (the loop's own path, its
    counters moving), and the logits of its n tokens: the last prefill
    dispatch's row, then its slot's — slot 0's — of each step."""
    del log[:]
    req = eng.submit(prompt, max_new_tokens=n)
    eng._admit()
    while req.finish_reason is None and req._error is None:
        eng._step()
    toks = list(req.result(timeout=5))
    last_prefill = max(i for i, e in enumerate(log) if e[0] == 'prefill')
    return toks, np.stack([log[last_prefill][2][0]]
                          + [e[2][0] for e in log[last_prefill + 1:]])


def test_concurrent_requests_serve_the_references_tokens(served):
    eng = served['eng']
    for i, prompt in enumerate(served['prompts']):
        toks = served['tokens'][i]
        assert len(toks) == served['n_new'][i]
        assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0
    moved = served['moved']
    live = sum(len(p) for p in served['prompts']) \
        + sum(n - 1 for n in served['n_new'])
    # four expert layers a dispatch, two experts a live row, of which the
    # 4 of 16 held are computed here
    assert moved['moe_assignments_total'] == 4 * 2 * live
    assert 0 < moved['moe_held_assignments_total'] \
        < moved['moe_assignments_total']
    # one global layer: every step's live rows once; four window layers: 12
    # keys a slot at most
    steps_rows = sum(n - 1 for n in served['n_new'])
    assert moved['kv_window_tokens_read_total'] % 4 == 0
    assert moved['kv_window_tokens_read_total'] <= 4 * 12 * steps_rows
    assert moved['kv_tokens_read_total'] > \
        moved['kv_window_tokens_read_total'] // 4
    assert moved['kv_window_blocks_recycled_total'] > 0
    assert not any(k.startswith('compile_cache_miss') for k in moved)


# (prompt, new tokens, block): shorter than the window; on its last key;
# one past it; chunked over the 32 bucket and over three windows; a ring
# (4 blocks of 8 = 32 rows; 5 of 4) that wraps twice in the prefill and
# again in the decode steps; a block that divides the window
THROUGH = [(5, 4, 8), (12, 10, 8), (13, 30, 8), (41, 12, 8), (77, 19, 8),
           (70, 26, 4)]


@pytest.mark.parametrize('n_prompt,n_new,bs', THROUGH)
def test_prefill_then_decode_through_both_pools_equals_the_full_forward(
        n_prompt, n_new, bs):
    eng = _engine(block_size=bs)
    eng.warmup()
    log = tap_logits(eng)
    prompt = np.random.RandomState(n_prompt).randint(
        2, TOY['vocab_size'], size=n_prompt).astype('int64')
    before = monitor.counters()
    toks, got = _serve_one(eng, log, prompt, n_new)
    moved = monitor.counter_delta(before)
    assert len(toks) == n_new
    np.testing.assert_array_equal(got.argmax(axis=1), toks)
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(
        eng.scope, TOY, seq, positions=np.arange(n_prompt - 1, len(seq))))
    assert logit_gap(got, want)[1] <= TOLERANCE
    # the prompt past the 32 bucket ran in chunks
    prefills = [e for e in log if e[0] == 'prefill']
    assert len(prefills) == -(-n_prompt // 32)
    # what the steps' attention read: the global layer every live key, the
    # four window layers 12 at most
    at = np.arange(n_prompt, n_prompt + n_new - 1)
    assert moved['kv_tokens_read_total'] == int((at + 1).sum())
    assert moved['kv_window_tokens_read_total'] == \
        4 * int(np.minimum(at + 1, 12).sum())
    # a block of the ring written over for each logical block past the
    # ring's, and the slot's blocks handed back at the end
    ring = T.window_ring(eng.config.model, bs)
    opened = -(-(n_prompt + n_new - 1) // bs)
    assert moved.get('kv_window_blocks_recycled_total', 0) == \
        max(0, opened - ring) + min(opened, ring)


def test_the_window_layers_have_pools_and_a_table_of_their_own(served):
    eng = served['eng']
    cfg = eng.config.model
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.kv_width) == (1, 4, 16)
    assert [cfg.layer_ordinal(i) for i in range(5)] == [0, 1, 2, 0, 3]
    assert [cfg.rotates(i) for i in range(5)] == [True] * 3 + [False, True]
    nb, ring = eng.config.num_blocks, T.window_ring(cfg, 8)
    assert ring == 4                       # ceil(12 / 8) + 2
    assert T.kv_cache_names(cfg) == (T.KV_CACHE_K, T.KV_CACHE_V,
                                     T.WINDOW_CACHE_K, T.WINDOW_CACHE_V)
    assert {n: tuple(eng.scope.get(n).shape)
            for n in T.kv_cache_names(cfg)} == {
        T.KV_CACHE_K: (nb, 1, 8, 16), T.KV_CACHE_V: (nb, 1, 8, 16),
        T.WINDOW_CACHE_K: (4 * ring + 1, 4, 8, 16),
        T.WINDOW_CACHE_V: (4 * ring + 1, 4, 8, 16)}
    ops = eng._step_vars['tokens'].block.ops
    attends = [op for op in ops if op.type == 'kv_decode_attention_paged']
    assert [op.attr('window', None) for op in attends] == \
        [12, 12, 12, None, 12]
    assert [op.inputs['BlockTables'] for op in attends] == \
        [['gen_wtab']] * 3 + [['gen_btab'], ['gen_wtab']]
    assert [op.inputs['KCache'] for op in attends] == \
        [[T.WINDOW_CACHE_K]] * 3 + [[T.KV_CACHE_K], [T.WINDOW_CACHE_K]]
    writes = [(op.inputs['Cache'][0], op.attr('layer'),
               op.attr('ring', False))
              for op in ops if op.type == 'kv_cache_update_paged']
    assert writes == [
        (name, layer, ring_) for layer, ring_, names in (
            (0, True, 'w'), (1, True, 'w'), (2, True, 'w'), (0, False, 'g'),
            (3, True, 'w'))
        for name in ((T.WINDOW_CACHE_K, T.WINDOW_CACHE_V) if names == 'w'
                     else (T.KV_CACHE_K, T.KV_CACHE_V))]
    rotated = [op for op in ops if op.type == 'rotary_embedding']
    assert len(rotated) == 2 * 4           # q and k of the window layers


def test_the_window_pool_does_not_grow_with_the_context():
    """Four slots through contexts of up to 90 positions: the window
    layers' blocks in use never pass slots x ring and are back at 0 when
    the slots empty; the global pool counts every block of every context,
    as before."""
    eng = _engine()
    eng.warmup()
    ring = eng.stats()['blocks']['window']['ring']
    assert eng.stats()['blocks']['window'] == {
        'capacity': 4 * ring, 'ring': ring, 'in_use': 0}
    rng = np.random.RandomState(4)
    reqs = [eng.submit(rng.randint(2, 97, size=n), max_new_tokens=k)
            for n, k in ((68, 26), (3, 30), (40, 35), (64, 26), (11, 5))]
    eng._admit()
    peak_window = peak_global = 0
    while any(r.finish_reason is None and r._error is None for r in reqs):
        eng._step()
        eng._admit()
        blocks = eng.stats()['blocks']
        assert blocks['window']['in_use'] <= 4 * ring
        peak_window = max(peak_window, blocks['window']['in_use'])
        peak_global = max(peak_global, blocks['in_use'])
    assert [len(r.result(timeout=5)) for r in reqs] == [26, 30, 35, 26, 5]
    assert peak_window == 4 * ring         # every ring filled at some step
    assert peak_global > 2 * 4 * ring      # ... under contexts far longer
    blocks = eng.stats()['blocks']
    assert blocks['window']['in_use'] == 0 and blocks['in_use'] == 0
    assert all(r.finish_reason == 'length' for r in reqs)


def test_window_rings_account_a_slots_blocks():
    rings = WindowRings(slots=3, ring=4, block_size=8)
    assert (rings.capacity, rings.in_use()) == (12, 0)
    # a column gets its block when its tenant opens it (0: the trash block)
    assert rings.table(0) == [0, 0, 0, 0] == rings.table(2)
    assert rings.advance(1, 20) == 0 and rings.in_use() == 3
    assert rings.table(1) == [1, 2, 3, 0] and rings.moved() == []
    assert rings.advance(1, 33) == 1       # a fifth block: one written over
    assert rings.advance(1, 33) == 0 and rings.in_use() == 4
    assert rings.advance(0, 100) == 13 - 4 and rings.in_use() == 8
    assert rings.release(1) == 4 and rings.release(1) == 0
    assert rings.release(0) == 4 and rings.in_use() == 0


# ---- 3. the controls --------------------------------------------------------

@pytest.fixture(scope='module')
def long_run():
    """One prompt of 41 tokens and 12 more through the engine: the
    system's logits, and the reference's."""
    eng = _engine()
    eng.warmup()
    log = tap_logits(eng)
    prompt = np.random.RandomState(41).randint(2, 97, size=41).astype('int64')
    toks, got = _serve_one(eng, log, prompt, 12)
    seq = np.concatenate([prompt, toks[:-1]])
    pos = np.arange(40, len(seq))
    return dict(eng=eng, seq=seq, pos=pos, got=got, want=np.asarray(
        ref.logits(eng.scope, TOY, seq, positions=pos)))


@pytest.mark.parametrize('control', sorted(kexaone_control.controls(TOY)))
def test_a_control_is_outside_the_tolerance(long_run, control):
    kw = kexaone_control.controls(TOY)[control]
    wrong = np.asarray(ref.logits(long_run['eng'].scope, TOY,
                                  long_run['seq'],
                                  positions=long_run['pos'], **kw))
    assert logit_gap(long_run['got'], long_run['want'])[1] <= TOLERANCE
    assert logit_gap(wrong, long_run['want'])[1] > 20 * TOLERANCE, control


def test_the_chip_comparison_runs_at_toy_width(long_run):
    eng = long_run['eng']
    engine = {'slots': 4, 'max_len': 96, 'block_size': 8, 'num_blocks':
              eng.config.num_blocks, 'prompt_buckets': [16, 32]}
    out = kexaone_control.compare(eng.config.model, engine, eng.scope, TOY,
                                  long_run['seq'][:41], 12)
    assert out['rows'] == 13 and out['logits_vs_ref'][1] <= TOLERANCE
    assert out['logits_vs_ref_given_routing'][1] <= TOLERANCE
    assert out['greedy_margin_worst'] == 0.0
    for name, reading in out['controls'].items():
        assert reading['logits_vs_ref'][1] > 20 * TOLERANCE, name
    eng._ensure_cache()


# ---- 4. Mosaic, at the cell's shapes ----------------------------------------

@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for k, v in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
                 ('TPU_WORKER_HOSTNAMES', 'localhost'),
                 ('TPU_SKIP_MDS_QUERY', '1')):
        os.environ.setdefault(k, v)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('span', [128, None], ids=['window', 'global'])
def test_mosaic_accepts_the_kernel_at_kexaones_shapes(one_chip, span):
    """64 query heads on 8 K/V heads of 128 in pages of 32: a page of 1024
    lanes, exactly two key-windows in the ring. The window layers' call
    over the 385-block pool and its 6-column rings, the global layer's
    over 10 240 blocks and a table of 160."""
    import jax
    assert pda.shapes_ok(64, 128, 32, 8)
    assert pda.ring_depth(8, 128, 32, 512 // 32) == 2 * (512 // 32)
    nb, ln, mb = (385, 4, 6) if span else (10240, 1, 160)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kw = {'attention_span': span} if span else {}
    compiled = jax.jit(lambda q, k, v, t, p, l: pda.paged_decode_attention(
        q, k, v, t, p, l, scale=128 ** -0.5, **kw)).lower(
        sds((64, 64, 128)), sds((nb, ln, 32, 1024)),
        sds((nb, ln, 32, 1024)), sds((64, mb), jnp.int32),
        sds((64,), jnp.int32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    assert ('paged_window_decode_attention' in text) == bool(span)


# ---- 5. the refusals --------------------------------------------------------

@pytest.mark.parametrize('option', ['prefix_sharing', 'speculative'])
def test_sharing_and_speculation_are_refused_for_window_layers(option):
    """Speculation is; since PR 51 a prefix is shared over window layers
    (tests/test_mellum2_serving.py has what that takes), and only then do
    the window pools hold more than the slots' rings."""
    if option == 'prefix_sharing':
        plain, shared = _engine(), _engine(prefix_sharing=True)
        ring = T.window_ring(plain.config.model, plain.config.block_size)
        rings = plain.config.slots * ring
        for eng, blocks in ((plain, rings + 1),
                            (shared, rings + 1 + rings // 2)):
            assert [p.shape[0] for p in eng._pools
                    if p.index == 'ring'] == [blocks] * 2
        return
    with pytest.raises(ValueError, match=r'%s=True with '
                                         r'LMConfig\.layer_types' % option):
        _engine(**{option: True})


REFUSERS = {
    'build_lm': lambda cfg: T.build_lm(cfg, is_test=True),
    'build_lm_drafter': lambda cfg: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
    'build_lm_verify': lambda cfg: T.build_lm_verify(cfg, 2, 3, 32, 9, 8),
}


@pytest.mark.parametrize('builder', sorted(REFUSERS))
def test_the_other_builders_refuse_window_layers_by_name(builder):
    cfg = LMConfig(vocab_size=64, seq_len=32, d_model=64, n_head=4,
                   n_layer=2, d_ff=32, dropout=0.0, sliding_window=8,
                   layer_types=['window', 'attention'])
    with program_guard(Program(), Program()):
        with pytest.raises(ValueError, match=r'LMConfig\.layer_types='):
            REFUSERS[builder](cfg)


def test_lmconfig_refuses_a_window_without_its_size_and_the_reverse():
    with pytest.raises(ValueError, match=r'LMConfig\.layer_types'):
        LMConfig(n_layer=2, layer_types=['attention', 'window'])
    with pytest.raises(ValueError, match=r'sliding_window'):
        LMConfig(n_layer=2, sliding_window=16)
    with pytest.raises(ValueError, match="'window'"):
        LMConfig(attention='mla', position='rope', q_lora_rank=8,
                 kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
                 n_head=4, n_layer=2, layer_types=['window', 'attention'],
                 sliding_window=8)
    cfg = LMConfig(n_layer=2, layer_types=['window', 'attention'],
                   sliding_window=8)
    with pytest.raises(ValueError, match='sized by the slots'):
        T.kv_cache_shapes(cfg, 9, 8)
