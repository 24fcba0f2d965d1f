"""The Jamba block in the Program path (ISSUE 43): Mamba-1 layers whose
recurrent state and convolution tail live A ROW A SLOT in two pools of
their own, beside multi-query attention layers in the block pool. The
two ops' every tier against the position-by-position recurrence, prefill
(whole, padded, in chunks) then decode through the pools against the plain
reference's FULL forward pass (logits, not tokens), a slot served twice, a
decode step between two chunks of one prompt, a step in flight at
`_release`, the rows' accounting, the counters, the padded group of the
paged decode kernel and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-jamba.json): d 64,
d_inner 128, 16 states, 4 taps, dt_rank 8, 4 query heads on ONE K/V head
of 16, 5 layers (mamba mamba attention mamba mamba), seeded weights with
Mamba's own initialisation of the recurrence.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import Scope, monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.ops import ssm_ops
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import jamba
from benchmark.reference import jamba_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import lower
from test_paged_decode_attention import _attend, _pools

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-jamba.json')) as _f:
    TOY = json.load(_f)

# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 1e-7 to 6e-7 over every comparison below); the controls move the
# logits by 3e-3 (a stale state, a chunk from zeros) to 8e-2.
TOLERANCE = 1e-4
STATE, TAIL = T.SSM_STATE, T.SSM_TAIL


def _scope(seed=5):
    scope = Scope()
    for name, value in jamba.init_params(TOY, seed).items():
        scope.set(name, value)
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=160, slots=4, **kw):
    kw.setdefault('block_size', 8)
    kw.setdefault('prefix_sharing', False)
    return GenerateEngine(GenerateConfig(
        model=jamba.lm_config(TOY, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope())


def _prompt(n, seed=None):
    return np.random.RandomState(n if seed is None else seed).randint(
        2, TOY['vocab_size'], size=n).astype('int64')


# ---- 1. the ops against the recurrence, position by position ----------------

def _weights(rng, di, n, r, k):
    w = {'ConvW': 0.3 * rng.randn(di, k), 'ConvB': 0.1 * rng.randn(di),
         'XProj': 0.2 * rng.randn(di, r + 2 * n),
         'DtNorm': 1 + 0.1 * rng.randn(r), 'BNorm': 1 + 0.1 * rng.randn(n),
         'CNorm': 1 + 0.1 * rng.randn(n), 'DtProj': 0.3 * rng.randn(r, di),
         'DtBias': np.log(np.expm1(np.exp(rng.uniform(
             np.log(1e-3), np.log(1e-1), di)))),
         'ALog': np.broadcast_to(np.log(np.arange(1, n + 1))[:, None],
                                 (n, di)),
         'D': 1 + 0.1 * rng.randn(di)}
    return {name: np.ascontiguousarray(v, 'float32')
            for name, v in w.items()}


def _walk(w, u, z, s, tail, eps=1e-6):
    """The layer's rows one position at a time, in float64: (the gated
    outputs [T, di], the state, the tail) after rows `u`, `z` [T, di]
    from the state `s` [N, di] and the tail [K - 1, di]."""
    w = {k: v.astype('float64') for k, v in w.items()}
    n, r = w['ALog'].shape[0], w['DtProj'].shape[0]
    s, tail = s.astype('float64'), tail.astype('float64')
    a = -np.exp(w['ALog'])

    def rms(x, g):
        return x / np.sqrt((x * x).mean() + eps) * g
    out = []
    for u_t, z_t in zip(u.astype('float64'), z.astype('float64')):
        window = np.concatenate([tail, u_t[None]])
        c = (window * w['ConvW'].T).sum(0) + w['ConvB']
        c = c / (1 + np.exp(-c))
        x = c @ w['XProj']
        dt = rms(x[:r], w['DtNorm'])
        b, cc = rms(x[r:r + n], w['BNorm']), rms(x[r + n:], w['CNorm'])
        delta = np.logaddexp(0, dt @ w['DtProj'] + w['DtBias'])
        s = np.exp(delta[None] * a) * s + (delta * c)[None] * b[:, None]
        y = (s * cc[:, None]).sum(0) + w['D'] * c
        out.append(y * z_t / (1 + np.exp(-z_t)))
        tail = window[1:]
    return np.stack(out), s, tail


TIERS = ['off', 'xla', 'interpret']


@pytest.mark.parametrize('tier', TIERS)
def test_ssm_decode_steps_every_live_row_and_no_other(monkeypatch, tier):
    """Four slots: rows 3, 0 (sits out), 1 and 0. The live rows read their
    state and tail, step once and write both back; the rows fed 0 read
    zeros and write the trash row; rows 2 and 4 of the pools and the other
    layer stand bit for bit."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    rng = np.random.RandomState(0)
    di, n, r, k, S = 256, 16, 8, 4, 4
    w = _weights(rng, di, n, r, k)
    state = rng.randn(5, 2, n, di).astype('float32')
    tails = rng.randn(5, 2, 8, di).astype('float32')
    u, z = rng.randn(2, S, di).astype('float32')
    rows = np.array([3, 0, 1, 0])[:, None]
    before = monitor.counters()
    out = lower('ssm_decode', {'layer': 1, 'epsilon': 1e-6}, X=u, Z=z,
                State=state, Tail=tails, Rows=rows, **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=ssm_decode}' % tier) == 1, moved
    got, new_state, new_tails = (np.asarray(out[x]) for x in
                                 ('Out', 'StateOut', 'TailOut'))
    for i, row in enumerate(rows[:, 0]):
        s0 = state[row, 1] if row else np.zeros((n, di))
        t0 = tails[row, 1, :k - 1] if row else np.zeros((k - 1, di))
        want, s1, t1 = _walk(w, u[i:i + 1], z[i:i + 1], s0, t0)
        np.testing.assert_allclose(got[i], want[0], rtol=2e-5, atol=2e-5)
        if row:
            np.testing.assert_allclose(new_state[row, 1], s1, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(new_tails[row, 1, :k - 1], t1, rtol=1e-6)
    for row in (2, 4):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])
    np.testing.assert_array_equal(new_tails[:, 0], tails[:, 0])


# (rows of the bucket, real rows, first position): a whole bucket from
# zeros; pad rows; a later chunk that resumes; a bucket that is no
# multiple of the xla tier's chunk of 16 rows; one real row
SCANS = [(32, 32, 0), (32, 21, 0), (64, 50, 128), (24, 24, 7), (16, 1, 0)]


@pytest.mark.parametrize('tier', TIERS)
@pytest.mark.parametrize('T_,length,off', SCANS)
def test_ssm_prefill_scans_the_real_rows_alone(monkeypatch, tier, T_,
                                               length, off):
    """The chunked scan (`xla` / `off`: an associative scan inside chunks
    of 16 rows; `interpret`: the kernel, strips of 512 lanes, chunks of
    rows) against the recurrence position by position. From position 0 the
    row's content is never read; past it the scan resumes from it; pad
    rows leave the state and the tail as of the last real row."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    rng = np.random.RandomState(T_ + length)
    di, n, r, k = 1024, 16, 8, 4
    w = _weights(rng, di, n, r, k)
    state = rng.randn(3, 2, n, di).astype('float32')
    tails = rng.randn(3, 2, 8, di).astype('float32')
    u, z = rng.randn(2, 1, T_, di).astype('float32')
    before = monitor.counters()
    out = lower('ssm_prefill', {'layer': 0, 'epsilon': 1e-6}, X=u, Z=z,
                State=state, Tail=tails, Rows=np.array([[2]]),
                Positions=(off + np.arange(T_))[None],
                Length=np.array([[length]]), **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=ssm_prefill}' % tier) == 1, moved
    s0 = state[2, 0] if off else np.zeros((n, di))
    t0 = tails[2, 0, :k - 1] if off else np.zeros((k - 1, di))
    want, s1, t1 = _walk(w, u[0, :length], z[0, :length], s0, t0)
    np.testing.assert_allclose(np.asarray(out['Out'])[0, :length], want,
                               rtol=1e-4, atol=1e-4)
    new_state, new_tails = np.asarray(out['StateOut']), \
        np.asarray(out['TailOut'])
    np.testing.assert_allclose(new_state[2, 0], s1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(new_tails[2, 0, :k - 1], t1, rtol=1e-6)
    np.testing.assert_array_equal(new_tails[2, 1], tails[2, 1])
    np.testing.assert_array_equal(new_state[:2], state[:2])
    np.testing.assert_array_equal(new_state[2, 1], state[2, 1])
    np.testing.assert_array_equal(new_tails[:2], tails[:2])


def test_the_kernels_take_whole_tiles_only(monkeypatch):
    """A width that is no whole vreg of lanes, a state that fills no
    sublane tile, a bucket of 12 rows: the request for the kernel lands on
    `xla`."""
    assert ssm_ops.shapes_ok(5120, 16, 512) and ssm_ops.shapes_ok(128, 16)
    assert not ssm_ops.shapes_ok(96, 16) and not ssm_ops.shapes_ok(128, 4)
    assert not ssm_ops.shapes_ok(128, 16, 12)
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    rng = np.random.RandomState(1)
    w = _weights(rng, 96, 16, 4, 4)
    before = monitor.counters()
    lower('ssm_decode', {'layer': 0, 'epsilon': 1e-6},
          X=np.zeros((2, 96), 'float32'), Z=np.zeros((2, 96), 'float32'),
          State=np.zeros((3, 1, 16, 96), 'float32'),
          Tail=np.zeros((3, 1, 8, 96), 'float32'),
          Rows=np.array([[1], [2]]), **w)
    assert monitor.counter_delta(before).get(
        'fused_kernel_dispatch_total{impl=xla,mesh=1,op=ssm_decode}') == 1


@pytest.mark.parametrize('S,H,dh,bs,MB', [(5, 20, 128, 32, 8),
                                          (3, 12, 64, 16, 6)],
                         ids=['jamba-20-on-1', '12-on-2'])
def test_the_paged_kernel_pads_a_group_to_whole_sublanes(monkeypatch, S, H,
                                                         dh, bs, MB):
    """Jamba's 20 queries on one K/V head of 128 run as 24 rows of the MXU
    body, 6 queries a head on 2 heads as 8: the kernel against the gather,
    the added rows' output dropped. LFM2's and K-EXAONE's groups stand
    (`padded_group`)."""
    Hkv = 1 if H == 20 else 2
    assert pda.padded_group(20, 1) == 24 and pda.padded_group(6, 2) == 8
    assert pda.padded_group(4, 8) == 4 and pda.padded_group(8, 8) == 8
    assert pda.shapes_ok(H, dh, bs, Hkv)
    rng = np.random.RandomState(H)
    kc, vc = _pools(rng, S * MB + 1, 2, bs, Hkv * dh)
    tables = (1 + rng.permutation(S * MB)).reshape(S, MB).astype('int32')
    pos = np.array([0, bs - 1, bs, 3 * bs + 5, MB * bs - 1][:S], 'int32')
    q = rng.randn(S, H, dh).astype('float32')
    want = _attend('off', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    got = _attend('interpret', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---- 2. the programs --------------------------------------------------------

def test_the_pools_are_a_row_a_slot_and_only_an_ssm_model_has_them():
    cfg = jamba.lm_config(TOY, 64, False)
    assert cfg.layer_types == ('ssm', 'ssm', 'attention', 'ssm', 'ssm')
    assert (cfg.n_ssm_layers, cfg.n_attn_layers, cfg.ssm_inner) == (4, 1,
                                                                    128)
    assert [cfg.layer_ordinal(i) for i in range(5)] == [0, 1, 0, 2, 3]
    assert not any(cfg.rotates(i) for i in range(5))
    assert T.kv_cache_names(cfg) == (T.KV_CACHE_K, T.KV_CACHE_V, STATE, TAIL)
    assert T.kv_cache_shapes(cfg, 28, 8, 3) == {
        T.KV_CACHE_K: (28, 1, 8, 16), T.KV_CACHE_V: (28, 1, 8, 16),
        STATE: (4, 4, 16, 128), TAIL: (4, 4, 8, 128)}
    with pytest.raises(ValueError, match='sized by the slots'):
        T.kv_cache_shapes(cfg, 28, 8)
    assert LMConfig(d_model=2560, n_head=20, n_layer=1,
                    layer_types=['ssm']).ssm_dt_rank == 160
    # a model without such layers declares neither pool nor feed
    plain = LMConfig(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    assert T.kv_cache_names(plain) == (T.KV_CACHE_K, T.KV_CACHE_V)
    eng = GenerateEngine(GenerateConfig(
        model=plain, slots=2, max_len=32, prompt_buckets=[8], block_size=8))
    ops = {op.type for op in eng._step_prog.global_block().ops}
    assert not ops & {'ssm_decode', 'ssm_prefill'}
    assert 'gen_srow' not in eng._step_prog.global_block().vars
    assert 'state' not in eng.stats()
    assert 'gen_srow' not in eng._tables_feed(np.zeros((2, 4), 'int64'),
                                              [(0, 0)])


def test_the_programs_hand_each_ssm_layer_its_ordinal_and_the_rows():
    eng = _engine()
    for prog, op_type in [(eng._step_prog, 'ssm_decode')] + [
            (p, 'ssm_prefill') for p, _ in eng._prefill.values()]:
        ops = [op for op in prog.global_block().ops if op.type == op_type]
        assert [op.attr('layer') for op in ops] == [0, 1, 2, 3]
        assert all(op.input('Rows') == ['gen_srow'] for op in ops)
        assert all(op.input('State') == [STATE] and op.output('StateOut')
                   == [STATE] for op in ops)
        assert all(op.attr('epsilon') == 1e-6 for op in ops)
        types = [op.type for op in prog.global_block().ops]
        assert 'rotary_embedding' not in types
        assert types.count('kv_decode_attention_paged'
                           if op_type == 'ssm_decode'
                           else 'kv_prefix_attention') == 1
    names = set(eng.scope.names())
    assert set(jamba.param_shapes(TOY)) <= names
    feed = eng._tables_feed(np.zeros((4, 20), 'int64'), [(0, 2), (3, 0)])
    np.testing.assert_array_equal(feed['gen_srow'][:, 0], [3, 0, 0, 1])


def test_the_startup_program_takes_mambas_initialisation():
    """An engine without a scope of weights: A_log = log(1 .. N) a
    channel, D = 1, the step's bias the inverse softplus of 0.01."""
    eng = GenerateEngine(GenerateConfig(
        model=jamba.lm_config(TOY, 32, False), slots=2, max_len=32,
        prompt_buckets=[8], block_size=8, prefix_sharing=False, seed=1))
    a_log = np.asarray(eng.scope.get('layer_0.ssm.A_log'))
    assert a_log.shape == (16, 128)
    np.testing.assert_allclose(a_log, np.broadcast_to(
        np.log(np.arange(1, 17))[:, None], (16, 128)), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(eng.scope.get('layer_0.ssm.D')),
                                  1.0)
    bias = np.asarray(eng.scope.get('layer_4.ssm.dt.b'))
    np.testing.assert_allclose(np.logaddexp(0, bias), 0.01, rtol=1e-5)
    assert list(eng.generate_once(_prompt(11), max_new_tokens=3))


# ---- 3. through the engine, against the reference ---------------------------

def _tap(eng):
    """Rebind a warmed engine's programs with their logits fetched beside
    the tokens; every dispatch's (kind, feed, logits) goes to the list
    returned (test_olmoe_serving.tap_logits, for a model without
    experts)."""
    log = []

    def tapped(bound, kind):
        def call(feed, return_numpy=True):
            out = bound(feed, return_numpy=return_numpy)
            log.append((kind, {k: np.array(x) for k, x in feed.items()},
                        np.asarray(out[1])))
            return out
        return call
    S, mb = eng.config.slots, eng._max_blocks
    for b, (prog, v) in eng._prefill.items():
        feed = {'gen_prompt': np.zeros((1, b), 'int64'),
                'gen_pos': np.zeros((1, b), 'int64'),
                'gen_len': np.ones((1, 1), 'int64')}
        feed.update(eng._tables_feed(np.zeros((1, mb), 'int64')))
        feed.update(eng._sample_feed(1))
        eng._prefill_bound[b] = tapped(eng.executor.bind(
            prog, feed, scope=eng.scope,
            fetch_list=[v['first_token'], v['logits']]), 'prefill')
    feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
            'gen_pos': np.zeros((S, 1), 'int64')}
    feed.update(eng._tables_feed(np.zeros((S, mb), 'int64')))
    feed.update(eng._sample_feed(S))
    eng._step_bound = tapped(eng.executor.bind(
        eng._step_prog, feed, scope=eng.scope,
        fetch_list=[eng._step_vars['next_tokens'],
                    eng._step_vars['logits']]), 'step')
    return log


def _serve_one(eng, log, prompt, n):
    """One request admitted and stepped by hand (the loop's own path, its
    counters moving): (its tokens, the logits of each — the last prefill
    dispatch's row, then its slot's of each step —, its slot)."""
    del log[:]
    req = eng.submit(prompt, max_new_tokens=n)
    eng._admit()
    slot = next(i for i, s in enumerate(eng._slots) if s is not None
                and s.req is req)
    while req.finish_reason is None and req._error is None:
        eng._step()
    toks = list(req.result(timeout=5))
    last_prefill = max(i for i, e in enumerate(log) if e[0] == 'prefill')
    return toks, np.stack([log[last_prefill][2][0]]
                          + [e[2][slot] for e in log[last_prefill + 1:]]), \
        slot


def _want(scope, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(
        scope, TOY, seq, positions=np.arange(len(prompt) - 1, len(seq))))


# (prompt, new tokens, buckets, max_len): one bucket filled; a bucket with
# pad rows; one row; THREE chunks of the widest bucket, the last padded;
# two chunks that end on a bucket's edge; several hundred positions in
# chunks of 128 with Mamba's initialisation (the slowest channels keep
# 0.999 of their state a position)
THROUGH = [(16, 5, (16, 32), 160), (21, 9, (16, 32), 160),
           (1, 4, (16, 32), 160), (75, 12, (16, 32), 160),
           (64, 6, (16, 32), 160), (300, 24, (32, 64, 128), 384)]


@pytest.mark.parametrize('n_prompt,n_new,buckets,max_len', THROUGH)
def test_prefill_then_decode_through_the_state_pool_equals_the_full_forward(
        n_prompt, n_new, buckets, max_len):
    eng = _engine(buckets=buckets, max_len=max_len)
    eng.warmup()
    log = _tap(eng)
    prompt = _prompt(n_prompt)
    before = monitor.counters()
    toks, got, slot = _serve_one(eng, log, prompt, n_new)
    moved = monitor.counter_delta(before)
    assert len(toks) == n_new
    np.testing.assert_array_equal(got.argmax(axis=1), toks)
    assert logit_gap(got, _want(eng.scope, prompt, toks))[1] <= TOLERANCE
    assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0
    wide = max(buckets)
    prefills = [e for e in log if e[0] == 'prefill']
    assert len(prefills) == -(-n_prompt // wide)
    # every dispatch was fed the slot's row, every step the row alone
    assert all(e[1]['gen_srow'][0, 0] == slot + 1 for e in prefills)
    steps = [e for e in log if e[0] == 'step']
    for e in steps:
        want_rows = np.zeros(4, 'int64')
        want_rows[slot] = slot + 1
        np.testing.assert_array_equal(e[1]['gen_srow'][:, 0], want_rows)
    # the scans walked the real rows, four layers each; the later chunks
    # resumed from the row; every step advanced one row a layer
    assert moved['ssm_prefill_rows_total'] == 4 * n_prompt
    assert moved.get('ssm_state_resumes_total', 0) == len(prefills) - 1
    assert moved['ssm_state_rows_updated_total'] == 4 * len(steps)
    # the one attention layer's K/V rows alone
    at = np.arange(n_prompt, n_prompt + n_new - 1)
    assert moved['kv_tokens_read_total'] == int((at + 1).sum())
    assert 'conv_tail_resumes_total' not in moved
    assert eng.stats()['state'] == {'capacity': 4, 'in_use': 0}


def test_a_slot_served_twice_gives_the_second_tenant_its_own_logits():
    """One slot, so the second request sits on the first's row: its logits
    are BIT FOR BIT those of a fresh engine that served it alone — the
    first chunk at position 0 never reads the row — and the reference's."""
    scope = _scope()
    eng = _engine(scope, slots=1)
    eng.warmup()
    log = _tap(eng)
    first, second = _prompt(40), _prompt(37, seed=9)
    _serve_one(eng, log, first, 7)
    state = np.asarray(eng.scope.get(STATE))
    assert np.abs(state[1]).max() > 0          # the first tenant's, left
    toks, got, slot = _serve_one(eng, log, second, 8)
    assert slot == 0
    alone = _engine(_scope(), slots=1)
    alone.warmup()
    toks_alone, got_alone, _ = _serve_one(alone, _tap(alone), second, 8)
    assert toks == toks_alone
    np.testing.assert_array_equal(got, got_alone)
    assert logit_gap(got, _want(eng.scope, second, toks))[1] <= TOLERANCE
    # what a row left in place would have served: the reference started
    # from the first tenant's state is another forward
    stale = ref.forward(scope, TOY, np.concatenate([first, [3] * 6]))[1]
    seq = np.concatenate([second, toks[:-1]])
    wrong = np.asarray(ref.logits(
        scope, TOY, seq, positions=np.arange(len(second) - 1, len(seq)),
        init_states=stale))
    assert logit_gap(wrong, _want(scope, second, toks))[1] > 10 * TOLERANCE


def _hold_slot(eng, prompt):
    """A slot and blocks taken as `_admit_one` takes them, for a prefill
    dispatched by hand."""
    slot = eng._free.pop()
    blocks = eng._alloc_blocks(-(-(len(prompt) + 8) // eng.config.block_size))
    return slot, blocks, eng._slot_table(blocks)


def test_a_step_between_two_chunks_leaves_the_chunked_slots_row():
    """A resident decodes while another slot's prompt is between its first
    and its second chunk (`_admit_run` gives a chunk a pass): that slot is
    not resident, the step feeds it row 0, and its rows of both pools
    stand BIT FOR BIT; the prompt's last chunk then resumes from them and
    the first token's logits are the reference's."""
    eng = _engine()
    eng.warmup()
    log = _tap(eng)
    resident = eng.submit(_prompt(12), max_new_tokens=30)
    eng._admit()
    eng._step()
    prompt = _prompt(75)
    slot, blocks, table = _hold_slot(eng, prompt)
    sample = (0.0, 0, 0.0, 0.0)
    out, off = eng._prefill_dispatch(prompt, 0, table, sample,
                                     eng._prefill_bound, slot)
    assert off == 32
    assert eng.stats()['state']['in_use'] == 2
    rows = [np.asarray(eng.scope.get(name))[slot + 1].copy()
            for name in (STATE, TAIL)]
    assert np.abs(rows[0]).max() > 0
    for _ in range(3):
        eng._step()
        assert log[-1][0] == 'step'
        assert log[-1][1]['gen_srow'][slot, 0] == 0     # sits out
    for name, was in zip((STATE, TAIL), rows):
        np.testing.assert_array_equal(
            np.asarray(eng.scope.get(name))[slot + 1], was)
    while off < len(prompt):
        out, off = eng._prefill_dispatch(prompt, off, table, sample,
                                         eng._prefill_bound, slot)
    got = log[-1][2]
    want = np.asarray(ref.logits(eng.scope, TOY, prompt,
                                 positions=[len(prompt) - 1]))
    assert logit_gap(got, want)[1] <= TOLERANCE
    assert resident.finish_reason is None
    eng._deref_blocks(blocks)
    eng._free.append(slot)


def test_a_step_in_flight_at_release_does_not_reach_the_next_tenant():
    """A row is released while a decode step that holds it is dispatched
    and not fetched (a finish by `eos`, an eviction): the step advances
    the departed tenant's state once more. The next tenant of the slot
    starts from zeros at position 0, after it in dispatch order, and
    serves the logits it serves alone."""
    eng = _engine(slots=1)
    eng.warmup()
    log = _tap(eng)
    eng.submit(_prompt(20), max_new_tokens=50)
    eng._admit()
    eng._step()
    before = np.asarray(eng.scope.get(STATE))[1].copy()
    flight = eng._step_dispatch()
    assert flight is not None and eng._slots[0] is not None
    st = eng._slots[0]
    eng._release(0)
    st.req._finish('stop')
    eng._step_complete(flight)
    # the stale step did write the row
    assert np.abs(np.asarray(eng.scope.get(STATE))[1] - before).max() > 0
    assert eng.stats()['state']['in_use'] == 0
    second = _prompt(33, seed=4)
    toks, got, slot = _serve_one(eng, log, second, 6)
    assert slot == 0
    assert logit_gap(got, _want(eng.scope, second, toks))[1] <= TOLERANCE


def test_the_state_never_passes_the_slots_and_returns_to_zero():
    """Nine requests through three slots, prompts over the widest bucket
    among them, the engine's own loop: `stats()['state']['in_use']` never
    passes the slots, ends at 0, and every request served the reference's
    tokens."""
    import threading
    eng = _engine(slots=3)
    eng.warmup()
    seen, stop = [], threading.Event()

    def watch():
        while not stop.wait(0.002):
            seen.append(eng.stats()['state']['in_use'])
    watcher = threading.Thread(target=watch, daemon=True)
    prompts = [_prompt(n) for n in (5, 70, 33, 16, 90, 8, 41, 64, 12)]
    with eng:
        watcher.start()
        handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
        tokens = [list(h.result(timeout=120)) for h in handles]
        stop.set()
        watcher.join()
    assert seen and max(seen) <= 3
    assert eng.stats()['state'] == {'capacity': 3, 'in_use': 0}
    for prompt, toks in zip(prompts, tokens):
        assert len(toks) == 10
        assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0


@pytest.mark.parametrize('option', ['prefix_sharing', 'speculative'])
def test_an_ssm_model_refuses_sharing_and_speculation_by_name(option):
    """Speculation is refused by name; sharing, refused until PR 58, builds
    an engine whose state rows have a snapshot row a slot behind them."""
    kw = {'prefix_sharing': False}
    kw[option] = True

    def build():
        return GenerateEngine(GenerateConfig(
            model=jamba.lm_config(TOY, 64, False), slots=2, max_len=64,
            prompt_buckets=[16], block_size=8, **kw))
    if option == 'prefix_sharing':
        assert build().stats()['state']['snapshots'] == {'rows': 2,
                                                         'in_use': 0}
        return
    with pytest.raises(ValueError, match=r"%s=True with LMConfig\."
                       r"layer_types=.*'ssm'.*state-space" % option):
        build()


def test_the_classic_builders_refuse_the_block_by_name():
    cfg = jamba.lm_config(TOY, 32, False)
    with pytest.raises(ValueError, match='LMConfig.norm'):
        T.build_lm(cfg)
    with pytest.raises(ValueError, match="'ssm'"):
        LMConfig(n_layer=2, layer_types=['ssm', 'mamba'])
    with pytest.raises(ValueError, match='mla'):
        LMConfig(n_layer=1, layer_types=['ssm'], attention='mla',
                 position='rope', q_lora_rank=8, kv_lora_rank=8,
                 qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
