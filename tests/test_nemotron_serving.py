"""The Nemotron-H block in the Program path (ISSUE 48): layers of ONE
sublayer each -- a Mamba-2 mixer whose matrix state a head and convolution
tail live A ROW A SLOT in two pools of their own, grouped-query attention
without rotation in the block pool, ungated relu^2 experts of which the
chip holds a share beside a shared one. The two new ops' every tier
against the position-by-position recurrence (the chunked SSD form and the
kernels in interpret mode), the ungated grouped FFN and its shares,
prefill (whole, padded, in chunks of several SSD blocks) then decode
through the pools against the plain reference's FULL forward pass (logits,
not tokens), a slot served twice, a decode step between two chunks of one
prompt, the rows' accounting, the counters, the paged kernel at 16 queries
a K/V head, the accepted builders' programs and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-nemotron.json): d
64, 8 Mamba-2 heads of 8 in 2 groups, 16 states, 4 taps, SSD blocks of 8
rows, 4 query heads on 2 K/V heads of 16, experts 2..5 of 8 held (3 a
token) of width 32 and a shared one of 48, 5 layers `MEM*E`, seeded weights
with Mamba-2's own initialisation of the recurrence.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.ops import ssd_ops
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import jamba, kexaone, lfm2, nemotron
from benchmark.reference import nemotron_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_jamba_serving import _hold_slot, _serve_one
from test_olmoe_serving import lower, program_listing, tap_logits
from test_paged_decode_attention import _attend, _pools

HERE = os.path.dirname(os.path.abspath(__file__))


def _toy(name):
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-%s.json' % name)) as f:
        return json.load(f)


TOY = _toy('nemotron')
# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 5e-8 to 7e-7 over every comparison below); the controls move the
# logits by 5e-5 (a chunk from zeros under a long prompt) to 5e-1.
TOLERANCE = 1e-5
STATE, TAIL = T.SSD_STATE, T.SSD_TAIL
N_SSD = 2                       # Mamba-2 layers of the toy


def _scope(seed=5, m=TOY):
    from paddle_tpu import Scope
    scope = Scope()
    for name, value in nemotron.init_params(m, seed).items():
        scope.set(name, value)
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=160, slots=4, **kw):
    kw.setdefault('block_size', 8)
    kw.setdefault('prefix_sharing', False)
    return GenerateEngine(GenerateConfig(
        model=nemotron.lm_config(TOY, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope())


def _prompt(n, seed=None):
    return np.random.RandomState(n if seed is None else seed).randint(
        2, TOY['vocab_size'], size=n).astype('int64')


# ---- 1. the ops against the recurrence, position by position ----------------

# heads, head size, groups, states, taps: a group's lanes a whole vreg and
# the convolution's channels (256 + 2 x 2 x 32) whole vregs, so that the
# kernels take the shapes
H, P, G, N, K = 16, 16, 2, 32, 4
DI, CW = H * P, H * P + 2 * G * N


def _weights(rng):
    w = {'ConvW': 0.3 * rng.randn(CW, K), 'ConvB': 0.1 * rng.randn(CW),
         'DtBias': np.log(np.expm1(np.exp(rng.uniform(
             np.log(1e-3), np.log(1e-1), H)))),
         'ALog': np.log(rng.uniform(1, 16, H)), 'D': 1 + 0.1 * rng.randn(H),
         'NormW': 1 + 0.1 * rng.randn(DI)}
    return {name: np.ascontiguousarray(v, 'float32')
            for name, v in w.items()}


def _walk(w, xbc, z, dt, s, tail, eps=1e-5):
    """The layer's rows one position at a time, in float64: (the gated,
    normed outputs [T, di], the state [N, di], the tail) after the rows
    from the state `s` [N, di] and the tail [K - 1, cw]."""
    w = {k: v.astype('float64') for k, v in w.items()}
    s, tail = s.astype('float64'), tail.astype('float64')
    a = -np.exp(w['ALog'])
    out = []
    for x_t, z_t, d_t in zip(xbc.astype('float64'), z.astype('float64'),
                             dt.astype('float64')):
        window = np.concatenate([tail, x_t[None]])
        c = (window * w['ConvW'].T).sum(0) + w['ConvB']
        c = c / (1 + np.exp(-c))
        x, b, cc = c[:DI], c[DI:DI + G * N].reshape(G, N), \
            c[DI + G * N:].reshape(G, N)
        d_t = np.logaddexp(0, d_t + w['DtBias'])
        y = np.zeros(DI)
        for h in range(H):
            at, g = slice(h * P, (h + 1) * P), h // (H // G)
            s[:, at] = np.exp(d_t[h] * a[h]) * s[:, at] \
                + b[g][:, None] * (d_t[h] * x[at])[None, :]
            y[at] = cc[g] @ s[:, at] + w['D'][h] * x[at]
        y = (y * z_t / (1 + np.exp(-z_t))).reshape(G, -1)
        y = y / np.sqrt((y * y).mean(-1, keepdims=True) + eps)
        out.append(y.reshape(DI) * w['NormW'])
        tail = window[1:]
    return np.stack(out), s, tail


TIERS = ['off', 'xla', 'interpret']


@pytest.mark.parametrize('tier', TIERS)
def test_ssd_decode_steps_every_live_row_and_no_other(monkeypatch, tier):
    """Four slots: rows 3, 0 (sits out), 1 and 0. The live rows read their
    state and tail, step once and write both back; the rows fed 0 read
    zeros and write the trash row; rows 2 and 4 of the pools and the other
    layer stand bit for bit."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    rng = np.random.RandomState(0)
    w, S = _weights(rng), 4
    state = rng.randn(5, 2, N, DI).astype('float32')
    tails = rng.randn(5, 2, 8, CW).astype('float32')
    xbc = rng.randn(S, CW).astype('float32')
    z, dt = rng.randn(S, DI).astype('float32'), \
        rng.randn(S, H).astype('float32')
    rows = np.array([3, 0, 1, 0])[:, None]
    before = monitor.counters()
    out = lower('ssd_decode', {'layer': 1, 'epsilon': 1e-5, 'groups': G},
                X=xbc, Z=z, Dt=dt, State=state, Tail=tails, Rows=rows, **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=ssd_decode}' % tier) == 1, moved
    got, new_state, new_tails = (np.asarray(out[x]) for x in
                                 ('Out', 'StateOut', 'TailOut'))
    for i, row in enumerate(rows[:, 0]):
        s0 = state[row, 1] if row else np.zeros((N, DI))
        t0 = tails[row, 1, :K - 1] if row else np.zeros((K - 1, CW))
        want, s1, t1 = _walk(w, xbc[i:i + 1], z[i:i + 1], dt[i:i + 1], s0,
                             t0)
        np.testing.assert_allclose(got[i], want[0], rtol=2e-5, atol=2e-5)
        if row:
            np.testing.assert_allclose(new_state[row, 1], s1, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(new_tails[row, 1, :K - 1], t1,
                                       rtol=1e-6)
    for row in (2, 4):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])
    np.testing.assert_array_equal(new_tails[:, 0], tails[:, 0])


# (rows of the bucket, real rows, first position, rows of an SSD block): a
# whole bucket of four blocks from zeros; pad rows in the last of four
# blocks; a later chunk that resumes; a bucket that is no whole number of
# blocks (the xla tier pads it); one real row; one block that is the bucket
SCANS = [(32, 32, 0, 8), (32, 21, 0, 8), (64, 50, 128, 16), (24, 24, 7, 16),
         (16, 1, 0, 8), (16, 16, 0, 128)]


@pytest.mark.parametrize('tier', TIERS)
@pytest.mark.parametrize('T_,length,off,chunk', SCANS)
def test_ssd_prefill_scans_the_real_rows_alone(monkeypatch, tier, T_, length,
                                               off, chunk):
    """The CHUNKED form (`xla` / `off`: einsums; `interpret`: the kernel,
    where the shapes tile) against the recurrence position by position.
    From position 0 the row's content is never read; past it the scan
    resumes from it; pad rows leave the state and the tail as of the last
    real row."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    rng = np.random.RandomState(T_ + length)
    w = _weights(rng)
    state = rng.randn(3, 2, N, DI).astype('float32')
    tails = rng.randn(3, 2, 8, CW).astype('float32')
    xbc = rng.randn(1, T_, CW).astype('float32')
    z, dt = rng.randn(1, T_, DI).astype('float32'), \
        rng.randn(1, T_, H).astype('float32')
    # the kernel takes one block that is the bucket, or blocks of whole
    # lane tiles; the toy's blocks of 8 and 16 rows land on the einsums
    tiles = ssd_ops.shapes_ok(DI, N, G, H, T_, min(chunk, T_))
    landed = tier if tier != 'interpret' or tiles else 'xla'
    before = monitor.counters()
    out = lower('ssd_prefill', {'layer': 0, 'epsilon': 1e-5, 'groups': G,
                                'chunk': chunk},
                X=xbc, Z=z, Dt=dt, State=state, Tail=tails,
                Rows=np.array([[2]]), Positions=(off + np.arange(T_))[None],
                Length=np.array([[length]]), **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=ssd_prefill}' % landed) == 1, moved
    s0 = state[2, 0] if off else np.zeros((N, DI))
    t0 = tails[2, 0, :K - 1] if off else np.zeros((K - 1, CW))
    want, s1, t1 = _walk(w, xbc[0, :length], z[0, :length], dt[0, :length],
                         s0, t0)
    np.testing.assert_allclose(np.asarray(out['Out'])[0, :length], want,
                               rtol=1e-4, atol=1e-4)
    new_state, new_tails = np.asarray(out['StateOut']), \
        np.asarray(out['TailOut'])
    np.testing.assert_allclose(new_state[2, 0], s1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(new_tails[2, 0, :K - 1], t1, rtol=1e-6)
    np.testing.assert_array_equal(new_tails[2, 1], tails[2, 1])
    np.testing.assert_array_equal(new_state[:2], state[:2])
    np.testing.assert_array_equal(new_state[2, 1], state[2, 1])
    np.testing.assert_array_equal(new_tails[:2], tails[:2])


@pytest.mark.parametrize('T_,chunk', [(32, 8), (256, 128)])
def test_the_scan_kernel_carries_the_state_across_its_blocks(T_, chunk):
    """`prefill_scan` in interpret mode over several blocks of rows (the
    real block of 128 among them), pad rows at the end, against the
    einsums and the recurrence a position."""
    rng = np.random.RandomState(T_)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (T_, H)))
    dt[T_ - 5:] = 0                                         # pad rows
    a = -rng.uniform(1, 16, H)
    x = rng.randn(T_, DI)
    b, c = rng.randn(2, T_, G, N)
    s0 = rng.randn(N, DI)
    dx = np.repeat(dt, P, axis=1) * x
    cum = np.cumsum((dt * a).reshape(-1, chunk, H), axis=1).reshape(-1, H)
    s, want = s0.copy(), []
    for t in range(T_):
        s = np.repeat(np.exp(dt[t] * a), P)[None] * s \
            + dx[t][None] * np.repeat(b[t].T, DI // G, axis=1)
        want.append((s * np.repeat(c[t].T, DI // G, axis=1)).sum(0))
    args = [jnp.asarray(v, jnp.float32) for v in (dx, cum, b, c, s0)]
    for got, last in (ssd_ops.prefill_scan(*args, chunk=chunk,
                                           interpret=True),
                      ssd_ops._prefill_scan_xla(*args, chunk)):
        np.testing.assert_allclose(got, np.stack(want), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(last, s, rtol=1e-4, atol=1e-5)


def test_the_kernels_take_whole_tiles_only(monkeypatch):
    """The published shapes tile (a strip of the decode grid holds 4 of
    the 8 groups, 1 MB of a slot's state); a group that is no whole vreg
    of lanes, a state that fills no sublane tile, blocks of rows that are
    neither the bucket nor whole lane tiles: the request for the kernel
    lands on `xla`."""
    assert ssd_ops.shapes_ok(4096, 128, 8, 64)
    assert ssd_ops.shapes_ok(4096, 128, 8, 64, 512, 128)
    assert ssd_ops._groups_a_strip(4096, 128, 8) == 4
    assert not ssd_ops.shapes_ok(64, 16, 2, 8)
    assert not ssd_ops.shapes_ok(256, 4, 2, 16)
    assert not ssd_ops.shapes_ok(256, 32, 2, 16, 32, 8)
    assert not ssd_ops.shapes_ok(256, 32, 2, 16, 24, 16)
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    before = monitor.counters()
    lower('ssd_decode', {'layer': 0, 'epsilon': 1e-5, 'groups': 2},
          X=np.zeros((2, 128), 'float32'), Z=np.zeros((2, 64), 'float32'),
          Dt=np.zeros((2, 8), 'float32'),
          State=np.zeros((3, 1, 16, 64), 'float32'),
          Tail=np.zeros((3, 1, 8, 128), 'float32'),
          Rows=np.array([[1], [2]]), ConvW=np.zeros((128, 4), 'float32'),
          ConvB=np.zeros(128, 'float32'), DtBias=np.zeros(8, 'float32'),
          ALog=np.zeros(8, 'float32'), D=np.ones(8, 'float32'),
          NormW=np.ones(64, 'float32'))
    assert monitor.counter_delta(before).get(
        'fused_kernel_dispatch_total{impl=xla,mesh=1,op=ssd_decode}') == 1


def test_the_paged_kernel_at_32_queries_on_2_kv_heads(monkeypatch):
    """Nemotron's attention: 16 queries a K/V head of 128, the MXU body
    against the gather."""
    S, Hq, Hkv, dh, bs, MB = 4, 32, 2, 128, 32, 6
    assert pda.shapes_ok(Hq, dh, bs, Hkv)
    assert pda.padded_group(16, 2) == 16
    rng = np.random.RandomState(32)
    kc, vc = _pools(rng, S * MB + 1, 2, bs, Hkv * dh)
    tables = (1 + rng.permutation(S * MB)).reshape(S, MB).astype('int32')
    pos = np.array([0, bs - 1, 3 * bs + 5, MB * bs - 1], 'int32')
    q = rng.randn(S, Hq, dh).astype('float32')
    want = _attend('off', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    got = _attend('interpret', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---- 2. the ungated experts and their shares --------------------------------

def _moe(rng, rows=24, d=16, e=8, width=12):
    return dict(X=rng.randn(rows, d).astype('float32'),
                RouterW=rng.randn(d, e).astype('float32'),
                SelectBias=(0.1 * rng.randn(e)).astype('float32')), \
        rng.randn(e, d, width).astype('float32') * 0.3, \
        rng.randn(e, width, d).astype('float32') * 0.3


ROUTER = {'top_k': 3, 'norm_topk_prob': True, 'score': 'sigmoid',
          'routed_scale': 2.5}


def test_the_ungated_expert_is_two_matrices_and_a_squared_relu():
    rng = np.random.RandomState(3)
    ins, up, down = _moe(rng)
    out = lower('moe_ffn', ROUTER, UpW=up, DownW=down, **ins)
    x = ins['X'].astype('float64')
    s = 1 / (1 + np.exp(-(x @ ins['RouterW'])))
    idx = np.argsort(-(s + ins['SelectBias']), axis=1)[:, :3]
    np.testing.assert_array_equal(np.sort(np.asarray(out['TopkIdx']), 1),
                                  np.sort(idx, 1))
    want = np.zeros_like(x)
    for n in range(len(x)):
        chosen = s[n, idx[n]]
        for e, wt in zip(idx[n], chosen / (chosen.sum() + 1e-20) * 2.5):
            want[n] += wt * (np.maximum(x[n] @ up[e], 0) ** 2) @ down[e]
    np.testing.assert_allclose(out['Out'], want, rtol=2e-4, atol=2e-5)
    assert int(np.asarray(out['ExpertLoad']).sum()) == 3 * len(x)


@pytest.mark.parametrize('shares', [8, 4, 2])
def test_the_shares_of_the_ungated_layer_add_up(shares):
    """model-configs section 4: the shares' routed parts (`experts_held`,
    each computing its own experts and leaving the rest out) sum to the
    uncut layer's output, the op's and the reference's -- where the shared
    expert is counted once."""
    rng = np.random.RandomState(shares)
    ins, up, down = _moe(rng)
    whole = np.asarray(lower('moe_ffn', ROUTER, UpW=up, DownW=down,
                             **ins)['Out'])
    per = 8 // shares
    parts = [np.asarray(lower(
        'moe_ffn', dict(ROUTER, first_expert=f), UpW=up[f:f + per],
        DownW=down[f:f + per], **ins)['Out']) for f in range(0, 8, per)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    # the reference: x + routed share (+ the shared expert)
    x = jnp.asarray(ins['X'])
    w = {'ln2.w': jnp.ones(16), 'moe.router.w': jnp.asarray(ins['RouterW']),
         'moe.router.bias': jnp.asarray(ins['SelectBias']),
         'moe.shared.up.w': jnp.asarray(rng.randn(16, 20), jnp.float32),
         'moe.shared.down.w': jnp.asarray(rng.randn(20, 16), jnp.float32)}

    def layer(first, count, shared):
        return np.asarray(ref._experts(
            x, dict(w, **{'moe.up.w': jnp.asarray(up[first:first + count]),
                          'moe.down.w': jnp.asarray(
                              down[first:first + count])}),
            top_k=3, first=first, scale=2.5, eps=1e-5, shared=shared)) \
            - ins['X']
    uncut = layer(0, 8, True)
    routed = sum(layer(f, per, False) for f in range(0, 8, per))
    only_shared = uncut - layer(0, 8, False)
    np.testing.assert_allclose(routed + only_shared, uncut, rtol=1e-4,
                               atol=1e-5)
    assert np.abs(only_shared).max() > 0 and np.abs(routed).max() > 0


# ---- 3. the programs --------------------------------------------------------

def test_the_pools_are_a_row_a_slot_and_only_an_ssd_model_has_them():
    cfg = nemotron.lm_config(TOY, 64, False)
    assert cfg.layer_types == ('ssd', 'ffn', 'ssd', 'attention', 'ffn')
    assert (cfg.n_ssd_layers, cfg.n_attn_layers, cfg.n_moe_layers) == (2, 1,
                                                                       2)
    assert (cfg.ssd_inner, cfg.ssd_conv_width) == (64, 128)
    assert [cfg.layer_ordinal(i) for i in range(5)] == [0, 0, 1, 0, 1]
    assert [cfg.has_mixer(i) for i in range(5)] == [1, 0, 1, 1, 0]
    assert [cfg.has_ffn(i) for i in range(5)] == [0, 1, 0, 0, 1]
    assert not any(cfg.rotates(i) for i in range(5))
    assert T.kv_cache_names(cfg) == (T.KV_CACHE_K, T.KV_CACHE_V, STATE, TAIL)
    assert T.kv_cache_shapes(cfg, 28, 8, 3) == {
        T.KV_CACHE_K: (28, 1, 8, 32), T.KV_CACHE_V: (28, 1, 8, 32),
        STATE: (4, 2, 16, 64), TAIL: (4, 2, 8, 128)}
    with pytest.raises(ValueError, match='sized by the slots'):
        T.kv_cache_shapes(cfg, 28, 8)
    # the table's new row: indexed by the slots' rows, neither rewound nor
    # copied, its series booked by the engine through `books`
    state, tail = T.cache_pools(cfg, 28, 8, 3)[2:]
    assert (state.index, state.rewinds, state.copies) == ('row', False,
                                                          False)
    assert state.books == {'step': ('ssd_state_rows_updated_total', 1),
                           'prefill': 'ssd_prefill_rows_total',
                           'resume': 'ssd_state_resumes_total'}
    assert tail.books == {} and 'Mamba-2' in state.why
    # a model without such layers declares neither pool nor op
    plain = LMConfig(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    assert T.kv_cache_names(plain) == (T.KV_CACHE_K, T.KV_CACHE_V)
    assert all(plain.has_mixer(i) and plain.has_ffn(i) for i in range(2))


def test_a_model_of_the_pattern_ME_star_builds_layers_of_one_sublayer():
    """Three layers, one of each letter: every layer has ONE norm, the
    mixers no FFN and the expert layer no mixer; each Mamba-2 op gets its
    ordinal and the rows."""
    m = dict(TOY, hybrid_override_pattern='ME*', num_hidden_layers=3)
    eng = GenerateEngine(GenerateConfig(
        model=nemotron.lm_config(m, 64, False), slots=2, max_len=64,
        prompt_buckets=[16], block_size=8, prefix_sharing=False, seed=1),
        scope=_scope(m=m))
    for prog, ssd, attn in [(eng._step_prog, 'ssd_decode',
                             'kv_decode_attention_paged')] + [
            (p, 'ssd_prefill', 'kv_prefix_attention')
            for p, _ in eng._prefill.values()]:
        types = [op.type for op in prog.global_block().ops]
        assert types.count('rms_norm') == 3 + 1        # a layer, the final
        assert (types.count(ssd), types.count('moe_ffn'),
                types.count(attn)) == (1, 1, 1)
        assert 'rotary_embedding' not in types and 'swish' not in types
        op = next(op for op in prog.global_block().ops if op.type == ssd)
        assert op.attr('layer') == 0 and op.input('Rows') == ['gen_srow']
        assert op.input('State') == [STATE] and op.attr('groups') == 2
        moe = next(op for op in prog.global_block().ops
                   if op.type == 'moe_ffn')
        assert not moe.input('GateW')           # ungated: relu^2
        assert moe.attr('first_expert') == 2
    names = set(eng.scope.names())
    assert set(nemotron.param_shapes(m)) <= names
    assert not {n for n in names if n.startswith('layer_0.ln2')
                or n.startswith('layer_1.ln1') or '.gate.' in n}
    toks = list(eng.generate_once(_prompt(21), max_new_tokens=4))
    assert ref.greedy_margins(eng.scope, m, _prompt(21), toks).max() == 0


def test_the_startup_program_takes_mamba2s_initialisation():
    eng = GenerateEngine(GenerateConfig(
        model=nemotron.lm_config(TOY, 32, False), slots=2, max_len=32,
        prompt_buckets=[8], block_size=8, prefix_sharing=False, seed=1))
    a_log = np.asarray(eng.scope.get('layer_0.ssd.A_log'))
    np.testing.assert_allclose(np.exp(a_log), np.linspace(1, 16, 8),
                               rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(eng.scope.get('layer_2.ssd.D')),
                                  1.0)
    bias = np.asarray(eng.scope.get('layer_2.ssd.dt.b'))
    np.testing.assert_allclose(np.logaddexp(0, bias), 0.01, rtol=1e-5)
    assert list(eng.generate_once(_prompt(11), max_new_tokens=3))


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
@pytest.mark.parametrize('family,builder', [('lfm2', lfm2),
                                            ('kexaone', kexaone),
                                            ('jamba', jamba)])
def test_the_accepted_builders_build_the_pr46_commits_programs(
        family, builder, program):
    """LFM2's, K-EXAONE's and Jamba2's toys build, op for op with every
    attribute, parameter and startup op, what they built at commit 2a7df7f
    (PR 46), the parent of the PR that brought layers of one sublayer,
    Mamba-2 and the ungated experts (fixtures/lm_programs_parent_pr46.json;
    fairseq-dense's, OLMoE's and JoyAI's listings are test_olmoe_serving's)."""
    with open(os.path.join(HERE, 'fixtures',
                           'lm_programs_parent_pr46.json')) as f:
        want = json.load(f)[family][program]
    assert program_listing(builder.lm_config(_toy(family), 32, False),
                           program, slots=4) == want


@pytest.mark.parametrize('precision', [None, 'highest'])
def test_a_programs_matmuls_take_the_precision_it_states(precision):
    """`Program.matmul_precision`: every matmul that states no precision
    of its own is traced under it; it is part of the fingerprint and of the
    serialized form, and of neither where it is not set."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.core import serialization
    from paddle_tpu.core.lowering import build_fn
    from paddle_tpu.framework import Program, program_guard
    main = Program()
    with program_guard(main, Program()):
        y = fluid.layers.fc(fluid.layers.data(name='x', shape=[8],
                                              dtype='float32'),
                            size=4, bias_attr=False)
    unset, plain = main._fingerprint(), serialization.program_to_dict(main)
    assert 'matmul_precision' not in plain
    main.matmul_precision = precision
    assert (main._fingerprint() == unset) == (precision is None)
    back = serialization.program_from_dict(json.loads(json.dumps(
        serialization.program_to_dict(main))))
    assert back.matmul_precision == precision
    assert back._fingerprint() == main._fingerprint()
    w, = [p.name for p in main.global_block().all_parameters()]
    fn, _, _ = build_fn(main, [y.name], [w], [])
    text = jax.jit(fn).lower(
        {'x': jnp.zeros((2, 8))}, {w: jnp.zeros((8, 4))}, {},
        jnp.zeros(2, jnp.uint32)).as_text()
    assert ('HIGHEST' in text) == (precision == 'highest')
    assert 'dot_general' in text


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
def test_the_serving_programs_take_the_models_precision(program):
    """Nemotron's two programs state 'highest' (`LMConfig.
    matmul_precision`); a model that does not say leaves its programs the
    backend's default, as every accepted builder's are."""
    from paddle_tpu import unique_name
    from paddle_tpu.framework import Program, program_guard

    def built(cfg):
        main = Program()
        with program_guard(main, Program()):
            with unique_name.guard():
                if program == 'decode_step':
                    T.build_lm_decode_step(cfg, 4, 32, block_size=8,
                                           num_blocks=9)
                else:
                    T.build_lm_prefill_paged(cfg, 16, 9, 8, 4, slots=4)
        return main
    assert built(nemotron.lm_config(TOY, 32, False)).matmul_precision \
        == 'highest'
    for family, builder in (('lfm2', lfm2), ('kexaone', kexaone),
                            ('jamba', jamba)):
        assert built(builder.lm_config(_toy(family), 32, False)) \
            .matmul_precision is None


# ---- 4. through the engine, against the reference ---------------------------

def _want(scope, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(
        scope, TOY, seq, positions=np.arange(len(prompt) - 1, len(seq))))


# (prompt, new tokens, buckets, max_len): one bucket filled (two SSD
# blocks); a bucket with pad rows; one row; THREE chunks of the widest
# bucket (four SSD blocks each), the last padded; two chunks that end on a
# bucket's edge; several hundred positions in chunks of 128 (16 blocks)
# with Mamba-2's initialisation (the slowest heads keep 0.999 of their
# state a position)
THROUGH = [(16, 5, (16, 32), 160), (21, 9, (16, 32), 160),
           (1, 4, (16, 32), 160), (75, 12, (16, 32), 160),
           (64, 6, (16, 32), 160), (300, 24, (32, 64, 128), 384)]


@pytest.mark.parametrize('n_prompt,n_new,buckets,max_len', THROUGH)
def test_prefill_then_decode_through_the_state_pool_equals_the_full_forward(
        n_prompt, n_new, buckets, max_len):
    eng = _engine(buckets=buckets, max_len=max_len)
    eng.warmup()
    log = tap_logits(eng)
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
    # the scans walked the real rows, both layers each; the later chunks
    # resumed from the row; every step advanced one row a layer -- booked
    # through the pool's `books`, under the new kind's names
    assert moved['ssd_prefill_rows_total'] == N_SSD * n_prompt
    assert moved.get('ssd_state_resumes_total', 0) == len(prefills) - 1
    assert moved['ssd_state_rows_updated_total'] == N_SSD * len(steps)
    assert not {k for k in moved if k.startswith('ssm_')}
    # the one attention layer's K/V rows alone
    at = np.arange(n_prompt, n_prompt + n_new - 1)
    assert moved['kv_tokens_read_total'] == int((at + 1).sum())
    # the two expert layers: 3 assignments a real row, those to experts
    # 2..5 computed here (of a chunked prompt the LAST chunk's loads are
    # fetched, with its first token: the engine's way since PR 41)
    rows = n_prompt - (len(prefills) - 1) * wide + len(steps)
    assert moved['moe_assignments_total'] == 2 * 3 * rows
    assert 0 < moved['moe_held_assignments_total'] \
        < moved['moe_assignments_total']
    assert moved['moe_layer_steps_total'] == 2 * (1 + len(steps))
    assert eng.stats()['state'] == {'capacity': 4, 'in_use': 0}


def test_a_slot_served_twice_gives_the_second_tenant_its_own_logits():
    """One slot, so the second request sits on the first's row: its logits
    are BIT FOR BIT those of a fresh engine that served it alone -- the
    first chunk at position 0 never reads the row -- and the reference's."""
    scope = _scope()
    eng = _engine(scope, slots=1)
    eng.warmup()
    log = tap_logits(eng)
    first, second = _prompt(40), _prompt(37, seed=9)
    _serve_one(eng, log, first, 7)
    state = np.asarray(eng.scope.get(STATE))
    assert np.abs(state[1]).max() > 0          # the first tenant's, left
    toks, got, slot = _serve_one(eng, log, second, 8)
    assert slot == 0
    alone = _engine(_scope(), slots=1)
    alone.warmup()
    toks_alone, got_alone, _ = _serve_one(alone, tap_logits(alone), second,
                                          8)
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


def test_a_step_between_two_chunks_leaves_the_chunked_slots_row():
    """A resident decodes while another slot's prompt is between its first
    and its second chunk: that slot is not resident, the step feeds it row
    0, and its rows of both pools stand BIT FOR BIT; the prompt's last
    chunk then resumes from them and the first token's logits are the
    reference's."""
    eng = _engine()
    eng.warmup()
    log = tap_logits(eng)
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


@pytest.mark.parametrize('control,kw', [
    ('group-0', {'one_group': True}),
    ('norm-all-channels', {'norm_groups': 1}),
    ('norm-before-gate', {'norm_first': True}),
    ('block-edge', {'zero_state_every': 8}),
    ('relu', {'act': 'relu'}), ('no-shared-expert', {'shared': False}),
    ('no-routed-scale', {'routed_scale': False}),
    ('bias-in-weights', {'bias_in_weights': True})])
def test_a_wrong_forward_is_outside_the_tolerance(control, kw):
    """The controls this family brings, at toy width: each moves the
    reference's logits by well over what the system is held to."""
    scope, prompt = _scope(), _prompt(50)
    own = np.asarray(ref.logits(scope, TOY, prompt))
    wrong = np.asarray(ref.logits(scope, TOY, prompt, **kw))
    assert logit_gap(wrong, own)[1] > 10 * TOLERANCE, control


# ---- 5. the refusals --------------------------------------------------------

@pytest.mark.parametrize('option', ['prefix_sharing', 'speculative'])
def test_an_ssd_model_refuses_sharing_and_speculation_by_name(option):
    """Speculation is refused by name; sharing, refused until PR 58, builds
    an engine whose state rows have a snapshot row a slot behind them."""
    kw = {'prefix_sharing': False}
    kw[option] = True

    def build():
        return GenerateEngine(GenerateConfig(
            model=nemotron.lm_config(TOY, 64, False), slots=2, max_len=64,
            prompt_buckets=[16], block_size=8, **kw))
    if option == 'prefix_sharing':
        assert build().stats()['state']['snapshots'] == {'rows': 2,
                                                         'in_use': 0}
        return
    with pytest.raises(ValueError, match=r"%s=True with LMConfig\."
                       r"layer_types=.*'ssd'.*Mamba-2" % option):
        build()


def test_the_classic_builders_and_lmconfig_refuse_by_name():
    cfg = nemotron.lm_config(TOY, 32, False)
    for build in (lambda: T.build_lm(cfg),
                  lambda: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
                  lambda: T.build_lm_verify(cfg, 2, 3, 32, 9, 8)):
        with pytest.raises(ValueError, match='cannot express LMConfig.norm'):
            build()
    # the new fields, each by its name
    classic = dict(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    with pytest.raises(ValueError, match='LMConfig.layer_types'):
        T.build_lm(LMConfig(layer_types=['ssd', 'attention'], ssm_heads=4,
                            ssm_head_dim=8, **classic))
    with pytest.raises(ValueError, match='LMConfig.layer_types'):
        T.build_lm(LMConfig(layer_types=['attention', 'ffn'], **classic))
    with pytest.raises(ValueError, match='LMConfig.matmul_precision'):
        LMConfig(matmul_precision='bfloat16', **classic)
    with pytest.raises(ValueError, match='LMConfig.matmul_precision'):
        T.build_lm(LMConfig(matmul_precision='highest', **classic))
    with pytest.raises(ValueError, match='LMConfig.expert_form'):
        LMConfig(expert_form='relu', **classic)
    with pytest.raises(ValueError, match="'ssd' layers: they need"):
        LMConfig(layer_types=['ssd', 'attention'], ssm_heads=6,
                 ssm_head_dim=8, ssm_groups=4, **classic)
    with pytest.raises(ValueError, match='mla'):
        LMConfig(n_layer=1, layer_types=['ssd'], ssm_heads=4, ssm_head_dim=8,
                 attention='mla', position='rope', q_lora_rank=8,
                 kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    for key, value in (('mlp_hidden_act', 'silu'), ('n_group', 2),
                       ('use_conv_bias', False)):
        with pytest.raises(ValueError, match='builds %s=' % key):
            nemotron.lm_config(dict(TOY, **{key: value}), 32, False)
    with pytest.raises(ValueError, match='served only'):
        nemotron.lm_config(TOY, 32, True)
