"""The Qwen3-Next block in the Program path (ISSUE 55): Gated DeltaNet
layers whose keys-by-values state a value head and convolution tail live A
ROW A SLOT in two pools of their own, a gated full-attention layer of heads
of 256 of which a quarter is rotated, zero-centred norms, softmax experts
of which the chip holds a share beside a GATED shared one. The two new ops'
every tier against the delta rule a position (the chunked form and the
kernels in interpret mode), the partial rotation and the zero-centred norm
against their one-liners, the eight expert shares against the uncut layer,
prefill (whole, padded, in three chunks) then decode through the pools
against the plain reference's FULL forward pass (logits, not tokens), a
slot served twice, a decode step between two chunks of one prompt, the
counters, the paged kernel at 8 queries a K/V head of 256, and the
refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-qwen3next.json): d
64, DeltaNet layers of 2 key heads of 16 and 4 value heads of 8, 4 taps,
blocks of 16 rows, 4 query heads on 2 K/V heads of 16 of which 4 numbers
are rotated, experts 4..7 of 16 held (3 a token) of width 32 and a gated
shared one of 24, 4 layers (three DeltaNet, one attention), seeded weights
with the family's initialisation of the recurrence.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import gdn_ops
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import qwen3next
from benchmark.reference import qwen3next_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_jamba_serving import _hold_slot, _serve_one
from test_olmoe_serving import lower, tap_logits
from test_paged_decode_attention import _attend, _pools

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-qwen3next.json')) as f:
    TOY = json.load(f)
# the toy with DeltaNet heads the kernels tile for: one key head of 128,
# two value heads of 128
WIDE = dict(TOY, linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128)
# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 3e-7 to 5e-7 over every comparison below); the controls move the
# logits by 1e-3 and more.
TOLERANCE = 1e-5
STATE, TAIL = T.GDN_STATE, T.GDN_TAIL
N_GDN = 3                       # DeltaNet layers of the toy


def _scope(seed=5, m=TOY):
    from paddle_tpu import Scope
    scope = Scope()
    for name, value in qwen3next.init_params(m, seed).items():
        scope.set(name, value)
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=160, slots=4, m=TOY, **kw):
    kw.setdefault('block_size', 8)
    kw.setdefault('prefix_sharing', False)
    return GenerateEngine(GenerateConfig(
        model=qwen3next.lm_config(m, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope(m=m))


def _prompt(n, seed=None):
    return np.random.RandomState(n if seed is None else seed).randint(
        2, TOY['vocab_size'], size=n).astype('int64')


# ---- 1. the ops against the delta rule, position by position ----------------

# key heads, value heads, key size, value size, taps: the toy's, and one the
# kernels take (a head's values a whole vreg, its keys too)
SMALL = (2, 4, 16, 8, 4)
TILED = (1, 2, 128, 128, 4)


def _sizes(shape):
    hk, hv, dk, dv, k = shape
    return hk, hv, dk, dv, k, 2 * hk * dk + hv * dv, hv * dv


def _weights(rng, shape):
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    w = {'ConvW': 0.3 * rng.randn(cw, k), 'ALog': np.log(rng.uniform(
        0.01, 16, hv)), 'DtBias': 1 + 0.5 * rng.randn(hv),
        'NormW': 1 + 0.1 * rng.randn(dv)}
    return {name: np.ascontiguousarray(v, 'float32')
            for name, v in w.items()}


def _walk(shape, w, x, z, b, a, s, tail, eps=1e-6):
    """The layer's rows one position at a time, in float64: (the normed,
    gated outputs [T, Hv dv], the state [dk, Hv dv], the tail) after the
    rows from the state `s` and the tail [K - 1, cw]."""
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    w = {n: v.astype('float64') for n, v in w.items()}
    s = s.astype('float64').reshape(dk, hv, dv).copy()
    tail = tail.astype('float64')
    out = []
    for x_t, z_t, b_t, a_t in zip(*[v.astype('float64')
                                    for v in (x, z, b, a)]):
        window = np.concatenate([tail, x_t[None]])
        c = (window * w['ConvW'].T).sum(0)
        c = c / (1 + np.exp(-c))
        q, kk = [v.reshape(hk, dk) / np.sqrt(
            (v.reshape(hk, dk) ** 2).sum(-1, keepdims=True) + 1e-6)
            for v in (c[:hk * dk], c[hk * dk:2 * hk * dk])]
        q = q * dk ** -0.5
        v = c[2 * hk * dk:].reshape(hv, dv)
        beta = 1 / (1 + np.exp(-b_t))
        g = -np.exp(w['ALog']) * np.logaddexp(0, a_t + w['DtBias'])
        o = np.zeros((hv, dv))
        for h in range(hv):
            kh, qh = kk[h // (hv // hk)], q[h // (hv // hk)]
            s[:, h] *= np.exp(g[h])
            u = beta[h] * (v[h] - s[:, h].T @ kh)
            s[:, h] += np.outer(kh, u)
            o[h] = s[:, h].T @ qh
        o = o / np.sqrt((o * o).mean(-1, keepdims=True) + eps) * w['NormW']
        z_t = z_t.reshape(hv, dv)
        out.append((o * z_t / (1 + np.exp(-z_t))).reshape(vd))
        tail = window[1:]
    return np.stack(out), s.reshape(dk, vd), tail


TIERS = [('off', SMALL), ('xla', SMALL), ('interpret', TILED),
         ('interpret', SMALL)]


@pytest.mark.parametrize('tier,shape', TIERS)
def test_gdn_decode_steps_every_live_row_and_no_other(monkeypatch, tier,
                                                      shape):
    """Four slots: rows 3, 0 (sits out), 1 and 0. The live rows read their
    state and tail, step once and write both back; the rows fed 0 read
    zeros and write the trash row; rows 2 and 4 of the pools and the other
    layer stand bit for bit. The toy's heads tile for no kernel: the
    request for one lands on `xla`."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    rng = np.random.RandomState(0)
    w, S = _weights(rng, shape), 4
    state = rng.randn(5, 2, dk, vd).astype('float32')
    tails = rng.randn(5, 2, 8, cw).astype('float32')
    x, z = rng.randn(S, cw).astype('float32'), \
        rng.randn(S, vd).astype('float32')
    b, a = rng.randn(S, hv).astype('float32'), \
        rng.randn(S, hv).astype('float32')
    rows = np.array([3, 0, 1, 0])[:, None]
    before = monitor.counters()
    out = lower('gdn_decode', {'layer': 1, 'epsilon': 1e-6, 'key_heads': hk},
                X=x, Z=z, B=b, A=a, State=state, Tail=tails, Rows=rows, **w)
    moved = monitor.counter_delta(before)
    landed = 'xla' if tier == 'interpret' and shape == SMALL else tier
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=gdn_decode}' % landed) == 1, moved
    got, new_state, new_tails = (np.asarray(out[n]) for n in
                                 ('Out', 'StateOut', 'TailOut'))
    for i, row in enumerate(rows[:, 0]):
        s0 = state[row, 1] if row else np.zeros((dk, vd))
        t0 = tails[row, 1, :k - 1] if row else np.zeros((k - 1, cw))
        want, s1, t1 = _walk(shape, w, x[i:i + 1], z[i:i + 1], b[i:i + 1],
                             a[i:i + 1], s0, t0)
        np.testing.assert_allclose(got[i], want[0], rtol=2e-5, atol=2e-5)
        if row:
            np.testing.assert_allclose(new_state[row, 1], s1, rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(new_tails[row, 1, :k - 1], t1,
                                       rtol=1e-6)
    for row in (2, 4):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])
    np.testing.assert_array_equal(new_tails[:, 0], tails[:, 0])


# (rows of the bucket, real rows, first position, rows of a block): a whole
# bucket of four blocks from zeros; pad rows in the last of four blocks; a
# later chunk that resumes; a bucket that is no whole number of blocks (the
# xla tier pads it); one real row; one block that is the bucket
SCANS = [(64, 64, 0, 16), (64, 41, 0, 16), (64, 50, 128, 32),
         (24, 24, 7, 16), (16, 1, 0, 16), (32, 32, 0, 64)]


@pytest.mark.parametrize('tier,shape', TIERS[:3])
@pytest.mark.parametrize('T_,length,off,chunk', SCANS)
def test_gdn_prefill_walks_the_real_rows_alone(monkeypatch, tier, shape, T_,
                                               length, off, chunk):
    """THE CHUNKED FORM AGAINST THE RECURRENCE: the outputs of the real
    rows, the state and the tail as of the last real row are the delta
    rule's a position; a chunk at position 0 never reads the row, a later
    one resumes from it; the pad rows advance nothing."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    rng = np.random.RandomState(T_ + length)
    w = _weights(rng, shape)
    state = rng.randn(3, 2, dk, vd).astype('float32')
    tails = rng.randn(3, 2, 8, cw).astype('float32')
    # correlated rows with a common part, as a convolution and a SiLU leave
    # them: the triangular system is far from the identity
    x = (rng.randn(1, T_, cw) + 0.7).astype('float32')
    z = rng.randn(1, T_, vd).astype('float32')
    b, a = rng.randn(1, T_, hv).astype('float32'), \
        rng.randn(1, T_, hv).astype('float32') - 2
    before = monitor.counters()
    out = lower('gdn_prefill', {'layer': 1, 'epsilon': 1e-6,
                                'key_heads': hk, 'chunk': chunk},
                X=x, Z=z, B=b, A=a, State=state, Tail=tails,
                Rows=np.array([[2]]), Positions=off + np.arange(T_)[None],
                Length=np.array([[length]]), **w)
    moved = monitor.counter_delta(before)
    tiles = gdn_ops.shapes_ok(dk, dv, hk, hv, T_, min(chunk, T_))
    landed = tier if tier != 'interpret' or tiles else 'xla'
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=gdn_prefill}' % landed) == 1, moved
    s0 = state[2, 1] if off else np.zeros((dk, vd))
    t0 = tails[2, 1, :k - 1] if off else np.zeros((k - 1, cw))
    want, s1, t1 = _walk(shape, w, x[0, :length], z[0, :length],
                         b[0, :length], a[0, :length], s0, t0)
    got = np.asarray(out['Out'])[0]
    np.testing.assert_allclose(got[:length], want, rtol=1e-4, atol=2e-5)
    assert np.isfinite(got).all()
    new_state, new_tails = np.asarray(out['StateOut']), \
        np.asarray(out['TailOut'])
    np.testing.assert_allclose(new_state[2, 1], s1, rtol=1e-4, atol=5e-6)
    np.testing.assert_allclose(new_tails[2, 1, :k - 1], t1, rtol=1e-6)
    for row in (0, 1):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])


@pytest.mark.parametrize('chunk', [16, 64])
def test_the_chunk_kernel_equals_the_einsums_on_near_parallel_keys(chunk):
    """Keys that share most of their direction and hardly decay (the
    triangular system's off-diagonal entries near beta): the kernel's way to
    the inverse -- substitution in the diagonal blocks, rounds of the
    blocks' nilpotent product -- gives what `solve_triangular` gives."""
    rng = np.random.RandomState(chunk)
    T_, hk, hv, dk, dv = 128, 1, 2, 128, 128
    base = rng.randn(1, hk, dk)
    q, k = [base + 0.3 * rng.randn(T_, hk, dk) for _ in range(2)]
    q, k = [v / np.linalg.norm(v, axis=-1, keepdims=True) for v in (q, k)]
    v = rng.randn(T_, hv, dv)
    g = -rng.uniform(1e-4, 1e-2, (T_, hv))
    beta = rng.uniform(0.5, 1.0, (T_, hv))
    args = [jnp.asarray(x, jnp.float32) for x in (
        q * dk ** -0.5, k, v, g, beta, rng.randn(dk, hv * dv))]
    want = gdn_ops._prefill_chunks_xla(*args, chunk)
    got = gdn_ops.prefill_chunks(*args, chunk=chunk, interpret=True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_the_kernels_take_whole_tiles_only():
    """The published shapes tile (a strip of the decode grid holds 16 of
    the 32 value heads, 1 MB of a slot's state); a head's values that are no
    whole vreg of lanes, keys that fill no sublane tile, value heads that
    are no whole groups a key head, blocks of rows that do not divide the
    bucket or are no whole diagonal blocks: the request for the kernel
    lands on `xla`."""
    assert gdn_ops.shapes_ok(128, 128, 16, 32)
    for rows in (128, 256, 512):
        assert gdn_ops.shapes_ok(128, 128, 16, 32, rows, 64)
    assert gdn_ops._heads_a_strip(128, 128, 32, 2) == 16
    assert gdn_ops._heads_a_strip(128, 128, 2, 2) == 2
    assert not gdn_ops.shapes_ok(16, 8, 2, 4)
    assert not gdn_ops.shapes_ok(12, 128, 2, 4)
    assert not gdn_ops.shapes_ok(128, 128, 3, 4)
    assert not gdn_ops.shapes_ok(128, 128, 16, 32, 96, 64)
    assert not gdn_ops.shapes_ok(128, 128, 16, 32, 128, 24)
    # since PR 58 heads whose keys are no whole vreg are laid a head first
    # round the prefill's kernel, and heads of 192 values are walked in
    # pairs (tests/test_olmohybrid_serving.py)
    assert gdn_ops.shapes_ok(64, 128, 2, 4, 128, 64)
    assert gdn_ops.shapes_ok(96, 192, 30, 30, 512, 64)
    assert not gdn_ops.shapes_ok(96, 192, 15, 15)


def test_the_paged_kernel_at_8_queries_on_a_kv_head_of_256(monkeypatch):
    """Qwen3-Next's full attention: 16 queries on 2 K/V heads of 256, a
    head two vregs of lanes -- the MXU body against the gather. One query a
    head of 256 stays refused: the VPU body sums a head inside one vreg."""
    S, Hq, Hkv, dh, bs, MB = 4, 16, 2, 256, 32, 6
    assert pda.shapes_ok(Hq, dh, bs, Hkv) and pda.form(Hq, Hkv) == 'mxu'
    assert not pda.shapes_ok(Hkv, dh, bs, Hkv)
    assert not pda.shapes_ok(Hq, 192, bs, Hkv)
    rng = np.random.RandomState(32)
    kc, vc = _pools(rng, S * MB + 1, 2, bs, Hkv * dh)
    tables = (1 + rng.permutation(S * MB)).reshape(S, MB).astype('int32')
    pos = np.array([0, bs - 1, 3 * bs + 5, MB * bs - 1], 'int32')
    q = rng.randn(S, Hq, dh).astype('float32')
    want = _attend('off', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    got = _attend('interpret', monkeypatch, q, kc, vc, tables, pos, 1, bs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a_part_of_a_head_is_rotated_and_the_rest_passes():
    rng = np.random.RandomState(1)
    pos = np.array([0, 63, 17, 5, 40])
    x = rng.randn(5, 4, 16).astype('float32')
    inv = 1e7 ** (-np.arange(0, 4, 2) / 4.0)
    ang = np.concatenate([pos[:, None] * inv] * 2, axis=-1)[:, None, :]
    part = x[..., :4]
    half = np.concatenate([-part[..., 2:], part[..., :2]], axis=-1)
    want = np.concatenate([part * np.cos(ang) + half * np.sin(ang),
                           x[..., 4:]], axis=-1)
    got = lower('rotary_embedding', {'theta': 1e7, 'rotary_dim': 4}, X=x,
                Positions=pos[:, None])['Out']
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got)[..., 4:], x[..., 4:])
    np.testing.assert_allclose(
        np.asarray(ref.rope(jnp.asarray(x), jnp.asarray(pos), 1e7, 4)), want,
        rtol=1e-5, atol=1e-5)
    # all of the head is another rotation
    whole = lower('rotary_embedding', {'theta': 1e7}, X=x,
                  Positions=pos[:, None])['Out']
    assert np.abs(np.asarray(whole) - want).max() > 0.1


def test_the_zero_centred_norm_multiplies_by_one_plus_its_weight():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 64).astype('float32') * 3
    w = (0.1 * rng.randn(64)).astype('float32')
    unit = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6)
    got = lower('rms_norm', {'epsilon': 1e-6, 'begin_norm_axis': 2,
                             'zero_centred': True}, X=x, Scale=w)['Out']
    np.testing.assert_allclose(got, unit * (1 + w), rtol=1e-5, atol=1e-6)
    plain = lower('rms_norm', {'epsilon': 1e-6, 'begin_norm_axis': 2},
                  X=x, Scale=w)['Out']
    np.testing.assert_allclose(plain, unit * w, rtol=1e-5, atol=1e-6)


# ---- 2. the expert shares ---------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over eight chips at toy width: 16 experts of
    which each chip holds two, 3 a token. The parts of the routed sum the
    eight shares give -- each the reference's own forward of the layer
    with that share held, the GATED shared expert (what every chip computes
    alike) taken out and counted once -- add up to what the uncut layer
    gives with all sixteen held; and the op gives each share what the
    reference gives it."""
    from paddle_tpu.ops.moe_ops import grouped_ffn, route
    rng = np.random.RandomState(3)
    d, e, width, rows, top_k = 32, 16, 24, 40, 3
    w = {'ln2.w': 0.1 * rng.randn(d), 'moe.router.w': rng.randn(d, e),
         'moe.gate.w': 0.2 * rng.randn(e, d, width),
         'moe.up.w': 0.2 * rng.randn(e, d, width),
         'moe.down.w': 0.2 * rng.randn(e, width, d),
         'moe.shared.gate.w': 0.2 * rng.randn(d, width),
         'moe.shared.up.w': 0.2 * rng.randn(d, width),
         'moe.shared.down.w': 0.2 * rng.randn(width, d),
         'moe.shared_gate.w': rng.randn(d, 1)}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    x = jnp.asarray(rng.randn(rows, d), jnp.float32)

    def layer(first, held, **kw):
        cut = dict(w, **{k: w[k][first:first + held]
                         for k in ('moe.gate.w', 'moe.up.w', 'moe.down.w')})
        return np.asarray(ref._experts(x, cut, top_k=top_k, first=first,
                                       eps=1e-6, **kw)) - np.asarray(x)
    whole = layer(0, e)
    zero = dict(w, **{'moe.shared.down.w': jnp.zeros((width, d))})
    shared = whole - (np.asarray(ref._experts(
        x, zero, top_k=top_k, first=0, eps=1e-6)) - np.asarray(x))
    assert np.abs(shared).max() > 1e-2
    parts = [layer(2 * i, 2) - shared for i in range(8)]
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-4,
                               atol=1e-5)
    # the gate counts: the shared expert ungated is another layer
    assert np.abs(layer(0, e, shared_gate=False) - whole).max() > 1e-2
    g = ref._rms(x, w['ln2.w'], 1e-6)
    weights, idx = route(g, w['moe.router.w'], top_k, True)
    for i in (0, 3, 7):
        got = grouped_ffn(g, weights, idx, w['moe.gate.w'][2 * i:2 * i + 2],
                          w['moe.up.w'][2 * i:2 * i + 2],
                          w['moe.down.w'][2 * i:2 * i + 2], 2 * i, e)
        np.testing.assert_allclose(np.asarray(got), parts[i], rtol=1e-4,
                                   atol=1e-5)


def test_the_router_scores_512_and_takes_10():
    """The published router's width and count at a chunk's 512 rows: 5 120
    assignments of which about an eighth fall to the 64 held; the weights
    of a row's ten add up to 1."""
    from paddle_tpu.ops.moe_ops import route
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(512, 64), jnp.float32)
    w, idx = route(x, jnp.asarray(rng.randn(64, 512), jnp.float32), 10, True)
    assert idx.shape == (512, 10) and int(idx.max()) < 512
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)
    held = int((np.asarray(idx) < 64).sum())
    assert 400 < held < 900
    rows = np.asarray(idx)
    assert all(len(set(r)) == 10 for r in rows)


# ---- 3. the model: pools, programs, refusals --------------------------------

def test_the_pools_are_a_row_a_slot_and_only_a_gdn_model_has_them():
    cfg = qwen3next.lm_config(TOY, 64, False)
    assert cfg.layer_types == ('gdn', 'gdn', 'gdn', 'attention')
    assert (cfg.n_gdn_layers, cfg.n_attn_layers) == (3, 1)
    assert (cfg.gdn_inner, cfg.gdn_conv_width) == (32, 96)
    assert T.kv_cache_shapes(cfg, 9, 8, 4) == {
        'gen_kv_k': (9, 1, 8, 32), 'gen_kv_v': (9, 1, 8, 32),
        STATE: (5, 3, 16, 32), TAIL: (5, 3, 8, 96)}
    by = {p.name: p for p in T.cache_pools(cfg, 9, 8, 4)}
    assert by[STATE].books == {'step': ('gdn_state_rows_updated_total', 1),
                               'prefill': 'gdn_prefill_rows_total',
                               'resume': 'gdn_state_resumes_total'}
    assert by[TAIL].books == {} and 'Gated DeltaNet' in by[STATE].why
    # since PR 58 a prefix is shared over the rows: a snapshot row
    assert by[STATE].reach == 1 and not by[TAIL].rewinds
    assert T.kv_cache_shapes(cfg, 9, 8, 4, shared=True)[STATE] == \
        (5 + T.snapshot_rows(4, True), 3, 16, 32)
    plain = LMConfig(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    assert STATE not in T.kv_cache_names(plain)


def test_the_programs_list_the_new_ops_and_fields():
    """The decode step of the toy: three gdn_decode ops with the layers'
    ordinals, the zero-centred norms (and the DeltaNet's plain one inside
    its op), the rotation of a part, the two sigmoid gates."""
    from paddle_tpu import unique_name
    from paddle_tpu.framework import Program, program_guard
    cfg = qwen3next.lm_config(TOY, 64, False)
    main = Program()
    with program_guard(main, Program()):
        with unique_name.guard():
            T.build_lm_decode_step(cfg, 4, 64, 8, 9)
    ops = main.global_block().ops
    gdn = [op for op in ops if op.type == 'gdn_decode']
    assert [op.attr('layer') for op in gdn] == [0, 1, 2]
    assert all(op.attr('key_heads') == 2 for op in gdn)
    norms = [op for op in ops if op.type == 'rms_norm']
    assert len(norms) == 4 * 2 + 1 + 2 and all(
        op.attr('zero_centred') for op in norms)
    rot = [op for op in ops if op.type == 'rotary_embedding']
    assert len(rot) == 2 and all(op.attr('rotary_dim') == 4 for op in rot)
    assert sum(op.type == 'sigmoid' for op in ops) == 1 + 4
    params = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert params == {k: tuple(v)
                      for k, v in qwen3next.param_shapes(TOY).items()}


def test_the_startup_program_takes_the_familys_initialisation():
    from paddle_tpu import Scope
    eng = GenerateEngine(GenerateConfig(
        model=qwen3next.lm_config(TOY, 64, False), slots=2, max_len=64,
        prompt_buckets=[16], eos_id=None, seed=3, block_size=8,
        prefix_sharing=False), scope=Scope())
    a_log = np.asarray(eng.scope.get('layer_0.gdn.A_log'))
    np.testing.assert_allclose(np.exp(a_log), np.linspace(1, 16, 4),
                               rtol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(eng.scope.get('layer_2.gdn.dt.b')), 1.0)
    np.testing.assert_array_equal(
        np.asarray(eng.scope.get('layer_1.gdn.norm.w')), 1.0)
    # a zero-centred norm starts at 0: times 1
    np.testing.assert_array_equal(np.asarray(eng.scope.get('layer_1.ln1.w')),
                                  0.0)
    np.testing.assert_array_equal(
        np.asarray(eng.scope.get('layer_3.attn.q_norm.w')), 0.0)
    assert list(eng.generate_once(_prompt(11), max_new_tokens=3))


# ---- 4. through the engine, against the reference ---------------------------

def _want(scope, prompt, toks, m=TOY):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(
        scope, m, seq, positions=np.arange(len(prompt) - 1, len(seq))))


# (prompt, new tokens, buckets, max_len): one bucket filled (a block of the
# delta rule); a bucket with pad rows; one row; THREE chunks of the widest
# bucket (two blocks each), the last padded -- two chunk's edges; two chunks
# that end on a bucket's edge; several hundred positions in chunks of 128
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
    # the chunks walked the real rows, three layers each; the later chunks
    # resumed from the row; every step advanced one row a layer -- booked
    # through the pool's `books`, under the new kind's names
    assert moved['gdn_prefill_rows_total'] == N_GDN * n_prompt
    assert moved.get('gdn_state_resumes_total', 0) == len(prefills) - 1
    assert moved['gdn_state_rows_updated_total'] == N_GDN * len(steps)
    assert not {k for k in moved if k.startswith(('ssm_', 'ssd_'))}
    # the one attention layer's K/V rows alone
    at = np.arange(n_prompt, n_prompt + n_new - 1)
    assert moved['kv_tokens_read_total'] == int((at + 1).sum())
    # the four expert layers: 3 assignments a real row, those to experts
    # 4..7 computed here (of a chunked prompt the LAST chunk's loads are
    # fetched, with its first token)
    rows = n_prompt - (len(prefills) - 1) * wide + len(steps)
    assert moved['moe_assignments_total'] == 4 * 3 * rows
    assert 0 < moved['moe_held_assignments_total'] \
        < moved['moe_assignments_total']
    assert moved['moe_layer_steps_total'] == 4 * (1 + len(steps))
    assert eng.stats()['state'] == {'capacity': 4, 'in_use': 0}


def test_the_kernels_serve_the_reference_through_the_engine(monkeypatch):
    """The same with heads the kernels tile for and every kernel
    interpreted: three chunks of two blocks, the last padded, then decode."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    before = monitor.counters()
    eng = _engine(m=WIDE)
    eng.warmup()
    moved = monitor.counter_delta(before)
    for op in ('gdn_decode', 'gdn_prefill'):
        assert moved.get('fused_kernel_dispatch_total{impl=interpret,mesh=1,'
                         'op=%s}' % op, 0) >= N_GDN, moved
    log = tap_logits(eng)
    prompt = _prompt(75)
    toks, got, _slot = _serve_one(eng, log, prompt, 6)
    assert logit_gap(got, _want(eng.scope, prompt, toks, WIDE))[1] \
        <= TOLERANCE


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
    tail = np.asarray(eng.scope.get(TAIL))
    assert np.abs(state[1]).max() > 0 and np.abs(tail[1]).max() > 0
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


@pytest.mark.parametrize('control,kw', [
    ('no-decay', {'decay': False}), ('beta-1', {'unit_beta': True}),
    ('no-l2norm', {'l2norm': False}), ('tiled-key-heads', {'tile_keys': True}),
    ('rotate-all', {'rotate_all': True}),
    ('no-attention-gate', {'attention_gate': False}),
    ('ungated-shared-expert', {'shared_gate': False}),
    ('2-experts', {'experts_fewer': 1}), ('plain-norm', {'plain_norm': True}),
    ('chunk-edge', {'zero_state_at': 32})])
def test_a_wrong_forward_is_outside_the_tolerance(control, kw):
    """The controls this family brings, at toy width: each moves the
    reference's logits by well over what the system is held to."""
    scope, prompt = _scope(), _prompt(50)
    own = np.asarray(ref.logits(scope, TOY, prompt))
    wrong = np.asarray(ref.logits(scope, TOY, prompt, **kw))
    assert logit_gap(wrong, own)[1] > 10 * TOLERANCE, control


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(HERE, '..', 'benchmark', 'reference',
                        'qwen3next_reference.py')
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split('.')[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or '').split('.')[0])
    assert names == {'functools', 'jax', 'numpy'}


# ---- 5. the refusals --------------------------------------------------------

@pytest.mark.parametrize('option', ['prefix_sharing', 'speculative'])
def test_a_gdn_model_refuses_sharing_and_speculation_by_name(option):
    """Speculation is refused by name; sharing, refused until PR 58, builds
    an engine whose state rows have a snapshot row a slot behind them
    (tests/test_olmohybrid_serving.py holds a hit to a miss's logits)."""
    kw = {'prefix_sharing': False}
    kw[option] = True

    def build():
        return GenerateEngine(GenerateConfig(
            model=qwen3next.lm_config(TOY, 64, False), slots=2, max_len=64,
            prompt_buckets=[16], block_size=8, **kw))
    if option == 'prefix_sharing':
        eng = build()
        assert [type(b).__name__ for b in eng._sides] == ['SlotRows']
        assert eng.stats()['state']['snapshots'] == {'rows': 2, 'in_use': 0}
        return
    with pytest.raises(ValueError, match=r"%s=True with LMConfig\."
                       r"layer_types=.*'gdn'.*gen_gdn_state.*Gated DeltaNet"
                       % option):
        build()


def test_the_classic_builders_and_lmconfig_refuse_by_name():
    cfg = qwen3next.lm_config(TOY, 32, False)
    for build in (lambda: T.build_lm(cfg),
                  lambda: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
                  lambda: T.build_lm_verify(cfg, 2, 3, 32, 9, 8)):
        with pytest.raises(ValueError, match='cannot express LMConfig.norm'):
            build()
    # the new fields, each by its name
    classic = dict(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    gdn = dict(gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8,
               gdn_value_dim=8)
    with pytest.raises(ValueError, match='LMConfig.layer_types'):
        T.build_lm(LMConfig(layer_types=['gdn', 'attention'],
                            **dict(classic, **gdn)))
    with pytest.raises(ValueError, match='LMConfig.attention_gate'):
        T.build_lm(LMConfig(attention_gate=True, **classic))
    with pytest.raises(ValueError, match="'gdn' layers: they need"):
        LMConfig(layer_types=['gdn', 'attention'], **classic)
    with pytest.raises(ValueError, match="'gdn' layers: they need"):
        LMConfig(layer_types=['gdn', 'attention'],
                 **dict(classic, **dict(gdn, gdn_key_heads=3)))
    with pytest.raises(ValueError, match='LMConfig.rotary_dim'):
        LMConfig(rotary_dim=4, **classic)               # no rope
    with pytest.raises(ValueError, match='LMConfig.rotary_dim'):
        LMConfig(rotary_dim=5, position='rope', **classic)
    with pytest.raises(ValueError, match='LMConfig.norm_zero_centred'):
        LMConfig(norm_zero_centred=True, **classic)
    with pytest.raises(ValueError, match='LMConfig.shared_expert_gate'):
        LMConfig(shared_expert_gate=True, **classic)
    with pytest.raises(ValueError, match='mla'):
        LMConfig(n_layer=1, layer_types=['gdn'], attention='mla',
                 position='rope', q_lora_rank=8, kv_lora_rank=8,
                 qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
                 **dict({k: v for k, v in classic.items() if k != 'n_layer'},
                        **gdn))
