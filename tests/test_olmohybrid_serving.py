"""The Olmo-Hybrid block in the Program path, its documents shared (ISSUE
58): Gated DeltaNet layers of heads that are no whole vregs (96 keys by 192
values published, 32 by 192 here) whose write strength lies in (0, 2), full
attention over unrotated K/V heads with the whole-width q/k-norm, the norm on
each sublayer's OUTPUT -- and SNAPSHOT ROWS: a prefix shared over recurrent
state. The two ops' every tier against the delta rule a position (pairs of
heads in the decode update, heads laid a head first round the prefill's
kernel), prefill (whole, padded, in three chunks) then decode through the
pools against the plain reference's FULL forward pass (logits, not tokens),
a slot served twice; a snapshot hit against a miss on logits for each row
kind (Mamba-1, Mamba-2, the delta rule), a hit at a shallower edge after the
deepest row was evicted, a new tenant after a hit, the bookkeeper and the
copy kernel alone, the counters and `stats()`, the controls, the device-less
Mosaic compile of the kernels at 96 x 192 x 30 and at Qwen3-Next's shapes,
and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-olmohybrid.json): d
64, DeltaNet layers of 6 heads of 32 keys by 192 values (THREE pairs of
heads, a head's values a vreg and a half), 4 taps, blocks of 16 rows, 4
unrotated heads of 16, a gated FFN of 96, 4 layers (three DeltaNet, one
attention), seeded weights with the family's initialisation of the
recurrence.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import gdn_ops, ssm_ops
from paddle_tpu.serving import GenerateConfig, GenerateEngine, kv_blocks

from benchmark.models import jamba, nemotron, olmohybrid, qwen3next
from benchmark.reference import olmohybrid_control as control
from benchmark.reference import olmohybrid_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import lower
from test_paged_decode_attention import one_chip          # noqa: F401

HERE = os.path.dirname(os.path.abspath(__file__))


def _toy(name):
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-%s.json' % name)) as f:
        return json.load(f)


TOY = _toy('olmohybrid')
# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 5e-6 to 1.1e-5 over the comparisons below, the toy's logits lying
# close together); the controls move the logits by 1e-3 and more.
TOLERANCE = 3e-5
STATE, TAIL = T.GDN_STATE, T.GDN_TAIL
N_GDN = 3                       # DeltaNet layers of the toy


def _scope(seed=5, m=TOY, model=olmohybrid):
    from paddle_tpu import Scope
    scope = Scope()
    for name, value in model.init_params(m, seed).items():
        scope.set(name, value)
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=160, slots=4, m=TOY,
            model=olmohybrid, **kw):
    kw.setdefault('block_size', 8)
    kw.setdefault('prefix_sharing', True)
    eng = GenerateEngine(GenerateConfig(
        model=model.lm_config(m, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope(m=m, model=model))
    eng.warmup()
    return eng, control.tap(eng)


def _prompt(n, seed=None, vocab=None):
    return np.random.RandomState(n if seed is None else seed).randint(
        2, vocab or TOY['vocab_size'], size=n).astype('int64')


def _serve(eng, log, prompt, n):
    """(tokens, logits, the row resumed at) of one request served alone."""
    return control.serve(eng, log, prompt, n - 1)


def _want(scope, prompt, toks, m=TOY):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(
        scope, m, seq, positions=np.arange(len(prompt) - 1, len(seq))))


# ---- 1. the ops against the delta rule, position by position ----------------

# key heads, value heads, key size, value size, taps: the toy's three pairs
# of heads of a vreg and a half; eight heads of 48 to a run of three vregs;
# two value heads a key head beside it; Qwen3-Next's whole vregs
PAIRS = (6, 6, 32, 192, 4)
EIGHTS = (8, 8, 8, 48, 4)
GROUPED = (3, 6, 64, 192, 4)
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


def _walk(shape, w, x, z, b, a, s, tail, wide=2.0, eps=1e-6):
    """The layer's rows one position at a time, in float64, the write
    strength ``wide x sigmoid(b)``: (the normed, gated outputs [T, Hv dv],
    the state [dk, Hv dv], the tail) after the rows from the state `s` and
    the tail [K - 1, cw]."""
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
        beta = wide / (1 + np.exp(-b_t))
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


TIERS = [('off', PAIRS), ('xla', PAIRS), ('interpret', PAIRS),
         ('interpret', EIGHTS), ('interpret', GROUPED), ('interpret', TILED)]
NEG = {'allow_neg_eigval': True}


@pytest.mark.parametrize('tier,shape', TIERS)
@pytest.mark.parametrize('attrs', [NEG, {}], ids=['beta-0-2', 'beta-0-1'])
def test_gdn_decode_steps_runs_of_heads_that_are_no_whole_vregs(
        monkeypatch, tier, shape, attrs):
    """Four slots: rows 3, 0 (sits out), 1 and 0. The live rows read their
    state and tail, step once with the write strength in (0, 2) (or, the
    attribute absent, in (0, 1)) and write both back; every shape here
    tiles for the kernel -- a run of two heads of 192, of eight of 48, of
    one of 128 -- and the request for it lands on it."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    assert gdn_ops.shapes_ok(dk, dv, hk, hv)
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
    out = lower('gdn_decode', dict(attrs, layer=1, epsilon=1e-6,
                                   key_heads=hk),
                X=x, Z=z, B=b, A=a, State=state, Tail=tails, Rows=rows, **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=gdn_decode}' % tier) == 1, moved
    got, new_state, new_tails = (np.asarray(out[n]) for n in
                                 ('Out', 'StateOut', 'TailOut'))
    for i, row in enumerate(rows[:, 0]):
        s0 = state[row, 1] if row else np.zeros((dk, vd))
        t0 = tails[row, 1, :k - 1] if row else np.zeros((k - 1, cw))
        want, s1, t1 = _walk(shape, w, x[i:i + 1], z[i:i + 1], b[i:i + 1],
                             a[i:i + 1], s0, t0, wide=2.0 if attrs else 1.0)
        np.testing.assert_allclose(got[i], want[0], rtol=2e-5, atol=2e-5)
        if row:
            np.testing.assert_allclose(new_state[row, 1], s1, rtol=2e-5,
                                       atol=4e-6)
            np.testing.assert_allclose(new_tails[row, 1, :k - 1], t1,
                                       rtol=1e-6)
    for row in (2, 4):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])


# (rows of the bucket, real rows, first position, rows of a block): a whole
# bucket of four blocks from zeros; pad rows in the last of four blocks; a
# later chunk that resumes; one real row
SCANS = [(64, 64, 0, 16), (64, 41, 0, 16), (64, 50, 128, 32), (16, 1, 0, 16)]


@pytest.mark.parametrize('tier,shape', TIERS[:5])
@pytest.mark.parametrize('T_,length,off,chunk', SCANS)
def test_gdn_prefill_lays_heads_that_are_no_whole_vregs_a_head_first(
        monkeypatch, tier, shape, T_, length, off, chunk):
    """THE CHUNKED FORM AGAINST THE RECURRENCE at beta in (0, 2): the
    outputs of the real rows, the state and the tail as of the last real
    row are the delta rule's a position; a chunk at position 0 never reads
    the row, a later one resumes from it; the pad rows advance nothing."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    hk, hv, dk, dv, k, cw, vd = _sizes(shape)
    assert gdn_ops.shapes_ok(dk, dv, hk, hv, T_, min(chunk, T_))
    rng = np.random.RandomState(T_ + length)
    w = _weights(rng, shape)
    state = rng.randn(3, 2, dk, vd).astype('float32')
    tails = rng.randn(3, 2, 8, cw).astype('float32')
    x = (rng.randn(1, T_, cw) + 0.7).astype('float32')
    z = rng.randn(1, T_, vd).astype('float32')
    b, a = rng.randn(1, T_, hv).astype('float32'), \
        rng.randn(1, T_, hv).astype('float32') - 2
    before = monitor.counters()
    out = lower('gdn_prefill', dict(NEG, layer=1, epsilon=1e-6,
                                    key_heads=hk, chunk=chunk),
                X=x, Z=z, B=b, A=a, State=state, Tail=tails,
                Rows=np.array([[2]]), Positions=off + np.arange(T_)[None],
                Length=np.array([[length]]), **w)
    moved = monitor.counter_delta(before)
    assert moved.get('fused_kernel_dispatch_total{impl=%s,mesh=1,'
                     'op=gdn_prefill}' % tier) == 1, moved
    s0 = state[2, 1] if off else np.zeros((dk, vd))
    t0 = tails[2, 1, :k - 1] if off else np.zeros((k - 1, cw))
    want, s1, t1 = _walk(shape, w, x[0, :length], z[0, :length],
                         b[0, :length], a[0, :length], s0, t0)
    got = np.asarray(out['Out'])[0]
    np.testing.assert_allclose(got[:length], want, rtol=2e-4, atol=4e-5)
    assert np.isfinite(got).all()
    new_state, new_tails = np.asarray(out['StateOut']), \
        np.asarray(out['TailOut'])
    np.testing.assert_allclose(new_state[2, 1], s1, rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(new_tails[2, 1, :k - 1], t1, rtol=1e-6)
    for row in (0, 1):
        np.testing.assert_array_equal(new_state[row], state[row])
        np.testing.assert_array_equal(new_tails[row], tails[row])
    np.testing.assert_array_equal(new_state[:, 0], state[:, 0])


def test_the_tiling_rule_is_runs_of_heads_and_strips_of_runs():
    """The published shapes tile: a run is two heads of 192 (three vregs),
    a strip of the decode grid ten heads of the thirty (737 KB of a slot's
    state); Qwen3-Next's strip stands as it was. An odd number of heads of
    192, keys that fill no sublane tile, blocks of rows that do not divide
    the bucket: the request for the kernel lands on `xla`."""
    assert gdn_ops.shapes_ok(96, 192, 30, 30)
    for rows in (128, 256, 512):
        assert gdn_ops.shapes_ok(96, 192, 30, 30, rows, 64)
    assert [gdn_ops._heads_a_run(dv) for dv in (128, 192, 256, 48, 64)] \
        == [1, 2, 1, 8, 2]
    assert gdn_ops._heads_a_strip(96, 192, 30, 1) == 10
    assert 10 * 96 * 192 * 4 == 737280
    assert gdn_ops._heads_a_strip(128, 128, 32, 2) == 16
    assert gdn_ops._heads_a_strip(24, 192, 6, 1) == 6
    assert gdn_ops._heads_a_strip(24, 192, 6, 2) == 6
    assert not gdn_ops.shapes_ok(96, 192, 15, 15)
    assert not gdn_ops.shapes_ok(12, 192, 2, 2)
    assert not gdn_ops.shapes_ok(96, 192, 30, 30, 96, 64)
    assert not gdn_ops.shapes_ok(96, 192, 30, 30, 128, 24)


# ---- 2. Mosaic, without a chip ----------------------------------------------

@pytest.mark.parametrize('hk,hv,dk,dv,slots', [
    (30, 30, 96, 192, 32), (16, 32, 128, 128, 64)],
    ids=['olmo-hybrid-7b-l8', 'qwen3-next-80b-a3b-ep8-l8'])
def test_mosaic_accepts_both_kernels_at_the_cells_shapes(one_chip,  # noqa
                                                         hk, hv, dk, dv,
                                                         slots):
    """The decode update over the cell's state pool (donated: updated in
    place, no pool-sized temporary) and the chunked prefill at every bucket,
    compiled for a described v5e: 96 x 192 x 30 takes the run of two heads
    and the head-first layout, 128 x 128 x 16/32 the paths it had."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    vd, rows = hv * dv, 2 * slots + 1
    c = jax.jit(lambda st, r, layer, d, b, v, q, k: gdn_ops.decode_update(
        st, r, layer, d, b, v, q, k, value_heads=hv),
        donate_argnums=0).lower(
        sds((rows, 6, dk, vd)), sds((slots,), jnp.int32),
        sds((), jnp.int32), sds((slots, vd)), sds((slots, vd)),
        sds((slots, vd)), sds((slots, hk, dk)),
        sds((slots, hk, dk))).compile()
    text = c.as_text()
    assert text.count('tpu_custom_call') == 1 and 'gdn_decode_update' in text
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
    for bucket in (128, 512):
        c = jax.jit(lambda q, k, v, g, b, s0: gdn_ops.prefill_chunks(
            q, k, v, g, b, s0, chunk=64)).lower(
            sds((bucket, hk, dk)), sds((bucket, hk, dk)),
            sds((bucket, hv, dv)), sds((bucket, hv)), sds((bucket, hv)),
            sds((dk, vd))).compile()
        text = c.as_text()
        assert text.count('tpu_custom_call') == 1
        assert 'gdn_prefill_chunk' in text


def test_mosaic_accepts_the_snapshot_copy_in_place(one_chip):  # noqa: F811
    """A row of the cell's state pool (and of its tails') copied over
    another by one DMA, the pool donated: no temporary at all."""
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    for shape in ((65, 6, 96, 5760), (65, 6, 8, 11520)):
        c = jax.jit(lambda p, s, d: ssm_ops.copy_row(p, s, d),
                    donate_argnums=0).lower(
            sds(shape), sds((), jnp.int32), sds((), jnp.int32)).compile()
        text = c.as_text()
        assert text.count('tpu_custom_call') == 1
        assert 'state_snapshot_copy' in text
        assert c.memory_analysis().temp_size_in_bytes < 1 << 16


@pytest.mark.parametrize('tier', ['xla', 'interpret'])
def test_the_snapshot_copy_moves_one_row_and_no_other(monkeypatch, tier):
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    pool = jnp.asarray(np.random.RandomState(0).randn(5, 2, 8, 128),
                       jnp.float32)
    got = np.asarray(ssm_ops.snapshot_copy(
        pool, jnp.array([3]), jnp.array([1]), kv_blocks.SlotRows.scope))
    want = np.asarray(pool).copy()
    want[1] = want[3]
    np.testing.assert_array_equal(got, want)


# ---- 3. the model: fields, pools, programs ----------------------------------

def test_the_pools_have_a_snapshot_row_a_slot_where_prefixes_are_shared():
    cfg = olmohybrid.lm_config(TOY, 64, False)
    assert cfg.layer_types == ('gdn', 'gdn', 'gdn', 'attention')
    assert (cfg.gdn_inner, cfg.gdn_conv_width) == (1152, 1536)
    assert T.kv_cache_shapes(cfg, 9, 8, 4) == {
        'gen_kv_k': (9, 1, 8, 64), 'gen_kv_v': (9, 1, 8, 64),
        STATE: (5, 3, 32, 1152), TAIL: (5, 3, 8, 1536)}
    shared = T.kv_cache_shapes(cfg, 9, 8, 4, shared=True)
    assert (T.snapshot_rows(4), T.snapshot_rows(4, True)) == (0, 4)
    assert shared[STATE] == (9, 3, 32, 1152) and shared[TAIL][0] == 9
    assert shared['gen_kv_k'] == (9, 1, 8, 64)
    by = {p.name: p for p in T.cache_pools(cfg, 9, 8, 4, True)}
    assert by[STATE].reach == 1 and not by[STATE].rewinds
    assert 'snapshot row' in by[STATE].why
    assert 'no state to resume from' not in by[STATE].why


def test_the_programs_list_the_ops_and_the_new_fields():
    """The decode step of the toy: three gdn_decode ops that say
    `allow_neg_eigval`, a norm on each sublayer's OUTPUT (2 a layer, the
    final one, the whole-width q and k norms), nothing rotated; Qwen3-Next's
    toy lists the attribute nowhere."""
    from paddle_tpu import unique_name
    from paddle_tpu.framework import Program, program_guard

    def ops_of(cfg):
        main = Program()
        with program_guard(main, Program()):
            with unique_name.guard():
                T.build_lm_decode_step(cfg, 4, 64, 8, 9)
        return main.global_block().ops
    cfg = olmohybrid.lm_config(TOY, 64, False)
    assert (cfg.norm_placement, cfg.gdn_allow_neg_eigval, cfg.position,
            cfg.qk_norm, cfg.ffn, cfg.bias, cfg.norm) == \
        ('post', True, 'none', True, 'gated', False, 'rms_norm')
    ops = ops_of(cfg)
    gdn = [op for op in ops if op.type == 'gdn_decode']
    assert [op.attr('layer') for op in gdn] == [0, 1, 2]
    assert all(op.attr('allow_neg_eigval') is True for op in gdn)
    assert len([op for op in ops if op.type == 'rms_norm']) == 4 * 2 + 1 + 2
    assert not [op for op in ops if op.type == 'rotary_embedding']
    # the first norm of a layer reads the mixer's output, not the stream
    first = next(op for op in ops if op.type == 'rms_norm')
    assert 'layer_0.ln1.w' in str(first.inputs)
    produced = {name for op in ops[:ops.index(first)]
                for names in op.outputs.values() for name in
                (getattr(v, 'name', v) for v in names)}
    assert any(getattr(v, 'name', v) in produced
               for v in first.inputs['X'])
    assert [op for op in ops[:ops.index(first)] if op.type == 'gdn_decode']
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-qwen3next.json')) as f:
        other = ops_of(qwen3next.lm_config(json.load(f), 64, False))
    assert not [op for op in other if op.has_attr('allow_neg_eigval')]


def test_the_classic_builders_and_lmconfig_refuse_the_new_fields_by_name():
    cfg = olmohybrid.lm_config(TOY, 32, False)
    for build in (lambda: T.build_lm(cfg),
                  lambda: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
                  lambda: T.build_lm_verify(cfg, 2, 3, 32, 9, 8)):
        with pytest.raises(ValueError, match='cannot express LMConfig.norm'):
            build()
    classic = dict(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    with pytest.raises(ValueError, match='LMConfig.norm_placement'):
        LMConfig(norm_placement='after', **classic)
    with pytest.raises(ValueError, match="norm_placement='post' is built "
                       "with norm='rms_norm'"):
        LMConfig(norm_placement='post', **classic)
    rms = dict(classic, norm='rms_norm')
    with pytest.raises(ValueError, match=r'LMConfig\.norm='):
        T.build_lm(LMConfig(norm_placement='post', **rms))
    fields = dict(T._CLASSIC_BLOCK)
    assert 'norm_placement' not in fields       # in the tuple beside it
    with pytest.raises(ValueError, match='LMConfig.gdn_allow_neg_eigval'):
        T._require_classic_block(LMConfig(gdn_allow_neg_eigval=True,
                                          **classic), 'build_lm')
    with pytest.raises(ValueError, match='speculative=True with LMConfig'):
        GenerateEngine(GenerateConfig(
            model=cfg, slots=2, max_len=32, prompt_buckets=[16],
            block_size=8, prefix_sharing=False, speculative=True))


# ---- 4. through the engine, against the reference ---------------------------

# (prompt, new tokens, buckets, max_len): one bucket filled; a bucket with
# pad rows; one row; THREE chunks of the widest bucket, the last padded;
# two chunks that end on a bucket's edge
THROUGH = [(16, 5, (16, 32), 160), (21, 9, (16, 32), 160),
           (1, 4, (16, 32), 160), (75, 12, (16, 32), 160),
           (64, 6, (16, 32), 160)]


@pytest.mark.parametrize('n_prompt,n_new,buckets,max_len', THROUGH)
def test_prefill_then_decode_through_the_pools_equals_the_full_forward(
        n_prompt, n_new, buckets, max_len):
    eng, log = _engine(buckets=buckets, max_len=max_len)
    prompt = _prompt(n_prompt)
    before = monitor.counters()
    toks, got, edge = _serve(eng, log, prompt, n_new)
    moved = monitor.counter_delta(before)
    assert len(toks) == n_new and edge == 0
    np.testing.assert_array_equal(got.argmax(axis=1), toks)
    assert logit_gap(got, _want(eng.scope, prompt, toks))[1] <= TOLERANCE
    assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0
    chunks = -(-n_prompt // max(buckets))
    assert moved['gdn_prefill_rows_total'] == N_GDN * n_prompt
    assert moved.get('gdn_state_resumes_total', 0) == chunks - 1
    assert moved['gdn_state_rows_updated_total'] == N_GDN * (n_new - 1)
    # a snapshot row where a dispatch ended on a block's edge
    ends = [min(n_prompt, (i + 1) * max(buckets)) for i in range(chunks)]
    assert moved.get('state_snapshot_rows_written_total', 0) == \
        sum(e % 8 == 0 for e in ends)


def test_the_kernels_serve_the_reference_through_the_engine(monkeypatch):
    """The same with every kernel interpreted -- the run of two heads, the
    head-first prefill, the snapshot's DMA: a miss in three chunks, then a
    hit that resumes at the second chunk's edge."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    before = monitor.counters()
    eng, log = _engine()
    moved = monitor.counter_delta(before)
    for op in ('gdn_decode', 'gdn_prefill', 'state_snapshot_copy'):
        assert moved.get('fused_kernel_dispatch_total{impl=interpret,mesh=1,'
                         'op=%s}' % op, 0) >= 1, moved
    doc = _prompt(64, 1)
    for seed, resumed in ((2, 0), (3, 64)):
        prompt = np.concatenate([doc, _prompt(11, seed)])
        toks, got, edge = _serve(eng, log, prompt, 5)
        assert edge == resumed
        assert logit_gap(got, _want(eng.scope, prompt, toks))[1] <= TOLERANCE


def test_a_slot_served_twice_gives_the_second_tenant_its_own_logits():
    """One slot, so the second request sits on the first's row: its logits
    are BIT FOR BIT those of a fresh engine that served it alone, and the
    reference's."""
    scope = _scope()
    eng, log = _engine(scope, slots=1)
    first, second = _prompt(40), _prompt(37, seed=9)
    _serve(eng, log, first, 7)
    assert np.abs(np.asarray(eng.scope.get(STATE))[1]).max() > 0
    toks, got, _ = _serve(eng, log, second, 8)
    alone, alone_log = _engine(_scope(), slots=1)
    toks_alone, got_alone, _ = _serve(alone, alone_log, second, 8)
    np.testing.assert_array_equal(toks, toks_alone)
    np.testing.assert_array_equal(got, got_alone)
    assert logit_gap(got, _want(eng.scope, second, toks))[1] <= TOLERANCE


# ---- 5. snapshot rows: a hit's logits are a miss's --------------------------

def _kind(name):
    model = {'olmohybrid': olmohybrid, 'qwen3next': qwen3next,
             'jamba': jamba, 'nemotron': nemotron}[name]
    return model, _toy(name)


@pytest.mark.parametrize('name', ['olmohybrid', 'qwen3next', 'jamba',
                                  'nemotron'])
def test_a_snapshot_hit_serves_a_misss_logits_for_every_row_kind(name):
    """Two readers of one document (64 tokens: two chunks of 32, eight
    blocks) on an engine that shares prefixes and on one that does not: the
    second reader resumes at the document's end from a snapshot row -- the
    K/V blocks shared, 64 tokens never prefilled -- and its tokens and
    logits are BIT FOR BIT the miss's. Each kind of 'row' pool: the delta
    rule's rows (two models), Mamba-1's, Mamba-2's."""
    model, m = _kind(name)
    assert {p.index for p in T.cache_pools(
        model.lm_config(m, 64, False), 9, 8, 4, True)} == {'block', 'row'}
    scope = _scope(m=m, model=model)
    doc = _prompt(64, 1, m['vocab_size'])
    prompts = [np.concatenate([doc, _prompt(n, n, m['vocab_size'])])
               for n in (11, 9)]
    served = {}
    for share in (False, True):
        eng, log = _engine(scope, m=m, model=model, prefix_sharing=share)
        before = monitor.counters()
        served[share] = [_serve(eng, log, p, 6) for p in prompts]
        moved = monitor.counter_delta(before)
        snaps = eng.stats()['state'].get('snapshots')
        if not share:
            assert snaps is None and not {
                k for k in moved if k.startswith('state_snapshot')}
            continue
        # the miss left a row at each chunk's edge, the hit took the deeper
        assert snaps == {'rows': 4, 'in_use': 2}
        assert moved['state_snapshot_rows_written_total'] == 2
        assert moved['state_snapshot_resumes_total'] == 1
        assert moved['state_snapshot_tokens_resumed_total'] == 64
        assert moved['kv_prefix_tokens_saved_total'] == 64
    assert [r[2] for r in served[True]] == [0, 64]
    assert [r[2] for r in served[False]] == [0, 0]
    for hit, miss in zip(served[True], served[False]):
        np.testing.assert_array_equal(hit[0], miss[0])
        np.testing.assert_array_equal(hit[1], miss[1])


def test_a_hit_resumes_at_a_shallower_edge_after_the_deepest_row_went():
    """Two snapshot rows (two slots) and a document of three chunks: the
    miss writes rows at 32 and 64, and for the third edge the rows' own
    pressure gives up the least recently used, the SHALLOWEST first. With
    the deepest entry's row then let go by hand, a reader resumes at 64,
    the deepest edge that still has one, prefills the rest and lands on the
    reference's logits; its own chunk's end at 96 takes a row again."""
    eng, log = _engine(slots=2)
    doc = _prompt(96, 1)
    before = monitor.counters()
    _serve(eng, log, np.concatenate([doc, _prompt(5, 2)]), 3)
    moved = monitor.counter_delta(before)
    assert moved['state_snapshot_rows_written_total'] == 3
    assert moved['state_snapshot_evictions_total'] == 1
    hashes = kv_blocks.chain_hashes(doc, 8)
    cache = eng._prefix
    assert [cache.has_side(h) for h in hashes] == \
        [False] * 7 + [True] + [False] * 3 + [True]     # edges 64 and 96
    # the deepest row goes (as pressure would take it once it is the oldest)
    e = cache._entries[hashes[11]]
    eng._sides[0].blocks.deref(e[3])
    e[3] = None
    prompt = np.concatenate([doc, _prompt(7, 3)])
    before = monitor.counters()
    toks, got, edge = _serve(eng, log, prompt, 5)
    moved = monitor.counter_delta(before)
    assert edge == 64
    assert moved['state_snapshot_tokens_resumed_total'] == 64
    assert moved['gdn_prefill_rows_total'] == N_GDN * (len(prompt) - 64)
    assert logit_gap(got, _want(eng.scope, prompt, toks))[1] <= TOLERANCE
    assert cache.has_side(hashes[11])
    # a request that matches no more than the first chunk misses: the row
    # of edge 32 went first
    short = np.concatenate([doc[:40], _prompt(9, 4)])
    assert _serve(eng, log, short, 3)[2] == 0


def test_a_new_tenant_after_a_hit_never_reads_the_last_tenants_row():
    """One slot. A reader of document A, a HIT on A, then a reader of
    document B in the same slot: B's first chunk starts from zeros, not
    from what the hit's copy and its tenant left in the row; then a hit on
    B copies B's row over A's tenant's. Each is the reference's."""
    eng, log = _engine(slots=1, max_len=96)
    a, b = _prompt(32, 1), _prompt(32, 2)
    for doc, seed, resumed in ((a, 3, 0), (a, 4, 32), (b, 5, 0), (b, 6, 32),
                               (a, 7, 0)):
        prompt = np.concatenate([doc, _prompt(9, seed)])
        toks, got, edge = _serve(eng, log, prompt, 4)
        # one snapshot row: B's edge took A's, and A's reader misses again
        assert edge == resumed
        assert logit_gap(got, _want(eng.scope, prompt, toks))[1] <= TOLERANCE


def test_a_wholly_shared_prompt_resumes_before_its_last_block():
    """A prompt that IS a published prefix (64 tokens, every block matched):
    no row pool copies a block, so the last block is recomputed -- from the
    deepest edge BEFORE it that has a row (32), else from zeros."""
    eng, log = _engine()
    doc = _prompt(64, 1)
    first = _serve(eng, log, doc, 4)
    again = _serve(eng, log, doc, 4)
    assert (first[2], again[2]) == (0, 32)
    np.testing.assert_array_equal(first[1], again[1])


# ---- 6. the bookkeeper alone ------------------------------------------------

def test_slot_rows_lends_a_row_for_a_copy_and_the_cache_keeps_it():
    free = [1, 0]
    rows = kv_blocks.SlotRows(2, free, snapshots=2, block_size=8)
    alloc = kv_blocks.BlockAllocator(9, 8)
    cache = kv_blocks.PrefixCache(alloc, rows.blocks)
    rows.cache = cache
    assert (rows.table(0), rows.table(1), rows.reach, rows.batch) == \
        (1, 2, 1, 1)
    hashes = kv_blocks.chain_hashes(list(range(32)), 8)
    blocks = alloc.alloc(4)
    rows.snapshot(0, 1)                    # slot 0's dispatch ended block 1
    sid = rows.held(0, 1)
    assert sid == 1 and rows.held(0, 0) is None and rows.held(1, 1) is None
    for i in (0, 1):
        held = rows.held(0, i)
        cache.register(hashes[i], i, blocks[i],
                       None if held is None else (held, 7))
    assert rows.moved() == [(1, 2 + sid)]       # slot 0's row -> its copy
    assert rows.blocks.refcount(sid) == 1       # the cache's alone
    assert rows.moved() == []
    # a reader of both blocks resumes at depth 2, of one block nowhere
    assert cache.side_run(hashes, 2, rows.reach) == (2, [sid])
    assert cache.side_run(hashes, 1, rows.reach) == (0, [])
    rows.blocks.ref(sid)                        # `_paged_plan`'s pin
    rows.resume(1, 2, [sid])
    assert rows.moved() == [(2 + sid, 2)]       # the copy -> slot 1's row
    assert rows.blocks.refcount(sid) == 1
    # two more edges: the second spare row, then the least recently used
    rows.snapshot(1, 2)
    cache.register(hashes[2], 2, blocks[2], (rows.held(1, 2), 7))
    rows.moved()
    before = monitor.counters()
    rows.snapshot(1, 3)
    assert monitor.counter_delta(before) == {
        'state_snapshot_evictions_total': 1,
        'state_snapshot_rows_written_total': 1}
    assert not cache.has_side(hashes[1]) and cache.has_side(hashes[2])
    stats = {}
    rows.report(stats)
    assert stats['state'] == {'capacity': 2, 'in_use': 0,
                              'snapshots': {'rows': 2, 'in_use': 2}}
    rows.release(1)
    assert rows.held(1, 3) is None
    # without sharing: no rows, no allocator, `report` says nothing of them
    plain = kv_blocks.SlotRows(2, free)
    plain.report(stats)
    assert plain.blocks is None and 'snapshots' not in stats['state']


def test_window_layers_and_state_rows_together_are_refused_by_name():
    """The prefix cache's entries hold ONE side block: a model with window
    layers AND state rows would need two (docs/serving.md)."""
    cfg = LMConfig(vocab_size=50, d_model=32, n_head=2, n_layer=3, d_ff=64,
                   norm='rms_norm', position='rope', bias=False, ffn='gated',
                   layer_types=['window', 'ssm', 'attention'],
                   sliding_window=16)
    with pytest.raises(ValueError, match="hold ONE side block"):
        GenerateEngine(GenerateConfig(
            model=cfg, slots=2, max_len=64, prompt_buckets=[16],
            block_size=8, prefix_sharing=True))


# ---- 7. the controls --------------------------------------------------------

@pytest.mark.parametrize('name,kw', [
    ('bfloat16', {'dtype': jnp.bfloat16}),
    ('bfloat16-state', {'state_dtype': jnp.bfloat16}),
    ('beta-in-0-1', {'neg_eigval': False}), ('pre-norm', {'pre_norm': True}),
    ('rope', {'rope_theta': control.ROPE_THETA}),
    ('chunk-edge', {'resume': (32, None, True, False)}),
    ('kv-shared-state-zero', {'resume': (32, None, True, True)}),
    ('tail-not-restored', {'resume': (32, None, False, True)}),
    ('another-prefix-snapshot', {'resume': (32, 'other', True, True)})])
def test_a_wrong_forward_is_outside_the_tolerance(name, kw):
    """The controls this configuration brings, at toy width: each moves the
    reference's logits by well over what the system is held to; and the
    resume the controls are made of is the identity where it is given the
    prompt's own rows."""
    scope, prompt = _scope(), _prompt(50)
    own = np.asarray(ref.logits(scope, TOY, prompt))
    if kw.get('resume', (0, None))[1] == 'other':
        kw = {'resume': (32, ref.forward(scope, TOY, _prompt(32, 7))[1],
                         True, True)}
        mine = ref.forward(scope, TOY, prompt[:32])[1]
        same = np.asarray(ref.logits(scope, TOY, prompt,
                                     resume=(32, mine, True, True)))
        assert logit_gap(same, own)[1] <= TOLERANCE
    wrong = np.asarray(ref.logits(scope, TOY, prompt, **kw))
    assert logit_gap(wrong[32:], own[32:])[1] > 10 * TOLERANCE, name
    assert name in control.controls(50, [16, 32], 32 * (
        'resume' in kw and name != 'chunk-edge'), None)


def test_the_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(HERE, '..', 'benchmark', 'reference',
                        'olmohybrid_reference.py')
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or '')
    assert names == {'functools', 'jax', 'jax.numpy', 'numpy'}
