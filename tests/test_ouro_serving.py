"""A looped language model in the Program path (ISSUE 63; Ouro,
arXiv:2510.25741): the layer stack run `LMConfig.passes` times a token over
ONE set of weights, the final norm and the exit gate after every pass, K and
V kept a (pass, layer) -- the first model here whose cache layers are not
its weight layers -- and a norm before and after each sublayer
(`norm_placement='sandwich'`). Prefill (whole, padded, chunked) then decode
through the pools against the plain reference's FULL forward of all passes
(logits, not tokens) at 1, 2 and 4 passes; a prefix hit (shared blocks, the
last one copied on write) against the no-hit logits; the pools, a block's
bytes and the engine's books R-fold where the parameters are not; the
passes' cache entries crossed in the pool and in the reference, and a pass
dropped; the three norm placements against a block written out by hand; the
counters against the reference's gate; the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-ouro.json): d 64, 4
heads of 16 rotated at theta 1e6, a gated FFN of 96, 3 layers, blocks of 8
rows, seeded weights with every norm spread round 1 and the gate's bias
spread.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import Scope, monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import ouro
from benchmark.reference import ouro_control as control
from benchmark.reference import ouro_reference as ref
from benchmark.reference.olmoe_control import logit_gap

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-ouro.json')) as f:
    TOY = json.load(f)
# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 6e-7 to 1.3e-6 over the comparisons below); the controls move the
# logits by 1e-2 (bfloat16) to 1 (a pass dropped, the caches crossed).
TOLERANCE = 1e-5
PASSES = (1, 2, 4)


def _toy(passes=4, **kw):
    return dict(TOY, total_ut_steps=passes, **kw)


def _scope(m, seed=5):
    scope = Scope()
    for name, value in ouro.init_params(m, seed).items():
        scope.set(name, value)
    return scope


def _engine(m, scope=None, buckets=(8, 16), max_len=64, slots=3, cfg=None,
            **kw):
    kw.setdefault('block_size', 8)
    eng = GenerateEngine(GenerateConfig(
        model=cfg or ouro.lm_config(m, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope(m))
    eng.warmup()
    return eng, control.tap(eng)


def _prompt(n, seed=None):
    return np.random.RandomState(n if seed is None else seed).randint(
        2, TOY['vocab_size'], size=n).astype('int64')


def _want(scope, m, prompt, toks, **kw):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(
        scope, m, seq, positions=np.arange(len(prompt) - 1, len(seq)), **kw))


# ---- 1. through the engine, against the reference ---------------------------

# (prompt, new tokens, buckets): a bucket filled; a bucket with pad rows; one
# row; THREE chunks of the widest bucket, the last padded
CASES = [(16, 6, (8, 16)), (11, 5, (8, 16)), (1, 4, (8, 16)),
         (37, 5, (8, 16))]


@pytest.mark.parametrize('passes', PASSES)
def test_prefill_then_decode_match_the_reference_on_logits(passes):
    m = _toy(passes)
    eng, log = _engine(m)
    for n, new, _buckets in CASES:
        prompt = _prompt(n)
        toks, got, edge = control.serve(eng, log, prompt, new - 1)
        assert edge == 0 and len(toks) == new
        gap = logit_gap(got, _want(eng.scope, m, prompt, toks))
        assert gap[1] < TOLERANCE, (passes, n, gap)
    # nothing compiled after warm-up: a bucket a prefill and the step
    assert len(eng._prefill) + 1 == 3


@pytest.mark.parametrize('passes', (2, 4))
def test_chunked_and_bucketed_prefills_agree(passes):
    m = _toy(passes)
    scope = _scope(m)
    prompt = _prompt(29)
    runs = []
    for buckets in ((32,), (8, 16), (8,)):      # whole; 16 + 16 pad; 4 chunks
        eng, log = _engine(m, scope, buckets)
        runs.append(control.serve(eng, log, prompt, 5))
    for toks, got, _ in runs[1:]:
        np.testing.assert_array_equal(toks, runs[0][0])
        assert logit_gap(got, runs[0][1])[1] < TOLERANCE


@pytest.mark.parametrize('passes', (2, 4))
def test_a_prefix_hit_gives_the_no_hit_logits_at_every_pass(passes):
    """Shared blocks hold every pass's K and V: a request that resumes
    behind them -- at a block's edge, and wholly shared with its last block
    copied on write -- reads what a request that computed them reads."""
    m = _toy(passes)
    scope = _scope(m)
    eng, log = _engine(m, scope, prefix_sharing=True)
    base = _prompt(24)
    first = control.serve(eng, log, base, 5)
    assert first[2] == 0
    again = control.serve(eng, log, base, 5)            # wholly shared
    longer = np.concatenate([base[:16], _prompt(9, seed=77)])
    tail = control.serve(eng, log, longer, 5)           # two blocks shared
    assert again[2] == 23 and tail[2] == 16
    assert eng.stats()['blocks']['prefix_entries'] > 0
    assert monitor.counters().get('kv_block_cow_total', 0) > 0
    np.testing.assert_array_equal(again[0], first[0])
    assert logit_gap(again[1], first[1])[1] < TOLERANCE
    for prompt, (toks, got, _) in ((base, again), (longer, tail)):
        assert logit_gap(got, _want(scope, m, prompt, toks))[1] < TOLERANCE
    # and a fresh engine that shares nothing reads the same
    plain, plog = _engine(m, scope)
    toks, got, edge = control.serve(plain, plog, longer, 5)
    assert edge == 0 and logit_gap(got, tail[1])[1] < TOLERANCE


# ---- 2. the pools are R-fold, the parameters are not ------------------------

@pytest.mark.parametrize('passes', PASSES)
def test_pools_and_books_are_r_fold_and_the_parameters_are_not(passes):
    m = _toy(passes)
    cfg = ouro.lm_config(m, 64, False)
    layers = m['num_hidden_layers']
    assert (cfg.passes, cfg.n_layer, cfg.n_attn_layers) == (passes, layers,
                                                            layers)
    shapes = T.kv_cache_shapes(cfg, 9, 8, 3)
    assert shapes == {T.KV_CACHE_K: (9, passes * layers, 8, 64),
                      T.KV_CACHE_V: (9, passes * layers, 8, 64)}
    assert [cfg.cache_ordinal(i, t) for t in range(passes)
            for i in range(layers)] == list(range(passes * layers))
    # a block's bytes: K and V of every layer once a pass
    block = sum(4 * int(np.prod(s[1:])) for s in shapes.values())
    assert block == 8 * passes * layers * 2 * 64 * 4
    # one set of weights whatever the passes (the gate comes with a loop)
    params = {k: v for k, v in ouro.param_shapes(m).items()
              if not k.startswith('exit_gate')}
    assert params == {k: v for k, v in ouro.param_shapes(_toy(1)).items()}
    eng, log = _engine(m)
    held = {n for n in eng.scope.names() if not n.startswith('gen_')}
    assert held == set(ouro.param_shapes(m))
    assert [tuple(eng.scope.get(p.name).shape) for p in eng._pools] == \
        [(eng.config.num_blocks, passes * layers, 8, 64)] * 2
    # the engine books a step's reads for the pool's own layers
    assert eng._step_reads == ((('kv_tokens_read_total', None),
                                passes * layers),)
    before = monitor.counters()
    toks = control.serve(eng, log, _prompt(10), 3)[0]
    delta = monitor.counter_delta(before)
    # three steps at positions 10, 11, 12: 11 + 12 + 13 rows a cache layer
    assert len(toks) == 4
    assert delta['kv_tokens_read_total'] == 36 * passes * layers


def test_the_step_holds_a_layer_body_a_pass_over_one_set_of_weights():
    m = _toy(4)
    eng, _log = _engine(m)
    ops = eng._step_prog.global_block().ops
    attend = [op for op in ops if op.type == 'kv_decode_attention_paged']
    assert [op.attr('layer') for op in attend] == list(range(12))
    assert [op.attr('trace_scope') for op in attend] == [
        'loop_pass_%d' % t for t in range(4) for _ in range(3)]
    # every op belongs to a pass but the embedding before the first, and
    # the head, the sampling and the masses behind the last
    scoped = [op.attr('trace_scope') for op in ops]
    first, last = scoped.index('loop_pass_0'), \
        len(scoped) - scoped[::-1].index('loop_pass_3')
    assert None not in scoped[first:last]
    assert set(scoped[:first]) | set(scoped[last:]) == {None}
    # the prefills' too; a one-pass model's ops carry nothing
    prog, _v = eng._prefill[16]
    walk = [op for op in prog.global_block().ops
            if op.type == 'kv_prefix_attention']
    assert [op.attr('layer') for op in walk] == list(range(12))
    assert {op.attr('trace_scope') for op in walk} == {
        'loop_pass_%d' % t for t in range(4)}
    one, _log = _engine(_toy(1))
    assert not [op for op in one._step_prog.global_block().ops
                if op.has_attr('trace_scope')]
    # the ops of a pass lower under its scope
    text = jax.jit(lambda x: T.layers is not None and _scoped(x)).lower(
        jnp.ones((4,))).as_text(debug_info=True)
    assert 'paddle_tpu:loop_pass_2' in text


def _scoped(x):
    from paddle_tpu.core import lowering
    with lowering._trace_scope('loop_pass_2'):
        return x * 2.0


# ---- 3. crossing two passes' entries, dropping a pass -----------------------

def test_the_controls_are_refused_by_the_limit_on_logits():
    m = _toy(4)
    eng, log = _engine(m)
    prompt = _prompt(21)
    toks, got, _ = control.serve(eng, log, prompt, 7)
    own = _want(eng.scope, m, prompt, toks)
    assert logit_gap(got, own)[0] < control.LOGITS_RMS_LIMIT
    for name, kw in control.controls(m).items():
        wrong = _want(eng.scope, m, prompt, toks, **kw)
        assert logit_gap(wrong, own)[0] > 100 * control.LOGITS_RMS_LIMIT, \
            name
    assert set(control.controls(m)) == {'bfloat16', 'crossed-cache',
                                        'three-passes'}
    # one pass more is another model too
    assert logit_gap(_want(eng.scope, m, prompt, toks, passes=5),
                     own)[0] > 0.01


def test_a_step_reads_the_entries_of_its_own_pass():
    """Two passes' cache entries swapped in the pools after the prefill:
    the next step's logits leave the reference's (and are what the
    reference gives with those passes' keys and values exchanged: the
    swap is seen, not averaged away)."""
    m = _toy(2)
    eng, log = _engine(m)
    prompt = _prompt(13)
    req = eng.submit(prompt, max_new_tokens=3)
    eng._admit()
    layers = m['num_hidden_layers']
    for name in (T.KV_CACHE_K, T.KV_CACHE_V):
        pool = eng.scope.get(name)
        eng.scope.set(name, jnp.concatenate(
            [pool[:, layers:], pool[:, :layers]], axis=1))
    while req.finish_reason is None and req._error is None:
        eng._step()
    toks = np.asarray(req.result(timeout=5))
    steps = [e[1] for e in log if e[0] == 'step']
    slot = 0
    first_step = steps[0][slot][None]
    want = _want(eng.scope, m, prompt, toks)[1:2]
    assert logit_gap(first_step, want)[0] > 100 * control.LOGITS_RMS_LIMIT


# ---- 4. the three norm placements against a block written out by hand -------

def _rms(x, w, eps=1e-6):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta=1e6):
    t, _h, dh = x.shape
    inv = theta ** (-np.arange(0, dh, 2, dtype='float64') / dh)
    ang = np.arange(t)[:, None] * inv[None]
    cos, sin = [np.concatenate([f(ang)] * 2, -1)[:, None] for f in (np.cos,
                                                                     np.sin)]
    half = np.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], -1)
    return x * cos + half * sin


def _by_hand(scope, tokens, placement, passes, layers, heads=4):
    """The forward in float64 numpy, the block written out a placement:
    logits [T, V]."""
    p = {n: np.asarray(scope.get(n), 'float64') for n in scope.names()
         if not n.startswith('gen_')}
    x = p['tok_emb.w'][tokens]
    t = len(tokens)
    mask = np.tril(np.ones((t, t), bool))

    def attention(n, i):
        qkv = n @ p['layer_%d.attn.qkv.w' % i]
        q, k, v = [a.reshape(t, heads, -1) for a in np.split(qkv, 3, -1)]
        q, k = _rope(q), _rope(k)
        s = np.einsum('qhd,khd->hqk', q, k) * q.shape[-1] ** -0.5
        s = np.where(mask[None], s, -np.inf)
        a = np.exp(s - s.max(-1, keepdims=True))
        a /= a.sum(-1, keepdims=True)
        return np.einsum('hqk,khd->qhd', a, v).reshape(t, -1) \
            @ p['layer_%d.attn.proj.w' % i]

    def ffn(n, i):
        g = n @ p['layer_%d.ffn.gate.w' % i]
        return (g / (1 + np.exp(-g)) * (n @ p['layer_%d.ffn.up.w' % i])) \
            @ p['layer_%d.ffn.down.w' % i]

    for _t in range(passes):
        for i in range(layers):
            for sub, ln in ((attention, 'ln1'), (ffn, 'ln2')):
                w = p.get('layer_%d.%s.w' % (i, ln))
                if placement == 'pre':
                    x = x + sub(_rms(x, w), i)
                elif placement == 'post':
                    x = x + _rms(sub(x, i), w)
                else:
                    x = x + _rms(sub(_rms(x, w), i),
                                 p['layer_%d.%s_out.w' % (i, ln)])
        x = _rms(x, p['final_ln.w'])
    return x @ p['lm_head.w']


@pytest.mark.parametrize('passes', (1, 2))
@pytest.mark.parametrize('placement', ('pre', 'post', 'sandwich'))
def test_each_norm_placement_against_a_block_written_out_by_hand(placement,
                                                                  passes):
    m = _toy(passes, num_hidden_layers=2, max_window_layers=2,
             layer_types=['full_attention'] * 2)
    cfg = LMConfig(
        vocab_size=97, seq_len=64, d_model=64, n_head=4, head_dim=16,
        n_layer=2, passes=passes, norm_placement=placement, norm='rms_norm',
        rms_eps=1e-6, position='rope', rope_theta=1e6, bias=False,
        ffn='gated', d_ff=96, dropout=0.0, attn_dropout=0.0)
    scope = Scope()
    for name, value in ouro.init_params(m, 11).items():
        if placement == 'sandwich' or '_out.' not in name:
            scope.set(name, value)
    eng, log = _engine(m, scope, cfg=cfg)
    held = {n for n in eng.scope.names() if not n.startswith('gen_')}
    assert ('layer_0.ln1_out.w' in held) == (placement == 'sandwich')
    prompt = _prompt(19)
    toks, got, _ = control.serve(eng, log, prompt, 4)
    seq = np.concatenate([prompt, toks[:-1]])
    want = _by_hand(eng.scope, seq, placement, passes, 2)[len(prompt) - 1:]
    assert logit_gap(got, want)[1] < TOLERANCE
    # ... and another placement's block is another model
    other = {'pre': 'post', 'post': 'pre', 'sandwich': 'pre'}[placement]
    wrong = _by_hand(eng.scope, seq, other, passes, 2)[len(prompt) - 1:]
    assert logit_gap(wrong, want)[0] > 0.01
    if placement == 'sandwich':
        assert logit_gap(got, _want(eng.scope, m, prompt, toks))[1] \
            < TOLERANCE


# ---- 5. the counters against the reference's gate ---------------------------

def test_loop_counters_sum_to_what_the_references_gate_gives():
    m = _toy(4)
    scope = _scope(m)
    eng = GenerateEngine(GenerateConfig(
        model=ouro.lm_config(m, 64, False), slots=3, max_len=64,
        prompt_buckets=[8, 16], block_size=8, eos_id=None, seed=3),
        scope=scope)
    eng.warmup()
    before = monitor.counters()
    prompt = _prompt(21)
    toks = np.asarray(eng.generate_once(prompt, max_new_tokens=7))
    delta = monitor.counter_delta(before)
    # a prefill in two chunks and six steps, four passes each
    assert delta['loop_passes_total{phase=prefill}'] == 2 * 4
    assert delta['loop_passes_total{phase=decode}'] == 6 * 4
    got = [delta['loop_exit_mass_total{pass=%d}' % t] for t in (1, 2, 3, 4)]
    # the reference's gate on the rows the steps computed: the tokens fed,
    # at positions 21 .. 26
    seq = np.concatenate([prompt, toks[:-1]])
    gates = ref.forward(scope, m, seq)[1]
    masses = ref.exit_masses(gates)
    np.testing.assert_allclose(masses.sum(axis=1), 1.0, atol=1e-6)
    want = masses[len(prompt):].sum(axis=0)
    np.testing.assert_allclose(got, want, atol=6 * 2.0 / T.EXIT_MASS_ONE
                               + 1e-5)
    assert sum(got) == pytest.approx(6.0, abs=1e-4)
    assert min(got) > 0
    # an idle slot's rows count for nothing: one live row a step
    stats = eng.stats()['passes']
    assert stats['a_token'] == 4
    assert stats['run'] == {'decode': 24, 'prefill': 8}
    np.testing.assert_allclose(stats['exit_mass'], got, atol=1e-9)
    # the step reads the gate, a prefill does not (and so does not list
    # its parameters: a program that never reads an input has no layout to
    # stage it in)
    prog, v = eng._prefill[16]
    assert 'exit_gates' not in v
    assert 'exit_gate.w' not in prog.global_block().vars
    assert len(eng._step_vars['exit_gates']) == 4
    assert 'tokens_and_load' in eng._step_vars
    # a one-pass model books none of it
    one = GenerateEngine(GenerateConfig(
        model=ouro.lm_config(_toy(1), 64, False), slots=3, max_len=64,
        prompt_buckets=[8, 16], block_size=8, eos_id=None, seed=3),
        scope=_scope(_toy(1)))
    one.warmup()
    before = monitor.counters()
    one.generate_once(prompt, max_new_tokens=3)
    assert not [k for k in monitor.counter_delta(before)
                if k.startswith('loop_')]
    assert 'passes' not in one.stats()
    assert 'tokens_and_load' not in one._step_vars


def test_the_traced_tally_is_what_a_profiler_sessions_steps_read(tmp_path):
    """`stats()['passes']['traced']`: the rows the decode steps dispatched
    under a live profiler session read, by series -- the bytes that belong
    to a trace's own kernel seconds
    (benchmark/layer_metrics/paged_decode_attention_roofline.loop.py)."""
    m = _toy(4)
    eng, log = _engine(m)
    control.serve(eng, log, _prompt(10), 3)
    assert eng.stats()['passes']['traced'] == {}
    assert monitor.tracing() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert monitor.tracing() is not None
        before = monitor.counters()
        control.serve(eng, log, _prompt(12), 4)
        delta = monitor.counter_delta(before)
    finally:
        jax.profiler.stop_trace()
    rows = (13 + 14 + 15 + 16) * 12
    assert delta['kv_tokens_read_total'] == rows
    assert eng.stats()['passes']['traced'] == {'kv_tokens_read_total': rows}
    control.serve(eng, log, _prompt(10), 3)
    assert eng.stats()['passes']['traced'] == {'kv_tokens_read_total': rows}


def test_the_loop_under_concurrency_equals_generate_once():
    m = _toy(4)
    eng, _log = _engine(m, slots=3)
    eng, = [GenerateEngine(GenerateConfig(
        model=ouro.lm_config(m, 64, False), slots=3, max_len=64,
        prompt_buckets=[8, 16], block_size=8, eos_id=None, seed=3),
        scope=eng.scope)]
    eng.warmup()
    prompts = [_prompt(n) for n in (5, 17, 30, 9)]
    solo = [list(eng.generate_once(p, max_new_tokens=6)) for p in prompts]
    eng.start()
    try:
        handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [list(h.result(timeout=120)) for h in handles]
    finally:
        eng.stop()
    assert got == solo
    stats = eng.stats()
    assert stats['passes']['run']['decode'] == 4 * stats['decode_steps'] \
        + 4 * 5 * len(prompts)     # the loop's steps and generate_once's


# ---- 6. the refusals --------------------------------------------------------

def test_the_classic_builders_and_lmconfig_refuse_passes_by_name():
    cfg = ouro.lm_config(_toy(1), 32, False)
    looped = ouro.lm_config(_toy(4), 32, False)
    for build in (lambda c: T.build_lm(c),
                  lambda c: T.build_lm_drafter(c, 2, 32, 2, 9, 8),
                  lambda c: T.build_lm_verify(c, 2, 3, 32, 9, 8)):
        for c in (cfg, looped):
            with pytest.raises(ValueError, match='cannot express LMConfig.'):
                build(c)
    classic = dict(vocab_size=50, d_model=32, n_head=2, n_layer=2, d_ff=64)
    with pytest.raises(ValueError, match=r'cannot express LMConfig\.passes'):
        T._require_classic_block(LMConfig(passes=2, **classic), 'build_lm')
    with pytest.raises(ValueError, match=r'LMConfig\.passes=0'):
        LMConfig(passes=0, **classic)
    for kw in (dict(layer_types=['attention', 'conv']),
               dict(ffn='moe', n_experts=4, experts_per_token=2,
                    expert_width=16),
               dict(attention='mla', position='rope', q_lora_rank=8,
                    kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8,
                    v_head_dim=8)):
        with pytest.raises(ValueError, match=r'LMConfig\.passes=2'):
            LMConfig(passes=2, **dict(classic, **kw))
        LMConfig(passes=1, **dict(classic, **kw))
    with pytest.raises(ValueError, match="norm_placement='sandwich' is "
                       "built with norm='rms_norm'"):
        LMConfig(norm_placement='sandwich', **classic)
    with pytest.raises(ValueError, match=r'cannot express LMConfig\.norm'):
        T.build_lm(LMConfig(norm_placement='sandwich', norm='rms_norm',
                            **classic))
    with pytest.raises(ValueError, match=r'cannot express LMConfig\.'):
        GenerateEngine(GenerateConfig(
            model=looped, slots=2, max_len=32, prompt_buckets=[16],
            block_size=8, speculative=True))
