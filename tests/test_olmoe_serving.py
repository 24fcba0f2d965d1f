"""The OLMoE block in the Program path (ISSUE 28): the three ops against
one-liners of their own, prefill-then-decode through the paged cache
against the plain reference's FULL forward pass (logits, not tokens), the
routing rule's three controls, the fairseq-dense programs unchanged from
the parent commit, and the builders that refuse the block by name.

Toy widths on the CPU: d 64, 4 heads x 16, 8 experts of width 32, top-2,
2 layers, seeded random weights.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import monitor, unique_name
from paddle_tpu.core.registry import get_op
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.reference import olmoe_control, olmoe_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))

TOY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
           num_hidden_layers=2, num_experts=8, num_experts_per_tok=2,
           intermediate_size=32, norm_topk_prob=False, rms_norm_eps=1e-5,
           rope_theta=10000, vocab_size=97)


def toy_config(**over):
    kw = dict(vocab_size=97, seq_len=64, d_model=64, n_head=4, n_layer=2,
              d_ff=32, dropout=0.0, norm='rms_norm', position='rope',
              head_dim=16, qk_norm=True, bias=False, ffn='moe', n_experts=8,
              experts_per_token=2, expert_width=32)
    kw.update(over)
    return LMConfig(**kw)


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
        self.outs[slot] = np.asarray(value)


def lower(op_type, attrs, **ins):
    ctx = _Ctx(**{k: jnp.asarray(v) for k, v in ins.items()})
    get_op(op_type).lower(ctx, _Op(**attrs))
    return ctx.outs


# ---- 1. the ops -----------------------------------------------------------

def test_rms_norm_against_its_one_liner():
    rng = np.random.RandomState(0)
    x = rng.randn(3, 5, 64).astype('float32') * 3
    w = rng.rand(64).astype('float32') + 0.5
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * w
    got = lower('rms_norm', {'epsilon': 1e-5, 'begin_norm_axis': 2},
                X=x, Scale=w)['Out']
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize('shape', ['decode', 'prefill'])
def test_rotary_embedding_at_positions_that_do_not_start_at_zero(shape):
    """A prefix-shared suffix: rows at global positions 37.., and decode
    slots each at its own position."""
    rng = np.random.RandomState(1)
    pos = np.array([37, 38, 39, 40, 41]) if shape == 'prefill' \
        else np.array([0, 63, 17, 5, 40])
    x = rng.randn(5, 4, 16).astype('float32')
    inv = 10000.0 ** (-np.arange(0, 16, 2) / 16.0)
    ang = np.concatenate([pos[:, None] * inv] * 2, axis=-1)[:, None, :]
    half = np.concatenate([-x[..., 8:], x[..., :8]], axis=-1)
    want = x * np.cos(ang) + half * np.sin(ang)
    if shape == 'prefill':
        got = lower('rotary_embedding', {'theta': 10000.0}, X=x[None],
                    Positions=pos[None])['Out'][0]
    else:
        got = lower('rotary_embedding', {'theta': 10000.0}, X=x,
                    Positions=pos[:, None])['Out']
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # position 0 is the identity
    zero = lower('rotary_embedding', {'theta': 10000.0}, X=x,
                 Positions=np.zeros((5, 1), 'int64'))['Out']
    np.testing.assert_array_equal(zero, x)


def _moe_by_masked_loop(x, router, gate, up, down, top_k, norm):
    """The op's definition, expert by expert over every row."""
    x64 = x.astype(np.float64)
    logits = x64 @ router
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = np.argsort(-p, axis=1, kind='stable')[:, :top_k]
    chosen = np.zeros_like(p, bool)
    np.put_along_axis(chosen, idx, True, axis=1)
    w = np.where(chosen, p, 0.0)
    if norm:
        w /= w.sum(-1, keepdims=True)
    y = np.zeros_like(x64)
    for e in range(router.shape[1]):
        a = x64 @ gate[e]
        y += w[:, e:e + 1] * (((a / (1 + np.exp(-a))) * (x64 @ up[e]))
                              @ down[e])
    return y, idx, chosen


MOE_CASES = {
    # rows, what the router is pushed to, Length, Valid
    'spread': (12, None, None, None),
    'one-row': (1, None, None, None),
    'two-experts-get-every-row': (9, (3, 5), None, None),
    'pad-rows': (8, None, 5, None),
    'idle-slots': (6, None, None, [1, 0, 1, 1, 0, 1]),
    'renormalised': (7, None, None, None),
}


@pytest.mark.parametrize('case', sorted(MOE_CASES))
def test_moe_ffn_against_the_masked_loop(case):
    n, favoured, length, valid = MOE_CASES[case]
    d, E, w, k = 64, 8, 32, 2
    rng = np.random.RandomState(len(case))
    x = rng.randn(n, d).astype('float32')
    router = (rng.randn(d, E) * 0.3).astype('float32')
    if favoured:
        # experts 3 and 5 take every row, the other six none
        x[:, 0] = 1.0
        router[0, list(favoured)] = 50.0
    gate, up = (rng.randn(E, d, w).astype('float32') * 0.2 for _ in '12')
    down = rng.randn(E, w, d).astype('float32') * 0.2
    norm = case == 'renormalised'
    ins = dict(X=x, RouterW=router, GateW=gate, UpW=up, DownW=down)
    if length is not None:
        ins['Length'] = np.array([[length]], 'int64')
    if valid is not None:
        ins['Valid'] = np.array(valid, 'int64')[:, None]
    out = lower('moe_ffn', {'top_k': k, 'norm_topk_prob': norm}, **ins)
    want, idx, chosen = _moe_by_masked_loop(x, router, gate, up, down, k,
                                            norm)
    np.testing.assert_allclose(out['Out'], want, rtol=2e-5, atol=2e-5)
    assert out['TopkIdx'].dtype == np.int32
    np.testing.assert_array_equal(np.sort(out['TopkIdx'], axis=1),
                                  np.sort(idx, axis=1))
    counted = np.ones(n, bool)
    if length is not None:
        counted &= np.arange(n) < length
    if valid is not None:
        counted &= np.array(valid, bool)
    assert out['ExpertLoad'].dtype == np.int32
    np.testing.assert_array_equal(out['ExpertLoad'],
                                  chosen[counted].sum(axis=0))
    if favoured:
        assert set(np.flatnonzero(out['ExpertLoad'])) == set(favoured)
    assert out['ExpertLoad'].sum() == counted.sum() * k     # dropless


# ---- 2. through the paged cache, against the full forward -----------------

def _drive(eng, prompts, n_new, late=None):
    """submit / admit / step by hand, `late` (index) admitted only after
    three steps of the others."""
    reqs = {}
    for i, (p, n) in enumerate(zip(prompts, n_new)):
        if i != late:
            reqs[i] = eng.submit(p, max_new_tokens=n)
    eng._admit()
    steps = 0
    while any(r.finish_reason is None and r._error is None
              for r in reqs.values()) or late not in reqs:
        eng._step()
        steps += 1
        if steps == 3 and late is not None and late not in reqs:
            reqs[late] = eng.submit(prompts[late],
                                    max_new_tokens=n_new[late])
        eng._admit()
    return [list(reqs[i].result(timeout=5)) for i in range(len(prompts))]


def tap_logits(eng):
    """Rebind a warmed engine's programs with their logits fetched beside
    the tokens; every dispatch's (kind, feed, logits) goes to the list
    returned."""
    log = []

    def tapped(bound_with_logits, kind):
        def call(feed, return_numpy=True):
            out = bound_with_logits(feed, return_numpy=return_numpy)
            log.append((kind, {k: np.array(x) for k, x in feed.items()},
                        np.asarray(out[1])))
            return out
        return call
    S, mb = eng.config.slots, eng._max_blocks
    for b, (prog, v) in eng._prefill.items():
        feed = {'gen_prompt': np.zeros((1, b), 'int64'),
                'gen_pos': np.zeros((1, b), 'int64'),
                'gen_len': np.ones((1, 1), 'int64')}
        # 'gen_btab', and a model with window layers' 'gen_wtab'
        feed.update(eng._tables_feed(np.zeros((1, mb), 'int64')))
        feed.update(eng._sample_feed(1))
        eng._prefill_bound[b] = tapped(eng.executor.bind(
            prog, feed, scope=eng.scope,
            fetch_list=[v['tokens_and_load'], v['logits']]), 'prefill')
    feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
            'gen_pos': np.zeros((S, 1), 'int64')}
    feed.update(eng._tables_feed(np.zeros((S, mb), 'int64')))
    feed.update(eng._sample_feed(S))
    eng._step_bound = tapped(eng.executor.bind(
        eng._step_prog, feed, scope=eng.scope,
        fetch_list=[eng._step_vars['tokens_and_load'],
                    eng._step_vars['logits']]), 'step')
    return log


def serve_five(eng, vocab):
    """Five requests of different lengths through a warmed 4-slot paged
    engine (block 8, buckets 16 and 32), every dispatch's feed and logits
    logged: prompts that end inside a block, on a block's last row and
    past the 16 bucket; outputs that cross block boundaries; request 3
    admitted while the others decode, request 4 after a slot frees."""
    log = tap_logits(eng)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(2, vocab, size=n).astype('int64')
               for n in (5, 16, 23, 8, 11)]
    n_new = [14, 9, 20, 12, 6]
    before = monitor.counters()
    tokens = _drive(eng, prompts, n_new, late=3)
    return dict(eng=eng, log=log, prompts=prompts, n_new=n_new,
                tokens=tokens, moved=monitor.counter_delta(before))


@pytest.fixture(scope='module')
def served():
    """`serve_five` on the toy OLMoE block."""
    cfg = toy_config()
    eng = GenerateEngine(GenerateConfig(
        model=cfg, slots=4, max_len=64, prompt_buckets=[16, 32],
        eos_id=None, seed=3, block_size=8))
    # the startup program's N(0, 0.02) experts add little to the residual
    # stream at this width: four times larger each (64 times the FFN's
    # output), a wrong choice of expert moves the logits
    for name in eng.scope.names():
        if '.moe.' in name and 'router' not in name:
            eng.scope.set(name, eng.scope.get(name) * 4.0)
    eng.warmup()
    return serve_five(eng, 97)


# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU — the system through its cache,
# ragged_dot and the sorted assignments, the reference at `highest` with a
# masked loop — so the routing is the same and what is left is summation
# order: 3.4e-7 to 5.6e-7 over the five requests. 1e-4 is ~200 times that,
# and over 1000 times under what the wrong computations below move the
# logits by (top-1 of 2: 0.13; renormalised: 0.42; bfloat16: > 4e-3).
TOLERANCE = 1e-4


def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        served):
    eng, log = served['eng'], served['log']
    assert [len(t) for t in served['tokens']] == served['n_new']
    # every dispatch's logits, row by row, belong to one request's one
    # position: collect them by (request, position)
    by_first = {}
    rows = {i: [] for i in range(5)}
    for kind, feed, lg in log:
        if kind == 'prefill':
            n = int(feed['gen_len'][0, 0])
            i = [j for j, p in enumerate(served['prompts'])
                 if len(p) == n][0]
            by_first[i] = tuple(feed['gen_btab'][0][:1])
            rows[i].append(lg[0])
        else:
            for s in range(4):
                first = tuple(feed['gen_btab'][s][:1])
                if first == (0,):
                    continue
                i = [j for j, f in by_first.items() if f == first][-1]
                rows[i].append(lg[s])
    crossed = 0
    for i, prompt in enumerate(served['prompts']):
        toks = served['tokens'][i]
        got = np.stack(rows[i])[:len(toks)]
        np.testing.assert_array_equal(got.argmax(axis=1), toks)
        seq = np.concatenate([prompt, toks[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        want = np.asarray(ref.logits(eng.scope, TOY, seq, positions=pos))
        rms, worst = olmoe_control.logit_gap(got, want)
        assert worst <= TOLERANCE, (i, rms, worst)
        crossed += len(seq) // 8 - len(prompt) // 8
    assert crossed >= 4                    # block boundaries crossed
    # the loads the steps and prefills fetched, beside the tokens: two
    # layers a dispatch, two experts a live row, never more than 8
    # experts a layer
    moved = served['moved']
    dispatches = len(log)
    assert moved['moe_layer_steps_total'] == 2 * dispatches
    live = sum(len(p) for p in served['prompts']) \
        + sum(n - 1 for n in served['n_new'])
    assert moved['moe_assignments_total'] == 2 * 2 * live
    assert 2 * dispatches <= moved['moe_experts_touched_total'] \
        <= 8 * 2 * dispatches
    assert moved['moe_max_expert_rows_total'] >= 2 * dispatches
    assert not any(k.startswith('compile_cache_miss') for k in moved)


def test_the_systems_routing_is_the_references(served):
    """On the CPU both route in float32: given the reference's own top-2,
    the system's logits are already inside the tolerance (above), and the
    served tokens are the reference's argmax."""
    eng = served['eng']
    for prompt, toks in zip(served['prompts'], served['tokens']):
        assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0


# ---- 3. the routing rule ----------------------------------------------------

@pytest.mark.parametrize('control', ['renormalised', 'top-1',
                                     'softmax-over-chosen'])
def test_a_wrong_routing_rule_is_outside_the_tolerance(served, control):
    """norm_topk_prob true, one expert fewer, the softmax over the chosen
    experts only: each in the system's place differs from the reference
    by far more than the tolerance the system is held to."""
    eng = served['eng']
    kw = olmoe_control.controls(TOY)[control]
    prompt, toks = served['prompts'][2], served['tokens'][2]
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(eng.scope, TOY, seq))
    wrong = np.asarray(ref.logits(eng.scope, TOY, seq, **kw))
    rms, worst = olmoe_control.logit_gap(wrong, want)
    assert worst > 40 * TOLERANCE, (control, rms, worst)


def test_the_bfloat16_control_is_outside_the_tolerance(served):
    eng = served['eng']
    prompt, toks = served['prompts'][2], served['tokens'][2]
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(eng.scope, TOY, seq))
    wrong = np.asarray(ref.logits(eng.scope, TOY, seq,
                                  dtype=jnp.bfloat16))
    assert olmoe_control.logit_gap(wrong, want)[1] > 40 * TOLERANCE


def test_the_chip_comparison_runs_at_toy_width(served):
    """benchmark/reference/olmoe_control.py's Session and compare, as its
    main() drives them on the chip."""
    eng = served['eng']
    cfg = eng.config.model
    scope = eng.scope
    session = olmoe_control.Session(
        cfg, {'slots': 4, 'max_len': 64, 'block_size': 8, 'num_blocks': 33,
              'prompt_buckets': [16, 32]}, scope)
    prompt = served['prompts'][2]
    toks, lg, chosen = session.generate(prompt, 10)
    assert len(toks) == 11 and lg.shape == (11, 97)
    assert [c.shape for c in chosen] == [(len(prompt) + 10, 2)] * 2
    out = olmoe_control.compare(scope, TOY, prompt, toks, lg, chosen)
    assert out['routing_rows_not_ref_top_k'] == 0.0
    assert out['logits_vs_ref_given_routing'][1] <= TOLERANCE
    assert out['logits_vs_ref_own_routing'][1] <= TOLERANCE
    assert out['greedy_margin_worst'] == 0.0
    for name, reading in out['controls'].items():
        assert reading['logits_vs_ref_own_routing'][1] > 40 * TOLERANCE, \
            name
    eng._ensure_cache()


# ---- 4. the fairseq-dense programs are the parent commit's ------------------

def _program_listing(build):
    main, start = Program(), Program()
    with program_guard(main, start):
        with unique_name.guard():
            build()
    block = main.global_block()
    return {
        'ops': [[op.type,
                 {k: list(v) for k, v in sorted(op.inputs.items())},
                 {k: list(v) for k, v in sorted(op.outputs.items())}]
                for op in block.ops],
        'params': [[p.name, list(p.shape)] for p in block.all_parameters()],
        'startup': [[op.type, sorted(n for vs in op.outputs.values()
                                     for n in vs)]
                    for op in start.global_block().ops]}


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
def test_todays_configuration_builds_the_parent_commits_program(program):
    """Op types, their inputs and outputs by name, the parameters and the
    startup program, in order, against a listing recorded from commit
    77b11f3 (PR 27) with the same toy LMConfig."""
    with open(os.path.join(HERE, 'fixtures',
                           'lm_programs_parent_pr27.json')) as f:
        want = json.load(f)[program]
    cfg = LMConfig(vocab_size=97, seq_len=32, d_model=32, n_head=4,
                   n_layer=2, d_ff=64, dropout=0.0)
    build = {
        'decode_step': lambda: T.build_lm_decode_step(
            cfg, 4, 32, block_size=8, num_blocks=9),
        'prefill_paged': lambda: T.build_lm_prefill_paged(cfg, 16, 9, 8, 4),
    }[program]
    got = json.loads(json.dumps(_program_listing(build)))
    assert got == want


LISTED = {
    'fairseq-dense': dict(vocab_size=97, seq_len=32, d_model=32, n_head=4,
                          n_layer=2, d_ff=64, dropout=0.0),
    'olmoe': dict(vocab_size=97, seq_len=32, d_model=64, n_head=4,
                  n_layer=2, d_ff=32, dropout=0.0, norm='rms_norm',
                  position='rope', head_dim=16, qk_norm=True, bias=False,
                  ffn='moe', n_experts=8, experts_per_token=2,
                  expert_width=32),
}
# where a block's listing was recorded: the two the benchmark had at PR 31
# (lm_programs_parent_pr31.json, from commit 44db736, the parent of the PR
# that brought latent attention, the gated FFN and the held share of the
# experts) build at PR 34 what they built then; JoyAI's toy
# (benchmark_tests/configs/toy-joyai.json) is recorded from commit 56ed6e0
# (PR 34), the parent of the PR that brought layer kinds, K/V-head counts
# and the tied head
RECORDED = {'fairseq-dense': 'pr31', 'olmoe': 'pr31', 'joyai': 'pr34'}


def listed_config(config):
    if config in LISTED:
        return LMConfig(**LISTED[config])
    from benchmark.models import joyai
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-joyai.json')) as f:
        return joyai.lm_config(json.load(f), 32, False)


def _plain(value):
    try:
        json.dumps(value)
    except TypeError:
        return repr(type(value))
    return value


def program_listing(cfg, program, slots=None):
    """A decode or prefill program of `cfg` at the listings' toy engine:
    every op with its inputs, outputs and ATTRIBUTES, the parameters, the
    startup program, in order. ``slots``: what the prefill of a model with
    pools the slots size is told."""
    build = {
        'decode_step': lambda: T.build_lm_decode_step(
            cfg, 4, 32, block_size=8, num_blocks=9),
        'prefill_paged': lambda: T.build_lm_prefill_paged(
            cfg, 16, 9, 8, 4, slots=slots),
    }[program]
    main, start = Program(), Program()
    with program_guard(main, start):
        with unique_name.guard():
            build()
    block = main.global_block()
    got = {
        'ops': [[op.type,
                 {k: list(v) for k, v in sorted(op.inputs.items())},
                 {k: list(v) for k, v in sorted(op.outputs.items())},
                 {k: _plain(v) for k, v in sorted(op.attrs.items())}]
                for op in block.ops],
        'params': [[p.name, list(p.shape)] for p in block.all_parameters()],
        'startup': [[op.type, sorted(n for vs in op.outputs.values()
                                     for n in vs)]
                    for op in start.global_block().ops]}
    return json.loads(json.dumps(got))


def parent_listing(config, program):
    with open(os.path.join(HERE, 'fixtures', 'lm_programs_parent_%s.json'
                           % RECORDED[config])) as f:
        return json.load(f)[config][program]


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
@pytest.mark.parametrize('config', sorted(RECORDED))
def test_the_benchmarks_configurations_build_the_pr31_commits_programs(
        config, program):
    """The same, with every op's ATTRIBUTES too, for every block the
    benchmark serves — fairseq-dense, OLMoE and (since PR 35) JoyAI —
    against listings recorded at the parent of the PR that touched the
    builders last (`RECORDED`): the blocks the benchmark already had
    build the programs they built."""
    assert program_listing(listed_config(config), program) == \
        parent_listing(config, program)


def test_an_engine_without_experts_fetches_the_tokens_alone():
    cfg = LMConfig(vocab_size=64, seq_len=32, d_model=32, n_head=2,
                   n_layer=1, d_ff=32, dropout=0.0)
    eng = GenerateEngine(GenerateConfig(
        model=cfg, slots=2, max_len=32, prompt_buckets=[8], eos_id=None,
        seed=0, block_size=8))
    v = eng._step_vars
    assert 'tokens_and_load' not in v
    assert eng._token_fetch(v, 'next_tokens') is v['next_tokens']
    before = monitor.counters()
    assert len(eng.generate_once(np.arange(2, 7), max_new_tokens=4)) == 4
    assert not [k for k in monitor.counter_delta(before)
                if k.startswith('moe_')]


# ---- 5. the refusals --------------------------------------------------------

REFUSERS = {
    'build_lm': lambda cfg: T.build_lm(cfg, is_test=True),
    'build_lm_drafter': lambda cfg: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
    'build_lm_verify': lambda cfg: T.build_lm_verify(cfg, 2, 3, 32, 9, 8),
}
FIELDS = {'norm': 'rms_norm', 'position': 'rope', 'qk_norm': True,
          'bias': False, 'ffn': 'moe', 'head_dim': 32}


@pytest.mark.parametrize('builder', sorted(REFUSERS))
def test_the_other_builders_refuse_the_block_by_the_fields_name(builder):
    with program_guard(Program(), Program()):
        with pytest.raises(ValueError, match=r'LMConfig\.norm='):
            REFUSERS[builder](toy_config())
    for field, value in sorted(FIELDS.items()):
        kw = {field: value}
        if field == 'ffn':
            kw.update(n_experts=4, experts_per_token=2, expert_width=8)
        cfg = LMConfig(vocab_size=64, seq_len=32, d_model=64, n_head=4,
                       n_layer=1, d_ff=32, dropout=0.0, **kw)
        with program_guard(Program(), Program()):
            with pytest.raises(ValueError,
                               match=r'LMConfig\.%s=' % field):
                REFUSERS[builder](cfg)


def test_the_contiguous_cache_is_gone_and_says_so():
    with pytest.raises(ValueError, match='contiguous KV cache is gone'):
        GenerateConfig(model=toy_config(), slots=2, max_len=32,
                       prompt_buckets=[8], eos_id=None, seed=0, paged=False)
    # the keyword stays for benchmark/drivers/serve.py, and selects nothing
    assert not hasattr(GenerateConfig(paged=True), 'paged')


def test_lmconfig_refuses_values_it_does_not_know():
    with pytest.raises(ValueError, match='LMConfig.norm'):
        LMConfig(norm='batch')
    with pytest.raises(ValueError, match='experts_per_token'):
        LMConfig(ffn='moe', n_experts=4, experts_per_token=5)
