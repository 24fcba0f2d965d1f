"""The plain reference agrees with the system at toy width on the CPU to
float32 rounding: the loss of build_lm(is_test=True), and the greedy
tokens of the paged engine through prefill and the cached decode step."""
import json
import os

import numpy as np

import paddle_tpu as fluid
from benchmark.models import lm as lm_model
from benchmark.reference import lm_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'configs', 'toy-lm.json')) as f:
    TOY = json.load(f)


def _scope(seed):
    scope = fluid.Scope()
    for name, value in lm_model.init_params(TOY, seed).items():
        scope.set(name, value)
    return scope


def test_param_shapes_match_the_program():
    from paddle_tpu.models.transformer import build_lm
    prog = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, fluid.Program()):
            build_lm(lm_model.lm_config(TOY, 16, False), is_test=True)
    have = {p.name: tuple(p.shape)
            for p in prog.global_block().all_parameters()}
    assert have == lm_model.param_shapes(TOY)
    assert sum(int(np.prod(s)) for s in have.values()) == \
        lm_model.flops.lm_param_count(TOY)


def test_init_params_is_seeded_and_typed():
    a, b, c = (lm_model.init_params(TOY, s) for s in (3, 3, 2 ** 31 + 7))
    assert all(v.dtype == np.float32 for v in a.values())
    assert np.array_equal(a['lm_head.w'], b['lm_head.w'])
    assert not np.array_equal(a['lm_head.w'], c['lm_head.w'])
    assert np.all(np.asarray(a['layer_0.ln1.w']) == 1.0)
    assert np.all(np.asarray(a['layer_1.ffn1.b']) == 0.0)


def test_loss_agrees_with_build_lm():
    from paddle_tpu.models.transformer import build_lm
    scope = _scope(11)
    prog = fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(prog, fluid.Program()):
            _t, _l, _lg, avg = build_lm(lm_model.lm_config(TOY, 16, False),
                                        is_test=True)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, TOY['vocab_size'], (1, 16)).astype('int64')
    labels = rng.randint(0, TOY['vocab_size'], (1, 16)).astype('int64')
    exe = fluid.Executor(fluid.TPUPlace(0))
    got, = exe.run(prog, feed={'tokens': tokens, 'labels': labels},
                   fetch_list=[avg], scope=scope)
    want = ref.loss(scope, TOY, tokens[0], labels[0])
    assert abs(float(np.asarray(got).reshape(-1)[0]) - want) < 2e-6 * want


def test_greedy_tokens_through_the_paged_cache_are_the_reference_argmax():
    from paddle_tpu.serving.generate import GenerateEngine, GenerateConfig
    scope = _scope(12)
    eng = GenerateEngine(GenerateConfig(
        model=lm_model.lm_config(TOY, 24, False), slots=2, max_len=24,
        paged=True, block_size=4, num_blocks=13, prompt_buckets=[8, 16],
        prefix_sharing=False, seed=24), scope=scope)
    prompt = np.random.RandomState(1).randint(1, TOY['vocab_size'], 11)
    tokens = list(eng.generate_once(prompt, max_new_tokens=6))
    margins = ref.greedy_margins(scope, TOY, prompt, tokens)
    assert len(tokens) == 6 and float(np.max(margins)) < 1e-5
    # and a wrong token is far outside the margin the chip check allows
    wrong = list(tokens)
    wrong[2] = (wrong[2] + 1) % TOY['vocab_size']
    assert float(np.max(ref.greedy_margins(scope, TOY, prompt, wrong))) \
        > ref.LOGIT_MARGIN
