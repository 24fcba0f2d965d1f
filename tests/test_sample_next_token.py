"""`sample_next_token` through the Program path, against the formula it
had before it stopped gathering the vocabulary (kept here as a plain
jax.numpy reference): the tokens are the same, bit for bit, whatever the
rows of a step ask for — all greedy (the argmax branch), all sampled,
mixed, top-k and top-p on and off, tied logits — at the prefill's one
row and the chat cell's 32, over a toy vocabulary and fairseq-dense's
50264. And the structure that makes it cheap: a `cond` on what the op is
fed, and no gather that yields `[S, V]` in either branch."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.core import lowering
from paddle_tpu.models import transformer


@jax.jit
def _old_formula(logits, temp, topk, topp, u):
    """The op's lowering as it stood at PR 28: argsort, then the whole
    vocabulary gathered through the order."""
    logits = logits.astype(jnp.float32)
    temp, topp, u = temp.reshape(-1), topp.reshape(-1), u.reshape(-1)
    topk = topk.reshape(-1).astype(jnp.int32)
    V = logits.shape[1]
    greedy = jnp.argmax(logits, axis=1).astype(jnp.int64)
    t = jnp.where(temp > 0, temp, 1.0)[:, None]
    order = jnp.argsort(-logits, axis=1)
    sorted_logits = jnp.take_along_axis(logits / t, order, axis=1)
    probs = jax.nn.softmax(sorted_logits, axis=1)
    ranks = jnp.arange(V)[None, :]
    k_eff = jnp.where(topk > 0, topk, V)[:, None]
    p_on = (topp > 0) & (topp < 1.0)
    p_eff = jnp.where(p_on, topp, 1.0)[:, None]
    cum = jnp.cumsum(probs, axis=1)
    keep = (ranks < k_eff) & ((cum - probs < p_eff) | (ranks == 0))
    masked = jnp.where(keep, probs, 0.0)
    mcum = jnp.cumsum(masked, axis=1)
    total = mcum[:, -1:]
    j = jnp.sum(mcum <= u[:, None] * total, axis=1)
    j = jnp.minimum(j, jnp.sum(keep, axis=1) - 1)
    sampled = jnp.take_along_axis(order, j[:, None], axis=1)[:, 0]
    return jnp.where(temp > 0, sampled.astype(jnp.int64), greedy)


@functools.lru_cache(maxsize=None)
def _program(V):
    """logits [rows, V] + the SAMPLE_FEEDS quad -> the op, nothing else."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        logits = layers.data(name='logits', shape=[V], dtype='float32')
        out = transformer._append_sample_op(
            main.global_block(), logits, transformer._sampling_inputs(),
            'next_token')
    return main, out


@functools.lru_cache(maxsize=None)
def _exe():
    return fluid.Executor(fluid.CPUPlace())


def _run(feed):
    main, out = _program(feed['logits'].shape[1])
    return np.asarray(_exe().run(main, feed=feed, fetch_list=[out])[0])


def _feed(S, V, case, seed):
    """One step's feed. Rows cycle through the case's settings, so a
    32-row step holds every one of them and a 1-row step the first."""
    rng = np.random.RandomState(seed)
    logits = (3.0 * rng.randn(S, V)).astype('float32')
    if case.startswith('ties'):
        # five distinct values: every maximum and every rank is shared
        logits = np.round(logits / 3.0).clip(-2, 2).astype('float32')
    rows = {
        'greedy': [(0.0, 0, 0.0)],
        'greedy_with_knobs': [(0.0, 5, 0.5), (-1.0, 0, 0.0)],
        'sampled': [(0.8, 0, 0.0), (1.0, 0, 0.0), (1.7, 0, 0.0)],
        'mixed': [(0.7, 0, 0.0), (0.0, 0, 0.0), (1.3, 40, 0.9),
                  (0.0, 3, 0.3)],
        'top_k': [(1.0, 5, 0.0), (0.8, 1, 0.0), (1.2, 0, 0.0),
                  (1.0, V + 7, 0.0)],
        'top_p': [(1.0, 0, 0.9), (0.8, 0, 0.05), (1.5, 0, 0.5)],
        'top_p_off': [(1.0, 0, 1.0), (1.0, 0, 1.5), (1.0, 0, 0.0),
                      (1.0, 0, -0.5)],
        'top_k_and_top_p': [(0.9, 20, 0.8), (1.1, 3, 0.99), (0.6, 50, 0.3)],
        'ties_greedy': [(0.0, 0, 0.0)],
        'ties_mixed': [(1.0, 0, 0.0), (0.0, 0, 0.0), (0.9, 4, 0.0),
                       (1.0, 0, 0.7)],
    }[case]
    temp, topk, topp = (np.array([rows[i % len(rows)][c] for i in range(S)],
                                 dt).reshape(S, 1)
                        for c, dt in enumerate(('float32', 'int64',
                                                'float32')))
    u = rng.uniform(0, 1, (S, 1)).astype('float32')
    if S > 2:
        u[0], u[1] = 0.0, np.float32(1.0) - np.float32(2.0 ** -24)
    return {'logits': logits, 'gen_temp': temp, 'gen_topk': topk,
            'gen_topp': topp, 'gen_u': u}


CASES = ['greedy', 'greedy_with_knobs', 'sampled', 'mixed', 'top_k', 'top_p',
         'top_p_off', 'top_k_and_top_p', 'ties_greedy', 'ties_mixed']


@pytest.mark.parametrize('case', CASES)
@pytest.mark.parametrize('S,V', [(1, 97), (32, 97), (1, 50264), (32, 50264)])
def test_tokens_are_bitwise_the_old_formulas(S, V, case):
    for seed in (11, 3000000019):
        feed = _feed(S, V, case, seed)
        got = _run(feed)
        want = np.asarray(_old_formula(
            feed['logits'], feed['gen_temp'], feed['gen_topk'],
            feed['gen_topp'], feed['gen_u']))
        assert got.dtype == want.dtype and got.shape == (S,)
        np.testing.assert_array_equal(got, want)
        greedy_rows = feed['gen_temp'].reshape(-1) <= 0
        # a greedy row is the FIRST maximum, beside a sampled row too
        np.testing.assert_array_equal(
            got[greedy_rows], feed['logits'].argmax(axis=1)[greedy_rows])
    if case == 'sampled' and S > 1:
        assert (got != feed['logits'].argmax(axis=1)).any()


def test_a_tied_sampled_row_draws_in_the_stable_order():
    """All logits equal: the stable sort keeps the vocabulary's order,
    the distribution is uniform, so u picks token floor(u * V)."""
    V = 97
    feed = _feed(4, V, 'sampled', 5)
    feed['logits'][:] = 0.25
    feed['gen_u'][:, 0] = [0.0, 0.55, 0.8, 0.999]
    np.testing.assert_array_equal(_run(feed), [0, 53, 77, 96])
    feed['gen_topk'][:] = 8
    np.testing.assert_array_equal(_run(feed), [0, 4, 6, 7])


def _eqns(jaxpr):
    """Every equation of a jaxpr, through its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize('S,V', [(1, 97), (32, 50264)])
def test_the_lowering_is_a_cond_and_gathers_no_vocabulary(S, V):
    main, out = _program(V)
    feed = _feed(S, V, 'mixed', 3)
    fn, _ro, _rw = lowering.build_fn(main, [out.name], [], [])
    jaxpr = jax.make_jaxpr(fn)(feed, {}, {}, jax.random.PRNGKey(0)).jaxpr
    conds = [e for e in _eqns(jaxpr) if e.primitive.name == 'cond']
    assert len(conds) == 1
    branches = conds[0].params['branches']
    assert len(branches) == 2
    names = [{e.primitive.name for e in _eqns(b.jaxpr)} for b in branches]
    # index 0 is the false branch: all greedy -> the argmax and a cast
    assert 'argmax' in names[0]
    assert not names[0] & {'sort', 'gather', 'cumsum', 'exp', 'reduce_sum',
                           'reduce_window_sum', 'div'}
    assert {'sort', 'argmax'} <= names[1]
    wide = [e for e in _eqns(jaxpr) if e.primitive.name == 'gather'
            and any(v.aval.shape == (S, V) for v in e.outvars)]
    assert not wide
    gathers = [e for e in _eqns(jaxpr) if e.primitive.name == 'gather']
    assert gathers and all(
        int(np.prod(v.aval.shape)) == S for e in gathers for v in e.outvars)


# ---- the counter that says how often the sampled branch engages ---------

def test_sampled_steps_counter_moves_only_with_a_sampled_resident():
    """One greedy and one sampled request, the loop driven inline: a
    step counts under generate_sampled_steps_total exactly when the
    sampled request is resident at its dispatch, and the greedy one's
    tokens beside it are its solo greedy tokens."""
    from paddle_tpu import monitor
    from paddle_tpu.serving import GenerateEngine
    from test_paged_generate import _paged_cfg, _prompt
    eng = GenerateEngine(_paged_cfg())
    prompt = _prompt(6, seed=7)
    solo = eng.generate_once(prompt, max_new_tokens=12)
    name = 'generate_sampled_steps_total'
    assert eng.stats()['sampled_steps'] == 0      # generate_once is no step
    greedy = eng.submit(prompt, max_new_tokens=12)
    sampled = eng.submit(prompt, max_new_tokens=5, temperature=0.8,
                         top_k=8, sample_seed=3)
    eng._admit()
    with_sampled = without = 0
    while greedy.finish_reason is None:
        resident = any(st is not None and st.req is sampled
                       for st in eng._slots)
        before = monitor.counters().get(name, 0)
        eng._step()
        moved = monitor.counters().get(name, 0) - before
        assert moved == (1 if resident else 0)
        with_sampled += resident
        without += not resident
        eng._evict_expired()
        eng._admit()
    assert with_sampled == 4 and without == 7     # first tokens: prefill's
    assert eng.stats()['sampled_steps'] == with_sampled
    assert eng.stats()['decode_steps'] == with_sampled + without
    assert greedy.result(10) == solo and len(sampled.result(10)) == 5
