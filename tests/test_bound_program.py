"""`Executor.bind`'s handle (`BoundProgram`): the read-only state is staged
at bind and again after a scope write, the read-written state comes from
the scope on every call, and what a call guarantees — the not-initialised
error, the retry with the donated state intact — is what it was when every
call staged everything.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu.serving import GenerateEngine

from test_paged_generate import _drive, _paged_cfg, _prompt

FEED = {'x': np.arange(8, dtype='float32').reshape(2, 4) / 8.0}


def _bound(layers=2):
    """A handle on: `layers` fc layers (2 read-only names each) and one
    persistable counter the program increments (the read-written name)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            h = fluid.layers.data(name='x', shape=[4], dtype='float32')
            for _ in range(layers):
                h = fluid.layers.fc(h, size=4)
            calls = fluid.layers.create_global_var(
                [1], value=0.0, dtype='float32', persistable=True,
                name='bound_calls')
            fluid.layers.increment(calls)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    bound = exe.bind(main, FEED, fetch_list=[h], scope=scope)
    assert len(bound._entry.ro_names) == 2 * layers
    assert list(bound._entry.rw_names) == ['bound_calls']
    return exe, scope, bound


def _restages():
    return monitor.counters().get('executor_bound_restage_total', 0)


@pytest.mark.parametrize('how', ['scope_set', 'tensor_shim', 'update'])
def test_a_rebound_weight_is_what_the_next_call_uses(how):
    exe, scope, bound = _bound()
    first = bound(FEED)[0]
    name = bound._entry.ro_names[0]
    new = np.asarray(scope.get(name)) * 2.0 + 1.0
    n0 = _restages()
    if how == 'scope_set':
        scope.set(name, new)
    elif how == 'tensor_shim':
        scope.find_var(name).get_tensor().set(new)
    else:
        scope.update({name: new})
    got = bound(FEED)[0]
    want = exe.run(bound._program, feed=FEED,
                   fetch_list=list(bound.fetch_names), scope=scope)[0]
    assert not np.allclose(got, first)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(bound(FEED)[0], want)
    # one write, one rebuild: the upload the restage itself cached back
    # into the scope is not a second
    assert _restages() - n0 == 1
    assert bound.restages == 1


def test_steady_calls_stage_the_read_written_names_alone(monkeypatch):
    exe, scope, bound = _bound(layers=3)
    staged = []
    real = exe._state_value

    def counting(scope_, name, program, cache=True):
        staged.append(name)
        return real(scope_, name, program, cache=cache)

    monkeypatch.setattr(exe, '_state_value', counting)
    n0 = _restages()
    for _ in range(5):
        bound(FEED)
    assert staged == ['bound_calls'] * 5
    assert _restages() == n0 and bound.restages == 0
    # bind's own run and the six calls since each incremented it once
    assert float(np.asarray(scope.get('bound_calls'))[0]) == 6.0
    # a write to a name no handle staged moves nothing
    scope.set('bound_calls', np.zeros([1], 'float32'))
    scope.set('nobody_reads_this', np.ones([1], 'float32'))
    bound(FEED)
    assert _restages() == n0
    assert float(np.asarray(scope.get('bound_calls'))[0]) == 1.0


def test_a_host_value_the_scope_cannot_cache_is_converted_every_call():
    """A view is never frozen or cached (`_state_value`), so a write
    through its base must reach the next call, as it does through run()."""
    exe, scope, bound = _bound()
    name = bound._entry.ro_names[0]
    base = np.array(np.asarray(scope.get(name)))[None]
    scope.set(name, base[0])            # a view of `base`
    first = bound(FEED)[0]
    base[0] += 1.0
    second = bound(FEED)[0]
    assert not np.allclose(first, second)
    np.testing.assert_array_equal(
        second, exe.run(bound._program, feed=FEED,
                        fetch_list=list(bound.fetch_names), scope=scope)[0])


@pytest.mark.parametrize('kind', ['read_only', 'read_written'])
def test_a_dropped_name_raises_not_initialised_at_the_call(kind):
    _exe, scope, bound = _bound()
    bound(FEED)
    name = bound._entry.ro_names[-1] if kind == 'read_only' \
        else 'bound_calls'
    scope.drop(name)
    with pytest.raises(RuntimeError, match='%r is not initialized' % name):
        bound(FEED)


def test_an_injected_run_fault_retries_with_the_donated_state_intact(
        monkeypatch):
    monkeypatch.setenv('PADDLE_RETRY_BASE_S', '0.001')
    _exe, scope, bound = _bound()
    want = bound(FEED)[0]
    before = monitor.counters()
    resilience.install_fault('run', mode='nth', value=1)
    try:
        got = bound(FEED)[0]
    finally:
        resilience.clear_faults()
    delta = monitor.counter_delta(before)
    assert delta.get('fault_injected_total{site=run}') == 1
    assert delta.get('retry_attempt_total{site=run}') == 1
    np.testing.assert_array_equal(got, want)
    # stepped once by the retried call, and alive
    assert float(np.asarray(scope.get('bound_calls'))[0]) == 3.0


def test_pools_rebound_between_two_steps_are_the_ones_the_step_reads():
    """Between two decode steps of a resident request another bound
    program (a prefill) and `_cow_copy` rebind the same pools: the step
    takes them from the scope, not from its own last output, so every
    request reads what a fresh engine serves it alone — and no handle
    staged its weights twice."""
    fresh = GenerateEngine(_paged_cfg())
    fresh.warmup()
    shared, other = _prompt(16, seed=31), _prompt(11, seed=32)
    want_shared = fresh.generate_once(shared, max_new_tokens=6)
    want_other = fresh.generate_once(other, max_new_tokens=12)

    eng = GenerateEngine(_paged_cfg())
    eng.warmup()
    before = monitor.counters()
    first = eng.submit(shared, max_new_tokens=6)    # registers its blocks
    _drive(eng, first)
    resident = eng.submit(other, max_new_tokens=12)
    eng._admit()
    for _ in range(3):
        eng._step()
    # two full shared blocks: the admission copies the last on write
    sharer = eng.submit(shared, max_new_tokens=6)
    _drive(eng, resident, sharer)
    delta = monitor.counter_delta(before)
    assert delta.get('kv_block_cow_total', 0) >= 1
    assert delta.get('kv_prefix_hit_total{outcome=hit}', 0) >= 1
    assert first.result(5) == want_shared
    assert sharer.result(5) == want_shared
    assert resident.result(5) == want_other
    assert eng.stats()['bound_restages'] == 0
    assert delta.get('executor_bound_restage_total', 0) == 0
    eng.stop()
    fresh.stop()
