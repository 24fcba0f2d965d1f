"""`Executor.bind`'s handle (`BoundProgram`): the read-only state is staged
at bind and again after a scope write to one of its names, the read-written
state comes from the scope after any write but the handle's own, and what a
call guarantees — the not-initialised
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
    # the handle's own rebind is the one write its record survives: a
    # call behind a call of its own looks nothing up
    assert staged == []
    assert _restages() == n0 and bound.restages == 0
    # bind's own run and the six calls since each incremented it once
    assert float(np.asarray(scope.get('bound_calls'))[0]) == 6.0
    # a write to a name no handle staged (another program's rebind of
    # the pools) sends the read-written names to the scope, them alone
    scope.set('bound_calls', np.zeros([1], 'float32'))
    scope.set('nobody_reads_this', np.ones([1], 'float32'))
    bound(FEED)
    assert staged == ['bound_calls']
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


# ---------------------------------------------------------------------------
# staged state lies as the compiled entry wants it

CHOSEN = (1, 0)         # the stand-in's choice, column-major


@pytest.fixture
def layouts(monkeypatch):
    """The bound path of a backend that offers layouts, on the CPU. The
    CPU's compiler answers every AUTO with the default, so a stand-in
    chooses: a leaf asked about for the first time lies column-major if
    its shape is one of the returned set's (empty: the test fills it),
    as the compiler pleases otherwise. Yields (shapes, the leaves asked
    about); the handles' executables kept on the process-wide compiled
    entries are forgotten before and after."""
    from jax.experimental.layout import Format, Layout
    from paddle_tpu import executor
    shapes, asked = set(), []
    real = executor._open_format

    def stand_in(leaf):
        asked.append(tuple(leaf.shape))
        if tuple(leaf.shape) in shapes:
            return Format(Layout(major_to_minor=CHOSEN), leaf.sharding)
        return real(leaf)
    def forget():
        # what an earlier stand-in chose stays with the process-wide
        # compiled entries (`_CompiledEntry.bound`): another test's
        # program of the same fingerprint would find it and ask nobody
        for _key, entry in executor._shared_cache.items():
            getattr(entry, 'bound', {}).clear()
    forget()
    monkeypatch.setattr(executor, '_layouts_offered', lambda: True)
    monkeypatch.setattr(executor, '_open_format', stand_in)
    yield shapes, asked
    forget()


def _named_net(scale):
    """x [2, 4] -> fc 'net.w1' [4, 6] -> fc 'net.w2' [6, 4], times
    `scale`: two programs of two scales share their four weights by name."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            h = fluid.layers.data(name='x', shape=[4], dtype='float32')
            for i, size in enumerate((6, 4)):
                h = fluid.layers.fc(
                    h, size=size,
                    param_attr=fluid.ParamAttr(name='net.w%d' % (i + 1)),
                    bias_attr=fluid.ParamAttr(name='net.b%d' % (i + 1)))
            h = fluid.layers.scale(h, scale=scale)
    return main, startup, h


def _relayouts():
    return monitor.counters().get('executor_bound_relayout_total', 0)


def _layout_of(value):
    return tuple(value.format.layout.major_to_minor)


def test_a_leaf_is_relaid_once_and_the_scope_holds_the_staged_leaf(layouts):
    shapes, asked = layouts
    shapes.add((4, 6))
    main, startup, out = _named_net(1.0)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    original = np.array(scope.get('net.w1'))
    n0, r0 = _relayouts(), _restages()
    bound = exe.bind(main, FEED, fetch_list=[out], scope=scope)
    at = bound._entry.fn.ro_names.index('net.w1')
    # one leaf of four chosen otherwise: relaid once, counted once
    assert sorted(asked) == sorted([(4, 6), (6,), (6, 4), (4,)])
    assert (bound.relayouts, _relayouts() - n0) == (1, 1)
    assert [_layout_of(v) for v in bound._ro].count(CHOSEN) == 1
    # the scope's value IS the staged leaf: one copy, the same values
    assert scope.get('net.w1') is bound._ro[at]
    assert _layout_of(scope.get('net.w1')) == CHOSEN
    np.testing.assert_array_equal(np.asarray(scope.get('net.w1')), original)
    # bind's own run went through the handle's entry; steady calls stage
    # nothing and lay nothing out
    want = fluid.Executor().run(main, feed=FEED, fetch_list=[out],
                                scope=scope)[0]
    np.testing.assert_array_equal(bound.first_out[0], want)
    for _ in range(3):
        np.testing.assert_array_equal(bound(FEED)[0], want)
    assert (bound.relayouts, bound.restages) == (1, 0)
    assert (_relayouts() - n0, _restages() - r0) == (1, 0)

    # a rebound weight restages, is relaid and counted again
    scope.set('net.w1', original * 2.0 + 1.0)
    got = bound(FEED)[0]
    assert (bound.relayouts, bound.restages) == (2, 1)
    assert (_relayouts() - n0, _restages() - r0) == (2, 1)
    assert scope.get('net.w1') is bound._ro[at]
    assert _layout_of(scope.get('net.w1')) == CHOSEN
    np.testing.assert_array_equal(np.asarray(scope.get('net.w1')),
                                  original * 2.0 + 1.0)
    assert not np.allclose(got, want)
    np.testing.assert_array_equal(got, fluid.Executor().run(
        main, feed=FEED, fetch_list=[out], scope=scope)[0])
    # the relaid leaf replaced the scope's value without a write: the
    # next call stages nothing again
    bound(FEED)
    assert (bound.relayouts, bound.restages) == (2, 1)


def test_a_program_bound_later_takes_the_layout_it_finds(layouts):
    shapes, asked = layouts
    shapes.add((4, 6))
    first, startup, out1 = _named_net(1.0)
    later, _, out2 = _named_net(3.0)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    one = exe.bind(first, FEED, fetch_list=[out1], scope=scope)
    del asked[:]
    # whoever chose would choose otherwise now: nobody is asked
    shapes.clear()
    shapes.add((6, 4))
    n0 = _relayouts()
    two = exe.bind(later, FEED, fetch_list=[out2], scope=scope)
    assert asked == [] and two.relayouts == 0 and _relayouts() == n0
    assert [f.layout for f in two._formats] == \
        [f.layout for f in one._formats]
    assert all(map(lambda a, b: a is b, one._ro, two._ro))
    np.testing.assert_allclose(two(FEED)[0], 3.0 * one(FEED)[0], rtol=1e-6)
    # a rebind reaches both; the leaf is relaid once, by whoever is
    # called first, and the other finds it so
    scope.set('net.w1', np.asarray(scope.get('net.w1')) + 1.0)
    np.testing.assert_allclose(two(FEED)[0], 3.0 * one(FEED)[0], rtol=1e-6)
    assert (one.restages, two.restages) == (1, 1)
    assert one.relayouts + two.relayouts == 2 and _relayouts() - n0 == 1
    at = one._entry.fn.ro_names.index('net.w1')
    assert scope.get('net.w1') is one._ro[at] is two._ro[at]
    # a second engine's handle on a fresh scope: the executable and its
    # formats are the entry's, nothing compiles and nobody is asked twice
    fresh = fluid.Scope()
    exe.run(startup, scope=fresh)
    shapes.clear()
    shapes.add((4, 6))
    again = exe.bind(first, FEED, fetch_list=[out1], scope=fresh)
    assert again._flat is one._flat and again.relayouts == 1


def test_where_no_layout_is_offered_the_bound_entry_is_the_jitted_one():
    """The CPU: no executable of the handle's own, no format, the entry
    every other caller goes through."""
    _exe, scope, bound = _bound()
    assert bound._flat is bound._entry.fn.flat and bound._formats is None
    assert bound.relayouts == 0 and bound._entry.bound == {}
    assert set(scope._staged.values()) == {None}


def test_an_engines_step_chooses_and_its_prefills_take_what_they_find(
        layouts, monkeypatch):
    """warmup() binds the decode step before the prefill buckets: every
    matrix is relaid once, by the step, and the tokens are those of an
    engine whose weights lie as they did (the CPU's own bound path)."""
    from paddle_tpu import executor
    shapes, asked = layouts
    monkeypatch.setattr(executor, '_layouts_offered', lambda: False)
    plain = GenerateEngine(_paged_cfg())
    plain.warmup()
    prompt = _prompt(13, seed=5)
    want = plain.generate_once(prompt, max_new_tokens=8)
    plain.stop()
    assert plain.stats()['bound_relayouts'] == 0 and asked == []
    monkeypatch.setattr(executor, '_layouts_offered', lambda: True)

    eng = GenerateEngine(_paged_cfg())
    matrices = {n for n in eng.scope.names()
                if np.ndim(eng.scope.get(n)) == 2
                and not n.startswith('gen_')}
    shapes.update(tuple(np.shape(eng.scope.get(n))) for n in matrices)
    n0 = _relayouts()
    eng.warmup()
    staged = [n for n in matrices if n in eng.scope._staged]
    assert staged and eng.stats()['bound_relayouts'] == len(staged)
    assert eng._step_bound.relayouts == len(
        [n for n in eng._step_bound._entry.fn.ro_names if n in matrices])
    assert all(b.relayouts == 0 for b in eng._prefill_bound.values()
               if set(b._entry.fn.ro_names) <=
               set(eng._step_bound._entry.fn.ro_names))
    assert all(_layout_of(eng.scope.get(n)) == CHOSEN for n in staged)
    assert eng.generate_once(prompt, max_new_tokens=8) == want
    req = eng.submit(prompt, max_new_tokens=8)
    _drive(eng, req)
    assert req.result(5) == want
    stats = eng.stats()
    assert stats['bound_relayouts'] == len(staged) == _relayouts() - n0
    assert stats['bound_restages'] == 0
    eng.stop()


WARM_PROCESS = r'''
import sys
sys.path[:0] = [%(root)r, %(tests)r]
import jax
import numpy as np
jax.config.update('jax_compilation_cache_dir', %(cache)r)
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
seen = []
jax.monitoring.register_event_listener(
    lambda e, **kw: seen.append(e.rsplit('/', 1)[-1]))
from jax.experimental.layout import Format, Layout
import paddle_tpu as fluid
from paddle_tpu import executor
from test_bound_program import CHOSEN, FEED, _named_net
real = executor._open_format
executor._layouts_offered = lambda: True
executor._open_format = lambda leaf: \
    Format(Layout(major_to_minor=CHOSEN), leaf.sharding) \
    if leaf.shape == (4, 6) else real(leaf)
main, startup, out = _named_net(1.0)
exe, scope = fluid.Executor(), fluid.Scope()
exe.run(startup, scope=scope)
scope.set('net.w1', np.random.RandomState(0).rand(4, 6).astype('float32'))
bound = exe.bind(main, FEED, fetch_list=[out], scope=scope)
print('RESULT', bound.relayouts,
      tuple(scope.get('net.w1').format.layout.major_to_minor) == CHOSEN,
      seen.count('cache_hits'), seen.count('compile_requests_use_cache'),
      bound(FEED)[0].ravel().tolist())
'''


def test_a_second_process_relays_the_leaf_its_cached_entry_asks_for(
        tmp_path):
    """The handle's entry comes out of JAX's persistent compile cache in a
    second process, asking for the layout it was compiled for; the small
    program that lays the leaf out is compiled by the process itself
    (`_compiled_here`): taken from that cache it hands the leaf back in
    the default layout, and the entry refuses it — what the first warm
    run on the chip did at PR 49."""
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    code = WARM_PROCESS % {'root': os.path.dirname(here), 'tests': here,
                           'cache': str(tmp_path / 'cache')}
    runs = []
    for _ in range(2):
        out = subprocess.run([sys.executable, '-c', code], check=True,
                             capture_output=True, text=True,
                             env=dict(os.environ, JAX_PLATFORMS='cpu'))
        line, = [l for l in out.stdout.splitlines()
                 if l.startswith('RESULT ')]
        runs.append(line.split(' ', 5)[1:])
    (cold, warm) = runs
    assert cold[:3] == ['1', 'True', '0'] and warm[:2] == ['1', 'True']
    # every compile the cache was asked for it served, the entry's too
    assert warm[2] == warm[3] == cold[3] and int(warm[3]) >= 2
    assert warm[4] == cold[4]
