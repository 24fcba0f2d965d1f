"""One compiled call on a scope, every way of making it: `Executor.run`,
`run_async`, `bind`'s handle, `run_fused`, `with_data_parallel` and
`MeshRunner` go through one step (`Executor._take` / `_call` / `_commit`)
— so each of them takes its state from the record its last call left,
walks the scope after a write, sees an injected `run` fault, and leaves a
readable scope behind a step that trips FLAGS_check_nan_inf. And one key
recipe (`Executor._entry_key`): `precompile`, `explain` and the warm farm
name the entry `run` then hits.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor, resilience
from paddle_tpu.warmfarm import WarmFarm

WAYS = ['run', 'run_async', 'bind', 'run_fused', 'with_data_parallel',
        'mesh_runner']


def _net(seed=5):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name='x', shape=[8], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            h = fluid.layers.fc(x, size=8, act='relu')
            loss = fluid.layers.mean(fluid.layers.square_error_cost(
                fluid.layers.fc(h, size=1), y))
            fluid.optimizer.SGD(1e-2).minimize(loss)
    return main, startup, loss


def _feed(poison=False):
    rng = np.random.RandomState(3)
    x = rng.randn(8, 8).astype('float32')
    if poison:
        x[0, 0] = np.inf
    return {'x': x, 'y': rng.randn(8, 1).astype('float32')}


class _Way(object):
    """`step(feed)` runs the train program once on `scope`, this way, and
    returns the loss on the host."""

    def __init__(self, way):
        from jax.sharding import PartitionSpec as P
        from paddle_tpu.parallel import make_mesh, MeshRunner
        self.way = way
        self.main, startup, self.loss = _net()
        self.exe, self.scope = fluid.Executor(), fluid.Scope()
        self.exe.run(startup, scope=self.scope)
        main, loss, exe, scope = self.main, self.loss, self.exe, self.scope
        if way == 'run':
            self.step = lambda feed: exe.run(
                main, feed=feed, fetch_list=[loss], scope=scope)[0]
        elif way == 'run_async':
            self.step = lambda feed: exe.run_async(
                main, feed=feed, fetch_list=[loss], scope=scope).result()[0]
        elif way == 'bind':
            bound = exe.bind(main, _feed(), fetch_list=[loss], scope=scope)
            self.step = lambda feed: bound(
                exe._prepare_feed(main, feed)[0])[0]
        elif way == 'run_fused':
            self.step = lambda feed: exe.run_fused(
                main, [feed, feed], fetch_list=[loss], scope=scope)[0]
        elif way == 'with_data_parallel':
            prog = fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name,
                places=[fluid.TPUPlace(i) for i in range(4)])
            self.step = lambda feed: exe.run(
                prog, feed=feed, fetch_list=[loss], scope=scope)[0]
        else:
            runner = MeshRunner(main, make_mesh([('data', 4)]),
                                feed_specs={'x': P('data'), 'y': P('data')})
            self.step = lambda feed: runner.run(feed, [loss.name], scope)[0]
        self.step(_feed())              # the signature's first call


@pytest.fixture(scope='module', params=WAYS)
def way(request):
    return _Way(request.param)


def _delta(before, name):
    return monitor.counter_delta(before).get(name, 0)


def test_a_steady_call_takes_the_record_and_a_write_sends_the_next_to_walk(
        way):
    before = monitor.counters()
    way.step(_feed())
    # a call behind a call of its own: nothing looked up
    assert _delta(before, 'executor_run_carried_total') == 1
    way.scope.set('unrelated', np.zeros(1, 'float32'))
    way.step(_feed())
    assert _delta(before, 'executor_run_carried_total') == 1
    way.step(_feed())
    way.step(_feed())
    assert _delta(before, 'executor_run_carried_total') == 3
    assert _delta(before, 'compile_cache_miss') == 0
    if way.way == 'mesh_runner':
        # MeshRunner counts its own runs
        assert _delta(before, 'executor_run_total') == 4


def test_a_parameter_set_between_two_calls_is_what_the_next_call_uses(way):
    name = way.main.all_parameters()[0].name
    before = np.asarray(way.scope.get(name))
    way.scope.set(name, np.zeros_like(before))
    n0 = monitor.counters()
    way.step(_feed())
    assert _delta(n0, 'executor_run_carried_total') == 0
    after = np.asarray(way.scope.get(name))
    # one SGD step (two, fused) away from zeros, not from what it was
    assert np.abs(after).max() < 0.5 * np.abs(before).max()
    way.scope.set(name, before)


def test_an_injected_run_fault_is_seen_and_retried(way, monkeypatch):
    monkeypatch.setenv('PADDLE_RETRY_BASE_S', '0.001')
    before = monitor.counters()
    resilience.install_fault('run', mode='nth', value=1)
    try:
        out = way.step(_feed())
    finally:
        resilience.clear_faults()
    assert _delta(before, 'fault_injected_total{site=run}') == 1
    assert _delta(before, 'retry_attempt_total{site=run}') == 1
    assert np.isfinite(out).all()


def test_a_poisoned_step_raises_and_leaves_a_readable_scope(way):
    """The rebind comes BEFORE the check: the call donated its inputs, so
    a scope left as it was would hold deleted buffers (what
    `with_data_parallel` did until the step was one)."""
    good = {n: np.array(way.scope.get(n)) for n in way.scope.names()}
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    try:
        with pytest.raises(RuntimeError, match='check_nan_inf'):
            way.step(_feed(poison=True))
    finally:
        fluid.set_flags({'FLAGS_check_nan_inf': False})
    for n in way.scope.names():
        np.asarray(way.scope.get(n))    # no deleted buffer
    # the step that raised left no record: the next walks, and is good
    for n, v in good.items():
        way.scope.set(n, v)
    before = monitor.counters()
    assert np.isfinite(way.step(_feed())).all()
    assert _delta(before, 'executor_run_carried_total') == 0


@pytest.mark.parametrize('how', ['precompile', 'explain', 'warm_farm'])
def test_the_key_recipe_names_the_entry_run_hits(how):
    main, startup, loss = _net(seed=11 + len(how))    # a program each
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _feed()
    key = WarmFarm().signature(exe, main, feed, fetch_list=[loss],
                               scope=scope)
    assert exe._cache_get(key) is None
    if how == 'precompile':
        assert exe.precompile(main, feed, fetch_list=[loss],
                              scope=scope)['compiled']
    elif how == 'explain':
        with fluid.scope_guard(scope):
            assert exe.explain(main, feed=feed, fetch_list=[loss],
                               memory=False)['flops'] > 0
    else:
        assert WarmFarm().warm(exe, main, [feed], fetch_list=[loss],
                               scope=scope)['compiled'] == 1
    entry = exe._cache_get(key)
    assert entry is not None
    before = monitor.counters()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert _delta(before, 'compile_cache_hit') == 1
    assert _delta(before, 'compile_cache_miss') == 0
    assert exe._cache_get(key) is entry and len(scope._held) == 2
