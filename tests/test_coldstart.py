"""Set-up books itself (paddle_tpu/coldstart.py): every stage's series
moves when a small program is built, bound and run, and none moves on the
second run; a `jit` nested in a traced function is counted once; a
persistent-cache hit is `cache_load` and a miss `compile`; the stages sum
to no more than the process has lived; the per-op lowering clock names the
op types lowered; the SPMD runners book Executor.run's phases."""
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import coldstart, monitor

SETUP, LOWERING = ('program_setup_seconds_total',
                   'program_lowering_seconds_total')
RUN = 'executor_run_phase_seconds_total'
STAGES = ('import', 'build', 'trace', 'lower', 'compile', 'cache_load',
          'place', 'first_run')


def _labels(key):
    return dict(kv.split('=', 1) for kv in key[key.index('{') + 1:-1]
                .split(','))


def _setup(counters, program=None):
    """{stage: seconds} of the set-up series in `counters`, of `program`
    alone where one is named."""
    out = {}
    for key, v in counters.items():
        if key.startswith(SETUP + '{'):
            lab = _labels(key)
            if program is None or lab.get('program') == program:
                out[lab['stage']] = out.get(lab['stage'], 0.0) + v
    return out


def _mlp(name, width):
    """A program no other test of the process has built: the entry cache
    is keyed by structure, not by name."""
    main, startup = fluid.Program(name), fluid.Program(name + '_startup')
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[width], dtype='float32')
        h = fluid.layers.fc(x, size=width, act='relu')
        loss = fluid.layers.mean(fluid.layers.fc(h, size=1))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


@pytest.fixture()
def listening():
    coldstart.listen()


def test_the_import_is_booked_once_and_has_no_program():
    keys = [k for k in monitor.counters() if k.startswith(SETUP)
            and 'stage=import' in k]
    # monitor.reset() in an earlier test of this process takes it away
    assert keys in ([], [SETUP + '{stage=import}'])
    assert 'import' in coldstart.__doc__


def test_every_stage_moves_on_the_first_run_and_none_on_the_second(
        listening):
    before = monitor.counters()
    main, startup, loss = _mlp('cs_first', 24)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    # a weight written from the host: its upload is `place`
    w = main.all_parameters()[0].name
    scope.set(w, np.asarray(scope.get(w)) * 1.0)
    feed = {'x': np.ones((4, 24), 'float32')}
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    first = monitor.counter_delta(before)
    mine = _setup(first, 'cs_first')
    for stage in ('build', 'trace', 'lower', 'compile', 'place',
                  'first_run'):
        assert mine.get(stage, 0.0) > 0.0, (stage, mine)
    assert first['compile_cache_miss'] >= 1
    # the startup program books under its own name
    assert _setup(first, 'cs_first_startup').get('compile', 0.0) > 0.0

    before = monitor.counters()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    second = monitor.counter_delta(before)
    assert not [k for k in second
                if k.startswith(SETUP) or k.startswith(LOWERING)], second
    assert RUN + '{phase=dispatch}' in second


def test_a_bound_program_books_its_frames_and_its_calls_nothing(listening):
    main, startup = fluid.Program('cs_bound'), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        out = fluid.layers.fc(x, size=16)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {'x': np.ones((2, 16), 'float32')}
    before = monitor.counters()
    bound = exe.bind(main, feed, fetch_list=[out], scope=scope)
    mine = _setup(monitor.counter_delta(before), 'cs_bound')
    assert mine['trace'] > 0 and mine['compile'] > 0
    assert mine['first_run'] > 0
    before = monitor.counters()
    for _ in range(3):
        bound(feed)
    moved = monitor.counter_delta(before)
    assert not [k for k in moved if k.startswith(SETUP)], moved


def test_the_first_run_is_the_frame_and_the_runs_compile_phase(listening):
    main, startup, loss = _mlp('cs_phase', 40)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    hist0 = monitor.hist_sum('compile_seconds')
    before = monitor.counters()
    t0 = time.perf_counter()
    exe.run(main, feed={'x': np.ones((4, 40), 'float32')},
            fetch_list=[loss], scope=scope)
    wall = time.perf_counter() - t0
    moved = monitor.counter_delta(before)
    mine = _setup(moved, 'cs_phase')
    frames = sum(mine.values())
    # the frames are the run's compile phase, to the clock's grain
    assert moved[RUN + '{phase=compile}'] == pytest.approx(frames, rel=0.05)
    assert frames <= wall
    # ... and compile_seconds is the whole of it, observed once
    seen = monitor.hist_sum('compile_seconds') - hist0
    assert frames <= seen * 1.001 <= wall * 1.001
    names = [s['name'] for s in monitor.spans()]
    assert 'compile' in names and 'generate.warmup' not in names


def test_a_jit_nested_in_a_traced_function_is_counted_once(listening):
    @jax.jit
    def inner(v):
        return jnp.sin(v) * 2.0

    def outer(v):
        return inner(v) + inner(v + 1.0) + inner(v * 3.0)

    seen = []

    def tap(event, duration, **_kw):
        if event == '/jax/core/compile/jaxpr_trace_duration':
            seen.append(duration)
    x = jnp.ones((8,))
    jax.monitoring.register_event_duration_secs_listener(tap)
    before = monitor.counters()
    try:
        t0 = time.perf_counter()
        with coldstart.stage('first_run', 'cs_nested'):
            jax.block_until_ready(jax.jit(outer)(x))
        wall = time.perf_counter() - t0
    finally:
        jax.monitoring.unregister_event_duration_listener(tap)
    mine = _setup(monitor.counter_delta(before), 'cs_nested')
    # JAX reported the inner traces and the outer one round them
    assert len(seen) >= 4
    assert mine['trace'] > 0
    # self time: what is booked is no more than the outermost duration
    # (a plain sum counts every inner one a second time), and the frame's
    # stages together no more than its wall time
    assert mine['trace'] <= max(seen) * 1.001 < sum(seen)
    assert sum(mine.values()) <= wall
    assert set(mine) <= set(STAGES)


def test_jax_records_a_scalar_where_each_nesting_duration_begins():
    """What `_on_scalar` stands on — a detail of jax's
    LogElapsedTimeContextManager, not an API: without the record at entry
    a trace nested in another would be counted in both."""
    seen = []

    def note(event, value, **_kw):
        seen.append(event)
    jax.monitoring.register_scalar_listener(note)
    try:
        jax.jit(lambda v: v * 3.0 - 0.125).lower(jnp.ones((3,))).compile()
    finally:
        jax.monitoring.unregister_scalar_listener(note)
    assert set(coldstart._NESTING) <= set(seen)


def test_a_host_value_uploaded_every_call_is_no_set_up(listening):
    """An int64 persistable narrows on the device (x64 off), so the scope
    keeps the host array and every run uploads it anew: the steady path's
    cost, which opens no `place` frame — a float32 one, uploaded once and
    kept, does."""
    main, startup = fluid.Program('cs_host'), fluid.Program('cs_host_s')
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[4], dtype='float32')
        step = fluid.layers.create_global_var(
            [1], 0, 'int64', persistable=True, name='cs_host_step')
        scale = fluid.layers.create_global_var(
            [1], 2.0, 'float32', persistable=True, name='cs_host_scale')
        out = fluid.layers.mean(x) * scale \
            + fluid.layers.cast(step, 'float32')
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    scope.set('cs_host_step', np.array([3], 'int64'))
    scope.set('cs_host_scale', np.array([2.0], 'float32'))
    feed = {'x': np.ones((2, 4), 'float32')}
    before = monitor.counters()
    first, = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    assert _setup(monitor.counter_delta(before), 'cs_host')['place'] > 0
    assert isinstance(scope.get('cs_host_step'), np.ndarray)
    assert not isinstance(scope.get('cs_host_scale'), np.ndarray)
    before = monitor.counters()
    again, = exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    assert not _setup(monitor.counter_delta(before))
    assert float(first[0]) == float(again[0]) == 5.0


def test_a_duration_outside_every_phase_is_the_process_own(listening):
    # out of the series, so no reader has to leave it out: in the
    # module's own table
    before, was = monitor.counters(), coldstart.outside()
    jax.block_until_ready(jax.jit(lambda v: v * 5.0 + 1.25)(jnp.ones((3,))))
    assert not _setup(monitor.counter_delta(before))
    out = coldstart.outside()
    assert out['trace'] > was.get('trace', 0.0)
    assert out['compile'] > was.get('compile', 0.0)
    # ... and inside a phase no frame holds, the package's, under the
    # phase's name; the phase keeps its self time whole
    before = monitor.counters()
    t0 = time.perf_counter()
    with monitor.phase('cs.plain', 'cs_plain_seconds_total'):
        jax.block_until_ready(
            jax.jit(lambda v: v * 7.0 - 0.75)(jnp.ones((3,))))
    wall = time.perf_counter() - t0
    moved = monitor.counter_delta(before)
    held = _setup(moved, 'cs.plain')
    assert held['compile'] > 0 and sum(held.values()) < wall
    assert moved['cs_plain_seconds_total'] == pytest.approx(wall, rel=0.05)


def test_a_duration_in_a_phase_nested_in_a_frame_is_the_frames(listening):
    before = monitor.counters()
    t0 = time.perf_counter()
    with coldstart.stage('first_run', 'cs_held'):
        with monitor.phase('cs.inside', 'cs_inside_seconds_total'):
            jax.block_until_ready(
                jax.jit(lambda v: v * 11.0 - 0.5)(jnp.ones((3,))))
    wall = time.perf_counter() - t0
    moved = monitor.counter_delta(before)
    held = _setup(moved, 'cs_held')
    assert held['compile'] > 0 and held['trace'] > 0
    assert sum(held.values()) <= wall
    assert not _setup(moved, 'cs.inside')


def test_a_cache_hit_is_cache_load_and_a_miss_is_compile(listening,
                                                         tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ('jax_compilation_cache_dir', 'jax_enable_compilation_cache',
            'jax_persistent_cache_min_compile_time_secs',
            'jax_persistent_cache_min_entry_size_bytes')
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update('jax_compilation_cache_dir', str(tmp_path))
    jax.config.update('jax_enable_compilation_cache', True)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    compilation_cache.reset_cache()

    def fn(v):
        return jnp.tanh(v @ v.T).sum() * 0.125

    def once(program):
        before = monitor.counters()
        with coldstart.stage('first_run', program):
            jax.block_until_ready(jax.jit(fn)(jnp.ones((16, 16))))
        return _setup(monitor.counter_delta(before), program)
    try:
        cold = once('cs_cold')
        if not os.listdir(str(tmp_path)):
            pytest.skip('this backend writes no persistent cache entry')
        jax.clear_caches()
        warm = once('cs_warm')
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    assert cold['compile'] > 0 and cold.get('cache_load', 0.0) == 0.0
    assert warm['cache_load'] > 0
    # what is left of the backend's duration round a hit is bookkeeping
    assert warm.get('compile', 0.0) < 0.5 * cold['compile']


def test_the_stages_sum_to_no_more_than_the_process_has_lived(listening):
    with open('/proc/self/stat') as f:
        started = float(f.read().rsplit(')', 1)[1].split()[19])
    with open('/proc/uptime') as f:
        up = float(f.read().split()[0])
    lived = up - started / os.sysconf('SC_CLK_TCK')
    main, startup, loss = _mlp('cs_sum', 48)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed={'x': np.ones((4, 48), 'float32')},
            fetch_list=[loss], scope=scope)
    booked = _setup(monitor.counters())
    assert set(booked) <= set(STAGES)
    assert 0.0 < sum(booked.values()) <= lived


def test_stages_nest_as_phases_do(listening):
    before = monitor.counters()
    t0 = time.perf_counter()
    with coldstart.stage('first_run', 'cs_outer'):
        time.sleep(0.02)
        t1 = time.perf_counter()
        with coldstart.stage('place', 'cs_inner'):
            time.sleep(0.03)
        inner_wall = time.perf_counter() - t1
    wall = time.perf_counter() - t0
    moved = monitor.counter_delta(before)
    inner, outer = _setup(moved, 'cs_inner'), _setup(moved, 'cs_outer')
    assert set(inner) == {'place'} and set(outer) == {'first_run'}
    # self time: the inner frame's seconds are not in the outer's
    assert 0.03 <= inner['place'] <= inner_wall
    assert 0.02 <= outer['first_run'] <= wall - inner['place']
    assert threading.get_ident() not in monitor._open_phase


def test_a_program_named_inside_its_guard_books_under_that_name():
    before = monitor.counters()
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        fluid.layers.data(name='x', shape=[4], dtype='float32')
        main.name = 'cs_named_late'
    assert _setup(monitor.counter_delta(before), 'cs_named_late')['build'] > 0


def test_the_program_label_is_the_modules_name():
    assert coldstart.label_of(fluid.Program('lm train/b=4')) \
        == 'lm_train_b_4'
    assert coldstart.label_of(fluid.Program()) == 'program'
    assert coldstart.label_of('generate.warmup') == 'generate.warmup'


def test_more_programs_than_the_default_cap_keep_their_stages():
    before = monitor.counters()
    for i in range(80):
        coldstart.book('trace', 0.001, 'cs_many_%d' % i)
    moved = monitor.counter_delta(before)
    assert len([k for k in moved if 'cs_many_' in k]) == 80


def test_the_lowering_clock_names_the_op_types_lowered(listening):
    main, startup, loss = _mlp('cs_ops', 56)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    before = monitor.counters()
    t0 = time.perf_counter()
    exe.run(main, feed={'x': np.ones((4, 56), 'float32')},
            fetch_list=[loss], scope=scope)
    wall = time.perf_counter() - t0
    moved = monitor.counter_delta(before)
    ops = {_labels(k)['op_type']: v for k, v in moved.items()
           if k.startswith(LOWERING)}
    types = {op.type for op in main.global_block().ops}
    assert set(ops) <= types
    assert {'mul', 'elementwise_add', 'relu', 'mean', 'backward',
            'sgd'} <= set(ops)
    assert all(v > 0 for v in ops.values())
    # self time: the forward ops under the vjp are not in `backward` too
    assert sum(ops.values()) <= wall


def _dp_loss():
    main, startup = fluid.Program('cs_mesh'), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, size=1), y))
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def _phases(moved):
    return {_labels(k)['phase']: v for k, v in moved.items()
            if k.startswith(RUN + '{')}


def test_the_data_parallel_runner_books_the_runs_phases(listening):
    main, startup, loss = _dp_loss()
    exe, scope = fluid.Executor(), fluid.Scope()
    feed = {'x': np.ones((8, 16), 'float32'),
            'y': np.ones((8, 1), 'float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        before = monitor.counters()
        exe.run(compiled, feed=feed, fetch_list=[loss], scope=scope)
        first = monitor.counter_delta(before)
        before = monitor.counters()
        for _ in range(3):
            exe.run(compiled, feed=feed, fetch_list=[loss], scope=scope)
        steady = monitor.counter_delta(before)
    # the first run: the frame, the parameters laid onto the mesh
    mine = _setup(first, 'cs_mesh')
    assert mine['trace'] > 0 and mine['compile'] > 0 and mine['place'] > 0
    assert 'compile' in _phases(first) and 'dispatch' not in _phases(first)
    # a steady run: the four phases, counted, and no set-up
    assert set(_phases(steady)) == {'prepare', 'dispatch', 'commit',
                                    'fetch'}
    assert steady['executor_run_total'] == 3
    assert not [k for k in steady if k.startswith(SETUP)], steady


def test_the_mesh_runner_books_the_runs_phases(listening):
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner
    main, startup, loss = _dp_loss()
    exe, scope = fluid.Executor(), fluid.Scope()
    feed = {'x': np.ones((8, 16), 'float32'),
            'y': np.ones((8, 1), 'float32')}
    runner = MeshRunner(main, make_mesh([('data', 2)]),
                        feed_specs={'x': P('data'), 'y': P('data')})
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        runner.run(feed, [loss.name], scope)
        before = monitor.counters()
        runner.run(feed, [loss.name], scope)
        runner.run(feed, [loss.name], scope)
        steady = monitor.counter_delta(before)
    assert set(_phases(steady)) == {'prepare', 'dispatch', 'commit',
                                    'fetch'}
    assert steady['executor_run_total'] == 2
    assert not [k for k in steady if k.startswith(SETUP)], steady
