"""A steady `Executor.run` takes its state from the run before
(`Executor._take` / `_commit`, the scope's one record an entry:
`Scope._held`, `_writes`, `_gen`): the read-written leaves a run returned
are what the next run of the same entry on the same scope is called with,
until something writes the scope. Held here: the carried path and the walk
give the same bits, every writer sends the next run down the walk, and a
record pins nothing a writer let go.
"""
import gc
import weakref

import numpy as np
import pytest

import jax
import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu import monitor
from paddle_tpu.contrib import mixed_precision as mp

STEPS = 6


def _programs(seed=7, dropout=0.1):
    """A small AMP + Adam train program (two fc layers, dropout, so the
    run key matters) and an eval program over the same parameters."""
    main, startup, test = fluid.Program(), fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = test.random_seed = seed

    def net(is_test):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='float32')
        h = fluid.layers.fc(x, size=32, act='relu')
        h = fluid.layers.dropout(h, dropout, is_test=is_test)
        cost = fluid.layers.square_error_cost(fluid.layers.fc(h, size=8), y)
        return fluid.layers.mean(cost)

    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            loss = net(False)
            mp.decorate(fluid.optimizer.Adam(1e-2)).minimize(loss)
    with fluid.unique_name.guard():
        with fluid.program_guard(test, fluid.Program()):
            test_loss = net(True)
    return main, startup, loss, test, test_loss


def _batches(n=STEPS, rows=8):
    rng = np.random.RandomState(0)
    return [{'x': rng.randn(rows, 16).astype('float32'),
             'y': rng.randn(rows, 1).astype('float32')} for _ in range(n)]


def _scalar(fetched):
    return float(np.asarray(fetched[0]).reshape(-1)[0])


def _carried():
    return monitor.counters().get('executor_run_carried_total', 0)


def _records(scope):
    """The scope's records that hold a leaf, by entry."""
    return {e: r for e, r in scope._held.items() if r.ro or r.rw}


def _state(scope):
    return {n: np.asarray(scope.get(n)) for n in scope.names()
            if n != 'unrelated' and scope.get(n) is not None}


def _runner(kind, main, loss):
    """What to hand Executor.run for `kind`: the program, or the program
    data-parallel over 4 devices in Reduce mode."""
    if kind == 'run':
        return main
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    return fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, build_strategy=bs,
        places=[fluid.TPUPlace(i) for i in range(4)])


def _train(kind, walk_every_step, steps=STEPS, between=None, **run_kw):
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    prog = _runner(kind, main, loss)
    losses, n0 = [], _carried()
    for i, feed in enumerate(_batches(steps)):
        if walk_every_step:
            # a write to a name no program reads: the walk, every step
            scope.set('unrelated', np.zeros(1, 'float32'))
        if between is not None:
            between(i, scope, exe, main)
        out, = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope,
                       **run_kw)
        losses.append(np.array(out))
    return losses, _state(scope), _carried() - n0


@pytest.mark.parametrize('kind', ['run', 'data_parallel_reduce'])
def test_carried_and_walked_steps_give_the_same_bits(kind):
    carried = _train(kind, False)
    walked = _train(kind, True)
    for a, b in zip(carried[0], walked[0]):
        np.testing.assert_array_equal(a, b)
    assert carried[1].keys() == walked[1].keys()
    for n in carried[1]:
        np.testing.assert_array_equal(carried[1][n], walked[1][n], err_msg=n)
    # every step but the first took its state from the step before; a
    # scope written before every step carries nothing
    assert carried[2] == STEPS - 1
    assert walked[2] == 0
    assert len({_scalar([l]) for l in carried[0]}) == STEPS


@pytest.mark.parametrize('kind', ['run', 'data_parallel_reduce'])
@pytest.mark.parametrize('how', ['scope_set', 'tensor_shim'])
def test_a_parameter_set_between_two_steps_is_what_the_next_step_uses(
        kind, how):
    def rewrite(i, scope, exe, main):
        if i == 3:
            name = main.all_parameters()[0].name
            new = np.asarray(scope.get(name)) * 0.5
            if how == 'scope_set':
                scope.set(name, new)
            else:
                scope.find_var(name).get_tensor().set(new)

    plain = _train(kind, False)
    carried = _train(kind, False, between=rewrite)
    walked = _train(kind, True, between=rewrite)
    assert carried[2] == STEPS - 2          # the step behind the write walks
    for a, b in zip(carried[0], walked[0]):
        np.testing.assert_array_equal(a, b)
    for n in carried[1]:
        np.testing.assert_array_equal(carried[1][n], walked[1][n], err_msg=n)
    assert not np.array_equal(carried[0][3], plain[0][3])


def test_a_checkpoint_loaded_between_two_steps_is_what_the_next_step_uses(
        tmp_path):
    def reload(i, scope, exe, main):
        with fluid.scope_guard(scope):
            if i == 2:
                fluid.io.save_persistables(exe, str(tmp_path), main)
            if i == 4:
                fluid.io.load_persistables(exe, str(tmp_path), main)

    carried = _train('run', False, between=reload)
    walked = _train('run', True, between=reload)
    plain = _train('run', False)
    # the save reads, the load writes: one more step on the walk
    assert carried[2] == STEPS - 2
    for a, b in zip(carried[0], walked[0]):
        np.testing.assert_array_equal(a, b)
    # step 4 ran on step 2's state again
    np.testing.assert_array_equal(carried[0][2], plain[0][2])
    assert not np.array_equal(carried[0][4], plain[0][4])


def test_an_eval_program_reads_what_the_train_step_wrote_and_moves_nothing():
    main, startup, loss, test, test_loss = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feeds = _batches()
    n0 = _carried()
    evals = []
    for feed in feeds:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        evals.append(_scalar(exe.run(test, feed=feeds[0],
                                     fetch_list=[test_loss], scope=scope)))
        # a second one, behind no write: its own record serves it
        again = _scalar(exe.run(test, feed=feeds[0],
                                fetch_list=[test_loss], scope=scope))
        assert again == evals[-1]
    # the eval writes nothing: every train step but the first is carried,
    # and so is every second eval
    assert _carried() - n0 == (STEPS - 1) + STEPS
    assert len(set(evals)) == STEPS         # never served a stale leaf
    # against a scope walked at every run
    main2, startup2, loss2, test2, test_loss2 = _programs()
    exe2, scope2 = fluid.Executor(), fluid.Scope()
    exe2.run(startup2, scope=scope2)
    for feed, want in zip(feeds, evals):
        exe2.run(main2, feed=feed, fetch_list=[loss2], scope=scope2)
        scope2.set('unrelated', 0.0)
        got = _scalar(exe2.run(test2, feed=feeds[0],
                              fetch_list=[test_loss2], scope=scope2))
        assert got == want


def test_two_fetch_lists_alternating_on_one_program():
    def run(walk):
        main, startup, loss, _t, _tl = _programs()
        other = main.all_parameters()[0]
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(startup, scope=scope)
        out = []
        for i, feed in enumerate(_batches()):
            if walk:
                scope.set('unrelated', 0.0)
            fetch = [loss] if i % 2 == 0 else [loss, other]
            out.append([np.array(v) for v in exe.run(
                main, feed=feed, fetch_list=fetch, scope=scope)])
        return out, _state(scope)

    n0 = _carried()
    carried = run(False)
    # two entries, each written over by the other's run: nothing carried
    assert _carried() - n0 == 0
    walked = run(True)
    for a, b in zip(carried[0], walked[0]):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
    for n in carried[1]:
        np.testing.assert_array_equal(carried[1][n], walked[1][n], err_msg=n)


def test_donate_false_keeps_the_state_of_the_run_before_alive():
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    name = main.all_parameters()[0].name
    n0 = _carried()
    kept = []
    for feed in _batches():
        kept.append(scope.get(name))
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                donate=False)
    assert _carried() - n0 == STEPS - 1
    # every step's input is still readable, and each differs from the next
    host = [np.asarray(v) for v in kept[1:]]
    assert all(not np.array_equal(a, b) for a, b in zip(host, host[1:]))
    got = _train('run', False, donate=False)
    want = _train('run', True)
    for a, b in zip(got[0], want[0]):
        np.testing.assert_array_equal(a, b)


def test_a_run_that_raises_drops_the_record_and_a_good_step_follows():
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feeds = _batches()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
    name = main.all_parameters()[0].name
    good = {n: np.array(v) for n, v in _state(scope).items()}
    fluid.set_flags({'FLAGS_check_nan_inf': True})
    try:
        bad = dict(feeds[2], x=np.full_like(feeds[2]['x'], np.inf))
        with pytest.raises(RuntimeError, match='check_nan_inf'):
            exe.run(main, feed=bad, fetch_list=[loss], scope=scope)
    finally:
        fluid.set_flags({'FLAGS_check_nan_inf': False})
    assert not _records(scope)          # the run that raised left none
    for n, v in good.items():           # the trainer's rollback
        scope.set(n, v)
    n0 = _carried()
    out, = exe.run(main, feed=feeds[2], fetch_list=[loss], scope=scope)
    assert _carried() - n0 == 0 and np.isfinite(out).all()
    assert np.isfinite(np.asarray(scope.get(name))).all()
    exe.run(main, feed=feeds[3], fetch_list=[loss], scope=scope)
    assert _carried() - n0 == 1


@pytest.mark.parametrize('kind', ['run', 'data_parallel_reduce'])
def test_a_dropped_name_is_freed_and_the_next_run_says_so(kind):
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    prog = _runner(kind, main, loss)
    feeds = _batches()
    for feed in feeds[:3]:
        exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
    name = main.all_parameters()[0].name
    ref = weakref.ref(scope.get(name))
    scope.drop(name)
    gc.collect()
    assert ref() is None                # the record did not keep it
    with pytest.raises(RuntimeError, match='%r is not initialized' % name):
        exe.run(prog, feed=feeds[3], fetch_list=[loss], scope=scope)


def test_a_replaced_array_is_held_by_no_record():
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    for feed in _batches(3):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                donate=False)
    name = main.all_parameters()[0].name
    old = scope.get(name)
    ref = weakref.ref(old)
    scope.set(name, np.asarray(old) + 1.0)
    del old
    gc.collect()
    assert ref() is None


def test_two_programs_sharing_parameters_on_one_scope():
    """Two train programs over the same parameter names, alternating: each
    run's writes send the other's next run down the walk, and the
    trajectory is the walked one."""
    def run(walk):
        a = _programs(seed=7)
        b = _programs(seed=11, dropout=0.3)
        exe, scope = fluid.Executor(), fluid.Scope()
        exe.run(a[1], scope=scope)
        exe.run(b[1], scope=scope)      # the same names, set anew
        losses = []
        for i, feed in enumerate(_batches(8)):
            if walk:
                scope.set('unrelated', 0.0)
            main, loss = (a[0], a[2]) if i % 3 else (b[0], b[2])
            losses.append(np.array(exe.run(main, feed=feed,
                                           fetch_list=[loss],
                                           scope=scope)[0]))
        return losses, _state(scope)

    n0 = _carried()
    carried = run(False)
    # steps 0 3 6 are b's, the rest a's: a runs twice in a row at 1-2,
    # 4-5, 7 follows 6 — two carried steps
    assert _carried() - n0 == 2
    walked = run(True)
    for x, y in zip(carried[0], walked[0]):
        np.testing.assert_array_equal(x, y)
    for n in carried[1]:
        np.testing.assert_array_equal(carried[1][n], walked[1][n], err_msg=n)


@pytest.mark.parametrize('seed,run', [(1, 1), (7, 2), (7, 3), (90210, 1),
                                      (2 ** 31 - 1, 12345), (42, 2 ** 20)])
def test_the_seeded_run_key_has_the_bits_of_the_eager_one(seed, run):
    want = jax.random.fold_in(jax.random.PRNGKey(seed), run)
    got = executor_mod._run_key(seed, run, 99)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_an_unseeded_run_key_is_the_counters():
    got = executor_mod._run_key(0, 5, 17)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.random.PRNGKey(17)))


def test_a_bound_program_on_a_scope_that_also_trains_stages_what_it_reads():
    """A handle over the eval program, its read-only names the very
    parameters the train step writes: every train step moves the handle's
    count, it restages, and reads what `run()` of the same program reads.
    The train step's own record is untouched by the handle's calls."""
    main, startup, loss, test, test_loss = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feeds = _batches()
    bound = exe.bind(test, feeds[0], fetch_list=[test_loss], scope=scope)
    n0 = _carried()
    for feed in feeds:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        got = bound(feeds[0])[0]
        want = exe.run(test, feed=feeds[0], fetch_list=[test_loss],
                       scope=scope)[0]
        np.testing.assert_array_equal(got, want)
    assert bound.restages == STEPS
    # the handle and the eval run write nothing: the train step carries
    assert _carried() - n0 >= STEPS - 1


def test_an_unrelated_handle_does_not_restage_while_the_scope_trains():
    """A handle whose names no train step writes, on the scope a trainer
    trains: `Scope._gen` stands still under the trainer's writes."""
    main, startup, loss, _t, _tl = _programs()
    side, side_start = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard('side_'):
        with fluid.program_guard(side, side_start):
            x = fluid.layers.data(name='x', shape=[16], dtype='float32')
            out = fluid.layers.fc(x, size=4,
                                  param_attr=fluid.ParamAttr(name='side.w'),
                                  bias_attr=fluid.ParamAttr(name='side.b'))
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(side_start, scope=scope)
    feeds = _batches()
    bound = exe.bind(side, {'x': feeds[0]['x']}, fetch_list=[out],
                     scope=scope)
    first = bound({'x': feeds[0]['x']})[0]
    n0 = _carried()
    for feed in feeds:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        np.testing.assert_array_equal(bound({'x': feeds[0]['x']})[0], first)
    assert bound.restages == 0
    assert _carried() - n0 == STEPS - 1


def test_a_host_value_the_scope_cannot_cache_is_never_carried():
    """A read-only leaf the scope holds as a view (uploaded anew every
    run, never cached): a write through the view's base is what the next
    run reads, so no record is kept."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            w = fluid.layers.create_global_var(
                [4], value=1.0, dtype='float32', persistable=True,
                name='carried_view_w')
            out = fluid.layers.elementwise_mul(x, w)
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    base = np.ones((2, 4), 'float32')
    scope.set('carried_view_w', base[0])
    feed = {'x': np.ones((1, 4), 'float32')}
    n0 = _carried()
    assert exe.run(main, feed=feed, fetch_list=[out], scope=scope)[0].sum() \
        == 4.0
    base[0, :] = 3.0
    assert exe.run(main, feed=feed, fetch_list=[out], scope=scope)[0].sum() \
        == 12.0
    assert _carried() - n0 == 0


def test_the_runs_own_rebind_is_the_only_write_a_record_survives():
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feeds = _batches()
    exe.run(main, feed=feeds[0], fetch_list=[loss], scope=scope)
    (entry, rec), = _records(scope).items()
    assert (rec.writes, rec.gen) == (scope._writes, scope._gen)
    # the record's read-written leaves ARE the scope's arrays: no copy
    assert all(v is scope.get(n)
               for n, v in zip(entry.fn.rw_names, rec.rw))
    assert all(v is scope.get(n)
               for n, v in zip(entry.fn.ro_names, rec.ro))
    scope.update({})                    # nothing written: nothing moves
    assert rec.rw and rec.writes == scope._writes
    for write in (lambda: scope.set('unrelated', 0.0),
                  lambda: scope.update({'unrelated': 1.0}),
                  lambda: scope.drop('unrelated')):
        exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
        before = scope._writes, scope._gen
        assert scope._held[entry].rw
        write()
        # every read-written leaf is let go; the read-only ones, whose
        # names nobody wrote, stay
        assert (scope._writes, scope._gen) == (before[0] + 1, before[1])
        assert not any(r.rw for r in scope._held.values())
        assert scope._held[entry].gen == scope._gen
    # a write to a name some record keeps read-only lets every record go
    exe.run(main, feed=feeds[1], fetch_list=[loss], scope=scope)
    assert entry.ro_names and scope._held[entry].rw
    scope.set(entry.ro_names[0], np.asarray(scope.get(entry.ro_names[0])))
    assert not scope._held and scope._gen == before[1] + 1


def test_a_steady_run_looks_no_leaf_up_and_its_cost_a_leaf_stays_small(
        monkeypatch):
    """tools/runoverhead.py's reading at 2 and at 1 200 read-written
    leaves: a guard against the walk coming back, not a timing claim —
    the limits are ten times what this host reads (2–5 us a leaf the
    whole run, 0.1–0.2 its `prepare`; the walk read 1.1–1.5 there)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import runoverhead
    looked_up = []
    real = fluid.Executor._state_value

    def counting(self, scope, name, program, cache=True):
        looked_up.append(name)
        return real(self, scope, name, program, cache=cache)

    monkeypatch.setattr(fluid.Executor, '_state_value', counting)
    n0 = _carried()
    got = runoverhead.measure_rw_slope(rounds=20)
    # each program's first run (it compiles) looks its leaves up: 2 +
    # 1 200 in all, none in the 2 x (2 + 20) runs behind them
    assert len(looked_up) == 2 + 1200
    assert _carried() - n0 == 2 * (2 + 20)
    assert set(got['run_overhead_us_rw']) == {'2', '1200'}
    assert got['run_overhead_us_per_rw_var'] < 50.0
    assert got['run_prepare_us_per_rw_var'] < 2.0


@pytest.mark.parametrize('reduce_mode', [False, True])
def test_the_sharded_entry_lowers_to_the_module_of_the_by_name_jit(
        reduce_mode):
    """The data-parallel entry takes its state flat; the compiled
    program's parameter list — an order is a schedule — is the one jit
    gives the same function called with the state by name."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.core import lowering
    from paddle_tpu.parallel import api, spmd
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    bs = fluid.BuildStrategy()
    if reduce_mode:
        bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    runner = spmd.DataParallelRunner(
        main, loss_name=loss.name, build_strategy=bs,
        places=[fluid.TPUPlace(i) for i in range(4)])
    feed, _lods = exe._prepare_feed(main, _batches(1)[0])
    fetch = [loss.name]
    entry = runner._compile(feed, fetch)
    assert entry.ro_names == tuple(sorted(entry.ro_names))
    assert entry.rw_names == tuple(sorted(entry.rw_names))
    # the state by name, as the runner jitted it before it went in flat
    read, written = lowering.analyze_state(main, fetch)
    needed = fluid.Executor._read_before_write(main, read, written,
                                               set(feed), fetch)
    fn, ro_names, rw_names = lowering.build_fn(
        main, fetch, needed, written, static_lods={}, lod_out={},
        lower_params=runner._strategy_knobs()[0])
    shard = entry.state_shardings
    mesh = runner._mesh
    by_name = jax.jit(
        fn, in_shardings=(entry.feed_shardings,
                          {n: shard[n] for n in ro_names},
                          {n: shard[n] for n in rw_names},
                          NamedSharding(mesh, P())),
        out_shardings=(None, {n: shard[n] for n in written}),
        donate_argnums=(2,))
    ro = {n: scope.get(n) for n in ro_names}
    rw = {n: scope.get(n) for n in rw_names}
    key = jax.random.PRNGKey(0)
    prev = api._ACTIVE_MESH, api._ACTIVE_PARAM_SPEC
    api._ACTIVE_MESH = mesh
    api._ACTIVE_PARAM_SPEC = lambda n: shard[n].spec
    try:
        with mesh:
            want = by_name.lower(feed, ro, rw, key).as_text()
            flat = entry.fn.flat.lower(
                feed, tuple(ro[n] for n in entry.ro_names),
                tuple(rw[n] for n in entry.rw_names), key).as_text()
            named = entry.fn.lower(feed, ro, rw, key).as_text()
    finally:
        api._ACTIVE_MESH, api._ACTIVE_PARAM_SPEC = prev
    assert flat == want
    assert named == want


def test_the_plain_entry_lowers_the_same_flat_and_by_name():
    main, startup, loss, _t, _tl = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _batches(1)[0]
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    (entry, rec), = _records(scope).items()
    ro, rw = rec.ro, rec.rw
    feed, _lods = exe._prepare_feed(main, feed)
    key = jax.random.PRNGKey(0)
    flat = entry.fn.flat.lower(feed, ro, rw, key).as_text()
    named = entry.fn.lower(feed, dict(zip(entry.fn.ro_names, ro)),
                           dict(zip(entry.fn.rw_names, rw)), key).as_text()
    assert flat == named


def test_a_record_goes_with_its_entry():
    """An inference scope nobody writes keeps a record a signature; the
    record does not keep an entry the executor's cache let go."""
    main, startup, loss, test, test_loss = _programs()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _batches(1)[0]
    exe.run(test, feed=feed, fetch_list=[test_loss], scope=scope)
    exe.run(test, feed=feed, fetch_list=[test_loss], scope=scope)
    # the startup program's (its own rebind is the last write) and the
    # eval program's
    assert len(scope._held) == 2
    exe._cache.clear()
    executor_mod._shared_cache.clear()
    gc.collect()
    assert len(scope._held) == 0
