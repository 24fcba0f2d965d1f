"""BuildStrategy fidelity under the SPMD data-parallel runner (reference
unittests/test_parallel_executor_* reduce-vs-allreduce / gradient-scale
comparisons, details/build_strategy.h:34-96)."""
import numpy as np
import pytest

import paddle_tpu as fluid


def _build(seed=11, lr=0.1, optimizer='sgd'):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=32, act='relu')
        p = fluid.layers.fc(h, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        if optimizer == 'adam':
            fluid.optimizer.Adam(lr * 0.1, fuse=False).minimize(loss)
        else:
            fluid.optimizer.SGD(lr).minimize(loss)
    return main, startup, loss


def _data():
    rng = np.random.RandomState(0)
    X = rng.randn(64, 16).astype('float32')
    Y = rng.randint(0, 4, (64, 1)).astype('int64')
    return X, Y


def _run(build_strategy, seed=11, lr=0.1, steps=4, optimizer='sgd',
         places=None):
    X, Y = _data()
    main, startup, loss = _build(seed=seed, lr=lr, optimizer=optimizer)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        compiled = main if build_strategy is None else \
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name, build_strategy=build_strategy,
                places=places)
        return [float(np.asarray(exe.run(
            compiled, feed={'x': X, 'y': Y}, fetch_list=[loss],
            scope=scope)[0]).reshape(())) for _ in range(steps)]


def _adam_forms(before):
    from paddle_tpu import monitor
    return {k.split('=')[1].rstrip('}'): v
            for k, v in monitor.counter_delta(before).items()
            if k.startswith('adam_update_form_total')}


@pytest.mark.parametrize('optimizer,ndev', [('sgd', None), ('adam', 4)])
def test_reduce_matches_allreduce(optimizer, ndev, monkeypatch):
    """Reduce mode (params sharded over 'data', reference
    ReduceSSAGraphBuilder) must be numerically identical to AllReduce —
    under SGD on every device, and under Adam on a 4-device mesh. There a
    gradient leaves a collective, not a GEMM, and the `adam` op leaves
    every update to XLA (`form=inline`; on one device the two matrices'
    are passes of their own once they are large enough,
    ops/optimizer_ops.py `_own_pass`): the sharded step gives the losses
    the one-device step gives."""
    from paddle_tpu import monitor
    from paddle_tpu.ops import optimizer_ops
    monkeypatch.setattr(optimizer_ops, '_OWN_PASS_MIN_ELEMENTS', 0)
    places = ndev and [fluid.TPUPlace(i) for i in range(ndev)]
    bs_all = fluid.BuildStrategy()
    bs_red = fluid.BuildStrategy()
    bs_red.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    ref = _run(bs_all, optimizer=optimizer, places=places)
    before = monitor.counters()
    red = _run(bs_red, optimizer=optimizer, places=places)
    np.testing.assert_allclose(red, ref, rtol=1e-5, atol=1e-6)
    if optimizer == 'adam':
        assert _adam_forms(before) == {'inline': 4}     # 2 weights, 2 biases
        before = monitor.counters()
        one = _run(None, optimizer=optimizer)
        assert _adam_forms(before) == {'own_pass': 2, 'inline': 2}
        np.testing.assert_allclose(red, one, rtol=1e-5, atol=1e-6)
        assert red[-1] < red[0]


def test_gradient_scale_one_equals_lr_times_ndev():
    """GradientScaleStrategy.One seeds the loss grad with 1 per device
    (vs 1/N): every gradient is num_devices times larger, so training with
    One at lr == training with CoeffNumDevice at lr * ndev."""
    import jax
    ndev = len(jax.devices())
    bs_one = fluid.BuildStrategy()
    bs_one.gradient_scale_strategy = \
        fluid.BuildStrategy.GradientScaleStrategy.One
    one = _run(bs_one, lr=0.01)
    coeff = _run(fluid.BuildStrategy(), lr=0.01 * ndev)
    np.testing.assert_allclose(one, coeff, rtol=1e-4, atol=1e-5)


def test_customized_scale_errors_loudly():
    bs = fluid.BuildStrategy()
    bs.gradient_scale_strategy = \
        fluid.BuildStrategy.GradientScaleStrategy.Customized
    with pytest.raises(NotImplementedError, match="Customized"):
        _run(bs)


def test_reduce_mode_shards_state_memory():
    """ZeRO contract: under Reduce mode the per-device shard of parameter
    and optimizer state is smaller than the full value; a param whose dim0
    is indivisible shards along another divisible axis instead of silently
    replicating (reference multi_devices_graph_pass.cc:594 balances whole
    params; the sharded analog must actually save memory)."""
    import jax
    X, _ = _data()
    Y = np.random.RandomState(1).randint(0, 4, (64, 1)).astype('int64')
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        # dim0=13 indivisible by 8 devices; dim1=64 divisible -> axis 1
        h = fluid.layers.fc(x, size=13, act='relu')
        h = fluid.layers.fc(h, size=64, act='relu')
        p = fluid.layers.fc(h, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.Momentum(0.1, momentum=0.9).minimize(loss)
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)
        exe.run(compiled, feed={'x': X, 'y': Y}, fetch_list=[loss],
                scope=scope)
        ndev = len(jax.devices())
        assert ndev == 8
        sharded = checked = 0
        for p_ in main.all_parameters():
            for name in (p_.name, p_.name + '_velocity_0'):
                v = scope.get(name)
                if not isinstance(v, jax.Array) or v.size < 64:
                    continue
                checked += 1
                shard = v.addressable_shards[0].data
                if int(np.prod(shard.shape)) * ndev == v.size:
                    sharded += 1
        # every large param/velocity with any divisible axis is sharded:
        # fc weights [16,13] (no divisible axis -> replicated is allowed),
        # [13,64] and [64,4]... dim checks below pin the key case
        w13_64 = next(p_.name for p_ in main.all_parameters()
                      if tuple(p_.shape) == (13, 64))
        v_ = scope.get(w13_64)
        shard_shape = v_.addressable_shards[0].data.shape
        assert tuple(shard_shape) == (13, 8), shard_shape  # axis-1 sharded
        assert sharded >= 2, (sharded, checked)


def test_reduce_mode_warns_on_forced_replication():
    """A large variable with no divisible axis must warn, not silently
    replicate."""
    import warnings as _w
    X = np.random.RandomState(0).randn(64, 17).astype('float32')
    Y = np.random.RandomState(1).randint(0, 3, (64, 1)).astype('int64')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[17], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=61, act='relu')   # [17,61]: no axis /8
        p = fluid.layers.fc(h, size=3, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    bs = fluid.BuildStrategy()
    bs.reduce_strategy = fluid.BuildStrategy.ReduceStrategy.Reduce
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        compiled = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, build_strategy=bs)
        with _w.catch_warnings(record=True) as rec:
            _w.simplefilter('always')
            exe.run(compiled, feed={'x': X, 'y': Y}, fetch_list=[loss],
                    scope=scope)
        msgs = [str(r.message) for r in rec
                if issubclass(r.category, RuntimeWarning)]
        assert any('no axis divisible' in m for m in msgs), msgs
