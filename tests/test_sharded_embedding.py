"""Vocab-sharded distributed embedding — the pserver replacement
(reference operators/distributed/parameter_prefetch.cc:177,
transpiler/distribute_transpiler.py:161 lookup-table special path) — and
the distributed op tail (ops/dist_ops.py).
"""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from test_detection_ops import _run_single_op


def _ctr_like(seed, vocab, dim, is_distributed, slots=4):
    """Tiny wide&deep: several sparse id slots -> shared-table embeddings ->
    sum-pool -> fc -> sigmoid loss. Sparse grads + distributed table."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name='ids', shape=[slots], dtype='int64')
        label = fluid.layers.data(name='label', shape=[1], dtype='float32')
        embs = []
        for s in range(slots):
            one = fluid.layers.slice(ids, axes=[1], starts=[s],
                                     ends=[s + 1])
            embs.append(fluid.layers.embedding(
                one, size=[vocab, dim], is_sparse=True,
                is_distributed=is_distributed,
                param_attr=fluid.ParamAttr(name='dist_emb')))
        concat = fluid.layers.concat(embs, axis=1)
        fc = fluid.layers.fc(concat, size=8, act='relu')
        logit = fluid.layers.fc(fc, size=1)
        loss = fluid.layers.mean(
            fluid.layers.sigmoid_cross_entropy_with_logits(logit, label))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _feed(rng, n, vocab, slots=4):
    return {'ids': rng.randint(0, vocab, size=(n, slots)).astype('int64'),
            'label': rng.randint(0, 2, size=(n, 1)).astype('float32')}


def test_distributed_embedding_matches_serial():
    """MeshRunner over (data=2, model=4) with the vocab-sharded table must
    reproduce the single-device loss trajectory AND grads (the sgd update
    is part of the trajectory)."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner
    vocab, dim = 64, 8
    rng = np.random.RandomState(0)
    feeds = [_feed(np.random.RandomState(i), 8, vocab) for i in range(4)]
    exe = fluid.Executor()

    main, startup, loss = _ctr_like(7, vocab, dim, is_distributed=False)
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = [float(exe.run(main, feed=f, fetch_list=[loss],
                             scope=s1)[0].reshape(())) for f in feeds]
        ref_table = np.asarray(s1.get('dist_emb'))

    main2, startup2, loss2 = _ctr_like(7, vocab, dim, is_distributed=True)
    t = fluid.transpiler.DistributeTranspiler()
    t.transpile(trainer_id=0, program=main2,
                pservers=','.join('h:%d' % i for i in range(4)), trainers=2)
    rules = t.sharding_plan.rules
    assert rules.spec_for('dist_emb') == P('model', None)
    mesh = make_mesh([('data', 2), ('model', 4)])
    runner = MeshRunner(main2, mesh, param_rules=rules,
                        feed_specs={'ids': P('data'), 'label': P('data')})
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        got = [float(runner.run(f, [loss2.name], s2)[0].reshape(()))
               for f in feeds]
        table = s2.get('dist_emb')
        # the table state stays sharded over 'model' between steps: each
        # device holds a [vocab/4, dim] slice, not the full table
        assert isinstance(table, jax.Array)
        starts = {idx[0].start or 0 for idx in
                  (sh.index for sh in table.addressable_shards)}
        assert len(starts) == 4, starts
        got_table = np.asarray(table)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_table, ref_table, rtol=1e-5, atol=1e-6)


def test_distributed_embedding_big_vocab_compiles():
    """A table sharded over model=8 with per-shard slices well under the
    full size — the giant-embedding use case (dryrun uses V>=1M; here a
    smaller stand-in keeps CI fast while still proving the sharded path)."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner
    vocab, dim = 4096, 16
    main, startup, loss = _ctr_like(3, vocab, dim, is_distributed=True)
    mesh = make_mesh([('data', 1), ('model', 8)])
    runner = MeshRunner(main, mesh,
                        param_rules=[(r'^dist_emb$', P('model', None))],
                        feed_specs={'ids': P(), 'label': P()})
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        f = _feed(np.random.RandomState(1), 8, vocab)
        l0 = float(runner.run(f, [loss.name], scope)[0].reshape(()))
        l1 = float(runner.run(f, [loss.name], scope)[0].reshape(()))
    assert np.isfinite([l0, l1]).all()
    assert l1 < l0          # sgd applied through the sharded scatter


def test_sharded_scatter_concat_partitioner():
    """Raw-jax check, no paddle_tpu machinery: a scatter-add whose
    indices/updates CONCAT batch-sharded vectors into a dim-0-sharded
    operand partitions correctly (an older XLA SPMD partitioner misplaced
    shard-0 updates; core/lowering.py still pins the concatenated
    SelectedRows rows/values replicated — ROADMAP D1 drops that pin)."""
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    vocab, dim, slots, batch = 64, 8, 4, 8
    rng = np.random.RandomState(0)
    w0 = rng.randn(vocab, dim).astype('float32')
    ids = rng.randint(0, vocab, (batch, slots)).astype('int32')
    lab = rng.randint(0, 2, (batch, 1)).astype('float32')

    def step(w, ids, lab):
        sites = [ids[:, s].reshape(-1) for s in range(slots)]
        vals = [jnp.take(w, s_, axis=0) * lab for s_ in sites]
        rows = jnp.concatenate(sites)
        v = jnp.concatenate(vals)
        return w.at[rows].add(-0.1 * v, mode='drop')

    ref = jax.jit(step)(w0, ids, lab)
    devs = np.array(jax.devices()).reshape(2, 4)
    with Mesh(devs, ('data', 'model')) as mesh:
        sh_w = NamedSharding(mesh, P('model', None))
        sh_b = NamedSharding(mesh, P('data', None))
        got = jax.jit(step, in_shardings=(sh_w, sh_b, sh_b),
                      out_shardings=sh_w)(
            jax.device_put(w0, sh_w), jax.device_put(ids, sh_b),
            jax.device_put(lab, sh_b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# op tail
# ---------------------------------------------------------------------------

def test_split_ids_merge_ids_roundtrip():
    """split_ids -> per-shard lookup -> merge_ids == direct lookup (the
    parameter_prefetch.cc:177 pipeline, static-shape layout)."""
    from paddle_tpu.framework import Program, program_guard
    vocab, dim, n_shard = 12, 4, 3
    rng = np.random.RandomState(2)
    ids = rng.randint(0, vocab, size=(9, 1)).astype('int64')
    table = rng.randn(vocab, dim).astype('float32')

    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        block = prog.global_block()
        v_ids = block.create_var(name='Ids', shape=ids.shape, dtype='int64')
        v_w = block.create_var(name='W', shape=table.shape, dtype='float32')
        split_outs = [block.create_var(name='split_%d' % k, dtype='int64')
                      for k in range(n_shard)]
        block.append_op(type='split_ids', inputs={'Ids': [v_ids]},
                        outputs={'Out': split_outs}, attrs={})
        # per-shard lookup: shard k owns rows with id % n_shard == k; the
        # masked layout keeps positions, sentinel -1 clamps harmlessly
        xs = []
        for k in range(n_shard):
            xk = block.create_var(name='x_%d' % k, dtype='float32')
            block.append_op(
                type='lookup_sparse_table',
                inputs={'W': [v_w], 'Ids': [split_outs[k]]},
                outputs={'Out': [xk]}, attrs={})
            xs.append(xk)
        merged = block.create_var(name='merged', dtype='float32')
        block.append_op(type='merge_ids',
                        inputs={'Ids': [v_ids], 'Rows': split_outs,
                                'X': xs},
                        outputs={'Out': [merged]}, attrs={})
    exe = fluid.Executor()
    out, = exe.run(prog, feed={'Ids': ids, 'W': table},
                   fetch_list=['merged'])
    np.testing.assert_allclose(out, table[ids.reshape(-1)], rtol=1e-6)


def test_split_selected_rows():
    from paddle_tpu.core.selected_rows import SelectedRows
    from paddle_tpu.ops.dist_ops import _split_selected_rows  # noqa: F401
    rows = jnp.asarray([7, 5, 7, 3, 0], jnp.int32)
    vals = jnp.asarray(np.arange(10).reshape(5, 2).astype('float32'))
    sr = SelectedRows(rows, vals, height=12)

    # run the lowering directly on a tiny fake ctx
    class _Op(object):
        type = 'split_selected_rows'

        def input(self, slot):
            return ['x'] if slot == 'X' else []

        def output(self, slot):
            return ['o0', 'o1'] if slot == 'Out' else []

        def attr(self, name, default=None):
            return [4, 8] if name == 'height_sections' else default

    class _Ctx(object):
        env = {'x': sr}

        def get(self, n):
            return self.env[n]

        def set(self, n, v):
            self.env[n] = v

    ctx = _Ctx()
    _split_selected_rows(ctx, _Op())
    o0, o1 = ctx.env['o0'], ctx.env['o1']
    assert o0.height == 4 and o1.height == 8
    dense = np.zeros((12, 2), 'float32')
    for r, v in zip(np.asarray(rows), np.asarray(vals)):
        dense[r] += v
    np.testing.assert_allclose(np.asarray(o0.to_dense()), dense[:4])
    np.testing.assert_allclose(np.asarray(o1.to_dense()), dense[4:])


def test_split_byref():
    x = np.arange(24).reshape(6, 4).astype('float32')
    outs = _run_single_op('split_byref', {'X': x},
                          {'Out': ['sb0', 'sb1']},
                          {'sections': [2, 4]})
    np.testing.assert_allclose(outs[0], x[:2])
    np.testing.assert_allclose(outs[1], x[2:])


def test_ref_by_trainer_id():
    xs = [np.full((2, 3), float(i), 'float32') for i in range(4)]
    out, = _run_single_op(
        'ref_by_trainer_id',
        {'X': xs, 'TrainerId': np.asarray([2], 'int64')},
        {'Out': ['rbt']}, {})
    np.testing.assert_allclose(out, xs[2])


def test_fake_init():
    out, = _run_single_op('fake_init', {}, {'Out': ['fi']},
                          {'shape': [3, 5]})
    assert out.shape == (3, 5)
    assert (out == 0).all()


def test_checkpoint_notify_saves_persistables():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='cnx', shape=[4], dtype='float32')
        y = fluid.layers.fc(x, size=2)
    with tempfile.TemporaryDirectory() as d:
        ckpt = os.path.join(d, 'ck')
        main.global_block().append_op(
            type='checkpoint_notify', inputs={}, outputs={},
            attrs={'dir': ckpt, 'epmap': [], 'lookup_table': '',
                   'trainer_id': 0})
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            exe.run(main, feed={'cnx': np.ones((2, 4), 'float32')},
                    fetch_list=[y], scope=scope)
        assert os.path.isdir(ckpt) and os.listdir(ckpt)


def test_conv2d_fusion_matches_unfused():
    rng = np.random.RandomState(4)
    x = rng.randn(2, 3, 8, 8).astype('float32')
    w = rng.randn(6, 3, 3, 3).astype('float32')
    b = rng.randn(6).astype('float32')
    res = rng.randn(2, 6, 8, 8).astype('float32')
    out, = _run_single_op(
        'conv2d_fusion',
        {'Input': x, 'Filter': w, 'Bias': b, 'ResidualData': res},
        {'Output': ['cf_out']},
        {'strides': [1, 1], 'paddings': [1, 1], 'dilations': [1, 1],
         'groups': 1, 'activation': 'relu'})
    conv, = _run_single_op(
        'conv2d', {'Input': x, 'Filter': w}, {'Output': ['c_out']},
        {'strides': [1, 1], 'paddings': [1, 1], 'dilations': [1, 1],
         'groups': 1})
    ref = np.maximum(conv + res + b.reshape(1, -1, 1, 1), 0)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_conv2d_fusion_split_channels():
    rng = np.random.RandomState(5)
    x = rng.randn(1, 2, 4, 4).astype('float32')
    w = rng.randn(6, 2, 1, 1).astype('float32')
    b = np.zeros(6, 'float32')
    outs = _run_single_op(
        'conv2d_fusion', {'Input': x, 'Filter': w, 'Bias': b},
        {'Output': ['cfs_out'], 'Outputs': ['cfs_a', 'cfs_b']},
        {'strides': [1, 1], 'paddings': [0, 0], 'dilations': [1, 1],
         'groups': 1, 'activation': 'identity',
         'split_channels': [2, 4]})
    full = outs[0]
    np.testing.assert_allclose(outs[1], full[:, :2])
    np.testing.assert_allclose(outs[2], full[:, 2:])


def test_conv2d_inception_fusion():
    """Output channel count follows the reference InferShape
    (fusion_conv_inception_op.cc:40-48) and equals the hand-composed
    branch graph."""
    rng = np.random.RandomState(6)
    n, c, h, wd = 2, 8, 6, 6
    x = rng.randn(n, c, h, wd).astype('float32') * 0.1
    # f0: pool->1x1 (oc0=4); f1: 1x1 (8 out, of which oc1 = 8 - 2*2 = 4
    # to output, 4 feed the grouped 3x3); f2: 3x3 groups=2, ic=2, oc=6
    # (oc2 = 6 - f3_ic); f3: 3x3 ic=3, oc3=5
    f0 = rng.randn(4, c, 1, 1).astype('float32') * 0.1
    f1 = rng.randn(8, c, 1, 1).astype('float32') * 0.1
    f2 = rng.randn(6, 2, 3, 3).astype('float32') * 0.1
    f3 = rng.randn(5, 3, 3, 3).astype('float32') * 0.1
    bs = [np.zeros(k, 'float32') for k in (4, 8, 6, 5)]
    out, t0, t1 = _run_single_op(
        'conv2d_inception_fusion',
        {'Input': x, 'Filter': [f0, f1, f2, f3], 'Bias': bs},
        {'Output': ['inc_out'], 'TempOutput': ['inc_t0', 'inc_t1']},
        {'pooling_type': 'avg', 'exclusive': True, 'activation': 'relu'})
    oc = 4 + (8 - 2 * 2) + (6 - 3) + 5
    assert out.shape == (n, oc, h, wd)
    assert np.isfinite(out).all()
    # relu output, branches active
    assert (out >= 0).all() and out.max() > 0
