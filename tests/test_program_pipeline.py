"""Program-level pipeline parallelism: PipelineTranspiler + gpipe_run
(VERDICT r3 #9 — auto-split a Program at layer boundaries, train the
flagship LM under mesh(pipe=4) from the fluid API)."""
import numpy as np
import pytest
import jax

import paddle_tpu as fluid


def _lm(seed, n_layer=4, flash=False):
    from paddle_tpu.models.transformer import build_lm, LMConfig
    cfg = LMConfig(vocab_size=128, seq_len=16, d_model=32, n_head=4,
                   n_layer=n_layer, d_ff=64, dropout=0.0, attn_dropout=0.0,
                   use_flash_attention=flash)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        tokens, labels, logits, avg_loss = build_lm(cfg)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_loss)
    return main, startup, avg_loss, cfg


def _feeds(cfg, batch, n):
    rng = np.random.RandomState(0)
    return [{'tokens': rng.randint(0, cfg.vocab_size,
                                   (batch, cfg.seq_len)).astype('int64'),
             'labels': rng.randint(0, cfg.vocab_size,
                                   (batch, cfg.seq_len)).astype('int64')}
            for _ in range(n)]


def test_transpiler_detects_layer_run():
    main, startup, loss, cfg = _lm(3)
    t = fluid.transpiler.PipelineTranspiler()
    t.transpile(main, num_stages=2)
    assert t.plan['n_layers'] == 4
    types = [op.type for op in main.global_block().ops]
    assert types.count('gpipe_run') == 1


@pytest.mark.slow
def test_serial_fallback_matches_original():
    """The rewritten program without a pipe mesh must reproduce the
    original loss trajectory exactly (same math, same op order).

    @slow (ISSUE 11 budget shave, ~37 s): two full LM trainings; the
    transpile structure stays covered by test_transpile_partitions_lm
    and the mesh trajectory by the moe/gpipe tier-1 tests."""
    feeds = None
    losses = {}
    for pipelined in (False, True):
        main, startup, loss, cfg = _lm(7)
        if feeds is None:
            feeds = _feeds(cfg, 8, 3)
        if pipelined:
            fluid.transpiler.PipelineTranspiler().transpile(main,
                                                            num_stages=2)
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            losses[pipelined] = [
                float(exe.run(main, feed=f, fetch_list=[loss],
                              scope=scope)[0].reshape(())) for f in feeds]
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_pipeline_mesh_matches_serial():
    """mesh(pipe=4) microbatch pipeline == serial trajectory (fwd + bwd +
    Adam; the reverse pipeline comes from jax.vjp through the schedule).

    @slow (ISSUE 11 budget shave, ~31 s): tier-1 keeps the pipe-mesh
    trajectory via test_program_pipeline_engages_batch_axis and the
    gpipe tests in test_pipeline_moe.py."""
    from paddle_tpu.parallel import make_mesh, MeshRunner

    main, startup, loss, cfg = _lm(11)
    feeds = _feeds(cfg, 8, 3)
    exe = fluid.Executor()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = [float(exe.run(main, feed=f, fetch_list=[loss],
                             scope=s1)[0].reshape(())) for f in feeds]

    main2, startup2, loss2, _ = _lm(11)
    fluid.transpiler.PipelineTranspiler().transpile(main2, num_stages=4)
    mesh = make_mesh([('pipe', 4)])
    runner = MeshRunner(main2, mesh)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        got = [float(runner.run(f, [loss2.name], s2)[0].reshape(()))
               for f in feeds]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_pipeline_flash_attention_variant():
    """The flash-attention LM (the flagship config's op mix) also splits
    and loss-matches under the pipeline.

    @slow (ISSUE 11 budget shave, ~27 s): flash-under-mesh stays tier-1
    covered by tests/test_attention.py::test_spmd_shard_map_kernel."""
    from paddle_tpu.parallel import make_mesh, MeshRunner

    main, startup, loss, cfg = _lm(13, flash=True)
    feeds = _feeds(cfg, 4, 2)
    exe = fluid.Executor()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = [float(exe.run(main, feed=f, fetch_list=[loss],
                             scope=s1)[0].reshape(())) for f in feeds]

    main2, startup2, loss2, _ = _lm(13, flash=True)
    fluid.transpiler.PipelineTranspiler().transpile(main2, num_stages=2)
    runner = MeshRunner(main2, make_mesh([('pipe', 2)]))
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        got = [float(runner.run(f, [loss2.name], s2)[0].reshape(()))
               for f in feeds]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def _two_stream(seed, n_layer=4, hid=8):
    """A layer run whose boundary carries TWO tensors (h, c) — the shape
    the round-4 single-crossing rule rejected (e.g. decoder h/c pairs,
    separately-materialized residual + branch)."""
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[hid], dtype='float32')
        c0 = fluid.layers.data(name='c0', shape=[hid], dtype='float32')
        # the entry boundary must be produced vars (feeds can't stream)
        h = fluid.layers.scale(x, scale=1.0, bias=0.1)
        c = fluid.layers.scale(c0, scale=1.0, bias=-0.1)
        for k in range(n_layer):
            z = fluid.layers.fc(h, size=hid, bias_attr=False,
                                param_attr='tw%d' % k)
            h = fluid.layers.tanh(fluid.layers.elementwise_add(z, c))
            c = fluid.layers.elementwise_add(
                c, fluid.layers.scale(h, scale=0.5))
        loss = fluid.layers.mean(fluid.layers.square(
            fluid.layers.elementwise_add(h, c)))
    return main, startup, loss, hid


def test_two_tensor_boundary_detected_and_serial_matches():
    """K=2 crossing activations per boundary (VERDICT r4 #6): the
    transpiler must detect the run, and the rewritten program must
    reproduce the original exactly without a mesh."""
    rng = np.random.RandomState(0)
    feeds = None
    outs = {}
    for pipelined in (False, True):
        main, startup, loss, hid = _two_stream(21)
        if feeds is None:
            feeds = [{'x': rng.randn(8, hid).astype('float32'),
                      'c0': rng.randn(8, hid).astype('float32')}
                     for _ in range(2)]
        if pipelined:
            t = fluid.transpiler.PipelineTranspiler()
            t.transpile(main, num_stages=2)
            assert t.plan['n_layers'] == 4
            assert t.plan['n_crossing'] == 2
            types = [op.type for op in main.global_block().ops]
            assert types.count('gpipe_run') == 1
        exe = fluid.Executor()
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            outs[pipelined] = [
                float(exe.run(main, feed=f, fetch_list=[loss],
                              scope=scope)[0].reshape(())) for f in feeds]
    np.testing.assert_allclose(outs[True], outs[False],
                               rtol=1e-6, atol=1e-7)


def test_two_tensor_boundary_mesh_matches_serial():
    """The (h, c) pair streams through mesh(pipe=2) as a tuple; results
    must match the serial run."""
    from paddle_tpu.parallel import make_mesh, MeshRunner

    rng = np.random.RandomState(3)
    main, startup, loss, hid = _two_stream(23)
    feeds = [{'x': rng.randn(8, hid).astype('float32'),
              'c0': rng.randn(8, hid).astype('float32')}
             for _ in range(2)]
    exe = fluid.Executor()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = [float(exe.run(main, feed=f, fetch_list=[loss],
                             scope=s1)[0].reshape(())) for f in feeds]

    main2, startup2, loss2, _ = _two_stream(23)
    fluid.transpiler.PipelineTranspiler().transpile(main2, num_stages=2)
    runner = MeshRunner(main2, make_mesh([('pipe', 2)]))
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        got = [float(runner.run(f, [loss2.name], s2)[0].reshape(()))
               for f in feeds]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def test_pipeline_rejects_indivisible_stages():
    main, startup, loss, cfg = _lm(5, n_layer=3)
    with pytest.raises(ValueError, match='divide'):
        fluid.transpiler.PipelineTranspiler().transpile(main, num_stages=2)


@pytest.mark.slow
def test_pipeline_composes_with_data_parallel():
    """mesh(data=2, pipe=4): each data replica runs the full microbatch
    pipeline over its batch shard, grads psum over 'data' — the
    trajectory must still equal the serial run exactly.

    @slow (ISSUE 11 budget shave, ~18 s): the minimized
    test_gpipe_2axis_mesh_lowering_jit_matches_serial (~2 s) below keeps
    the same jit x shard_map composition in tier-1."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner

    main, startup, loss, cfg = _lm(17)
    feeds = _feeds(cfg, 8, 3)
    exe = fluid.Executor()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = [float(exe.run(main, feed=f, fetch_list=[loss],
                             scope=s1)[0].reshape(())) for f in feeds]

    main2, startup2, loss2, _ = _lm(17)
    fluid.transpiler.PipelineTranspiler().transpile(main2, num_stages=4)
    mesh = make_mesh([('data', 2), ('pipe', 4)])
    runner = MeshRunner(main2, mesh,
                        feed_specs={'tokens': P('data'),
                                    'labels': P('data')})
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        got = [float(np.asarray(runner.run(f, [loss2.name], s2)[0]
                                ).reshape(())) for f in feeds]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)


def _lowered_gpipe_fn(num_stages=4, hid=8, n_layer=4, seed=31):
    """Minimized LOWERING-LEVEL harness for the gpipe-under-2-axis-mesh
    divergence (ROADMAP open item): a 4-layer fc/tanh stack — no
    attention, no optimizer, no MeshRunner — transpiled to one gpipe_run
    and lowered with core.lowering.build_fn. Returns (fn, feed, state,
    serial_loss): calling fn under an active mesh(data=2, pipe=4)
    reproduces (or refutes) the bug in ~2 s instead of the full LM
    compose test."""
    from paddle_tpu.core import lowering

    def build():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = seed
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name='x', shape=[hid], dtype='float32')
            h = fluid.layers.scale(x, scale=1.0, bias=0.1)
            for k in range(n_layer):
                z = fluid.layers.fc(h, size=hid, bias_attr=False,
                                    param_attr='gplow_w%d' % k)
                h = fluid.layers.tanh(z)
            loss = fluid.layers.mean(fluid.layers.square(h))
        return main, startup, loss

    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(8, hid).astype('float32')}
    exe = fluid.Executor()

    main, startup, loss = build()
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(startup, scope=s1)
        ref = float(exe.run(main, feed=feed, fetch_list=[loss],
                            scope=s1)[0].reshape(()))

    main2, startup2, loss2 = build()
    fluid.transpiler.PipelineTranspiler().transpile(main2,
                                                    num_stages=num_stages)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(startup2, scope=s2)
        state = {n: np.asarray(s2.get(n)) for n in s2.names()}

    fetch = [loss2.name]
    read, written = lowering.analyze_state(main2, fetch)
    needed = fluid.Executor._read_before_write(main2, read, written,
                                               {'x'}, fetch)

    def call(wrap):
        from paddle_tpu.parallel import make_mesh
        from paddle_tpu.parallel import api as papi
        mesh = make_mesh([('data', 2), ('pipe', num_stages)])
        prev = papi._ACTIVE_MESH
        papi._ACTIVE_MESH = mesh      # what MeshRunner.run sets up
        try:
            fn, ro_names, rw_names = lowering.build_fn(
                main2, fetch, needed, written)
            ro = {n: state[n] for n in ro_names}
            rw = {n: state[n] for n in rw_names}
            with mesh:
                fetches, _ = wrap(fn)(feed, ro, rw, jax.random.PRNGKey(0))
        finally:
            papi._ACTIVE_MESH = prev
        return float(np.asarray(fetches[0]).reshape(()))

    return call, ref


def test_gpipe_2axis_mesh_lowering_eager_is_exact():
    """Control for the jit test below: the SAME lowered gpipe_run under
    the SAME mesh(data=2, pipe=4), called eagerly (no surrounding jit),
    is exact."""
    call, ref = _lowered_gpipe_fn()
    got = call(lambda fn: fn)
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-7)


def test_gpipe_2axis_mesh_lowering_jit_matches_serial():
    """jax.jit of a program whose gpipe_run lowers through shard_map
    (manual over {'pipe', 'data'}) under mesh(data=2, pipe=4) computes
    the serial forward."""
    call, ref = _lowered_gpipe_fn()
    got = call(jax.jit)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_program_pipeline_engages_batch_axis(monkeypatch):
    """The gpipe_run lowering must actually pass batch_axis='data' under
    a data x pipe mesh — trajectory equality alone cannot distinguish a
    genuinely sharded composition from silent full-batch replication."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner
    from paddle_tpu.parallel import pipeline as pipeline_mod

    captured = {}
    real_gpipe = pipeline_mod.gpipe

    def spy(*args, **kw):
        captured['batch_axis'] = kw.get('batch_axis')
        return real_gpipe(*args, **kw)

    # the lowering imports gpipe from parallel.pipeline at call time
    monkeypatch.setattr(pipeline_mod, 'gpipe', spy)

    main, startup, loss, cfg = _lm(19)
    fluid.transpiler.PipelineTranspiler().transpile(main, num_stages=4)
    mesh = make_mesh([('data', 2), ('pipe', 4)])
    runner = MeshRunner(main, mesh,
                        feed_specs={'tokens': P('data'),
                                    'labels': P('data')})
    s = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(s):
        exe.run(startup, scope=s)
        f = _feeds(cfg, 8, 1)[0]
        out, = runner.run(f, [loss.name], s)
    assert np.isfinite(float(np.asarray(out).reshape(-1)[0]))
    assert captured.get('batch_axis') == 'data', captured
