"""Mesh-partitioned fused kernels (ISSUE 11 tentpole).

Contracts pinned here:
- every fused unit (fused CE / fused_adam / layernorm+residual; the
  embedding lookup is XLA's own gather since PR 33) dispatches a
  PARTITIONED pallas-or-interpret impl under an active >1-device mesh — `fused_kernel_dispatch_total` advances
  with `mesh=n` and `impl=interpret`, not the xla fallback;
- kernel-level parity vs the unfused reference under mesh(data=2) AND
  mesh(data=2, model=2) — forward and gradients (incl. the lse-aware
  all-reduce of the vocab-sharded CE and the psum'd cotangents of
  replicated tables/scales);
- sharded-LM trajectory parity: under mesh(data=2) the fused program at
  tier 'off' BITWISE matches the unfused program (the parity anchor
  holds under a mesh), and the interpret tier (real pallas kernels per
  shard) tracks the same trajectory allclose; the @slow variant adds
  mesh(data=2, model=2) and the unsharded-pallas cross-check;
- the per-op fallback chain still degrades per shard: shapes that no
  longer tile AFTER partitioning fall back pallas -> xla (counted with
  mesh=n).
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.parallel import api as papi


def _mesh(shape, axes):
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), axes)


MESHES = [((2,), ('data',)), ((2, 2), ('data', 'model'))]


# ---------------------------------------------------------------------------
# kernel-level parity under both mesh shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('shape,axes', MESHES)
def test_spmd_ce_parity_and_grad(shape, axes):
    from paddle_tpu.ops.ce_ops import fused_softmax_ce_spmd
    from paddle_tpu.ops.nn_ops import _ce_hard
    rng = np.random.RandomState(0)
    n, v = 256, 512
    x = jnp.asarray((rng.randn(n, v) * 3).astype('float32'))
    lab = rng.randint(0, v, n).astype('int32')
    lab[5] = -100                                    # ignored row
    lab = jnp.asarray(lab)
    w = jnp.arange(n, dtype=jnp.float32)
    ref = _ce_hard(x, lab, -100)
    gref = jax.grad(lambda z: jnp.sum(_ce_hard(z, lab, -100) * w))(x)
    mesh = _mesh(shape, axes)
    got = fused_softmax_ce_spmd(x, lab, mesh, -100, 'interpret')
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert float(got[5]) == 0.0
    gg = jax.grad(lambda z: jnp.sum(
        fused_softmax_ce_spmd(z, lab, mesh, -100, 'interpret') * w))(x)
    scale = np.abs(np.asarray(gref)).max()
    np.testing.assert_allclose(np.asarray(gg), np.asarray(gref),
                               atol=5e-6 * max(scale, 1.0))
    assert np.abs(np.asarray(gg)[5]).max() == 0.0


@pytest.mark.parametrize('table', ['replicated', 'sharded'])
@pytest.mark.parametrize('shape,axes', MESHES)
def test_spmd_embedding_gather_parity_and_grad(shape, axes, table):
    """The lookup under a mesh is the same XLA gather (PR 33: no
    shard_map'ped kernel): ids over 'data', the table replicated or its
    vocabulary sharded over the last mesh axis — the SPMD partitioner
    splits it either way; rows bitwise, the scatter-add gradient and the
    bias's allclose against the unsharded run."""
    from jax.sharding import NamedSharding
    from paddle_tpu.ops.embedding_ops import embedding_gather
    rng = np.random.RandomState(1)
    w = jnp.asarray(rng.randn(64, 128).astype('float32'))
    ids = jnp.asarray(rng.randint(0, 64, 40).astype('int32'))
    bias = jnp.asarray(rng.randn(128).astype('float32'))

    def loss(wv, iv, bv):
        return jnp.sum(embedding_gather(wv, iv, bv) ** 2)

    ref = embedding_gather(w, ids, bias)
    gw_r, gb_r = jax.grad(loss, argnums=(0, 2))(w, ids, bias)
    mesh = _mesh(shape, axes)
    w_spec = P() if table == 'replicated' else P(axes[-1], None)
    put = [NamedSharding(mesh, s) for s in (w_spec, P('data'), P())]
    args = [jax.device_put(x, s) for x, s in zip((w, ids, bias), put)]
    got = jax.jit(embedding_gather)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    gw_g, gb_g = jax.jit(jax.grad(loss, argnums=(0, 2)))(*args)
    np.testing.assert_allclose(np.asarray(gw_g), np.asarray(gw_r),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gb_g), np.asarray(gb_r),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize('shape,axes', MESHES)
def test_spmd_ln_residual_parity_and_grad(shape, axes):
    from paddle_tpu.ops.nn_ops import fused_ln_residual_spmd
    rng = np.random.RandomState(2)
    n, d = 64, 128
    x = jnp.asarray(rng.randn(n, d).astype('float32'))
    r = jnp.asarray(rng.randn(n, d).astype('float32'))
    sc = jnp.asarray(rng.randn(d).astype('float32'))
    b = jnp.asarray(rng.randn(d).astype('float32'))
    eps = 1e-5

    def ref_fn(x, r, sc, b):
        s = x + r
        m = jnp.mean(s, axis=-1, keepdims=True)
        v = jnp.var(s, axis=-1, keepdims=True)
        return (s - m) / jnp.sqrt(v + eps) * sc + b, s

    wy = jnp.asarray(rng.randn(n, d).astype('float32'))
    ws = jnp.asarray(rng.randn(n, d).astype('float32'))

    def loss_of(f):
        def go(x, r, sc, b):
            y, s = f(x, r, sc, b)
            return jnp.sum(y * wy) + jnp.sum(s * ws)
        return go

    yr, sr = ref_fn(x, r, sc, b)
    grefs = jax.grad(loss_of(ref_fn), argnums=(0, 1, 2, 3))(x, r, sc, b)
    mesh = _mesh(shape, axes)
    f = lambda x, r, sc, b: fused_ln_residual_spmd(x, r, sc, b, mesh,
                                                   eps, 'interpret')
    y, s = f(x, r, sc, b)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))
    gg = jax.grad(loss_of(f), argnums=(0, 1, 2, 3))(x, r, sc, b)
    for a, bb, name in zip(gg, grefs, ('x', 'r', 'scale', 'bias')):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_spmd_fused_adam_groups_by_param_spec():
    """Each spec-group updates per shard (no all-gather); replicated
    params take the replicated path; a spec that does not tile its param
    is excluded by _mesh_spec_ok (per-param fallback)."""
    from paddle_tpu.ops.optimizer_ops import (_adam_dense, _mesh_spec_ok,
                                              _fused_adam_group_spmd)
    rng = np.random.RandomState(0)
    mesh = _mesh((2, 2), ('data', 'model'))
    b1, b2, eps = 0.9, 0.999, 1e-8
    lr_t = jnp.float32(0.01)
    shapes = [(8, 128), (128,), (16, 64)]
    ps = [jnp.asarray(rng.randn(*s).astype('float32')) for s in shapes]
    gs = [jnp.asarray(rng.randn(*s).astype('float32')) for s in shapes]
    m1 = [jnp.asarray(rng.randn(*s).astype('float32')) for s in shapes]
    m2 = [jnp.asarray(np.abs(rng.randn(*s)).astype('float32'))
          for s in shapes]
    refs = [_adam_dense(p, g, a, b, lr_t, b1, b2, eps)
            for p, g, a, b in zip(ps, gs, m1, m2)]
    # a non-dividing spec is rejected up front (the fallback rule)
    assert not _mesh_spec_ok(mesh, P('data', None), (5, 128))
    assert not _mesh_spec_ok(mesh, P('oops'), (8,))
    for spec in (P(), P('model', None), P(None, 'data')):
        sel = [i for i, s in enumerate(shapes)
               if _mesh_spec_ok(mesh, spec, s)]
        po, m1o, m2o = _fused_adam_group_spmd(
            mesh, spec, [ps[i] for i in sel], [gs[i] for i in sel],
            [m1[i] for i in sel], [m2[i] for i in sel], lr_t, b1, b2,
            eps, 'interpret')
        for j, i in enumerate(sel):
            np.testing.assert_allclose(np.asarray(po[j]),
                                       np.asarray(refs[i][0]),
                                       rtol=2e-6, atol=2e-6)
            np.testing.assert_allclose(np.asarray(m2o[j]),
                                       np.asarray(refs[i][2]),
                                       rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# fallback chain per shard + counter mesh labels
# ---------------------------------------------------------------------------

def test_mesh_fallback_chain_and_counter_labels(monkeypatch):
    """Per-shard untileable shapes degrade pallas -> xla WITH the mesh=n
    label; tileable ones keep the kernels. The dispatch decision is the
    per-op rule applied to post-partitioning local shapes."""
    from paddle_tpu.ops.ce_ops import spmd_shapes_ok
    from paddle_tpu.ops.nn_ops import ln_res_spmd_ok
    from paddle_tpu.ops import kernel_tier as kt
    mesh = _mesh((2,), ('data',))
    # 256 rows tile at 128/shard; 100 rows do not even reach a shard tile
    assert spmd_shapes_ok(mesh, 256, 512)
    assert not spmd_shapes_ok(mesh, 100, 512)
    # [256, 512] tiles unsharded but NOT per shard at 128 rows? it does;
    # vocab 500 never tiles
    assert not spmd_shapes_ok(mesh, 256, 500)
    assert ln_res_spmd_ok(mesh, 256, 128)
    assert not ln_res_spmd_ok(mesh, 256, 100)

    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')
    before = monitor.counters()
    assert kt.dispatch('softmax_with_cross_entropy', pallas_ok=False,
                       mesh=mesh) == 'xla'
    assert kt.dispatch('fused_ln_residual', pallas_ok=True,
                       mesh=mesh) == 'pallas'
    assert kt.dispatch('lookup_table', pallas_ok=False, xla_ok=False,
                       mesh=mesh) == 'off'
    d = monitor.counter_delta(before)
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=xla,mesh=n,op=softmax_with_cross_entropy}') == 1
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=pallas,mesh=n,op=fused_ln_residual}') == 1
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=off,mesh=n,op=lookup_table}') == 1


# ---------------------------------------------------------------------------
# sharded-LM trajectory parity (all four units in one program)
# ---------------------------------------------------------------------------

def _train_lm_mesh(fuse, tier, mesh_axes, steps=2):
    """Tiny LM under a MeshRunner: batch 8 x seq 32 = 128 rows/shard at
    data=2 (the CE row tile), d_model=128, vocab 512 (model=2 shards to
    256-wide blocks). Returns (losses, final state dict)."""
    from paddle_tpu.models.transformer import build_lm, LMConfig
    from paddle_tpu.parallel import MeshRunner
    os.environ.pop('PADDLE_FUSED_TIER', None)
    if tier is not None:
        os.environ['PADDLE_FUSED_TIER'] = tier
    try:
        cfg = LMConfig(vocab_size=512, seq_len=32, d_model=128, n_head=4,
                       n_layer=1, d_ff=128, dropout=0.0, attn_dropout=0.0,
                       use_flash_attention=False)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 5
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            tokens, labels, logits, avg_loss = build_lm(cfg)
            fluid.optimizer.Adam(1e-3, fuse=fuse).minimize(avg_loss)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        losses = []
        runner = None
        if mesh_axes is not None:
            mesh = _mesh(*mesh_axes)
            runner = MeshRunner(main, mesh,
                                feed_specs={'tokens': P('data'),
                                            'labels': P('data')})
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            for _ in range(steps):
                f = {'tokens': rng.randint(0, 512, (8, 32)).astype('int64'),
                     'labels': rng.randint(0, 512, (8, 32)).astype('int64')}
                if runner is not None:
                    l, = runner.run(f, [avg_loss], scope)
                else:
                    l, = exe.run(main, feed=f, fetch_list=[avg_loss],
                                 scope=scope)
                losses.append(float(np.asarray(l).reshape(())))
            state = {n: np.asarray(scope.get(n))
                     for n in sorted(scope.names())
                     if hasattr(scope.get(n), 'shape')}
        return losses, state
    finally:
        os.environ.pop('PADDLE_FUSED_TIER', None)


def _assert_traj(got, ref, bitwise, tag):
    losses_g, state_g = got
    losses_r, state_r = ref
    if bitwise:
        assert losses_g == losses_r, (tag, losses_g, losses_r)
        for n in state_r:
            np.testing.assert_array_equal(state_g[n], state_r[n],
                                          err_msg='%s %s' % (tag, n))
    else:
        np.testing.assert_allclose(losses_g, losses_r, rtol=1e-5,
                                   err_msg=tag)
        for n in state_r:
            np.testing.assert_allclose(state_g[n], state_r[n], rtol=1e-4,
                                       atol=1e-5,
                                       err_msg='%s %s' % (tag, n))


def test_sharded_lm_trajectory_data2():
    """mesh(data=2): the fused program at tier 'off' BITWISE matches the
    unfused program; the interpret tier (real pallas kernels, partitioned
    per shard) tracks the same trajectory allclose — and every one of the
    three fused units dispatched a partitioned (mesh=n) interpret impl,
    not the xla fallback (the acceptance-criteria counter proof)."""
    m = ((2,), ('data',))
    ref = _train_lm_mesh(fuse=False, tier='off', mesh_axes=m)
    _assert_traj(_train_lm_mesh(fuse=True, tier='off', mesh_axes=m), ref,
                 bitwise=True, tag='off')
    before = monitor.counters()
    _assert_traj(_train_lm_mesh(fuse=True, tier='interpret', mesh_axes=m),
                 ref, bitwise=False, tag='interpret')
    d = monitor.counter_delta(before)
    for op in ('softmax_with_cross_entropy', 'fused_adam', 'lookup_table',
               'fused_ln_residual'):
        # the lookup has one lowering (XLA's gather, which the
        # partitioner splits): it counts as `off` at every tier
        key = ('fused_kernel_dispatch_total{impl=%s,mesh=n,op=%s}'
               % ('off' if op == 'lookup_table' else 'interpret', op))
        assert d.get(key, 0) >= 1, (op, d)
        assert not any('impl=xla' in k and op in k and 'mesh=n' in k
                       for k in d), (op, d)


@pytest.mark.slow
def test_sharded_lm_trajectory_data2_model2_and_unsharded_cross():
    """mesh(data=2, model=2) trajectory parity for the same program, plus
    the unsharded-pallas cross-check: the partitioned kernels track the
    SINGLE-DEVICE interpret run allclose."""
    m22 = ((2, 2), ('data', 'model'))
    ref = _train_lm_mesh(fuse=False, tier='off', mesh_axes=m22)
    _assert_traj(_train_lm_mesh(fuse=True, tier='off', mesh_axes=m22),
                 ref, bitwise=True, tag='off22')
    got = _train_lm_mesh(fuse=True, tier='interpret', mesh_axes=m22)
    _assert_traj(got, ref, bitwise=False, tag='interpret22')
    single = _train_lm_mesh(fuse=True, tier='interpret', mesh_axes=None)
    _assert_traj(got, single, bitwise=False, tag='vs-unsharded-pallas')
