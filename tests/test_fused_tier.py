"""Fused-kernel tier round 2 (PADDLE_FUSED_TIER) + the int8 inference path.

Contracts pinned here:
- every fused kernel has an unfused reference path, and tier 'off'
  reproduces the legacy lowering BITWISE (trajectory-level asserts);
- fused-vs-unfused parity per kernel through the Pallas INTERPRETER on
  CPU (cross-checking discipline of ops/attention_ops.py);
- quant_ops straight-through-estimator gradients;
- int8 programs (PTQ full-int8 and weight-only) match fp32 within a
  stated tolerance and round-trip save/load_inference_model + Predictor;
- under PADDLE_PROFILE_OPS=1 a fused unit attributes as ONE op;
- the fused-tier dispatch check adds <=5us to the un-fused Executor.run
  hot path (interleaved best-of-N minima; the check is one env read).
"""
import gc
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.ops import kernel_tier


@pytest.fixture
def tier_env(monkeypatch):
    def set_tier(v):
        if v is None:
            monkeypatch.delenv('PADDLE_FUSED_TIER', raising=False)
        else:
            monkeypatch.setenv('PADDLE_FUSED_TIER', v)
    yield set_tier
    monkeypatch.delenv('PADDLE_FUSED_TIER', raising=False)


# ---------------------------------------------------------------------------
# kernel-level parity (interpret = the real kernels, CPU-executed)
# ---------------------------------------------------------------------------

class TestFusedCrossEntropy(object):
    def _data(self, n=256, v=512):
        rng = np.random.RandomState(0)
        x = (rng.randn(n, v) * 3).astype('float32')
        lab = rng.randint(0, v, n).astype('int32')
        lab[5] = -100                                   # ignored row
        return jnp.asarray(x), jnp.asarray(lab)

    @pytest.mark.parametrize('impl', ['xla', 'interpret'])
    def test_forward_and_grad_parity(self, impl):
        from paddle_tpu.ops.ce_ops import fused_softmax_ce
        from paddle_tpu.ops.nn_ops import _ce_hard
        x, lab = self._data()
        ref = _ce_hard(x, lab, -100)
        got = fused_softmax_ce(x, lab, -100, impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # ignored row: exactly zero loss
        assert float(got[5]) == 0.0
        w = jnp.arange(x.shape[0], dtype=jnp.float32)   # row weights
        gr = jax.grad(lambda z: jnp.sum(_ce_hard(z, lab, -100) * w))(x)
        gg = jax.grad(
            lambda z: jnp.sum(fused_softmax_ce(z, lab, -100, impl) * w))(x)
        scale = np.abs(np.asarray(gr)).max()
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                   atol=2e-6 * max(scale, 1.0))
        # ignored row's gradient row is exactly zero
        assert np.abs(np.asarray(gg)[5]).max() == 0.0

    def test_shape_fallback_rule(self):
        from paddle_tpu.ops.ce_ops import pallas_shapes_ok
        assert pallas_shapes_ok(256, 512)
        assert not pallas_shapes_ok(100, 512)    # rows don't tile
        assert not pallas_shapes_ok(256, 500)    # vocab doesn't tile


class TestFusedEmbeddingGather(object):
    """The lookup has ONE lowering since PR 33 (XLA's gather of the table
    as it is stored, ops/embedding_ops.py): what the Pallas unit's cases
    held — the rows, the bias, the scatter-add gradient, the sparse table
    beside a trainable bias, the program-level op — is held against it,
    at every tier name."""

    def test_gather_bias_grad_bitwise(self):
        from paddle_tpu.ops.embedding_ops import embedding_gather
        rng = np.random.RandomState(1)
        w = rng.randn(64, 128).astype('float32')
        ids = rng.randint(0, 64, 37).astype('int32')
        ids[5] = ids[9]                                  # a row twice
        bias = rng.randn(128).astype('float32')
        got = embedding_gather(jnp.asarray(w), jnp.asarray(ids),
                               jnp.asarray(bias))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.take(w, ids, axis=0) + bias)
        np.testing.assert_array_equal(
            np.asarray(embedding_gather(jnp.asarray(w), jnp.asarray(ids))),
            np.take(w, ids, axis=0))
        # d/dw of sum(c * rows) is c scatter-ADDED at the ids, d/dbias
        # its column sums
        c = rng.randn(37, 128).astype('float32')
        gw, gb = jax.grad(
            lambda wv, bv: jnp.sum(embedding_gather(wv, jnp.asarray(ids), bv)
                                   * c), argnums=(0, 1))(
            jnp.asarray(w), jnp.asarray(bias))
        want = np.zeros_like(w)
        np.add.at(want, ids, c)
        np.testing.assert_allclose(np.asarray(gw), want, rtol=1e-6,
                                   atol=1e-6)
        assert np.abs(np.asarray(gw)[np.setdiff1d(np.arange(64), ids)]
                      ).max() == 0.0
        np.testing.assert_allclose(np.asarray(gb), c.sum(0), rtol=1e-5,
                                   atol=1e-5)

    def test_out_of_range_ids_read_the_nearest_row(self):
        """What the TPU's gather does and the Pallas unit did: no NaN
        fill, no wrap-around; the gradient lands on the row read."""
        from paddle_tpu.ops.embedding_ops import embedding_gather
        w = jnp.arange(16 * 128, dtype=jnp.float32).reshape(16, 128)
        ids = jnp.asarray([-3, 0, 15, 16, 10 ** 6], jnp.int32)
        got = np.asarray(embedding_gather(w, ids))
        np.testing.assert_array_equal(
            got, np.asarray(w)[[0, 0, 15, 15, 15]])
        gw = jax.grad(lambda wv: jnp.sum(embedding_gather(wv, ids)))(w)
        np.testing.assert_array_equal(
            np.asarray(gw)[:, 0], [2.0] + [0.0] * 14 + [3.0])

    @pytest.mark.parametrize('tier', ['off', 'xla', 'interpret'])
    def test_lookup_table_is_one_lowering_at_every_tier(self, tier_env,
                                                        tier):
        """padding_idx rows zero, a trailing-1 ids shape folds, and the
        dispatch counter reads the surviving implementation (`off`: the
        unfused gather) whatever tier is asked for."""
        from test_detection_ops import _run_single_op
        rng = np.random.RandomState(3)
        w = rng.randn(32, 128).astype('float32')
        ids = rng.randint(0, 32, (3, 7, 1)).astype('int64')
        ids[0, :3, 0] = 4
        tier_env(tier)
        before = monitor.counters()
        out, = _run_single_op('lookup_table', {'W': w, 'Ids': ids},
                              {'Out': ['lt_out']}, {'padding_idx': 4})
        want = w[ids[..., 0]] * (ids != 4)
        np.testing.assert_array_equal(out, want)
        assert out.shape == (3, 7, 128)
        d = monitor.counter_delta(before)
        assert {k: v for k, v in d.items() if 'op=lookup_table' in k} == {
            'fused_kernel_dispatch_total{impl=off,mesh=1,op=lookup_table}':
            1}, d

    def test_sparse_table_with_trainable_bias_trains(self, tier_env):
        """fused_embedding_gather on an is_sparse table WITH a trainable
        Bias (under the interpret tier, as when a kernel stood here): the
        table grad rides the sparse scout/dummy path while the bias adds
        after the stop_gradient'd gather, on plain AD — both the table
        rows and the bias must move."""
        tier_env('interpret')
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 9
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            ids = fluid.layers.data(name='bi', shape=[1], dtype='int64')
            y = fluid.layers.data(name='by', shape=[1], dtype='float32')
            helper = fluid.layer_helper.LayerHelper('feg')
            w = helper.create_parameter(fluid.ParamAttr(name='feg_w'),
                                        [32, 128], 'float32')
            b = helper.create_parameter(fluid.ParamAttr(name='feg_b'),
                                        [128], 'float32', is_bias=True)
            block = main.global_block()
            emb = block.create_var(name='feg_out', dtype='float32',
                                   shape=(-1, 128))
            block.append_op(type='fused_embedding_gather',
                            inputs={'W': [w], 'Ids': [ids], 'Bias': [b]},
                            outputs={'Out': [emb]},
                            attrs={'is_sparse': True})
            p = fluid.layers.fc(emb, size=1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
            fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            b0 = np.asarray(scope.get('feg_b')).copy()
            w0 = np.asarray(scope.get('feg_w')).copy()
            f = {'bi': rng.randint(0, 32, (8, 1)).astype('int64'),
                 'by': rng.randn(8, 1).astype('float32')}
            exe.run(main, feed=f, fetch_list=[loss], scope=scope)
            b1 = np.asarray(scope.get('feg_b'))
            w1 = np.asarray(scope.get('feg_w'))
        assert np.abs(b1 - b0).max() > 0            # bias trained
        touched = np.unique(f['bi'].reshape(-1))
        moved = np.nonzero(np.abs(w1 - w0).max(axis=1) > 0)[0]
        # sparse grads: exactly the looked-up rows move
        assert set(moved) == set(touched), (moved, touched)

    def test_fused_embedding_gather_op(self, tier_env):
        from test_detection_ops import _run_single_op
        rng = np.random.RandomState(2)
        w = rng.randn(16, 128).astype('float32')
        ids = rng.randint(0, 16, (5, 1)).astype('int64')
        b = rng.randn(128).astype('float32')
        tier_env('interpret')
        out, = _run_single_op(
            'fused_embedding_gather', {'W': w, 'Ids': ids, 'Bias': b},
            {'Out': ['feg_out']}, {})
        np.testing.assert_allclose(out, w[ids.reshape(-1)] + b, rtol=1e-6)


# ---------------------------------------------------------------------------
# program-level trajectory parity across tiers
# ---------------------------------------------------------------------------

def _train_lm(fuse, tier, steps=3):
    """Tiny LM (d_model=128 so the gather kernel tiles) -> loss list +
    final parameter state."""
    from paddle_tpu.models.transformer import build_lm, LMConfig
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
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            for _ in range(steps):
                f = {'tokens': rng.randint(0, 512, (4, 32)).astype('int64'),
                     'labels': rng.randint(0, 512, (4, 32)).astype('int64')}
                l, = exe.run(main, feed=f, fetch_list=[avg_loss],
                             scope=scope)
                losses.append(float(np.asarray(l).reshape(())))
            state = {n: np.asarray(scope.get(n))
                     for n in sorted(scope.names())
                     if hasattr(scope.get(n), 'shape')}
        return losses, state
    finally:
        os.environ.pop('PADDLE_FUSED_TIER', None)


def test_lm_trajectory_off_bitwise_and_fused_parity():
    """fuse=True + tier 'off' bit-matches the legacy per-param program;
    the interpret (real pallas kernels) tier reproduces the same
    trajectory (tight allclose — measured bitwise on this model). The
    xla tier's numerics are covered at kernel level above and by the
    sparse fused_adam test below; skipping its whole-LM build keeps this
    file inside the tier-1 budget (suite is borderline vs 870s)."""
    ref_losses, ref_state = _train_lm(fuse=False, tier='off')
    for tier, bitwise in (('off', True), ('interpret', False)):
        losses, state = _train_lm(fuse=True, tier=tier)
        if bitwise:
            assert losses == ref_losses, (tier, losses, ref_losses)
            for n in ref_state:
                np.testing.assert_array_equal(state[n], ref_state[n],
                                              err_msg='%s %s' % (tier, n))
        else:
            np.testing.assert_allclose(losses, ref_losses, rtol=1e-6,
                                       err_msg=tier)
            for n in ref_state:
                # atol-dominated: fp32 reassociation puts ~1e-6-scale
                # noise on near-zero params after 3 steps
                np.testing.assert_allclose(
                    state[n], ref_state[n], rtol=1e-4, atol=1e-5,
                    err_msg='%s %s' % (tier, n))


def test_fused_adam_sparse_grads_fall_back_per_param(tier_env):
    """SelectedRows grads take the row-wise path inside fused_adam: the
    trajectory with an is_sparse embedding bit-matches per-param adam."""
    def run(fuse, tier):
        tier_env(tier)
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 3
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            ids = fluid.layers.data(name='i', shape=[1], dtype='int64')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            emb = fluid.layers.embedding(ids, size=[50, 8], is_sparse=True)
            p = fluid.layers.fc(fluid.layers.reshape(emb, [-1, 8]), size=1)
            loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
            fluid.optimizer.Adam(0.01, fuse=fuse).minimize(loss)
        exe = fluid.Executor()
        scope = fluid.Scope()
        rng = np.random.RandomState(1)
        out = []
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            for _ in range(3):
                f = {'i': rng.randint(0, 50, (8, 1)).astype('int64'),
                     'y': rng.randn(8, 1).astype('float32')}
                l, = exe.run(main, feed=f, fetch_list=[loss], scope=scope)
                out.append(float(np.asarray(l).reshape(())))
        return out

    ref = run(False, None)
    # xla exercises the SelectedRows-vs-flat split; the interpret dense
    # kernel is already covered by the LM trajectory test (budget-lean)
    assert run(True, 'xla') == ref


# ---------------------------------------------------------------------------
# quant_ops STE gradients
# ---------------------------------------------------------------------------

def test_fake_quant_dequant_ste_gradient():
    """round() has zero gradient; the straight-through estimator must pass
    d(dequant(quant(x)))/dx == 1 exactly (scale is stop_gradient), which
    is what lets QAT keep training fp32 master weights."""
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='sx', shape=[6], dtype='float32')
        x.stop_gradient = False
        block = prog.global_block()
        q = block.create_var(name='ste_q', dtype='float32', shape=(-1, 6))
        s = block.create_var(name='ste_s', dtype='float32', shape=(1,))
        dq = block.create_var(name='ste_dq', dtype='float32', shape=(-1, 6))
        block.append_op(type='fake_quantize_abs_max', inputs={'X': [x]},
                        outputs={'Out': [q], 'OutScale': [s]},
                        attrs={'bit_length': 8})
        block.append_op(type='fake_dequantize_max_abs',
                        inputs={'X': [q], 'Scale': [s]},
                        outputs={'Out': [dq]},
                        attrs={'max_range': 127.0})
        loss = fluid.layers.mean(block.var('ste_dq'))
        grads = fluid.backward.append_backward(loss, parameter_list=['sx'])
    exe = fluid.Executor()
    xv = (np.random.RandomState(0).randn(4, 6) * 2).astype('float32')
    g, = exe.run(prog, feed={'sx': xv},
                 fetch_list=[grads[0][1].name])
    # d(mean)/dx = 1/N through the STE, exactly
    np.testing.assert_array_equal(np.asarray(g),
                                  np.full((4, 6), 1.0 / 24, 'float32'))


# ---------------------------------------------------------------------------
# int8 inference path
# ---------------------------------------------------------------------------

def test_ptq_int8_rank3_parity_and_predictor_roundtrip(tmp_path):
    """BERT-shaped rank-3 fc stack: PTQ rewrite -> int8 GEMMs within 1.2%
    of fp32 — per-OUTPUT-CHANNEL weight scales (the per-tensor scale only
    held 2%; what remains is the int8 ACTIVATION rounding floor,
    step/sqrt(12) per element, which no weight-side scale can remove);
    save_inference_model exports int8 blobs (and DROPS the unused fp32
    weights); the Predictor serves the loaded artifact bit-identical to
    the in-process quantized program. The weight-only rewrite of the SAME
    rank-3 stack — activations fp32, so the weight scales are the whole
    error — holds the tightened <0.5% bound below."""
    from paddle_tpu.contrib.quantize import post_training_quantize
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='qx', shape=[8, 16], dtype='float32')
        h = fluid.layers.fc(x, size=32, num_flatten_dims=2, act='relu')
        out = fluid.layers.fc(h, size=4, num_flatten_dims=2)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    calib = [{'qx': rng.randn(4, 8, 16).astype('float32')}
             for _ in range(3)]
    feed = {'qx': rng.randn(2, 8, 16).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = main.clone(for_test=True)
        ref, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
        before = monitor.counters()
        idx = post_training_quantize(exe, infer, scope, calib)
        assert len(idx) == 2            # both rank-3 fc matmuls rewritten
        got, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
        ref, got = np.asarray(ref), np.asarray(got)
        assert np.max(np.abs(got - ref)) / (np.abs(ref).max() or 1) < 0.012
        d = str(tmp_path / 'int8')
        fluid.io.save_inference_model(
            d, ['qx'], [infer.global_block().var(out.name)], exe,
            main_program=infer)
    pred = fluid.create_predictor(d)
    served, = pred.run(feed)
    np.testing.assert_array_equal(np.asarray(served), got)
    names = set(pred.scope.names())
    assert {n for n in names if n.endswith('.int8')}, names
    # the fp32 originals are gone from the export
    assert not any(n.endswith('.w_0') for n in names), names
    delta = monitor.counter_delta(before)
    assert delta.get('quantized_program_total{kind=ptq_int8}') == 1
    assert delta.get('quantized_program_total{kind=loaded}') == 1


def test_weight_only_rank3_per_channel_half_percent():
    """The satellite's tightened bound: per-OUTPUT-CHANNEL weight scales
    on the BERT rank-3 fc stack, weight-only (fp32 activations, so the
    weight quantization IS the error) — parity <0.5%, vs ~2% under the
    old per-tensor scale. Also pins the scale artifacts: a [out_channels]
    vector per 2-D weight, threaded through fake_dequantize_max_abs."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='qx', shape=[8, 16], dtype='float32')
        h = fluid.layers.fc(x, size=32, num_flatten_dims=2, act='relu')
        out = fluid.layers.fc(h, size=4, num_flatten_dims=2)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {'qx': rng.randn(2, 8, 16).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = main.clone(for_test=True)
        ref, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
        blobs = QuantizeTranspiler().convert_to_int8_program(
            infer, scope=scope)
        got, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
    for name, (blob, scale) in blobs.items():
        scale = np.asarray(scale)
        # one scale per output channel of the 2-D fc weight
        assert scale.shape == (blob.shape[1],), (name, scale.shape)
        assert np.all(scale > 0)
    ref, got = np.asarray(ref), np.asarray(got)
    assert np.max(np.abs(got - ref)) / (np.abs(ref).max() or 1) < 0.005


def test_weight_only_int8_program_and_slim_strategy():
    """QuantizeTranspiler.convert_to_int8_program: int8(weight)/fp32(act)
    execution within quantization tolerance (per-channel scales hold 1%
    on this wider stack); the slim QuantizationStrategy hands the same
    artifact back at compress end."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='wx', shape=[16], dtype='float32')
        out = fluid.layers.fc(fluid.layers.fc(x, size=64, act='relu'),
                              size=8)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feed = {'wx': rng.randn(8, 16).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = main.clone(for_test=True)
        ref, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
        blobs = QuantizeTranspiler().convert_to_int8_program(
            infer, scope=scope)
        assert len(blobs) == 2
        assert all(b.dtype == np.int8 for b, _ in blobs.values())
        got, = exe.run(infer, feed=feed, fetch_list=[out.name], scope=scope)
    ref, got = np.asarray(ref), np.asarray(got)
    assert np.max(np.abs(got - ref)) / (np.abs(ref).max() or 1) < 0.01


def test_quantized_program_serves_zero_recompiles(tmp_path):
    """A PTQ int8 artifact behind ServingEngine.warmup: mixed-batch live
    traffic after warmup compiles nothing (the acceptance-criteria
    serving contract)."""
    from paddle_tpu.contrib.quantize import post_training_quantize
    from paddle_tpu.serving import ServingEngine, ServingConfig
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='sx', shape=[16], dtype='float32')
        out = fluid.layers.fc(fluid.layers.fc(x, size=32, act='relu'),
                              size=4)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = main.clone(for_test=True)
        post_training_quantize(
            exe, infer, scope,
            [{'sx': rng.randn(4, 16).astype('float32')}])
        d = str(tmp_path / 'int8_srv')
        fluid.io.save_inference_model(
            d, ['sx'], [infer.global_block().var(out.name)], exe,
            main_program=infer)
    eng = ServingEngine(ServingConfig(d, max_batch_size=2, max_wait_ms=1.0,
                                      num_workers=1))
    eng.start()
    try:
        eng.warmup({'sx': rng.randn(1, 16).astype('float32')})
        before = monitor.counters()
        reqs = [eng.submit({'sx': rng.randn(b, 16).astype('float32')})
                for b in (1, 2, 1, 2, 1)]
        for r in reqs:
            r.result(timeout=30)
        delta = monitor.counter_delta(before)
        assert delta.get('compile_cache_miss', 0) == 0, delta
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# attribution: a fused unit is ONE op row
# ---------------------------------------------------------------------------

def test_fused_units_attribute_as_one_op(tier_env, monkeypatch):
    from paddle_tpu import analysis
    tier_env('xla')
    monkeypatch.setenv('PADDLE_PROFILE_OPS', '1')
    analysis.reset_op_profile()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='px', shape=[32], dtype='float32')
        y = fluid.layers.data(name='py', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=128)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(h, y))
        fluid.optimizer.Adam(1e-3, fuse=True).minimize(loss)
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    exe.run(startup)
    exe.run(main, feed={'px': rng.randn(8, 32).astype('float32'),
                        'py': rng.randint(0, 128, (8, 1)).astype('int64')},
            fetch_list=[loss])
    prof = analysis.op_profile()
    rows = {r['type']: r for r in prof['ops']}
    assert rows['fused_adam']['calls'] == 1     # whole param set, one unit
    assert 'adam' not in rows
    assert rows['softmax_with_cross_entropy']['calls'] == 1
    # contrib.op_frequence ranks from THIS table (one source of truth),
    # joined with the static census
    offenders = fluid.contrib.top_offenders(program=main, profile=prof)
    assert {r['type'] for r in offenders} == set(rows)
    assert all('total_s' in r and 'program_count' in r for r in offenders)
    with pytest.raises(RuntimeError, match='PADDLE_PROFILE_OPS'):
        fluid.contrib.top_offenders(profile={'ops': []})


# ---------------------------------------------------------------------------
# hot-path guard: the tier dispatch check on the UN-fused run path
# ---------------------------------------------------------------------------

def test_fused_tier_dispatch_overhead_under_5us():
    """The only per-run cost the tier adds to Executor.run is the
    cache_token() env read folded into _feed_signature. Measure the exact
    added call interleaved with a no-op baseline, min-of-per-call (one
    preempted timeslice poisons averages on this box — see BASELINE
    notes), gc disabled; assert the ADDITION <= 5us."""
    tok = kernel_tier.cache_token
    n = 2000
    best_tok = best_base = float('inf')
    gc_was = gc.isenabled()
    gc.disable()
    try:
        def noop():
            return ''
        for _ in range(10):                      # interleaved best-of-10
            for fn, key in ((tok, 'tok'), (noop, 'base')):
                best = float('inf')
                for _ in range(n):
                    t0 = time.perf_counter()
                    fn()
                    dt = time.perf_counter() - t0
                    if dt < best:
                        best = dt
                if key == 'tok':
                    best_tok = min(best_tok, best)
                else:
                    best_base = min(best_base, best)
    finally:
        if gc_was:
            gc.enable()
    added = best_tok - best_base
    assert added <= 5e-6, (best_tok, best_base, added)


def test_dispatch_counter_and_fallback(tier_env):
    tier_env('pallas')
    before = monitor.counters()
    # shapes that cannot tile force the per-op fallback: pallas -> xla
    from paddle_tpu.ops import kernel_tier as kt
    assert kt.dispatch('softmax_with_cross_entropy', pallas_ok=False) \
        == 'xla'
    assert kt.dispatch('lookup_table', pallas_ok=False, xla_ok=False) \
        == 'off'
    assert kt.dispatch('fused_adam', pallas_ok=True) == 'pallas'
    d = monitor.counter_delta(before)
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=xla,mesh=1,op=softmax_with_cross_entropy}') == 1
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=off,mesh=1,op=lookup_table}') == 1
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=pallas,mesh=1,op=fused_adam}') == 1


def test_scout_pass_counts_dispatch_once(tier_env):
    """is_sparse programs lower the forward segment TWICE (sparse scout +
    vjp fwd, core/lowering.py); the dispatch counter must count each
    decision once or bench deltas double for sparse models."""
    tier_env('xla')
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data(name='ci', shape=[1], dtype='int64')
        emb = fluid.layers.embedding(ids, size=[16, 8], is_sparse=True)
        loss = fluid.layers.mean(fluid.layers.fc(
            fluid.layers.reshape(emb, [-1, 8]), size=1))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        before = monitor.counters()
        exe.run(main, feed={'ci': np.zeros((4, 1), 'int64')},
                fetch_list=[loss], scope=scope)
    d = monitor.counter_delta(before)
    assert d.get('fused_kernel_dispatch_total'
                 '{impl=off,mesh=1,op=lookup_table}') == 1, d


def test_kernbench_smoke():
    """tools/kernbench.py runs and produces comparable rows (lean: ONE
    tiny case, two tiers — the full sweep is a CLI, not a tier-1 cost).
    The --mesh path runs one case over mesh(data=2) and must carry the
    fused_kernel_dispatch_total{...,mesh=n} proof row showing the
    PARTITIONED kernel dispatched."""
    from tools.kernbench import measure_kernbench
    res = measure_kernbench(cases=['fused_adam'], tiers=['off', 'xla'],
                            rounds=1, k=2)
    for tier in ('off', 'xla'):
        assert res['fused_adam'][tier].get('wall_us'), res
    assert res['fused_adam']['xla'].get('vs_off') is not None
    res = measure_kernbench(cases=['layernorm_residual'],
                            tiers=['interpret'], rounds=1, k=1, mesh=2)
    row = res['layernorm_residual']['interpret']
    assert row.get('wall_us'), res
    assert row['mesh_dispatch'].get(
        'fused_kernel_dispatch_total'
        '{impl=interpret,mesh=n,op=fused_ln_residual}'), res


def test_bad_tier_value_raises(tier_env):
    tier_env('warp-speed')
    with pytest.raises(ValueError, match='PADDLE_FUSED_TIER'):
        kernel_tier.resolve_tier()
