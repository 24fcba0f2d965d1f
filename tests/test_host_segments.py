"""Heterogeneous execution: host-callback ops inside device programs.

By default host ops (py_func, print, ...) compile into the program as
callbacks. Under PADDLE_SEGMENT_HOST_OPS=1 — and only then — the
executor partitions such programs into compiled device segments with the
host op run eagerly between them (executor.py _run_segmented) — the
TPU-native analog of the reference's kernel fallback + cross-place
PrepareData (framework/operator.cc:930,1003).

These tests turn the segmented path on and check it produces exactly
what the one-shot compiled path produces.
"""
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.framework import Program, program_guard


@pytest.fixture
def forced_segmentation(monkeypatch):
    monkeypatch.setenv('PADDLE_SEGMENT_HOST_OPS', '1')


def _build_pyfunc_prog():
    prog, startup = Program(), Program()
    with program_guard(prog, startup):
        x = fluid.layers.data(name='x', shape=[3, 4], dtype='float32')
        h = fluid.layers.scale(x, scale=2.0)
        out_var = prog.global_block().create_var(
            name='seg_pyf', shape=(3, 4), dtype='float32')
        fluid.layers.py_func(lambda a: np.tanh(a) + 1.0, h, out_var)
        y = fluid.layers.scale(out_var, scale=3.0)
    return prog, startup, y


class TestSegmentedExecution(object):
    def test_pyfunc_between_device_segments(self, forced_segmentation):
        prog, startup, y = _build_pyfunc_prog()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        X = np.random.RandomState(0).randn(3, 4).astype(np.float32)
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            o, = exe.run(prog, feed={'x': X}, fetch_list=[y], scope=scope)
        np.testing.assert_allclose(
            o, 3.0 * (np.tanh(2.0 * X) + 1.0), rtol=1e-6)

    def test_matches_unsegmented(self, monkeypatch):
        X = np.random.RandomState(1).randn(3, 4).astype(np.float32)
        outs = {}
        for mode in ('0', '1'):
            monkeypatch.setenv('PADDLE_SEGMENT_HOST_OPS', mode)
            prog, startup, y = _build_pyfunc_prog()
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup, scope=scope)
                o, = exe.run(prog, feed={'x': X}, fetch_list=[y],
                             scope=scope)
            outs[mode] = np.asarray(o)
        np.testing.assert_array_equal(outs['0'], outs['1'])

    def test_segmented_only_when_asked(self, monkeypatch):
        """Unset (or any value but '1') keeps the one-shot compiled
        callback path: segmentation is an explicit request, never the
        answer of a backend probe."""
        X = np.random.RandomState(3).randn(3, 4).astype(np.float32)
        for mode in (None, 'auto', '1'):
            if mode is None:
                monkeypatch.delenv('PADDLE_SEGMENT_HOST_OPS', raising=False)
            else:
                monkeypatch.setenv('PADDLE_SEGMENT_HOST_OPS', mode)
            prog, startup, y = _build_pyfunc_prog()
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup, scope=scope)
                exe.run(prog, feed={'x': X}, fetch_list=[y], scope=scope)
            segmented = any(isinstance(k, tuple) and k and k[0] == 'hostseg'
                            for k in exe._cache)
            assert segmented == (mode == '1'), mode

    def test_print_after_training_step(self, forced_segmentation, capsys):
        """print + a full train step: backward/optimizer segment compiles,
        the print runs host-side, state updates land in the scope. The
        print op must come AFTER minimize — a host op inside the
        differentiated forward span is not splittable (executor.py run())
        and would silently take the ordinary path."""
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            y = fluid.layers.data(name='y', shape=[1], dtype='float32')
            pred = fluid.layers.fc(x, size=1, param_attr='seg_w',
                                   bias_attr=False)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            loss_p = fluid.layers.Print(loss, message='seg loss:')
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        rng = np.random.RandomState(2)
        X = rng.randn(8, 4).astype(np.float32)
        Y = (X @ np.array([[1.], [2.], [-1.], [0.5]],
                          np.float32)).astype(np.float32)
        losses = []
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            for _ in range(5):
                l, = exe.run(prog, feed={'x': X, 'y': Y},
                             fetch_list=[loss_p], scope=scope)
                losses.append(float(np.asarray(l).reshape(-1)[0]))
        assert losses[-1] < losses[0]
        # the segmented path really ran (not the ordinary compiled path)
        assert any(isinstance(k, tuple) and k and k[0] == 'hostseg'
                   for k in exe._cache), \
            "print-after-minimize program did not take the segmented path"
        # and the print op really printed, host-side
        assert 'seg loss:' in capsys.readouterr().out

    def test_rng_stream_independent_of_segmentation(self, monkeypatch):
        """Per-op PRNG keys fold the op's GLOBAL block index (lowering
        op_offset), so (a) two rng ops in different segments never draw
        identical bits and (b) the segmented stream matches the
        unsegmented program exactly."""
        def _run(mode):
            monkeypatch.setenv('PADDLE_SEGMENT_HOST_OPS', mode)
            prog, startup = Program(), Program()
            prog.random_seed = 1234
            with program_guard(prog, startup):
                a = fluid.layers.uniform_random([2, 3])
                a_p = fluid.layers.Print(a, message='rngseg:')
                b = fluid.layers.uniform_random([2, 3])
                out = fluid.layers.elementwise_add(a_p, b)
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup, scope=scope)
                av, bv, _ = exe.run(prog, fetch_list=[a, b, out],
                                    scope=scope)
            return np.asarray(av), np.asarray(bv)

        a1, b1 = _run('1')
        a0, b0 = _run('0')
        # (a) the two draws sit at the same within-segment index (0) in
        # different segments — they must still be distinct
        assert not np.array_equal(a1, b1)
        # (b) segmentation must not change the random stream
        np.testing.assert_array_equal(a1, a0)
        np.testing.assert_array_equal(b1, b0)

    def test_statefulness_across_segments(self, forced_segmentation):
        """A persistable var updated before a host op is visible after it."""
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            x = fluid.layers.data(name='x', shape=[2], dtype='float32')
            counter = fluid.layers.create_global_var(
                shape=[1], value=0.0, dtype='float32', persistable=True,
                name='seg_counter')
            fluid.layers.assign(
                fluid.layers.elementwise_add(
                    counter, fluid.layers.fill_constant(
                        [1], 'float32', 1.0)), counter)
            pyf = prog.global_block().create_var(
                name='seg_state_pyf', shape=(1, 2), dtype='float32')
            fluid.layers.py_func(lambda a: a * 10.0, x, pyf)
            total = fluid.layers.elementwise_add(
                fluid.layers.reduce_sum(pyf, keep_dim=True),
                counter)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            for step in range(1, 4):
                t, = exe.run(prog,
                             feed={'x': np.ones((1, 2), np.float32)},
                             fetch_list=[total], scope=scope)
                assert float(np.asarray(t).reshape(-1)[0]) == \
                    pytest.approx(20.0 + step)

    def test_detection_map_segmented(self, forced_segmentation):
        """detection_map (host metric) with LoD feeds through the
        segmented path."""
        det = np.array([[0, 0.9, 0.1, 0.1, 0.4, 0.4],
                        [0, 0.3, 0.5, 0.5, 0.9, 0.9],
                        [1, 0.8, 0.2, 0.2, 0.6, 0.6]], np.float32)
        lab = np.array([[0, 0, 0.1, 0.1, 0.4, 0.4],
                        [1, 0, 0.2, 0.2, 0.6, 0.6]], np.float32)
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            d = fluid.layers.data(name='det', shape=[6], dtype='float32',
                                  lod_level=1)
            g = fluid.layers.data(name='lab', shape=[6], dtype='float32',
                                  lod_level=1)
            m = fluid.layers.detection_map(d, g, class_num=2)
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            out, = exe.run(prog,
                           feed={'det': (det, [[0, 3]]),
                                 'lab': (lab, [[0, 2]])},
                           fetch_list=[m], scope=scope)
        v = float(np.asarray(out).reshape(-1)[0])
        assert 0.0 <= v <= 1.0 and v > 0.5
