"""Program introspection tier (paddle_tpu/analysis.py): XLA cost/memory
analytics + Executor.explain, op-level attribution profiling, NaN
provenance, and the contrib memory_usage rewire. docs/observability.md."""
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, monitor, profiler


def _build_mlp_train(batch_hint=64, hidden=64):
    """mnist-mlp train program in the CURRENT default programs (the
    conftest fixture provides fresh ones per test)."""
    img = fluid.layers.data(name='img', shape=[784], dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    h = fluid.layers.fc(input=img, size=hidden, act='relu')
    h = fluid.layers.fc(input=h, size=hidden, act='relu')
    pred = fluid.layers.fc(input=h, size=10, act='softmax')
    cost = fluid.layers.cross_entropy(input=pred, label=label)
    avg = fluid.layers.mean(cost)
    fluid.optimizer.Adam(learning_rate=0.01).minimize(avg)
    return avg, pred


def _feed(batch=64, seed=0):
    rng = np.random.RandomState(seed)
    return {'img': rng.randn(batch, 784).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}


class TestExplain(object):
    def test_explain_mnist_mlp_nonzero_flops_and_peak(self):
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        rep = exe.explain(fluid.default_main_program(), feed=_feed(),
                          fetch_list=[avg])
        assert rep['flops'] > 0
        assert rep['bytes_accessed'] > 0
        assert rep['peak_bytes'] > 0
        assert rep['argument_bytes'] > 0
        assert rep['output_bytes'] > 0
        assert rep['op_count'] > 5
        assert rep['ops'].get('adam', 0) >= 1
        assert rep['fingerprint'].startswith(('fp:', 'uid:'))

    def test_explain_shares_compile_with_run(self):
        """explain() then run() of the same signature must not recompile:
        the explained entry lands in the executor's program cache."""
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = _feed()
        exe.explain(fluid.default_main_program(), feed=feed,
                    fetch_list=[avg], memory=False)
        before = monitor.counters()
        exe.run(fluid.default_main_program(), feed=feed, fetch_list=[avg])
        delta = monitor.counter_delta(before)
        assert not delta.get('compile_cache_miss'), delta

    def test_run_registers_analytics_and_snapshot_flushes_gauges(self):
        # whatever ran on this worker before: 64 other programs fill the
        # gauge's series cap, and a program some earlier test compiled is
        # neither registered nor exported a second time — so an empty
        # monitor and a program of this test's own
        monitor.reset()
        avg, _ = _build_mlp_train(hidden=61)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[avg])
        fp = fluid.default_main_program()._fingerprint()
        rec = analysis.lookup(fp)
        assert rec is not None
        snap = monitor.snapshot()       # triggers the lazy cost flush
        label = 'fingerprint=%s' % fp[:12]
        flops = [v for k, v in snap['gauges'].items()
                 if k.startswith('program_flops') and label in k]
        assert flops and flops[0] > 0

    def test_explain_does_not_execute(self):
        """explain() is static: state values must not change."""
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        scope = fluid.executor.global_scope()
        name = [n for n in scope.names() if 'fc' in n][0]
        before = np.asarray(scope.get(name)).copy()
        exe.explain(fluid.default_main_program(), feed=_feed(),
                    fetch_list=[avg], memory=False)
        np.testing.assert_array_equal(before, np.asarray(scope.get(name)))


class TestOpProfiling(object):
    def test_attribution_table_sums_close_to_wall(self):
        """Acceptance: per-op times sum to within 2x of the measured
        profiled step wall time (exclusive accounting — nested vjp spans
        subtract from their parent)."""
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = _feed()
        with profiler.profile_ops() as an:
            # warm eager caches once, then measure the second run
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[avg])
            an.reset_op_profile()
            t0 = time.perf_counter()
            out = exe.run(fluid.default_main_program(), feed=feed,
                          fetch_list=[avg])
            wall = time.perf_counter() - t0
        assert np.isfinite(float(np.asarray(out[0]).reshape(-1)[0]))
        prof = an.op_profile()
        assert prof['runs'] == 1
        assert prof['ops'], "empty attribution table"
        acc = prof['accounted_s']
        assert wall / 2 <= acc <= wall * 2, (acc, wall)
        types = {r['type'] for r in prof['ops']}
        assert 'backward' in types and 'adam' in types
        # every row carries the full column set
        row = prof['ops'][0]
        for col in ('calls', 'total_s', 'min_s', 'max_s', 'avg_s',
                    'out_bytes', 'ratio'):
            assert col in row
        table = analysis.format_op_profile(prof)
        assert 'Op Profiling Report' in table and 'backward' in table

    def test_env_var_activates_and_spans_recorded(self, monkeypatch):
        monkeypatch.setenv('PADDLE_PROFILE_OPS', '1')
        analysis.reset_op_profile()
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        monitor.clear_spans()
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[avg])
        assert analysis.op_profile()['ops']
        names = {s['name'] for s in monitor.spans()}
        assert 'profile_ops' in names
        assert any(n.startswith('op:') for n in names)
        # results match the compiled path (same program, same state
        # semantics): a second profiled run still trains
        monkeypatch.delenv('PADDLE_PROFILE_OPS')
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[avg])

    def test_context_is_thread_local(self):
        """profile_ops() on one thread must not drag another thread's
        runs (a live serving pool) onto the interpreting path."""
        import threading
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = _feed()
        exe.run(fluid.default_main_program(), feed=feed, fetch_list=[avg])
        errs = []

        def other_thread_run():
            try:
                assert not analysis.profile_ops_active()
                exe.run(fluid.default_main_program(), feed=feed,
                        fetch_list=[avg])
            except Exception as e:      # noqa: BLE001 — surfaced below
                errs.append(e)

        before = monitor.counters()
        with profiler.profile_ops():
            t = threading.Thread(target=other_thread_run)
            t.start()
            t.join()
        assert not errs, errs
        assert analysis.op_profile()['runs'] == 0
        assert not monitor.counter_delta(before).get('op_profile_run_total')

    def test_profiled_matches_compiled_numerics(self):
        """The interpreting path must compute the same step as the
        compiled path (identical init, fresh scopes)."""
        avg, _ = _build_mlp_train()
        main = fluid.default_main_program()
        exe = fluid.Executor(fluid.CPUPlace())
        feed = _feed()
        init = fluid.Scope()
        with fluid.scope_guard(init):
            exe.run(fluid.default_startup_program(), scope=init)
        losses = []
        for profiled in (False, True):
            scope = fluid.Scope()
            for n in init.names():      # bit-identical starting state
                scope.set(n, np.array(np.asarray(init.get(n))))
            with fluid.scope_guard(scope):
                if profiled:
                    with profiler.profile_ops():
                        out = exe.run(main, feed=feed, fetch_list=[avg],
                                      scope=scope)
                else:
                    out = exe.run(main, feed=feed, fetch_list=[avg],
                                  scope=scope)
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
        assert losses[0] == pytest.approx(losses[1], rel=1e-4)


class TestNanProvenance(object):
    def _boom_program(self):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        h = fluid.layers.fc(input=x, size=8, act='relu')
        big = fluid.layers.scale(h, scale=1e20)
        boom = fluid.layers.scale(big, scale=1e20)      # inf in float32
        loss = fluid.layers.mean(boom)
        return boom, loss

    def test_executor_localizes_injected_inf(self, monkeypatch):
        monkeypatch.setenv('PADDLE_NAN_LOCALIZE', '1')
        boom, loss = self._boom_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        before = monitor.counters()
        fluid.set_flags({'FLAGS_check_nan_inf': True})
        try:
            with pytest.raises(RuntimeError) as ei:
                exe.run(fluid.default_main_program(),
                        feed={'x': np.ones((4, 8), np.float32)},
                        fetch_list=[loss])
        finally:
            fluid.set_flags({'FLAGS_check_nan_inf': False})
        msg = str(ei.value)
        assert 'NaN/Inf' in msg
        assert "type='scale'" in msg and boom.name in msg
        delta = monitor.counter_delta(before)
        assert delta.get('nonfinite_localized_total{op_type=scale}') == 1

    def test_training_guard_localizes_and_escalates_with_op(
            self, monkeypatch):
        """Acceptance: inject a mid-program inf op, run under
        TrainingGuard, localization names exactly that op and
        nonfinite_localized increments."""
        monkeypatch.setenv('PADDLE_NAN_LOCALIZE', '1')
        boom, loss = self._boom_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        guard = fluid.TrainingGuard(exe, fluid.default_main_program(),
                                    loss_name=loss.name, max_bad_steps=2)
        before = monitor.counters()
        guard.step(feed={'x': np.ones((4, 8), np.float32)},
                   fetch_list=[loss])
        assert guard.last_step_skipped
        info = guard.last_localization
        assert info is not None
        assert info['op_type'] == 'scale'
        assert info['bad_outputs'] == [boom.name]       # exactly that op
        assert info['input_stats']                      # input stats carried
        delta = monitor.counter_delta(before)
        assert delta.get('nonfinite_localized_total{op_type=scale}') == 1
        # escalation names the op too
        with pytest.raises(fluid.resilience.NonFiniteError) as ei:
            guard.step(feed={'x': np.ones((4, 8), np.float32)},
                       fetch_list=[loss])
        assert "type='scale'" in str(ei.value)

    def test_guard_reuses_executor_localization_no_double_count(
            self, monkeypatch):
        """check_nan_inf + TrainingGuard both armed: the guard must reuse
        the localization the executor's raise carried — ONE replay, ONE
        nonfinite_localized count per bad step."""
        monkeypatch.setenv('PADDLE_NAN_LOCALIZE', '1')
        boom, loss = self._boom_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        guard = fluid.TrainingGuard(exe, fluid.default_main_program(),
                                    loss_name=loss.name, max_bad_steps=9)
        before = monitor.counters()
        fluid.set_flags({'FLAGS_check_nan_inf': True})
        try:
            guard.step(feed={'x': np.ones((4, 8), np.float32)},
                       fetch_list=[loss])
        finally:
            fluid.set_flags({'FLAGS_check_nan_inf': False})
        assert guard.last_step_skipped
        assert guard.last_localization['op_type'] == 'scale'
        delta = monitor.counter_delta(before)
        assert delta.get('nonfinite_localized_total{op_type=scale}') == 1
        assert delta.get('op_profile_run_total') is None

    def test_explain_seeds_cache_with_localization_armed(
            self, monkeypatch):
        """PADDLE_NAN_LOCALIZE + check_nan_inf force donation off at run
        time; explain must cache under that SAME key (0 misses after)."""
        monkeypatch.setenv('PADDLE_NAN_LOCALIZE', '1')
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = _feed()
        fluid.set_flags({'FLAGS_check_nan_inf': True})
        try:
            exe.explain(fluid.default_main_program(), feed=feed,
                        fetch_list=[avg], memory=False)
            before = monitor.counters()
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[avg])
        finally:
            fluid.set_flags({'FLAGS_check_nan_inf': False})
        delta = monitor.counter_delta(before)
        assert not delta.get('compile_cache_miss'), delta

    def test_localization_off_by_default(self, monkeypatch):
        monkeypatch.delenv('PADDLE_NAN_LOCALIZE', raising=False)
        _, loss = self._boom_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        before = monitor.counters()
        guard = fluid.TrainingGuard(exe, fluid.default_main_program(),
                                    loss_name=loss.name, max_bad_steps=9)
        guard.step(feed={'x': np.ones((4, 8), np.float32)},
                   fetch_list=[loss])
        assert guard.last_step_skipped
        assert guard.last_localization is None
        assert not any('nonfinite_localized' in k
                       for k in monitor.counter_delta(before))


class TestMemoryUsage(object):
    def test_static_fallback_band(self):
        """No compiled executable: the reference-style ±30% dtype-size
        estimate (regression for the pre-analysis behavior)."""
        _build_mlp_train()
        from paddle_tpu.contrib import memory_usage
        lo, hi = memory_usage(fluid.default_main_program(), batch_size=16)
        assert 0 < lo < hi
        assert hi / lo == pytest.approx(1.3 / 0.7, rel=1e-6)
        with pytest.raises(ValueError):
            memory_usage(fluid.default_main_program(), batch_size=0)

    def test_fused_record_never_anchors_the_band(self):
        """A run_fused entry's peak covers the WHOLE k-step scan (stacked
        feeds included) and its feed dim 0 is the scan length — it must
        not be mistaken for a matching-batch compiled record."""
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batch, n_steps = 4, 4       # scan length == requested batch size
        exe.run_fused(fluid.default_main_program(),
                      feed_list=[_feed(batch, seed=i)
                                 for i in range(n_steps)],
                      fetch_list=[avg])
        rec = analysis.lookup(fluid.default_main_program(), kind='fused')
        assert rec is not None and rec.feed_batch == batch
        from paddle_tpu.contrib import memory_usage
        lo, hi = memory_usage(fluid.default_main_program(),
                              batch_size=n_steps)
        assert hi / lo == pytest.approx(1.3 / 0.7, rel=1e-6)   # static band

    def test_compiled_band_from_xla_peak(self):
        """With an analyzed executable at the same batch, the band comes
        from XLA buffer assignment (±10%, anchored at real peak_bytes)."""
        avg, _ = _build_mlp_train()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        batch = 16
        rep = exe.explain(fluid.default_main_program(), feed=_feed(batch),
                          fetch_list=[avg], memory=True)
        from paddle_tpu.contrib import memory_usage
        lo, hi = memory_usage(fluid.default_main_program(),
                              batch_size=batch)
        peak_mb = rep['peak_bytes'] / (1024.0 ** 2)
        assert lo == pytest.approx(peak_mb * 0.9, rel=1e-6)
        assert hi == pytest.approx(peak_mb * 1.1, rel=1e-6)
        # a different batch size must NOT reuse the compiled numbers
        lo2, hi2 = memory_usage(fluid.default_main_program(),
                                batch_size=batch * 2)
        assert hi2 / lo2 == pytest.approx(1.3 / 0.7, rel=1e-6)


class TestCostReportTool(object):
    def test_measure_costreport(self):
        import sys
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.costreport import measure_costreport, print_report
        rep = measure_costreport(batch=8, hidden=16)
        assert rep['train']['flops'] > rep['infer']['flops'] > 0
        assert rep['train']['peak_bytes'] > 0
        lo, hi = rep['memory_usage_mb']
        assert 0 < lo < hi
        import io
        buf = io.StringIO()
        print_report(rep, out=buf)
        assert 'peak_bytes' in buf.getvalue()
