"""Goodput/MFU accounting layer + perf-regression sentinel
(paddle_tpu/goodput.py, tools/perfwatch.py).

Load-bearing contracts:

- every dispatch kind (run / run_fused / bind / MeshRunner) contributes
  (device-busy seconds, flops, bytes) keyed by program fingerprint, and
  the live gauges agree with the analysis registry's XLA numbers;
- in a tight training loop the breakdown ACCOUNTS for the wall: execute
  plus the named loss buckets (compile / ckpt / retry_backoff / ...)
  sum to >= 90% of the window (the ISSUE 14 acceptance bound);
- the sentinel trips EXACTLY once per injected condition (step-time
  drift, recompile storm, spec accept collapse, queue-SLO burn), as
  perf_regression_total{kind} plus an always-kept trace event;
- the dispatch hook costs <= 5 us (min-of-per-call, gc off — the PR 9
  guard methodology) and introduces ZERO recompiles after warmup;
- perfwatch --merge aggregates rank logs into fleet numbers (flops/s,
  goodput_frac, fleet MFU) no single rank could report.

The fc programs share one structure family so the process-wide
fingerprint cache compiles each shape once per suite. The real
two-process rank-log merge is @slow (tests/conftest.py asserts this
file's marker split); tier-1 exercises the same merge math on crafted
rank snapshots.
"""
import gc
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import analysis, goodput, monitor


@pytest.fixture(autouse=True)
def _fresh_goodput():
    goodput.reset()
    yield
    goodput.reset()


def _fc_program(width=128, layers=2):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data('x', shape=[width], dtype='float32')
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, size=width, act='relu')
        out = fluid.layers.reduce_mean(h)
    return main, startup, out


def _warm(exe, scope, main, startup, out, batch=64, width=128):
    feed = {'x': np.random.RandomState(0)
            .rand(batch, width).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    return feed


def test_run_accounting_matches_registry(monkeypatch):
    """N steady-state runs account N dispatches whose flops equal
    N x the registry's XLA count, the gauges exist on snapshot, and
    step_mfu divides by the (env-pinned) peak exactly."""
    monkeypatch.setenv('PADDLE_PEAK_FLOPS', '1e12')
    monkeypatch.setenv('PADDLE_PEAK_HBM_BPS', '1e11')
    exe, scope = fluid.Executor(), fluid.Scope()
    main, startup, out = _fc_program()
    feed = _warm(exe, scope, main, startup, out)
    goodput.reset()
    before = monitor.counters()
    with fluid.scope_guard(scope):
        for _ in range(20):
            exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    st = goodput.stats()
    assert st['dispatches'] == 20
    assert set(st['by_kind']) == {'run'}
    assert st['by_kind']['run']['steps'] == 20
    assert 0.0 < st['goodput_frac'] <= 1.0
    rec = analysis.lookup(main, kind='run')
    assert rec is not None and rec.flops
    assert st['flops'] == pytest.approx(20 * rec.flops)
    assert st['step_mfu'] == pytest.approx(
        st['flops'] / st['productive_s'] / 1e12, rel=1e-3)
    assert st['hbm_bw_util_frac'] > 0
    # zero recompiles introduced by the accounting layer after warmup
    delta = monitor.counter_delta(before)
    assert not any(k.startswith('compile_cache_miss') for k in delta), \
        delta
    snap = monitor.snapshot()
    for g in ('goodput_frac', 'step_mfu', 'model_flops_per_s',
              'goodput_wall_seconds', 'goodput_productive_seconds'):
        assert g in snap['gauges'], g
    assert any(k.startswith('goodput_loss_seconds')
               for k in snap['gauges'])
    assert any(k.startswith('goodput_device_seconds_total')
               for k in snap['counters'])
    # engine-style fingerprint filtering: this program's fp keeps the
    # dispatches, a foreign fp sees none
    assert goodput.stats(fps=[main._fingerprint()])['dispatches'] == 20
    assert goodput.stats(fps=['fp:nope'])['dispatches'] == 0


def test_fused_bound_mesh_kinds_account():
    """run_fused (steps multiplied), bind (per-token decode path) and
    MeshRunner each contribute under their own kind; fused flops scale
    by the scan length (XLA counts the while body once)."""
    import jax
    exe, scope = fluid.Executor(), fluid.Scope()
    main, startup, out = _fc_program()
    feed = _warm(exe, scope, main, startup, out)
    with fluid.scope_guard(scope):
        # fused: compile pass, then an accounted steady pass
        stacked = {'x': np.stack([feed['x']] * 3)}
        exe.run_fused(main, stacked, fetch_list=[out], scope=scope)
        goodput.reset()
        exe.run_fused(main, stacked, fetch_list=[out], scope=scope)
        bound = exe.bind(main, feed, fetch_list=[out], scope=scope)
        bound(feed)
        bound(feed)
    st = goodput.stats()
    assert st['by_kind']['fused']['dispatches'] == 1
    assert st['by_kind']['fused']['steps'] == 3
    assert st['by_kind']['bound']['dispatches'] == 2
    rec = analysis.lookup(main, kind='fused')
    assert st['by_kind']['fused']['flops'] == pytest.approx(
        3 * rec.flops)

    # mesh: one compile call, then an accounted steady call
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.parallel import make_mesh, MeshRunner
    mesh_main, mesh_start, mesh_out = _fc_program(width=64, layers=1)
    runner = MeshRunner(mesh_main, make_mesh([('data', 2)]),
                        feed_specs={'x': P('data')})
    s2 = fluid.Scope()
    mfeed = {'x': np.random.rand(8, 64).astype('float32')}
    with fluid.scope_guard(s2):
        exe.run(mesh_start, scope=s2)
        runner.run(mfeed, [mesh_out.name], s2)      # compile (not busy)
        runner.run(mfeed, [mesh_out.name], s2)
    st = goodput.stats()
    assert st['by_kind']['mesh']['dispatches'] == 1
    assert st['by_kind']['mesh']['flops'] > 0, \
        "MeshRunner executables must register flops analytics"


def test_live_mfu_agrees_with_offline_window():
    """The live flops rate over the accounted window agrees with the
    offline formula (registry flops / measured wall), with a CI margin
    for box noise."""
    exe, scope = fluid.Executor(), fluid.Scope()
    main, startup, out = _fc_program()
    feed = _warm(exe, scope, main, startup, out)
    analysis.lookup(main, kind='run')       # warm the XLA cost mining
    goodput.reset()
    t0 = time.perf_counter()
    with fluid.scope_guard(scope):
        for _ in range(30):
            exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    wall = time.perf_counter() - t0
    st = goodput.stats()
    offline_rate = st['flops'] / wall       # includes host tax
    live_rate = st['flops'] / st['productive_s']
    # live ≥ offline (productive ⊆ wall) and same order of magnitude on
    # this tiny model where host tax is comparable to device time; the
    # bench flagship cross-check (larger steps) pins the 10% bound
    assert offline_rate <= live_rate < offline_rate * 6
    assert st['productive_s'] <= wall * 1.05


def test_breakdown_accounts_90pct_of_wall():
    """ISSUE 14 acceptance: in a training loop with injected compile,
    checkpoint and retry-backoff losses, execute + the named loss
    buckets sum to >= 90% of the goodput window's wall."""
    import tempfile
    import shutil
    import orbax.checkpoint              # noqa: F401 — the first orbax
    # import costs ~2 s and happens lazily inside save_checkpoint;
    # warming it keeps one-time process setup out of the loss window
    from paddle_tpu import checkpoint, resilience
    exe, scope = fluid.Executor(), fluid.Scope()
    main, startup, out = _fc_program(width=512, layers=4)
    feed = {'x': np.random.RandomState(1)
            .rand(256, 512).astype('float32')}
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    analysis.lookup(main, kind='run')
    goodput.reset()
    ckpt_dir = tempfile.mkdtemp(prefix='goodput_ckpt_')
    try:
        with fluid.scope_guard(scope):
            for i in range(40):
                exe.run(main, feed=feed, fetch_list=[out], scope=scope)
                if i == 10:
                    # a mid-loop recompile: fresh structure -> the
                    # compile loss bucket
                    m2, s2, o2 = _fc_program(width=96, layers=1)
                    sc2 = fluid.Scope()
                    f2 = _warm(exe, sc2, m2, s2, o2, batch=8, width=96)
                if i == 20:
                    # a blocking checkpoint write -> the ckpt bucket
                    checkpoint.save_checkpoint(ckpt_dir,
                                               main_program=main,
                                               scope=scope, step=i)
                if i == 30:
                    # a transient failure -> the retry_backoff bucket
                    boom = [True]

                    def _flaky():
                        if boom[0]:
                            boom[0] = False
                            raise resilience.InjectedFault(
                                'test', 'transient', transient=True)
                        return 1
                    policy = resilience.RetryPolicy(
                        max_attempts=2, base_delay_s=0.05,
                        max_delay_s=0.05, jitter=0.0)
                    assert policy.call(_flaky, site='test_goodput') == 1
        st = goodput.stats()
        wall = st['window_s']
        accounted = st['productive_s'] + sum(st['loss_buckets'].values())
        assert st['loss_buckets']['compile'] > 0
        assert st['loss_buckets']['ckpt'] > 0
        assert st['loss_buckets']['retry_backoff'] >= 0.04
        assert accounted >= 0.90 * wall, \
            (accounted / wall, st['loss_buckets'], st['productive_s'],
             wall)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _regression_count(kind):
    return monitor.counters().get(
        'perf_regression_total{kind=%s}' % kind, 0)


def test_sentinel_step_drift_trips_exactly_once(monkeypatch):
    monkeypatch.setenv('PADDLE_PERFWATCH_MIN_SAMPLES', '8')
    monkeypatch.setenv('PADDLE_PERFWATCH_EWMA', '1.0')
    monkeypatch.setenv('PADDLE_PERFWATCH_STEP_DRIFT', '2.0')
    before = _regression_count('step_drift')
    t = time.perf_counter()
    for i in range(8):                      # baseline: 1 ms steps
        goodput.note_dispatch('fp:drift', 'run', t, t + 0.001)
        t += 0.002
    for i in range(12):                     # sustained 10 ms drift
        goodput.note_dispatch('fp:drift', 'run', t, t + 0.010)
        t += 0.012
    goodput.flush()
    assert _regression_count('step_drift') == before + 1
    trips = [r for r in goodput.regressions()
             if r['kind'] == 'step_drift']
    assert trips and trips[-1]['ewma_ms'] > trips[-1]['baseline_ms']


def test_sentinel_recompile_storm_after_warmup(monkeypatch):
    """Warmup compiles never trip (no frozen baseline yet); a burst of
    fresh-signature compiles in steady state trips exactly once."""
    monkeypatch.setenv('PADDLE_PERFWATCH_MIN_SAMPLES', '4')
    monkeypatch.setenv('PADDLE_PERFWATCH_RECOMPILE_N', '4')
    monkeypatch.setenv('PADDLE_PERFWATCH_RECOMPILE_WINDOW_S', '30')
    before = _regression_count('recompile_storm')
    exe, scope = fluid.Executor(), fluid.Scope()
    main, startup, out = _fc_program()
    feed = _warm(exe, scope, main, startup, out)    # warmup compile
    with fluid.scope_guard(scope):
        for _ in range(4):                          # freeze a baseline
            exe.run(main, feed=feed, fetch_list=[out], scope=scope)
    goodput.flush()
    assert _regression_count('recompile_storm') == before, \
        "warmup compiles must not trip the storm sentinel"
    # storm: 4 fresh signatures (same program, new feed shapes — the
    # classic shape-churn production storm)
    with fluid.scope_guard(scope):
        for b in (3, 5, 7, 11):
            exe.run(main, feed={'x': np.random.rand(b, 128)
                                .astype('float32')},
                    fetch_list=[out], scope=scope)
    assert _regression_count('recompile_storm') == before + 1


def test_sentinel_accept_collapse_and_queue_burn(monkeypatch, tmp_path):
    monkeypatch.setenv('PADDLE_PERFWATCH_MIN_SAMPLES', '8')
    monkeypatch.setenv('PADDLE_PERFWATCH_EWMA', '1.0')
    monkeypatch.setenv('PADDLE_PERFWATCH_ACCEPT_DROP', '0.5')
    monkeypatch.setenv('PADDLE_PERFWATCH_QUEUE_SLO_MS', '10')
    log = tmp_path / 'trace.jsonl'
    monkeypatch.setenv('PADDLE_TRACE_LOG', str(log))
    b_acc = _regression_count('accept_collapse')
    b_q = _regression_count('queue_burn')
    for _ in range(8):
        goodput.note_accept(1.0, model='m')         # baseline 1.0
    for _ in range(10):
        goodput.note_accept(0.1, model='m')         # collapse
    assert _regression_count('accept_collapse') == b_acc + 1
    for _ in range(10):
        goodput.note_queue_wait(0.05)               # 50 ms >> 10 ms SLO
    assert _regression_count('queue_burn') == b_q + 1
    # the trip events rode the always-kept trace channel
    events = [json.loads(l) for l in log.read_text().splitlines()]
    kinds = {e.get('regression') for e in events
             if e.get('event') == 'perf_regression'}
    assert {'accept_collapse', 'queue_burn'} <= kinds


def test_sentinel_bench_row_drift():
    """The registered-baseline row sentinel (PR 16, backs the
    servebench serving-row registration): readings within
    baseline * floor stay silent, a reading below the floor trips
    bench_row_drift once per cooldown, and floor_frac overrides the
    PADDLE_PERFWATCH_ROW_DRIFT default."""
    before = _regression_count('bench_row_drift')
    # 1.6 vs baseline 1.77: well inside the default 0.5 floor
    assert goodput.note_bench_row('serving_speedup', 1.6, 1.77)
    assert _regression_count('bench_row_drift') == before
    # the r06-style reading (0.84 < 1.77 * 0.5) trips — but only once
    # for the same row inside the cooldown window
    assert not goodput.note_bench_row('serving_speedup', 0.84, 1.77)
    assert not goodput.note_bench_row('serving_speedup', 0.85, 1.77)
    assert _regression_count('bench_row_drift') == before + 1
    # per-row cooldown keys: a different row still trips, and an
    # explicit floor_frac tightens the default
    assert not goodput.note_bench_row('other_row', 0.9, 1.0,
                                      floor_frac=0.95)
    assert _regression_count('bench_row_drift') == before + 2
    trips = [r for r in goodput.regressions()
             if r['kind'] == 'bench_row_drift']
    assert trips[-1]['row'] == 'other_row'
    assert trips[-1]['baseline'] == 1.0


def test_stats_returns_while_a_loop_keeps_a_dispatch_in_flight():
    """A pipelined serving loop always has a step in flight: by the time
    one record's leaf is done the next record is queued. A blocking
    drain takes what was queued when it began and returns — stats()
    must not chase the queue for as long as the loop runs (on the chip:
    `eng.stats()` held a benchmark's window open for 50 s)."""
    class _Leaf(object):
        """Done after 1 ms, by which time the loop dispatched again."""

        def block_until_ready(self):
            time.sleep(0.001)
            now = time.perf_counter()
            goodput.note_dispatch('fp:loop', 'run', now, now, leaf=_Leaf())
            return self
    now = time.perf_counter()
    goodput.note_dispatch('fp:loop', 'run', now, now, leaf=_Leaf())
    t0 = time.perf_counter()
    done = goodput.stats()
    assert time.perf_counter() - t0 < 1.0
    assert done['dispatches'] >= 1


def test_dispatch_hook_overhead_guard():
    """The exact per-dispatch addition (note_dispatch) stays <= 5 us:
    interleaved min-of-per-call, gc disabled — the PR 9 methodology (a
    preempted timeslice poisons block averages but only one call)."""
    import paddle_tpu.goodput as gp
    n = 3000
    t = time.perf_counter()
    best_on = best_off = float('inf')
    gc.disable()
    try:
        for i in range(n):
            if i % 2 == 0:
                os.environ.pop('PADDLE_PERFWATCH', None)
                t0 = time.perf_counter()
                gp.note_dispatch('fp:guard', 'run', t, t)
                best_on = min(best_on, time.perf_counter() - t0)
            else:
                os.environ['PADDLE_PERFWATCH'] = '0'
                t0 = time.perf_counter()
                gp.note_dispatch('fp:guard', 'run', t, t)
                best_off = min(best_off, time.perf_counter() - t0)
    finally:
        gc.enable()
        os.environ.pop('PADDLE_PERFWATCH', None)
    assert best_on <= 5e-6, best_on
    assert best_off <= 5e-6, best_off


def _rank_snapshot(rank, wall, productive, flops, mfu):
    fp = 'fp:lm%d' % rank
    return {
        'ts': 1.0 + rank, 'rank': rank,
        'gauges': {
            'goodput_wall_seconds': wall,
            'goodput_productive_seconds': productive,
            'goodput_frac': productive / wall,
            'step_mfu': mfu,
            'goodput_loss_seconds{bucket=compile}': 0.5,
        },
        'counters': {
            'goodput_device_seconds_total{fingerprint=%s,kind=run,'
            'model=lm}' % fp: productive,
            'goodput_dispatch_total{fingerprint=%s,kind=run,model=lm}'
            % fp: 100,
            'goodput_steps_total{fingerprint=%s,kind=run,model=lm}'
            % fp: 100,
            'goodput_flops_total{fingerprint=%s,kind=run,model=lm}'
            % fp: flops,
            'goodput_bytes_total{fingerprint=%s,kind=run,model=lm}'
            % fp: flops / 10.0,
            'perf_regression_total{kind=step_drift}': rank,  # rank1 only
        },
        'histograms': {},
    }


def test_perfwatch_merge_two_ranks(tmp_path, capsys):
    """Fleet aggregation neither rank could produce alone: fleet
    flops/s and fleet MFU come from SUMMED cross-rank counters against
    a peak inferred from one rank's own gauge."""
    from tools import perfwatch
    peak = 1e12
    # rank0: 5 s busy of 10 s wall at MFU 0.2 -> 1e12 flops
    # rank1: 8 s busy of 10 s wall at MFU 0.3 -> 2.4e12 flops
    s0 = _rank_snapshot(0, 10.0, 5.0, 5.0 * 0.2 * peak, 0.2)
    s1 = _rank_snapshot(1, 10.0, 8.0, 8.0 * 0.3 * peak, 0.3)
    rep = perfwatch.report_from_snapshots([s0, s1])
    assert rep['ranks'] == 2
    assert rep['productive_s'] == pytest.approx(13.0)
    assert rep['goodput_frac'] == pytest.approx(13.0 / 20.0)
    fleet_flops = 1e12 + 2.4e12
    assert rep['flops'] == pytest.approx(fleet_flops)
    # fleet MFU = sum-flops / sum-busy / peak — 0.2615..., a number
    # that appears in NEITHER rank's gauges
    assert rep['step_mfu'] == pytest.approx(fleet_flops / 13.0 / peak,
                                            rel=1e-6)
    assert rep['step_mfu'] not in (0.2, 0.3)
    assert rep['regression_counts'] == {'step_drift': 1}

    # the CLI path end to end: rank logs + a sentinel trace event line
    f0, f1 = tmp_path / 'log.rank0', tmp_path / 'log.rank1'
    f0.write_text(json.dumps(s0) + '\n')
    f1.write_text(json.dumps(s1) + '\n' + json.dumps(
        {'trace_id': 'x', 'kind': 'perf', 'event': 'perf_regression',
         'regression': 'step_drift', 'ts': 2.0}) + '\n')
    perfwatch.main(['--merge', str(f0), str(f1), '--json'])
    out = json.loads(capsys.readouterr().out)
    assert out['flops'] == pytest.approx(fleet_flops)
    assert out['regression_events'][0]['regression'] == 'step_drift'
    # human report renders without error
    perfwatch.main(['--merge', str(f0), str(f1)])
    text = capsys.readouterr().out
    assert 'goodput' in text and 'step_drift' in text


@pytest.mark.slow
def test_two_rank_merge_real_processes(tmp_path):
    """The real thing: two worker processes (rank-tagged like
    distributed.launch) each train, log snapshots, and perfwatch
    --merge recovers the fleet view. Heavy (two fresh jax imports) —
    tier-1 covers the merge math on crafted snapshots above."""
    import subprocess
    import sys
    prog = r'''
import os, numpy as np
import paddle_tpu as fluid
from paddle_tpu import monitor, goodput
exe, scope = fluid.Executor(), fluid.Scope()
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data('x', shape=[128], dtype='float32')
    h = fluid.layers.fc(x, size=128, act='relu')
    h = fluid.layers.fc(h, size=128, act='relu')
    out = fluid.layers.reduce_mean(h)
feed = {'x': np.random.rand(64, 128).astype('float32')}
with fluid.scope_guard(scope):
    exe.run(startup, scope=scope)
    for _ in range(12):
        exe.run(main, feed=feed, fetch_list=[out], scope=scope)
monitor.log_snapshot(os.environ['GOODPUT_LOG'])
'''
    logs = []
    for rank in range(2):
        log = tmp_path / ('run.jsonl.rank%d' % rank)
        logs.append(str(log))
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   PADDLE_TRAINER_ID=str(rank),
                   GOODPUT_LOG=str(log))
        subprocess.run([sys.executable, '-c', prog], check=True,
                       env=env, timeout=300, cwd='/root/repo')
    from tools import perfwatch
    snaps = [perfwatch.read_log(p)[0] for p in logs]
    rep = perfwatch.report_from_snapshots(snaps)
    assert rep['ranks'] == 2
    assert rep['productive_s'] > 0
    assert rep['flops'] > 0
    # both ranks contributed dispatches the other cannot see
    assert sum(r['dispatches'] for r in rep['signatures']) >= 22
