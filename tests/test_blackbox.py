"""Incident flight recorder (paddle_tpu.blackbox +
tools/blackbox.py): every wired detector — retry give-up, goodput
bench-row drift, TrainingGuard NaN escalation — publishes exactly one
atomic machine-readable bundle; the replay CLI reproduces the NaN
localization offline; rotation and per-kind rate limiting bound a
trip storm; clean runs (and the default-off recorder) publish nothing;
the un-triggered executor hook stays under the 5 us hot-path budget."""
import gc
import json
import os
import time
import uuid

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import blackbox, goodput, monitor, resilience


@pytest.fixture
def bb(tmp_path, monkeypatch):
    """Recorder ON into a private root, unlimited rate, tiny retry
    backoffs; drained and reset on the way out so no other test sees a
    half-written queue. The goodput window starts clean too: its
    sentinels are process-wide, and a steady state left by an earlier
    file of the same worker would read this test's first compiles as a
    recompile storm."""
    d = str(tmp_path / 'bb')
    monkeypatch.setenv('PADDLE_BLACKBOX', '1')
    monkeypatch.setenv('PADDLE_BLACKBOX_DIR', d)
    monkeypatch.setenv('PADDLE_BLACKBOX_RATE', '0')
    monkeypatch.setenv('PADDLE_RETRY_BASE_S', '0.001')
    monkeypatch.setenv('PADDLE_RETRY_MAX_S', '0.01')
    blackbox.reset()
    goodput.reset()
    yield d
    blackbox.flush(10.0)
    blackbox.reset()


def _manifest(bundle):
    with open(os.path.join(bundle, 'manifest.json')) as f:
        return json.load(f)


def _boom_program():
    """The test_analysis inf-injection idiom: scale twice by 1e20 so the
    SECOND scale overflows float32 deterministically (no rng in the bad
    value's provenance — the replay must reproduce it bit-for-bit)."""
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    h = fluid.layers.fc(input=x, size=8, act='relu')
    big = fluid.layers.scale(h, scale=1e20)
    boom = fluid.layers.scale(big, scale=1e20)
    loss = fluid.layers.mean(boom)
    return boom, loss


# ---------------------------------------------------------------------------
# detector -> bundle paths


def test_retry_giveup_publishes_bundle(bb):
    def _always_down():
        raise ConnectionError('simulated wire drop')

    policy = resilience.RetryPolicy(max_attempts=2, base_delay_s=0.001,
                                    max_delay_s=0.002)
    with pytest.raises(ConnectionError):
        resilience.retry_call(_always_down, site='bb_unit', policy=policy)
    assert blackbox.flush(10.0)
    found = blackbox.bundles(bb)
    assert len(found) == 1
    m = _manifest(found[0])
    assert m['kind'] == 'retry_giveup'
    assert m['trigger']['site'] == 'bb_unit'
    assert m['trigger']['reason'] == 'exhausted'
    assert m['trigger']['attempts'] == 2
    assert 'ConnectionError' in m['error']
    for name in ('monitor.json', 'metrics.prom', 'env.json',
                 'traces.jsonl'):
        assert name in m['files']
        assert os.path.exists(os.path.join(found[0], name))
    # the capture is machine-readable all the way down
    with open(os.path.join(found[0], 'monitor.json')) as f:
        snap = json.load(f)
    assert 'retry_giveup_total{site=bb_unit}' in snap['counters']
    # atomic publish: no tmp litter next to the bundle
    assert not [e for e in os.listdir(bb) if e.startswith('.tmp.')]


def test_bench_row_drift_bundle_carries_baseline(bb):
    row = 'bb_row_' + uuid.uuid4().hex[:8]     # dodge the per-row cooldown
    assert not goodput.note_bench_row(row, 1.0, 10.0)
    assert blackbox.flush(10.0)
    found = blackbox.bundles(bb)
    assert len(found) == 1
    m = _manifest(found[0])
    assert m['kind'] == 'bench_row_drift'
    assert m['trigger']['row'] == row
    assert m['trigger']['baseline'] == 10.0
    assert m['trigger']['value'] == 1.0
    # the goodput ledger rode along (stats() only carries the regression
    # ring once a dispatch epoch exists, so assert the ring in-process)
    assert 'goodput.json' in m['files']
    trips = [r for r in goodput.regressions() if r.get('row') == row]
    assert trips and trips[-1]['baseline'] == 10.0


def test_nonfinite_escalation_bundle_replays(bb, monkeypatch, capsys):
    """Acceptance: the escalation bundle embeds the localization AND
    carries enough state that ``tools/blackbox.py replay`` re-executes
    the failed step offline and reproduces the same op provenance."""
    monkeypatch.setenv('PADDLE_NAN_LOCALIZE', '1')
    boom, loss = _boom_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    guard = fluid.TrainingGuard(exe, fluid.default_main_program(),
                                loss_name=loss.name, max_bad_steps=1)
    with pytest.raises(resilience.NonFiniteError):
        guard.step(feed={'x': np.ones((4, 8), np.float32)},
                   fetch_list=[loss])
    assert blackbox.flush(10.0)
    found = blackbox.bundles(bb)
    assert len(found) == 1
    m = _manifest(found[0])
    assert m['kind'] == 'nonfinite_escalate'
    assert m['replayable'] is True
    assert m['localization'] is not None
    assert m['localization']['op_type'] == 'scale'
    assert boom.name in m['localization']['bad_outputs']
    assert 'program.json' in m['files']
    assert 'replay/replay.json' in m['files']
    assert m['rng'] is not None
    # offline half: the CLI rebuilds program + state + rng key and runs
    # the step back through the localizer
    import tools.blackbox as bb_cli
    bb_cli.main(['replay', found[0]])
    out = capsys.readouterr().out
    assert 'REPRODUCED' in out


# ---------------------------------------------------------------------------
# negative space: no incident, no bundle


def test_disabled_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv('PADDLE_BLACKBOX', raising=False)
    monkeypatch.setenv('PADDLE_BLACKBOX_DIR', str(tmp_path / 'off'))
    blackbox.reset()
    assert not blackbox.enabled()
    assert blackbox.record('step_drift') is False
    blackbox.note_step(object())            # must be a no-op, not a stash
    assert blackbox._last_step[1] is None
    assert not os.path.exists(str(tmp_path / 'off'))
    blackbox.reset()


def test_clean_run_publishes_nothing(bb):
    """Recorder ON, healthy training: finite steps under the guard must
    not shed bundles (the clean-full-suite-zero-bundles contract)."""
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='int64')
    h = fluid.layers.fc(x, size=16, act='relu')
    p = fluid.layers.fc(h, size=4, act='softmax')
    loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    guard = fluid.TrainingGuard(exe, fluid.default_main_program(),
                                loss_name=loss.name, max_bad_steps=2)
    rng = np.random.RandomState(0)
    feed = {'x': rng.randn(16, 8).astype('float32'),
            'y': rng.randint(0, 4, (16, 1)).astype('int64')}
    for _ in range(3):
        guard.step(feed=feed, fetch_list=[loss])
    assert blackbox.flush(10.0)
    assert blackbox.bundles(bb) == []


# ---------------------------------------------------------------------------
# storm bounds: rotation + per-kind rate limit


def test_rotation_keeps_newest_n(bb, monkeypatch):
    monkeypatch.setenv('PADDLE_BLACKBOX_KEEP', '3')
    for i in range(5):
        assert blackbox.record('step_drift', storm_seq=i)
    assert blackbox.flush(10.0)
    found = blackbox.bundles(bb)
    assert len(found) == 3
    assert [_manifest(b)['trigger']['storm_seq'] for b in found] == \
        [2, 3, 4]                           # oldest rotated out, in order


def test_rate_limit_coalesces_storm(bb, monkeypatch):
    monkeypatch.setenv('PADDLE_BLACKBOX_RATE', '60')
    key = 'blackbox_rate_limited_total{kind=queue_burn}'
    before = monitor.counters().get(key, 0)
    results = [blackbox.record('queue_burn', n=i) for i in range(5)]
    assert results == [True, False, False, False, False]
    assert blackbox.flush(10.0)
    assert len(blackbox.bundles(bb)) == 1
    assert monitor.counters()[key] - before == 4
    # a DIFFERENT kind is not throttled by queue_burn's window
    assert blackbox.record('step_drift')
    assert blackbox.flush(10.0)
    assert len(blackbox.bundles(bb)) == 2


# ---------------------------------------------------------------------------
# hot path + log channel integration


def test_note_step_overhead_guard():
    """The exact per-dispatch addition (note_step) stays <= 5 us on AND
    off: interleaved min-of-per-call, gc disabled — the PR 9 methodology
    (a preempted timeslice poisons block averages but only one call)."""
    prog = object()
    n = 3000
    best_on = best_off = float('inf')
    gc.disable()
    try:
        for i in range(n):
            if i % 2 == 0:
                os.environ['PADDLE_BLACKBOX'] = '1'
                t0 = time.perf_counter()
                blackbox.note_step(prog)
                best_on = min(best_on, time.perf_counter() - t0)
            else:
                os.environ.pop('PADDLE_BLACKBOX', None)
                t0 = time.perf_counter()
                blackbox.note_step(prog)
                best_off = min(best_off, time.perf_counter() - t0)
    finally:
        gc.enable()
        os.environ.pop('PADDLE_BLACKBOX', None)
        blackbox.reset()
    assert best_on <= 5e-6, best_on
    assert best_off <= 5e-6, best_off


def test_bundle_pointer_rides_trace_log(bb, monkeypatch, tmp_path, capsys):
    """Publishing a bundle drops one pointer line on the trace/monitor
    log channel; tracereport separates it from spans, obsreport skips it
    as a snapshot and lists it under --bundles."""
    log = str(tmp_path / 'trace.jsonl')
    monkeypatch.setenv('PADDLE_TRACE_LOG', log)
    assert blackbox.record('step_drift', why='pointer_test')
    assert blackbox.flush(10.0)
    bundle = blackbox.bundles(bb)[0]
    with open(log) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    pointers = [r for r in recs if 'blackbox_bundle' in r]
    assert len(pointers) == 1
    assert pointers[0]['blackbox_bundle'] == bundle
    assert pointers[0]['kind'] == 'step_drift'
    assert pointers[0]['trace_id']          # always correlatable

    import tools.obsreport as obs
    import tools.tracereport as tr
    traces, _events, bundles = tr.read_records([log])
    assert [b['blackbox_bundle'] for b in bundles] == [bundle]
    assert all('blackbox_bundle' not in t for t in traces)
    assert obs._is_bundle_pointer(pointers[0])
    assert not obs._is_snapshot(pointers[0])
    obs.print_bundles([log])
    out = capsys.readouterr().out
    assert bundle in out and 'tools/blackbox.py show' in out


def test_list_and_show_cli(bb, capsys):
    assert blackbox.record('queue_burn', slo_ms=5.0, ewma_ms=9.0)
    assert blackbox.flush(10.0)
    bundle = blackbox.bundles(bb)[0]
    import tools.blackbox as bb_cli
    bb_cli.main(['list', bb])
    out = capsys.readouterr().out
    assert 'queue_burn' in out and '1 bundle(s)' in out
    bb_cli.main(['show', bundle])
    out = capsys.readouterr().out
    assert 'queue_burn' in out and 'slo_ms' in out


# ---------------------------------------------------------------------------
# heavy drill (nightly): the full elastic kill -> resume -> bundle chain


@pytest.mark.slow
def test_elastic_kill_drill_publishes_bundle():
    """chaosbench end-to-end: a fatal mid-run kill under
    elastic_train_loop still bit-matches the uninterrupted baseline AND
    publishes an elastic_resume bundle whose write cost lands on the
    bench row (measure_elastic_resume raises if the bundle is missing)."""
    from tools.chaosbench import measure_elastic_resume
    row = measure_elastic_resume(steps=6, kill_at=3)
    assert row['trajectory_parity'] is True
    assert row['bundles'] >= 1
    assert row['bundle_write_ms'] is not None
    assert row['bundle_write_ms'] >= 0.0


@pytest.mark.slow
def test_shrink_grow_drill_publishes_both_bundles():
    """chaosbench shrink-THEN-grow end-to-end: the kill halves the
    fleet, capacity returns mid-run and the loop re-expands — the drill
    bit-matches the uninterrupted baseline, reports time-to-recover in
    BOTH directions, and publishes bundles for both the elastic_resume
    and the elastic_grow incidents (measure_shrink_grow raises if
    either is missing)."""
    from tools.chaosbench import measure_shrink_grow
    row = measure_shrink_grow(steps=10, kill_at=3, grow_at=6)
    assert row['trajectory_parity'] is True
    assert row['time_to_recover_shrink_s'] is not None
    assert row['time_to_recover_grow_s'] is not None
    assert row['counters'].get('elastic_grow_total', 0) == 1
