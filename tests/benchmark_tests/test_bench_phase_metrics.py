"""The per-layer metrics that read the program's phase counters
(benchmark/phase_counters.py and its readers): each on a hand-written
`facts`, its value and nothing where there is nothing to divide by or to
read, as on a program from before the phases; and run.py's traced line at
toy width on the CPU printing every one of them."""
import os

import pytest

from benchmark import phase_counters
from test_bench_run import (MANIFEST, ROOT, _last_json,  # noqa: F401
                            _load_run, run_on_cpu)

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.phases.json')
LOOP, RUN = 'generate_loop_seconds_total', 'executor_run_phase_seconds_total'

SERVE = {
    'decode_steps': 100,
    'counters': {
        LOOP + '{phase=admit}': 0.05, LOOP + '{phase=feed}': 0.1,
        LOOP + '{phase=dispatch}': 0.2, LOOP + '{phase=deliver}': 0.15,
        LOOP + '{phase=prefill}': 1.0, LOOP + '{phase=wait}': 8.0,
        LOOP + '{phase=admit_overlapped}': 0.3,
        'generate_loop_wall_seconds_total': 10.0,
        'generate_queue_wait_seconds_total': 0.9,
        'generate_admit_total': 30, 'decode_tokens_total': 3200}}
TRAIN = {
    'counters': {
        RUN + '{phase=prepare}': 0.2, RUN + '{phase=dispatch}': 0.5,
        RUN + '{phase=commit}': 0.1, RUN + '{phase=fetch}': 30.0,
        'executor_run_total': 100}}

# metric -> (facts, value, the key whose absence or zero leaves nothing)
CASES = {
    'decode_host_gap_ms': (SERVE, 5.0, 'decode_steps'),
    'decode_host_gap_ms.admit': (SERVE, 0.5, 'decode_steps'),
    'decode_host_gap_ms.feed': (SERVE, 1.0, 'decode_steps'),
    'decode_host_gap_ms.dispatch': (SERVE, 2.0, 'decode_steps'),
    'decode_host_gap_ms.deliver': (SERVE, 1.5, 'decode_steps'),
    'queue_wait_ms': (SERVE, 30.0, 'generate_admit_total'),
    'server_loop_unaccounted_share': (
        SERVE, 2.0, 'generate_loop_wall_seconds_total'),
    'run_host_ms': (TRAIN, 8.0, 'executor_run_total'),
    'run_host_ms.prepare': (TRAIN, 2.0, 'executor_run_total'),
    'run_host_ms.dispatch': (TRAIN, 5.0, 'executor_run_total'),
    'run_host_ms.commit': (TRAIN, 1.0, 'executor_run_total'),
}


def _reader(name):
    return _load_run().load_module(
        os.path.join(ROOT, 'benchmark', 'layer_metrics', name + '.py'))


def _without(facts, key, zero):
    """`facts` with `key` (of facts or of its counters) zero or gone."""
    out = dict(facts, counters=dict(facts['counters']))
    where = out if key in out else out['counters']
    if zero:
        where[key] = 0
    else:
        del where[key]
    return out


@pytest.mark.parametrize('name', sorted(CASES))
def test_reader_value(name):
    facts, value, _key = CASES[name]
    assert _reader(name).read(facts) == pytest.approx(value)


@pytest.mark.parametrize('name', sorted(CASES))
def test_reader_reads_nothing_without_its_denominator(name):
    facts, _value, key = CASES[name]
    read = _reader(name).read
    assert read(_without(facts, key, zero=True)) is None
    assert read(_without(facts, key, zero=False)) is None


@pytest.mark.parametrize('name', sorted(CASES))
def test_reader_reads_nothing_from_a_program_without_the_phases(name):
    """The parent of the PR that brought the phases: the old counters
    move, the phase counters do not exist. Nothing is read, nothing
    raises."""
    old = {'decode_steps': 100, 'histograms': {},
           'counters': {'executor_run_total': 100,
                        'decode_tokens_total': 3200}}
    assert _reader(name).read(old) is None
    assert _reader(name).read({}) is None


def test_phase_seconds_sums_the_phases_asked_for():
    c = SERVE['counters']
    assert phase_counters.phase_seconds(c, LOOP, ('feed', 'wait')) \
        == pytest.approx(8.1)
    assert phase_counters.phase_seconds(c, LOOP) == pytest.approx(9.8)
    assert phase_counters.phase_seconds(c, LOOP, ('idle',)) is None
    assert phase_counters.phase_seconds(c, RUN) is None
    assert phase_counters.per_ms(None, 3) is None
    assert phase_counters.per_ms(0.5, 0) is None


def check_phase_metrics(manifest):
    assert {m['name'] for m in manifest['per_layer']} >= set(CASES)
    for m in manifest['per_layer']:
        if m['name'] in CASES:
            assert m['source'] == 'program_counter'
            assert m['layer'] == ('trainer API' if CASES[m['name']][0]
                                  is TRAIN else 'server')


def test_every_phase_metric_of_the_manifest_has_a_case():
    check_phase_metrics(MANIFEST)


@pytest.mark.parametrize('workload,metrics', [
    ('toy-train', {n for n in CASES if n.startswith('run_host_ms')}),
    ('toy-serve', {n for n in CASES if not n.startswith('run_host_ms')})])
def test_traced_line_prints_the_phase_metrics(run_on_cpu, capsys,  # noqa: F811
                                              workload, metrics):
    rc = run_on_cpu.main(['--workload', workload, '--seed', '2147483999',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    assert metrics <= set(out['metrics'])
    for name in metrics:
        value = out['metrics'][name]['value']
        assert value == value and abs(value) < 1e6, (name, value)
    assert out['metrics']['server_loop_unaccounted_share' if
                          workload == 'toy-serve' else 'run_host_ms'][
        'value'] >= 0
