"""The seven per-layer metrics PR 37 brought (benchmark/layer_metrics/
admission_ms*.py, token_gap_ms.*.py, token_gap_admission_share.py): each on
a hand-written `facts`, its value, and nothing where its counter is not
there (the parent commit: its `prefill` phase moves, the three nested in
it and the gap counters do not exist) or its denominator is 0; their
manifest entries by name; and run.py's traced line at toy width on the CPU
printing all seven, the parts of an admission inside the whole and the whole
beside prefill_seconds' mean."""
import os

import pytest

from test_bench_run import (MANIFEST, ROOT, _last_json,  # noqa: F401
                            _load_run, by_name, run_on_cpu)

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.admission.json')
LOOP = 'generate_loop_seconds_total{phase=%s}'
GAP_S = 'generate_token_gap_seconds_total{held=%s}'
GAPS = 'generate_token_gaps_total{held=%s}'
# The readers find their counters in all five serve cells. The manifest
# lists three: test_bench_joyai.py and test_bench_lfm2.py hold the SET of
# metrics that list their cells with `==`, and a PR of this kind edits no
# file the benchmark has. A `benchmark` PR appends the two (PERF.md 7).
SERVE_CELLS = ['fd355m-serve-chat', 'fd1.3b-serve-doc', 'olmoe-serve-chat16']

FACTS = {
    'decode_steps': 100,
    'counters': {
        LOOP % 'admit': 0.05, LOOP % 'feed': 0.1, LOOP % 'wait': 8.0,
        LOOP % 'prefill': 0.02, LOOP % 'prefill.dispatch': 0.06,
        LOOP % 'prefill.drain': 0.12, LOOP % 'prefill.fetch': 0.4,
        'generate_loop_wall_seconds_total': 10.0,
        'generate_admit_total': 20, 'decode_tokens_total': 3200,
        GAP_S % 'admission': 9.6, GAPS % 'admission': 320,
        GAP_S % 'none': 28.8, GAPS % 'none': 2880}}

# metric -> (unit, value on FACTS, the keys each of whose absence or zero
# leaves nothing)
CASES = {
    'admission_ms': ('ms', 30.0,
                     ['generate_admit_total', LOOP % 'prefill.fetch']),
    'admission_ms.dispatch': ('ms', 3.0, ['generate_admit_total',
                                          LOOP % 'prefill.dispatch']),
    'admission_ms.drain': ('ms', 6.0, ['generate_admit_total',
                                       LOOP % 'prefill.fetch']),
    'admission_ms.fetch': ('ms', 20.0, ['generate_admit_total',
                                        LOOP % 'prefill.fetch']),
    'token_gap_ms.admission': ('ms', 30.0, [GAPS % 'admission']),
    'token_gap_ms.plain': ('ms', 10.0, [GAPS % 'none']),
    'token_gap_admission_share': ('%', 10.0, []),
}


def _reader(name):
    return _load_run().load_module(
        os.path.join(ROOT, 'benchmark', 'layer_metrics', name + '.py')).read


def _with(**counters):
    """FACTS with these counters set; None takes one away."""
    out = dict(FACTS['counters'])
    for key, value in counters.items():
        if value is None:
            del out[key]
        else:
            out[key] = value
    return dict(FACTS, counters=out)


@pytest.mark.parametrize('name', sorted(CASES))
def test_reader_value(name):
    got = _reader(name)(FACTS)
    assert got == pytest.approx(CASES[name][1]) and isinstance(got, float)


@pytest.mark.parametrize('name,key', [(n, k) for n in sorted(CASES)
                                      for k in CASES[n][2]])
def test_reader_reads_nothing_without_its_counter_or_denominator(name, key):
    read = _reader(name)
    assert read(_with(**{key: None})) is None
    if key.startswith('generate_loop_seconds'):
        return      # a phase that did not move is not in the delta at all
    assert read(_with(**{key: 0})) is None


@pytest.mark.parametrize('name', sorted(CASES))
def test_reader_reads_nothing_from_the_parent_commit(name):
    """The program before PR 37: `prefill` and the old counters move, the
    nested phases and the gap counters do not exist. Nothing is read,
    nothing raises."""
    parent = {'decode_steps': 100, 'histograms': {}, 'counters': {
        LOOP % 'prefill': 0.6, LOOP % 'admit': 0.05, LOOP % 'wait': 8.0,
        'generate_loop_wall_seconds_total': 10.0,
        'generate_admit_total': 20, 'decode_tokens_total': 3200}}
    read = _reader(name)
    assert read(parent) is None
    assert read({'counters': {}}) is None and read({}) is None


def test_a_window_whose_admissions_found_nothing_in_flight_drains_zero():
    """`prefill.drain` opens only behind a step in flight: with the other
    phases there and this one not, the wait was 0, not unknown."""
    facts = _with(**{LOOP % 'prefill.drain': None})
    assert _reader('admission_ms.drain')(facts) == 0.0
    assert _reader('admission_ms')(facts) == pytest.approx(24.0)


def test_the_share_reads_both_ends():
    read = _reader('token_gap_admission_share')
    assert read(_with(**{GAPS % 'admission': None})) == 0.0
    assert read(_with(**{GAPS % 'none': None})) == 100.0
    assert read(_with(**{GAPS % 'admission': None, GAPS % 'none': None})) \
        is None
    assert read(_with(**{GAPS % 'admission': 0, GAPS % 'none': 0})) is None


def test_the_parts_of_an_admission_are_inside_the_whole():
    whole = _reader('admission_ms')(FACTS)
    parts = sum(_reader('admission_ms.' + p)(FACTS)
                for p in ('dispatch', 'drain', 'fetch'))
    assert parts <= whole and whole - parts == pytest.approx(1.0)


def check_admission_metrics(manifest, cells=SERVE_CELLS):
    """The seven entries, whole, by name; a cell appended to their lists
    since is the order test's to hold."""
    for name, (unit, _value, _keys) in CASES.items():
        got = by_name(manifest['per_layer'], name)
        assert dict(got, workloads=got['workloads'][:len(cells)]) == {
            'name': name, 'unit': unit, 'better': 'lower',
            'source': 'program_counter', 'layer': 'server',
            'moves': 'itl_p95_ms', 'workloads': cells}
        assert os.path.isfile(os.path.join(
            ROOT, 'benchmark', 'layer_metrics', name + '.py'))


def test_manifest_entries():
    check_admission_metrics(MANIFEST)
    # in the manifest's own order, as the metric they move lists them
    itl = by_name(MANIFEST['end_to_end'], 'itl_p95_ms')['workloads']
    assert itl[:len(SERVE_CELLS)] == SERVE_CELLS


def test_traced_line_prints_all_seven(run_on_cpu, capsys):  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve', '--seed', '3000000037',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    got = {n: out['metrics'][n] for n in CASES}     # KeyError: one missing
    for name, (unit, _value, _keys) in CASES.items():
        value = got[name]['value']
        assert got[name]['unit'] == unit
        assert value == value and 0 <= value < 1e6, (name, value)
    value = {n: m['value'] for n, m in got.items()}
    parts = sum(value['admission_ms.' + p]
                for p in ('dispatch', 'drain', 'fetch'))
    assert 0 < parts <= value['admission_ms'] * 1.001
    # the same stretch the histogram times, on the window's own counters
    assert value['admission_ms'] == pytest.approx(
        out['metrics']['prefill_ms.ttft']['value'], rel=0.25)
    # three closed-loop clients on three slots, five tokens a request:
    # admissions all through the window, and gaps of both kinds
    assert 0 < value['token_gap_admission_share'] < 100
    assert value['token_gap_ms.admission'] > value['token_gap_ms.plain'] > 0
