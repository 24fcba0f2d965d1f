"""layer_metrics/decode_overlapped_step_share.py: on hand-written `facts`
it reads a share, 0 where the counter did not move (no step had a
predecessor in flight), and nothing where there is no decode step or
where the program does not count (the parent of the PR that brought the
pipeline); never more than 100 on what the serve driver's snapshots can
give it (the loop books the observation, then the counter; the driver
reads the histograms outside the counters, so one booking can be split
when the window opens and none when it closes), while a count that is
off by more shows; and run.py's traced line at toy width on the CPU
prints a share above zero and at most 100, the toy loop keeping its
slots resident. BENCHMARK.json lists the metric since PR 34
(test_bench_manifest.py holds the entry)."""
import os

import pytest

from test_bench_run import (ROOT, _last_json, _load_run,  # noqa: F401
                            run_on_cpu)

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.overlapped.json')
NAME = 'decode_overlapped_step_share'
COUNTER = 'generate_overlapped_steps_total'


def _facts(steps, overlapped=None, counts=True):
    facts = {'histograms': {'decode_step_seconds': (steps, 0.005 * steps),
                            'prefill_seconds': (3, 0.1)},
             'counters': {'decode_tokens_total': 32 * steps},
             'engine_stats': {'decode_steps': 1000 + steps}}
    if overlapped is not None:
        facts['counters'][COUNTER] = overlapped
    if counts:
        facts['engine_stats']['overlapped_steps'] = 990
    return facts


@pytest.mark.parametrize('facts,value', [
    (_facts(200, overlapped=200), 100.0),
    (_facts(200, overlapped=199), 99.5),
    (_facts(200, overlapped=50), 25.0),
    (_facts(200), 0.0),
    (_facts(200, overlapped=201), 100.0),
    (_facts(200, overlapped=203), 101.5),
    (_facts(0), None),
    (_facts(0, overlapped=0), None),
    (_facts(200, overlapped=200, counts=False), None),
    ({'histograms': {}, 'counters': {}}, None),
    ({}, None),
], ids=['every-step', 'one-start', 'a-quarter', 'serial-loop',
        'one-booking-split-at-the-opening', 'a-counting-fault-shows',
        'no-decode-step', 'no-decode-step-counter-there',
        'program-without-the-counter', 'no-histogram', 'no-facts'])
def test_reader(facts, value):
    read = _load_run().load_module(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', NAME + '.py')).read
    got = read(facts)
    assert got is None if value is None else got == pytest.approx(value)
    assert value is None or isinstance(got, float)


def test_the_reader_never_passes_100_on_what_the_snapshots_can_give():
    """Every count of overlapped steps from none to one more than the
    window's steps, the most the opening snapshots can split off."""
    read = _load_run().load_module(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', NAME + '.py')).read
    for steps in (1, 2, 7, 200, 1733, 8650):
        for overlapped in {0, 1, steps // 2, steps - 1, steps, steps + 1}:
            assert 0.0 <= read(_facts(steps, overlapped=overlapped)) <= 100.0


def test_traced_line_reads_the_pipeline(run_on_cpu, capsys):  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve', '--seed', '3000000031',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    share = out['metrics'][NAME]
    assert share['unit'] == '%' and 50.0 < share['value'] <= 100.0
    assert out['metrics']['decode_step_ms']['value'] > 0
