"""layer_metrics/decode_overlapped_step_share.py: on hand-written `facts`
it reads a share, 0 where the counter did not move (no step had a
predecessor in flight), and nothing where there is no decode step or
where the program does not count (the parent of the PR that brought the
pipeline); and run.py's traced line at toy width on the CPU prints a
share above zero, the toy loop keeping its slots resident.

BENCHMARK.json does not list the metric yet, only the toy manifest here
does: test_bench_sampled_step_share.py holds ITS entry to be the last of
`per_layer`, a new entry goes at the end, and only a `benchmark` PR may
edit that test (PERF.md section 7 has the entry to add)."""
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
    (_facts(0), None),
    (_facts(0, overlapped=0), None),
    (_facts(200, overlapped=200, counts=False), None),
    ({'histograms': {}, 'counters': {}}, None),
    ({}, None),
], ids=['every-step', 'one-start', 'a-quarter', 'serial-loop',
        'no-decode-step', 'no-decode-step-counter-there',
        'program-without-the-counter', 'no-histogram', 'no-facts'])
def test_reader(facts, value):
    read = _load_run().load_module(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', NAME + '.py')).read
    got = read(facts)
    assert got is None if value is None else got == pytest.approx(value)
    assert value is None or isinstance(got, float)


def test_traced_line_reads_the_pipeline(run_on_cpu, capsys):  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve', '--seed', '3000000031',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    share = out['metrics'][NAME]
    assert share['unit'] == '%' and 50.0 < share['value'] < 110.0
    assert out['metrics']['decode_step_ms']['value'] > 0
