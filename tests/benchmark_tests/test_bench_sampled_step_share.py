"""layer_metrics/decode_sampled_step_share.py: on hand-written `facts`
it reads 0 (the counter did not move: every step was the argmax), a
share, and nothing where there is no decode step or where the program
does not count (the parent of the PR that brought the counter); the
manifest lists it for the serve cells; and run.py's traced line at toy
width on the CPU prints it as 0, the toy traffic being greedy."""
import os

import pytest

from test_bench_run import (MANIFEST, ROOT, _last_json,  # noqa: F401
                            _load_run, by_name, run_on_cpu)

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.sampled.json')
NAME = 'decode_sampled_step_share'
COUNTER = 'generate_sampled_steps_total'


def _facts(steps, sampled=None, counts=True):
    facts = {'histograms': {'decode_step_seconds': (steps, 0.02 * steps),
                            'prefill_seconds': (3, 0.1)},
             'counters': {'decode_tokens_total': 32 * steps},
             'engine_stats': {'decode_steps': 1000 + steps}}
    if sampled is not None:
        facts['counters'][COUNTER] = sampled
    if counts:
        facts['engine_stats']['sampled_steps'] = 17
    return facts


@pytest.mark.parametrize('facts,value', [
    (_facts(200), 0.0),
    (_facts(200, sampled=50), 25.0),
    (_facts(200, sampled=200), 100.0),
    (_facts(0), None),
    (_facts(0, sampled=0), None),
    (_facts(200, counts=False), None),
    ({'histograms': {}, 'counters': {}}, None),
    ({}, None),
], ids=['all-greedy', 'a-quarter', 'every-step', 'no-decode-step',
        'no-decode-step-counter-there', 'program-without-the-counter',
        'no-histogram', 'no-facts'])
def test_reader(facts, value):
    read = _load_run().load_module(os.path.join(
        ROOT, 'benchmark', 'layer_metrics', NAME + '.py')).read
    got = read(facts)
    assert got is None if value is None else got == pytest.approx(value)
    assert value is None or isinstance(got, float)


def check_manifest_entry(manifest):
    """The whole entry, found by name, listed for every cell that reports
    the metric it moves."""
    serve = by_name(manifest['end_to_end'], 'itl_p95_ms')['workloads']
    assert by_name(manifest['per_layer'], NAME) == {
        'name': NAME, 'unit': '%', 'better': 'lower',
        'source': 'program_counter', 'layer': 'model step',
        'moves': 'itl_p95_ms', 'workloads': serve}


def test_manifest_entry():
    check_manifest_entry(MANIFEST)


def test_traced_line_reads_zero_on_greedy_traffic(run_on_cpu,  # noqa: F811
                                                  capsys):
    rc = run_on_cpu.main(['--workload', 'toy-serve', '--seed', '3000000029',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, _lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    assert out['metrics'][NAME] == {'value': 0.0, 'unit': '%'}
    assert out['metrics']['decode_step_ms']['value'] > 0
