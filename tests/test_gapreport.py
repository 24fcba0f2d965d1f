"""tools/gapreport.py: device idle time by the innermost `paddle_tpu:` span
at each gap's middle and busy time by XLA module, on hand-made lists and on
220 ms of a trace recorded on the v5e (tests/fixtures/)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), 'tools'))

import gapreport  # noqa: E402

# one device, ns: ops [0,20) [30,40) [60,70) in a window [0,100); the run
# span [18,78) holds prepare [18,32) and fetch [45,55); two modules
TRACE = {
    'devices': {'/device:TPU:0': [('a', 0, 10), ('b', 5, 15), ('a', 30, 10),
                                  ('c', 60, 10)]},
    'host': [('bench:traced', 0, 100)],
    'spans': [('run', 18, 60), ('run.prepare', 18, 14),
              ('run.fetch', 45, 10)],
    'modules': {'/device:TPU:0': [('jit_lm_train', 0, 41),
                                  ('jit_program', 58, 14)]},
}


def test_idle_goes_to_the_innermost_span_at_the_gaps_middle():
    rep = gapreport.report(TRACE, min_gap_ns=5)
    assert rep['window_s'] == pytest.approx(100e-9)
    assert rep['busy_s'] == pytest.approx(40e-9)
    assert rep['idle_s'] == pytest.approx(60e-9)
    # [20,30) middle 25 -> prepare; [40,60) middle 50 -> fetch, nested in
    # run; [70,100) middle 85 -> after run closed
    assert rep['idle'] == {'run.prepare': [pytest.approx(10e-9), 1],
                           'run.fetch': [pytest.approx(20e-9), 1],
                           'none': [pytest.approx(30e-9), 1]}
    assert rep['labelled_share'] == pytest.approx(0.5)
    assert rep['short_gaps_s'] == 0


def test_short_gaps_are_summed_apart():
    rep = gapreport.report(TRACE, min_gap_ns=15)
    assert set(rep['idle']) == {'run.fetch', 'none'}
    assert rep['short_gaps_s'] == pytest.approx(10e-9)
    assert rep['idle_s'] == pytest.approx(60e-9)
    assert rep['labelled_share'] == pytest.approx(0.4)


def test_busy_goes_to_the_module_the_operations_ran_under():
    rep = gapreport.report(TRACE, min_gap_ns=5)
    # jit_lm_train [0,41) holds the unions [0,20) and [30,40)
    assert rep['busy'] == {'jit_lm_train': pytest.approx(30e-9),
                           'jit_program': pytest.approx(10e-9)}
    assert sum(rep['busy'].values()) == pytest.approx(rep['busy_s'])
    assert rep['runs'] == {'jit_lm_train': 1, 'jit_program': 1}


def test_the_split_follows_the_innermost_span_through_a_gap():
    rep = gapreport.report(TRACE, min_gap_ns=5)
    # [20,30): prepare; [40,60): run 5, fetch 10, run 5; [70,100): run 8,
    # then nothing
    assert rep['split'] == {'run.prepare': pytest.approx(10e-9),
                            'run.fetch': pytest.approx(10e-9),
                            'run': pytest.approx(18e-9),
                            'none': pytest.approx(22e-9)}
    assert sum(rep['split'].values()) == pytest.approx(
        rep['idle_s'] - rep['short_gaps_s'])


def test_under_a_span_idle_and_busy_by_module():
    rep = gapreport.report(TRACE, min_gap_ns=15)
    # prepare [18,32): train's ops to 20 and from 30, the gap between;
    # fetch [45,55) is all gap; run's own [32,45) [55,78): train to 40,
    # program [60,70); before and after the run: train to 18, idle from 78
    assert rep['under'] == {
        'run.prepare': {'open_s': pytest.approx(14e-9),
                        'idle_s': pytest.approx(10e-9),
                        'busy': {'jit_lm_train': pytest.approx(4e-9)}},
        'run.fetch': {'open_s': pytest.approx(10e-9),
                      'idle_s': pytest.approx(10e-9), 'busy': {}},
        'run': {'open_s': pytest.approx(36e-9),
                'idle_s': pytest.approx(18e-9),
                'busy': {'jit_lm_train': pytest.approx(8e-9),
                         'jit_program': pytest.approx(10e-9)}},
        'none': {'open_s': pytest.approx(40e-9),
                 'idle_s': pytest.approx(22e-9),
                 'busy': {'jit_lm_train': pytest.approx(18e-9)}}}
    # the whole window, once: short gaps too
    assert sum(r['open_s'] for r in rep['under'].values()) \
        == pytest.approx(rep['window_s'])
    assert sum(r['idle_s'] for r in rep['under'].values()) \
        == pytest.approx(rep['idle_s'])
    assert sum(sum(r['busy'].values()) for r in rep['under'].values()) \
        == pytest.approx(rep['busy_s'])


def test_first_tokens_left_on_the_device_of_the_windows_admissions():
    """The third table's last line: admission spans that start in the
    window, and the runs of the module that writes a prefill's first
    token into the next step's input; nothing where no admission is."""
    assert gapreport.report(TRACE, min_gap_ns=5)['first_tokens'] == \
        {'admitted': 0, 'on_device': 0}
    assert 'first tokens' not in gapreport.render(
        gapreport.report(TRACE, min_gap_ns=5), 5e-6)
    served = dict(TRACE, spans=TRACE['spans'] + [
        ('generate.prefill', 20, 4), ('generate.prefill.dispatch', 21, 2),
        ('generate.prefill', 50, 4), ('generate.prefill', 120, 4)])
    served['modules'] = {'/device:TPU:0': TRACE['modules'][
        '/device:TPU:0'] + [('jit_first_token_put', 41, 1)]}
    rep = gapreport.report(served, min_gap_ns=5)
    assert rep['first_tokens'] == {'admitted': 2, 'on_device': 1}
    assert 'first tokens left on the device: 1 of 2 admissions' \
        in gapreport.render(rep, 5e-6)


def test_timeline_is_the_open_span_that_started_last():
    segs = gapreport.timeline([('outer', 0, 100), ('a', 10, 20),
                               ('b', 40, 5), ('late', 120, 10)])
    assert segs == [(0, 10, 'outer'), (10, 30, 'a'), (30, 40, 'outer'),
                    (40, 45, 'b'), (45, 100, 'outer'), (120, 130, 'late')]
    starts = [a for a, _b, _n in segs]
    assert gapreport.split(segs, starts, 25, 50) == \
        {'a': 5, 'outer': 15, 'b': 5}
    assert gapreport.split(segs, starts, 95, 125) == \
        {'outer': 5, 'late': 5, 'none': 20}
    assert gapreport.split([], [], 3, 9) == {'none': 6}


def test_render_names_every_row():
    text = gapreport.render(gapreport.report(TRACE, min_gap_ns=5), 5e-6)
    for word in ('run.prepare', 'run.fetch', 'none', 'jit_lm_train',
                 'at their middle: 50.0 %', 'under each span',
                 'jit_program 0.0000, jit_lm_train 0.0000'):
        assert word in text


def test_a_trace_recorded_on_the_v5e():
    """220 ms of the doc cell's kept trace (PR 25, `gapreport.load` of the
    .xplane.pb cut to five decode steps, two of them cut by the edges, and
    one 768-bucket prefill, times rebased to 0): every idle gap of a millisecond has a loop phase at its
    middle, most of the idle time is the fetch's latency inside `wait` and
    the head of `dispatch`, and the prefill's module is told from the
    decode step's."""
    with open(os.path.join(HERE, 'fixtures',
                           'gapreport_doc_220ms.json')) as f:
        fx = json.load(f)
    rep = gapreport.report(fx)
    assert rep['window_s'] == pytest.approx(0.22)
    assert rep['busy_s'] + rep['idle_s'] == pytest.approx(rep['window_s'])
    assert rep['labelled_share'] == 1.0
    assert set(rep['idle']) == {'generate.wait', 'generate.prefill'}
    assert rep['idle']['generate.wait'][1] == 3
    assert sum(rep['split'].values()) == pytest.approx(
        rep['idle_s'] - rep['short_gaps_s'])
    assert rep['split']['generate.wait'] > rep['split']['generate.dispatch'] \
        > rep['split']['generate.deliver'] > rep['split']['generate.admit']
    assert rep['split']['none'] < 0.02 * rep['idle_s']
    assert set(rep['busy']) == {'jit_lm_decode_step',
                                'jit_lm_prefill_paged_b768'}
    assert sum(rep['busy'].values()) == pytest.approx(rep['busy_s'],
                                                      rel=1e-3)
    assert rep['runs'] == {'jit_lm_decode_step': 5,
                           'jit_lm_prefill_paged_b768': 1}
    assert rep['busy']['jit_lm_prefill_paged_b768'] == \
        pytest.approx(0.0619, abs=1e-4)
    # the prefill ran under the loop's `prefill` phase and nowhere else
    # (a trace from before the phases nested in it)
    assert rep['under']['generate.prefill']['busy'][
        'jit_lm_prefill_paged_b768'] == pytest.approx(0.0619, abs=1e-4)
    assert sum(r['open_s'] for r in rep['under'].values()) \
        == pytest.approx(rep['window_s'])
