"""reduce_trace.py on small lists: the hand-computed busy union, idle gaps,
per-name sums and gap labels; and on a trace recorded on the v5e."""
import json
import os

import pytest

from benchmark import reduce_trace as rt

HERE = os.path.dirname(os.path.abspath(__file__))

# one device, times in ns: [0,10) a, [5,20) b overlaps a, [30,40) a,
# [32,36) inner nested in that a, [60,70) c; traced window [0,100)
DEV = [('a', 0, 10), ('b', 5, 15), ('a', 30, 10), ('inner', 32, 4),
       ('c', 60, 10)]
HOST = [('bench:traced', 0, 100), ('bench:feed', 18, 14),
        ('bench:run', 38, 30), ('bench:fetch', 45, 10)]


def test_merge_and_busy():
    assert rt.merge(DEV) == [(0, 20), (30, 40), (60, 70)]
    assert rt.busy_ns(DEV) == 40


def test_idle_gaps():
    assert rt.idle_gaps(DEV, 0, 100) == [(20, 10), (40, 20), (70, 30)]
    assert rt.idle_gaps(DEV, 5, 65) == [(20, 10), (40, 20)]


def test_clip():
    assert rt.clip(DEV, 8, 33) == [('a', 8, 2), ('b', 8, 12), ('a', 30, 3),
                                   ('inner', 32, 1)]


def test_leaves_and_sums():
    names = rt.sum_by_name(rt.leaves(DEV))
    # the second 'a' contains 'inner' and is left out of the per-name sums
    assert names == {'a': 10, 'b': 15, 'inner': 4, 'c': 10}


def test_span_at_takes_the_innermost():
    assert rt.span_at(HOST, 25) == 'feed'
    assert rt.span_at(HOST, 50) == 'fetch'
    assert rt.span_at(HOST, 66) == 'run'
    assert rt.span_at(HOST, 90) == 'none'


def test_reduce_by_hand():
    red = rt.reduce({'devices': {'/device:TPU:0': DEV}, 'host': HOST})
    assert red['devices'] == 1
    assert red['window_s'] == pytest.approx(100e-9)
    assert red['busy_s'] == pytest.approx(40e-9)
    assert red['device_ops'][0] == ['b', pytest.approx(15e-9)]
    assert red['idle_gaps'] == [['none', pytest.approx(30e-9)],
                                ['fetch', pytest.approx(20e-9)],
                                ['feed', pytest.approx(10e-9)]]


def test_reduce_averages_over_devices():
    red = rt.reduce({'devices': {'/device:TPU:0': DEV,
                                 '/device:TPU:1': [('a', 0, 100)]},
                     'host': HOST})
    assert red['devices'] == 2
    assert red['busy_s'] == pytest.approx(70e-9)
    assert red['op_seconds']['a'] == pytest.approx(55e-9)


def test_window_falls_back_to_the_device_events():
    assert rt.traced_window({'devices': {'d': DEV}, 'host': []}) == (0, 70)


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        rt.reduce({'devices': {}, 'host': HOST})


def test_only_the_ops_line_of_a_tpu_plane_is_read():
    assert rt.is_ops_line('/device:TPU:0', 'XLA Ops')
    assert not rt.is_ops_line('/device:TPU:0', 'XLA Modules')
    assert not rt.is_ops_line('/host:CPU', 'XLA Ops')


def test_op_name_cuts_the_instruction_and_marks_mosaic_kernels():
    assert rt.op_name(
        '%jvp_flash_attention_fwd_.24 = f32[4,16,2048,64]{3,2,1,0} '
        'custom-call(bf16[4,16,2048,64]{3,2,1,0} %x), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == 'mosaic:jvp_flash_attention_fwd_'
    assert rt.op_name('%divide_subtract_fusion.28 = (f32[4096,1024]{1,0}) '
                      'fusion(f32[4096,1024]{1,0} %p), kind=kLoop'
                      ) == 'divide_subtract_fusion'
    assert rt.op_name('%convolution_add_fusion.9.remat2 = f32[8,8]{1,0} '
                      'fusion(f32[8]{0} %b)') == 'convolution_add_fusion'
    assert rt.op_name('%copy-done.1791 = f32[8]{0} copy-done(%c)') == \
        'copy-done'
    assert rt.op_name('dot_general.1') == 'dot_general'


# ---- a trace recorded on the v5e (fd355m-train-2k, PR 24): the first
# 60 ms of the traced window, 885 device events

def _recorded():
    with open(os.path.join(HERE, 'fixtures', 'train_trace_60ms.json')) as f:
        fx = json.load(f)
    return {'devices': {k: [tuple(e) for e in v]
                        for k, v in fx['devices'].items()},
            'host': [tuple(e) for e in fx['host']]}


def _busy_by_sweep(events, t0, t1):
    """The union's length by another route: count open events along the
    sorted end points."""
    points = []
    for _n, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort()
    busy, open_n, last = 0, 0, None
    for t, step in points:
        if open_n > 0:
            busy += t - last
        open_n += step
        last = t
    return busy


def test_recorded_trace_busy_union_and_gaps():
    tr = _recorded()
    red = rt.reduce(tr)
    evs = tr['devices']['/device:TPU:0']
    assert red['window_s'] == pytest.approx(0.060)
    assert red['busy_s'] * 1e9 == pytest.approx(
        _busy_by_sweep(evs, 0, 60_000_000))
    # busy + gaps fill the window
    gaps = rt.idle_gaps(evs, 0, 60_000_000)
    assert sum(d for _s, d in gaps) + rt.busy_ns(evs) == 60_000_000
    # the longest gap is the 4.1 ms before the step program starts: the
    # host is inside Executor.run (the benchmark's `run` span is open)
    assert red['idle_gaps'][0] == ['run', pytest.approx(0.004124247)]
    assert red['busy_s'] == pytest.approx(0.051674243)


def test_recorded_trace_names():
    red = rt.reduce(_recorded())
    names = red['op_seconds']
    assert 'mosaic:jvp_flash_attention_fwd_' in names
    assert 'mosaic:jvp_fused_ln_residual_fwd_' in names
    assert 'mosaic:jvp_embedding_gather_' in names
    assert not any(' = ' in n or n.startswith('%') for n in names)
    # leaves only: the per-name sums never exceed the busy time
    assert sum(names.values()) <= red['busy_s'] * 1.0001
    assert len(red['device_ops']) == 10
    assert red['device_ops'][0][1] >= red['device_ops'][-1][1]
