"""The traffic generator: deterministic in the seed, the same set of
lengths for every seed, clips honoured, and an open loop that times from
the due instant and reports its own lateness."""
import json
import os
import threading
import time

import numpy as np
import pytest

from benchmark import traffic_gen as tg

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIXES = ['chat-closed', 'doc-closed']


def _mix(name):
    with open(os.path.join(ROOT, 'benchmark', 'traffic', name + '.json')) as f:
        return json.load(f)


@pytest.mark.parametrize('name', MIXES)
def test_same_seed_same_requests(name):
    a = tg.make_requests(_mix(name), 50264, 3000000001)
    b = tg.make_requests(_mix(name), 50264, 3000000001)
    assert len(a) == len(b) == _mix(name)['pool_size']
    for x, y in zip(a, b):
        assert np.array_equal(x['prompt'], y['prompt'])
        assert x['max_new_tokens'] == y['max_new_tokens']


@pytest.mark.parametrize('name', MIXES)
def test_every_seed_has_the_same_set_of_lengths(name):
    a = tg.make_requests(_mix(name), 50264, 1)
    b = tg.make_requests(_mix(name), 50264, 2 ** 31 + 5)
    # the same (prompt length, output length) pairs, in another order
    assert sorted((len(r['prompt']), r['max_new_tokens']) for r in a) == \
        sorted((len(r['prompt']), r['max_new_tokens']) for r in b)
    assert [len(r['prompt']) for r in a] != [len(r['prompt']) for r in b]


@pytest.mark.parametrize('name', MIXES)
def test_lengths_honour_their_clips_and_fit_the_engine(name):
    mix = _mix(name)
    reqs = tg.make_requests(mix, 50264, 7)
    p, o = mix['prompt_len'], mix['output_len']
    for r in reqs:
        assert p['min'] <= len(r['prompt']) <= p['max']
        assert o['min'] <= r['max_new_tokens'] <= o['max']
        assert r['prompt'].min() >= 1 and r['prompt'].max() < 50264
        assert len(r['prompt']) + r['max_new_tokens'] \
            <= mix['engine']['max_len']
    assert max(len(r['prompt']) for r in reqs) \
        <= max(mix['engine']['prompt_buckets'])


@pytest.mark.parametrize('name', MIXES)
def test_every_client_gets_a_share_that_spans_the_range(name):
    mix = _mix(name)
    clients = mix['arrival']['clients']
    pool = sorted(tg.length_pool(mix['prompt_len'], mix['pool_size']))
    for seed in (1, 2 ** 31 + 5):
        reqs = tg.make_requests(mix, 50264, seed)
        shares = [sorted(len(r['prompt']) for r in reqs[c::clients])
                  for c in range(clients)]
        # the shares are the pool dealt round the clients like cards
        assert sorted(shares) == sorted(pool[c::clients]
                                        for c in range(clients))


def test_lognormal_pool_has_the_named_median_and_a_tail():
    pool = tg.length_pool({'dist': 'lognormal', 'median': 128, 'sigma': 0.7,
                           'min': 16, 'max': 512}, 512)
    assert abs(np.median(pool) - 128) <= 1
    assert pool.min() == 16 or pool.min() > 16
    assert pool.max() == 512          # the tail reaches the clip


def test_fixed_and_uniform_pools():
    assert set(tg.length_pool({'dist': 'fixed', 'value': 9}, 5)) == {9}
    u = tg.length_pool({'dist': 'uniform', 'min': 10, 'max': 20}, 100)
    assert u.min() == 10 and u.max() == 20 and abs(u.mean() - 15) < 0.2


def test_prefix_groups_share_their_first_tokens():
    mix = dict(_mix('chat-closed'), shared_prefix_len=12, group_size=4,
               pool_size=16, arrival={'kind': 'open', 'rate_rps': 5.0},
               prompt_len={'dist': 'fixed', 'value': 20})
    reqs = tg.make_requests(mix, 1000, 3)
    for g in range(4):
        grp = reqs[4 * g:4 * g + 4]
        assert all(np.array_equal(r['prompt'][:12], grp[0]['prompt'][:12])
                   for r in grp)
        assert not np.array_equal(grp[0]['prompt'][12:], grp[1]['prompt'][12:])
    assert not np.array_equal(reqs[0]['prompt'][:12], reqs[4]['prompt'][:12])


def test_train_batches_are_fresh_and_seeded():
    a = tg.train_batches(2 ** 31 + 9, 2, 8, 50)
    b = tg.train_batches(2 ** 31 + 9, 2, 8, 50)
    a1, a2, b1 = next(a), next(a), next(b)
    assert a1['tokens'].shape == (2, 8) and a1['tokens'].dtype == np.int64
    assert np.array_equal(a1['tokens'], b1['tokens'])
    assert not np.array_equal(a1['tokens'], a2['tokens'])
    assert a1['tokens'].max() < 50


def test_open_schedule_rate_and_bursts():
    due = tg.open_schedule({'kind': 'open', 'rate_rps': 50.0, 'burst': 4},
                           400, 1)
    assert len(due) == 400 and np.all(np.diff(due) >= 0)
    assert np.all(due[0:4] == due[0]) and due[4] > due[3]
    assert 6.0 < due[-1] < 10.5               # 400 requests at 50 a second
    assert np.array_equal(due, tg.open_schedule(
        {'kind': 'open', 'rate_rps': 50.0, 'burst': 4}, 400, 1))


class _Handle(object):
    def __init__(self, n, delay):
        self.n, self.delay, self.finish_reason = n, delay, None

    def stream(self):
        for i in range(self.n):
            time.sleep(self.delay)
            yield i
        self.finish_reason = 'length'


def test_open_loop_times_from_the_due_instant_and_reports_lateness():
    """One server that takes requests one at a time, 30 ms each, offered 20
    requests at 100 a second: the queue grows, so time to first token,
    counted from when each request was DUE, grows with it — a clock started
    at the send would hide that."""
    gate = threading.Lock()

    def submit(prompt, max_new_tokens):
        with gate:                      # the stall: sends queue behind it
            time.sleep(0.03)
        return _Handle(max_new_tokens, 0.0)

    reqs = [{'prompt': np.ones(3, np.int64), 'max_new_tokens': 2}] * 20
    load = tg.Load({'kind': 'open', 'rate_rps': 100.0, 'burst': 1}, reqs,
                   submit, seed=5).start()
    load.wait_ramped(10)
    time.sleep(1.0)
    load.stop()
    load.join(10)
    recs = sorted(load.records, key=lambda r: r.index)
    assert len(recs) == 20 and all(r.ok for r in recs)
    assert all(r.t_due is not None for r in recs)
    assert len(load.lateness_s) == 20 and min(load.lateness_s) >= 0.0
    assert max(load.lateness_s) < 0.05   # the dispatcher itself is on time
    w = tg.window_stats(recs, recs[0].t_due - 1.0, recs[-1].t_end + 1.0)
    assert w['attempted'] == 20 and w['failed'] == 0 and w['tokens'] == 40
    # 20 x 30 ms of service against ~200 ms of arrivals: the last requests
    # wait some 400 ms from their due instant
    assert max(w['ttft_s']) > 0.25
    from_send = [r.token_t[0] - r.t_send for r in recs]
    assert max(w['ttft_s']) >= max(from_send) - 1e-9


def test_closed_loop_and_the_window():
    def submit(prompt, max_new_tokens):
        return _Handle(max_new_tokens, 0.002)

    reqs = [{'prompt': np.ones(3, np.int64), 'max_new_tokens': 3}] * 8
    load = tg.Load({'kind': 'closed', 'clients': 2, 'stagger_s': 0.01},
                   reqs, submit, seed=5).start()
    load.wait_ramped(10)
    t0 = time.perf_counter()
    time.sleep(0.3)
    t1 = time.perf_counter()
    load.stop()
    load.join(10)
    w = tg.window_stats(load.records, t0, t1)
    assert w['attempted'] > 5 and w['failed'] == 0
    # a request astride an edge of the window is not `attempted`, its tokens
    # inside count: 2 clients x 2 edges x at most 3 tokens
    assert abs(w['tokens'] - 3 * w['attempted']) <= 12
    assert len(w['itl_s']) >= 2 * w['attempted']
    assert len(w['ttft_s']) >= w['attempted']
    assert tg.percentile([1, 2, 3, 4], 50) == 2
    assert tg.percentile(list(range(1, 101)), 95) == 95


def test_a_short_or_failed_request_counts_as_failed():
    ok = tg.Record(0, 2)
    ok.t_send, ok.token_t, ok.t_end = 1.0, [1.1, 1.2], 1.2
    short = tg.Record(1, 3)
    short.t_send, short.token_t, short.t_end = 1.0, [1.1], 1.3
    full = tg.Record(2, 1)
    full.t_send, full.token_t, full.t_end = 1.0, [1.1], 1.3
    full.finish_reason = 'cache_full'
    err = tg.Record(3, 1)
    err.t_send, err.t_end, err.error = 1.0, 1.1, 'LoadShedError: full'
    late = tg.Record(4, 5)               # still in flight at the close
    late.t_send, late.token_t = 1.5, [1.7, 1.9, 2.1]
    w = tg.window_stats([ok, short, full, err, late], 0.0, 2.0)
    assert w['attempted'] == 4 and w['failed'] == 3
    # its tokens, first token and gap inside the window count all the same
    assert w['tokens'] == 2 + 1 + 1 + 2
    assert len(w['ttft_s']) == 4 and max(w['ttft_s']) == pytest.approx(0.2)
    assert w['itl_s'] == [pytest.approx(0.1), pytest.approx(0.2)]
