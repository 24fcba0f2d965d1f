"""The decode loop's pipeline (serving/generate.py `_loop`): with step k
dispatched and not fetched, step k + 1 goes out on step k's tokens as they
are on the device, and step k is fetched and delivered behind it. Held
here: the order and that a carried token never becomes numpy; the one
finish the host cannot foresee (`eos`) with a step in flight, and the
slot's and the blocks' next tenant; streams bitwise `generate_once`'s
through every other finish; a failed step; a speculative engine's
fallback steps; and that the device-fed step is the executable
`warmup()` bound, not a second compile.

Since PR 38 a prefill's first token stays on the device as well (part
(e)): an admission dispatches its prefill and returns, the row joins the
next step on the token as the prefill left it, and the loop picks the
token up before that step's own fetch — over a dense pool, one with
prefix sharing, and LFM2's three pools with the convolution tails.

Engines share test_paged_generate.py's tiny-LM shape family, so the
process-wide compile cache keeps warmups at milliseconds.
"""
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from paddle_tpu import monitor, resilience
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import GenerateConfig, GenerateEngine
from paddle_tpu.serving import generate as generate_mod
from paddle_tpu.serving.batcher import DeadlineExceededError

from benchmark.models import lfm2

MAX_LEN = 48
BS = 8


def _cfg(**kw):
    kw.setdefault('model', LMConfig(
        vocab_size=64, seq_len=32, d_model=32, n_head=2, n_layer=2,
        d_ff=64, dropout=0.0, attn_dropout=0.0, use_flash_attention=False))
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', MAX_LEN)
    kw.setdefault('prompt_buckets', [8, 16])
    kw.setdefault('eos_id', None)
    kw.setdefault('seed', 0)
    kw.setdefault('block_size', BS)
    return GenerateConfig(**kw)


POOLS = ['dense', 'prefix-sharing', 'lfm2-tails']
with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'benchmark_tests', 'configs', 'toy-lfm2.json')) as _f:
    TOY_LFM2 = json.load(_f)


def _pool_cfg(pool, **kw):
    """The engine's config over one of the three kinds of pool: K/V
    alone, K/V with the prefix index on, and LFM2's — K/V of the
    attention layers, the convolution layers' tails under the same block
    ids, prefix sharing on (tests/test_lfm2_serving.py's toy)."""
    if pool == 'lfm2-tails':
        kw.setdefault('model', lfm2.lm_config(TOY_LFM2, MAX_LEN, False))
    kw.setdefault('prefix_sharing', pool != 'dense')
    return _cfg(**kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 64, size=n) \
        .astype('int64')


def _record(eng, log, slow_s=0.0):
    """Wrap the bound decode step and `_deliver`: `log` gets ('dispatch',
    the type of the fed gen_tokens) and ('deliver', rows) in the order the
    loop thread reaches them."""
    step, deliver = eng._step_bound, eng._deliver

    def bound(feed, **kw):
        log.append(('dispatch', type(feed['gen_tokens'])))
        if slow_s:
            time.sleep(slow_s)
        return step(feed, **kw)

    def delivered(active, tokens):
        log.append(('deliver', len(active)))
        return deliver(active, tokens)
    eng._step_bound, eng._deliver = bound, delivered


# ---------------------------------------------------------------------------
# (a) the order, and where the token lives


def test_next_step_goes_out_before_the_last_is_fetched_on_device_tokens():
    eng = GenerateEngine(_cfg())
    p, n = _prompt(6, seed=1), 12
    ref = eng.generate_once(p, max_new_tokens=n)
    log = []
    _record(eng, log)
    before = monitor.counters()
    with eng:
        assert list(eng.submit(p, max_new_tokens=n).result(60)) == ref
    kinds = [k for k, _ in log]
    # n - 1 decode steps: the first has no predecessor, every later one is
    # dispatched with its predecessor unfetched — two dispatches lead,
    # then a delivery and a dispatch alternate, and two deliveries trail
    # (the host foresees `length` and leaves the row out of a step n)
    assert kinds == ['dispatch'] * 2 + ['deliver', 'dispatch'] * (n - 3) \
        + ['deliver'] * 2
    fed = [t for k, t in log if k == 'dispatch']
    # every step's feeds are split on the device (`_stage_feeds`): never
    # numpy
    assert all(issubclass(t, jax.Array) for t in fed)
    delta = monitor.counter_delta(before)
    # the prefill's token: admitted with nothing in flight, the serial
    # pass, so the first step took it from the host's column
    assert 'generate_first_token_carried_total' not in delta
    assert delta['generate_overlapped_steps_total'] == n - 2
    assert 'generate_discarded_rows_total' not in delta
    st = eng.stats()
    assert st['overlapped_steps'] == n - 2 and st['discarded_rows'] == 0
    assert st['decode_steps'] == n - 1


@pytest.mark.parametrize('pool', POOLS)
def test_the_device_fed_step_is_the_executable_warmup_bound(pool):
    """jax's own compiles, counted: none after warmup(), although the
    loop feeds the step a device int32 where warmup's numpy feed is
    int64; and the paddle-level compile cache stays quiet too. Six
    admissions through four slots, the last two behind steps in flight:
    the select's third source, the buffer of first tokens, and the
    update that fills it are compiled too."""
    eng = GenerateEngine(_pool_cfg(pool))
    eng.warmup()
    compiles = []

    def listener(event, _secs, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(listener)
    before = monitor.counters()
    try:
        with eng:
            reqs = [eng.submit(_prompt(5 + i, seed=20 + i),
                               max_new_tokens=6 + i,
                               temperature=0.7 * (i % 2), sample_seed=i)
                    for i in range(6)]
            assert [len(r.result(60)) for r in reqs] == list(range(6, 12))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []
    delta = monitor.counter_delta(before)
    assert not any(k.startswith('compile_cache_miss') for k in delta), delta
    assert delta['generate_overlapped_steps_total'] > 0
    assert delta['generate_first_token_carried_total'] >= 2


@pytest.mark.parametrize('fetch_s', [0.0, 0.01],
                         ids=['host-paced', 'device-paced'])
def test_a_pass_admits_in_one_fixed_order(monkeypatch, fetch_s):
    """No fork in a pass: step k + 1 dispatched, step k fetched and
    delivered, the consumers' turn, THEN the admission — whether step k
    is done when the loop looks (the host paces it) or the fetch waits
    for the device (a slow fetch stands for it). A prefill so queues
    behind the one step just dispatched either way, and costs the pass
    its bound call. Nothing probes the step: `_Flight` has no `ready`.
    The tokens are generate_once's."""
    assert not hasattr(generate_mod._Flight, 'ready')
    fetch = generate_mod._Flight.fetch

    def slow(self):
        time.sleep(fetch_s)
        return fetch(self)
    monkeypatch.setattr(generate_mod._Flight, 'fetch', slow)
    eng = GenerateEngine(_cfg())
    work = [(_prompt(6, seed=71), 12), (_prompt(9, seed=72), 8),
            (_prompt(4, seed=73), 10)]
    ref = [eng.generate_once(p, max_new_tokens=n) for p, n in work]
    log = []
    _record(eng, log)
    admit = eng._admit

    def admitted():
        log.append(('admit', None))
        return admit()
    eng._admit = admitted
    with eng:
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        assert [list(r.result(60)) for r in reqs] == ref
    kinds = [k for k, _ in log]
    at = [i for i, k in enumerate(kinds) if k == 'dispatch']
    # a pass with a step in flight that dispatched another: from its
    # dispatch to the next pass's
    full = [kinds[a:b] for a, b in zip(at, at[1:]) if b - a > 1]
    assert len(full) >= 8
    assert all(p == ['dispatch', 'deliver', 'admit'] for p in full), full


# ---------------------------------------------------------------------------
# (b) `eos` with a step in flight


@pytest.mark.parametrize('prefix_sharing', [True, False],
                         ids=['prefix-cache', 'no-prefix-cache'])
def test_eos_with_a_step_in_flight_and_the_slots_next_tenant(prefix_sharing):
    """The row that ends by `eos` is in the step in flight already: its
    stream ends AT the eos, the row's result is dropped, and the next
    tenant of the slot — one slot, a pool of four blocks, so of the same
    blocks too — serves exactly generate_once's tokens."""
    kw = dict(slots=1, num_blocks=5, prefix_sharing=prefix_sharing)
    pa = _prompt(10, seed=7)
    probe = GenerateEngine(_cfg(**kw))
    ref_a = probe.generate_once(pa, max_new_tokens=24)
    eos = ref_a[3]      # a token the model does emit, mid-sequence
    cut_a = ref_a[:ref_a.index(eos) + 1]
    for seed in range(8, 40):   # a tenant that decodes before any eos
        pb = _prompt(13, seed=seed)
        ref_b = probe.generate_once(pb, max_new_tokens=15)
        if eos not in ref_b[:4]:
            break
    cut_b = ref_b[:ref_b.index(eos) + 1] if eos in ref_b else ref_b

    eng = GenerateEngine(_cfg(eos_id=eos, **kw))
    eng.warmup()
    start = eng.stats()['blocks']['in_use']
    owned = {}
    step = eng._step_bound

    def bound(feed, **kw):
        for st in eng._slots:
            if st is not None:
                owned.setdefault(id(st.req), set()).update(st.blocks)
        return step(feed, **kw)
    eng._step_bound = bound
    before = monitor.counters()
    with eng:
        a = eng.submit(pa, max_new_tokens=24)
        b = eng.submit(pb, max_new_tokens=15)
        got_a, got_b = list(a.result(60)), list(b.result(60))
    assert got_a == cut_a and a.finish_reason == 'eos'
    assert got_b == cut_b
    assert len(got_a) > 2 and len(got_b) > 2    # both did decode
    delta = monitor.counter_delta(before)
    assert delta['generate_discarded_rows_total'] >= 1
    assert eng.stats()['discarded_rows'] >= 1
    assert owned[id(a)] & owned[id(b)], (owned, 'no block changed hands')
    assert eng.stats()['blocks']['in_use'] == start == 0


# ---------------------------------------------------------------------------
# (c) every other finish, with a step in flight; streams bitwise


@pytest.mark.parametrize('pool', ['roomy', 'dry'])
def test_streams_are_generate_onces_through_every_finish(pool):
    """Greedy and pinned-seed sampled rows under concurrency, with a
    step in flight at every finish: `length`; `cache_full` by max_len
    (roomy) or by a dry pool (dry: five residents that would grow to 18
    blocks on a pool of 11); a deadline eviction. Whatever a request got
    is generate_once's tokens, bitwise, from the first on."""
    eng = GenerateEngine(_cfg(
        slots=5, **(dict(num_blocks=12) if pool == 'dry' else {})))
    work = {
        'greedy': dict(prompt=_prompt(6, seed=31), max_new_tokens=14),
        'sampled': dict(prompt=_prompt(9, seed=32), max_new_tokens=17,
                        temperature=0.8, top_k=8, sample_seed=11),
        'nucleus': dict(prompt=_prompt(4, seed=33), max_new_tokens=9,
                        temperature=1.1, top_p=0.9, sample_seed=5),
        'long': dict(prompt=_prompt(10, seed=34), max_new_tokens=200),
    }
    ref = {k: eng.generate_once(**w) for k, w in work.items()}
    assert len(ref['long']) == MAX_LEN - 10 + 1
    log = []
    _record(eng, log, slow_s=0.01)
    before = monitor.counters()
    with eng:
        doomed = eng.submit(_prompt(5, seed=35), max_new_tokens=40,
                            deadline_s=0.3)
        reqs = {k: eng.submit(deadline_s=60.0, **w)
                for k, w in work.items()}
        got_doomed = []
        with pytest.raises(DeadlineExceededError):
            for tok in doomed.stream(timeout=30.0):
                got_doomed.append(tok)
        got = {k: list(r.result(60)) for k, r in reqs.items()}
        after = eng.generate(_prompt(5, seed=35), max_new_tokens=40,
                             deadline_s=60.0)
    if pool == 'roomy':
        for k in ('greedy', 'sampled', 'nucleus'):
            assert reqs[k].finish_reason == 'length', k
        assert got == ref               # 'long' ended by max_len
    else:
        # whom the dry pool starves is the allocator's to say: what a
        # request got before is its reference's tokens all the same
        for k in work:
            assert 1 <= len(got[k]) <= len(ref[k]), k
            assert got[k] == ref[k][:len(got[k])], k
            assert reqs[k].finish_reason == (
                'length' if len(got[k]) == work[k]['max_new_tokens']
                else 'cache_full'), k
        assert any(len(got[k]) < len(ref[k]) for k in work)
    assert reqs['long'].finish_reason == 'cache_full'
    assert 0 < len(got_doomed) < 40
    assert list(after)[:len(got_doomed)] == got_doomed  # a prefix, bitwise
    delta = monitor.counter_delta(before)
    assert delta['generate_request_total{outcome=deadline}'] == 1
    assert delta['generate_request_total{outcome=ok}'] == 5
    assert delta['generate_overlapped_steps_total'] > 0
    # the evicted row was in the step in flight: dropped, not booked
    assert delta.get('generate_discarded_rows_total', 0) >= 1
    st = eng.stats()
    assert st['active'] == 0 and st['blocks']['in_use'] == 0
    steps = monitor.snapshot()['histograms']['decode_step_seconds']
    assert steps['count'] >= st['decode_steps']     # process-wide >= ours


# ---------------------------------------------------------------------------
# (d) a step that fails


def _fail_fetch_once(monkeypatch, at):
    calls = []
    fetch = generate_mod._Flight.fetch

    def failing(self):
        calls.append(1)
        if len(calls) == at:
            raise RuntimeError('async failure at the fetch')
        return fetch(self)
    monkeypatch.setattr(generate_mod._Flight, 'fetch', failing)


@pytest.mark.parametrize('where', ['dispatch', 'fetch'])
def test_a_failed_step_takes_both_steps_residents_once(monkeypatch, where):
    """A step fails at its dispatch (the `run` fault site, retries
    exhausted) or at its fetch (an async failure) with another step in
    flight: every resident of the two steps gets the error once, after
    the tokens it streamed; the loop lives and serves the next request."""
    monkeypatch.setenv('PADDLE_RETRY_MAX_ATTEMPTS', '2')
    monkeypatch.setenv('PADDLE_RETRY_BASE_S', '0.01')
    eng = GenerateEngine(_cfg())
    eng.warmup()
    ref = eng.generate_once(_prompt(5, seed=43), max_new_tokens=4)
    log = []
    _record(eng, log, slow_s=0.005)
    before = monitor.counters()
    error = resilience.InjectedFault if where == 'dispatch' \
        else RuntimeError
    with eng:
        reqs = [eng.submit(_prompt(5 + i, seed=40 + i), max_new_tokens=40,
                           deadline_s=60.0) for i in range(3)]
        streams = [r.stream(timeout=30.0) for r in reqs]
        got = [[next(s), next(s)] for s in streams]     # all resident
        if where == 'dispatch':
            resilience.install_fault('run', mode='always')
        else:
            _fail_fetch_once(monkeypatch, at=1)
        try:
            for s, g in zip(streams, got):
                with pytest.raises(error):
                    for tok in s:
                        g.append(tok)
        finally:
            resilience.clear_faults()
        assert all(len(g) < 40 for g in got)
        out = eng.generate(_prompt(5, seed=43), max_new_tokens=4,
                           deadline_s=60.0)
        assert list(out) == ref
    delta = monitor.counter_delta(before)
    assert delta['generate_step_error_total'] == 1
    assert delta['generate_request_total{outcome=error}'] == 3  # once each
    assert delta['generate_request_total{outcome=ok}'] == 1
    assert eng.stats()['active'] == 0
    assert eng.stats()['blocks']['in_use'] == 0


# ---------------------------------------------------------------------------
# a speculative engine: rounds serial, fallback steps pipelined


def test_speculative_fallback_steps_pipeline_and_rounds_stay_serial():
    """A sampled rider pins a speculative engine on plain steps: those
    pipeline like any other engine's (the counter moves, tokens are
    generate_once's); the rounds before and after run with nothing in
    flight, and accept every proposal of a draft that IS the target."""
    eng = GenerateEngine(_cfg(speculative=True, spec_k=3))
    pg, ps = _prompt(6, seed=51), _prompt(9, seed=52)
    ref_g = eng.generate_once(pg, max_new_tokens=20)
    ref_s = eng.generate_once(ps, max_new_tokens=7, temperature=0.8,
                              top_k=8, sample_seed=3)
    rounds_in_flight = []
    spec_round = eng._spec_round

    def watched():
        rounds_in_flight.append(list(eng._flights))
        return spec_round()
    eng._spec_round = watched
    with eng:
        rs = eng.submit(ps, max_new_tokens=7, temperature=0.8, top_k=8,
                        sample_seed=3)
        rg = eng.submit(pg, max_new_tokens=20)
        assert list(rs.result(60)) == ref_s
        assert list(rg.result(60)) == ref_g
    st = eng.stats()
    assert st['overlapped_steps'] > 0
    assert st['spec']['fallback_rounds'] > 0 and st['spec']['rounds'] > 0
    assert rounds_in_flight and not any(rounds_in_flight)


# ---------------------------------------------------------------------------
# stop() with a step in flight


def test_stop_lands_the_step_in_flight():
    eng = GenerateEngine(_cfg())
    eng.warmup()
    log = []
    _record(eng, log, slow_s=0.01)
    eng.start()
    req = eng.submit(_prompt(6, seed=61), max_new_tokens=40,
                     deadline_s=60.0)
    stream = req.stream(timeout=30.0)
    got = [next(stream), next(stream), next(stream)]
    eng.stop()
    with pytest.raises(generate_mod.EngineStoppedError):
        for tok in stream:
            got.append(tok)
    assert eng._flights == []
    # every step dispatched was fetched and delivered before the thread
    # ended: nothing on the device outlives the engine's loop
    kinds = [k for k, _ in log]
    assert kinds.count('dispatch') == kinds.count('deliver')
    assert not any(t.name == 'paddle-generate' for t in threading.enumerate())


# ---------------------------------------------------------------------------
# (e) a prefill's first token stays on the device


def _watch(eng, log):
    """`log` gets, in the loop's order: ('prefill', bucket) and ('step',
    rows whose token came from the first tokens' buffer) at the two
    kinds of dispatch, ('fetch', n) where a fetched vector crosses to the
    host — n is 1 for a prefill's output, the slots for a step's."""
    step, split, stage = eng._step_bound, eng._split_load, eng._stage_feeds
    src = []

    def carried(prev, s, toks, feed):
        src.append(int((s == 2).sum()))
        return stage(prev, s, toks, feed)

    def bound(feed, **kw):
        log.append(('step', src.pop() if src else 0))
        return step(feed, **kw)

    def fetched(out, n):
        log.append(('fetch', n))
        return split(out, n)
    eng._stage_feeds, eng._step_bound, eng._split_load = \
        carried, bound, fetched
    for b, f in list(eng._prefill_bound.items()):
        eng._prefill_bound[b] = (
            lambda feed, _b=b, _f=f, **kw:
            log.append(('prefill', _b)) or _f(feed, **kw))


@pytest.mark.parametrize('pool', POOLS)
def test_a_first_token_reaches_the_next_step_without_a_host_round_trip(pool):
    """A is resident, step k in flight; B and C are admitted in ONE pass.
    Each prefill is a dispatch and nothing else: step k + 1 takes A's
    token from step k and both new rows' from the buffer, on the device,
    and goes out BEFORE any fetch of a prefill's output; step k lands
    without them; the two first tokens are picked up before step k + 1's
    own fetch, so every stream sees its first token before its second;
    all three streams are generate_once's, and the counter counts the
    two admissions that found a step in flight."""
    eng = GenerateEngine(_pool_cfg(pool))
    work = [(_prompt(6, seed=81), 9), (_prompt(11, seed=82), 7),
            (_prompt(4, seed=83), 6)]
    ref = [eng.generate_once(p, max_new_tokens=n) for p, n in work]
    log = []
    _watch(eng, log)
    before = monitor.counters()
    a = eng.submit(work[0][0], max_new_tokens=work[0][1])
    eng._admit()                        # nothing in flight: the serial pass
    assert a.tokens == ref[0][:1] and not eng._firsts
    k = eng._step_dispatch()
    del log[:]
    b, c = [eng.submit(p, max_new_tokens=n) for p, n in work[1:]]
    eng._admit()
    assert [e for e, _ in log] == ['prefill', 'prefill']
    assert b.tokens == c.tokens == [] and len(eng._firsts) == 2
    nxt = eng._step_dispatch(prev=k)
    assert log[2] == ('step', 2) and len(log) == 3     # no fetch yet
    assert eng._firsts == [] and len(nxt.firsts) == 2 and not k.firsts
    eng._step_complete(k, nxt)
    assert log[3:] == [('fetch', eng.config.slots)]
    assert b.tokens == c.tokens == [] and a.tokens == ref[0][:2]
    eng._step_complete(nxt)
    assert log[4:] == [('fetch', 1), ('fetch', 1), ('fetch', eng.config.slots)]
    assert [len(r.tokens) for r in (a, b, c)] == [3, 2, 2]
    reqs = (a, b, c)
    while any(r.finish_reason is None for r in reqs):
        eng._step()
    assert [list(r.result(5)) for r in reqs] == ref
    delta = monitor.counter_delta(before)
    assert delta['generate_admit_total'] == 3
    assert delta['generate_first_token_carried_total'] == 2 \
        == eng.stats()['first_tokens_carried']
    assert eng.stats()['discarded_rows'] == 0


def _resident(eng, prompt, n=40):
    """A request under the loop, two tokens streamed: from here on a step
    is in flight whenever the loop admits."""
    req = eng.submit(prompt, max_new_tokens=n, deadline_s=60.0)
    stream = req.stream(timeout=30.0)
    return req, stream, [next(stream), next(stream)]


@pytest.mark.parametrize('pool', POOLS)
def test_eos_as_a_first_token_and_the_slots_next_tenant(pool):
    """The prefill's own token is the `eos`, and a neighbour's steps are
    in flight: by the pick-up the row is in one or two of them already.
    Its stream is that one token, the row's results are dropped, the
    neighbour's stream and the slot's next tenant's are
    generate_once's."""
    kw = dict(slots=2)
    pa, pb = _prompt(10, seed=7), _prompt(13, seed=9)
    probe = GenerateEngine(_pool_cfg(pool, **kw))
    eos = probe.generate_once(pa, max_new_tokens=1)[0]

    def cut(p, n):
        ref = probe.generate_once(p, max_new_tokens=n)
        return ref[:ref.index(eos) + 1] if eos in ref else ref
    for seed in range(100, 140):    # a neighbour that outlives the admission
        pn = _prompt(6, seed=seed)
        cut_n = cut(pn, 40)
        if len(cut_n) > 24:
            break
    cut_b = cut(pb, 15)
    assert len(cut_n) > 24
    eng = GenerateEngine(_pool_cfg(pool, eos_id=eos, **kw))
    eng.warmup()
    before = monitor.counters()
    with eng:
        n, stream, got_n = _resident(eng, pn)
        a = eng.submit(pa, max_new_tokens=24)
        got_a = list(a.result(60))
        b = eng.submit(pb, max_new_tokens=15)
        got_b = list(b.result(60))
        got_n += list(stream)
    assert got_a == [eos] and a.finish_reason == 'eos'
    assert got_b == cut_b and got_n == cut_n
    delta = monitor.counter_delta(before)
    assert delta['generate_first_token_carried_total'] >= 1
    assert delta['generate_discarded_rows_total'] >= 1
    assert eng.stats()['active'] == 0
    assert eng.stats()['blocks']['in_use'] == 0


@pytest.mark.parametrize('pool', POOLS)
def test_a_request_of_one_token_is_never_put_into_a_step(pool):
    """`max_new_tokens == 1` is foreseen at the admission: behind a step
    in flight the row is left out of every step, its token picked up by
    the step a neighbour is in, or by the next dispatch that finds
    nothing to step."""
    eng = GenerateEngine(_pool_cfg(pool))
    pa, pb = _prompt(7, seed=91), _prompt(5, seed=92)
    ref_a = eng.generate_once(pa, max_new_tokens=1)
    ref_b = eng.generate_once(pb, max_new_tokens=5)
    log = []
    _watch(eng, log)
    before = monitor.counters()
    b = eng.submit(pb, max_new_tokens=3)
    eng._admit()
    eng._step()                         # b has two of its three tokens
    k = eng._step_dispatch()            # ... and its last in flight
    a = eng.submit(pa, max_new_tokens=1)
    eng._admit()
    assert a.tokens == [] and len(eng._firsts) == 1
    del log[:]
    assert eng._step_dispatch(prev=k) is None   # b ends, a is one token
    assert log == [('fetch', 1)]        # nothing to step: picked up now
    assert list(a.result(5)) == ref_a and a.finish_reason == 'length'
    eng._step_complete(k)
    assert list(b.result(5)) == ref_b[:3]
    b2 = eng.submit(pb, max_new_tokens=5)
    eng._admit()
    k = eng._step_dispatch()
    a2 = eng.submit(pa, max_new_tokens=1)
    eng._admit()
    nxt = eng._step_dispatch(prev=k)    # b2's step takes a2's pick-up over
    assert [st.req for _i, st in nxt.active] == [b2] and len(nxt.firsts) == 1
    eng._step_complete(k, nxt)
    assert a2.tokens == []
    eng._step_complete(nxt)
    assert list(a2.result(5)) == ref_a and b2.tokens == ref_b[:3]
    while b2.finish_reason is None:
        eng._step()
    assert list(b2.result(5)) == ref_b
    delta = monitor.counter_delta(before)
    assert delta['generate_admit_total'] == 4
    assert 'generate_first_token_carried_total' not in delta
    assert eng.stats()['discarded_rows'] == 0
    assert eng.stats()['active'] == 0


@pytest.mark.parametrize('pool', POOLS)
def test_a_prefill_that_fails_at_its_pick_up(pool):
    """An async failure of a prefill surfaces where its token is picked
    up: its request gets the error, and so do the residents of the steps
    dispatched since — the cache is threaded through the failed prefill
    into them — once each, after the tokens they streamed. The loop
    lives: the next request is generate_once's."""
    eng = GenerateEngine(_pool_cfg(pool))
    eng.warmup()
    ref = eng.generate_once(_prompt(5, seed=43), max_new_tokens=4)
    split, armed = eng._split_load, []

    def failing(out, n):
        if n == 1 and armed:
            armed.pop()
            raise RuntimeError('async failure of the prefill')
        return split(out, n)
    eng._split_load = failing
    before = monitor.counters()
    with eng:
        residents = [_resident(eng, _prompt(5 + i, seed=40 + i))
                     for i in range(2)]
        armed.append(1)
        late = eng.submit(_prompt(9, seed=49), max_new_tokens=40,
                          deadline_s=60.0)
        with pytest.raises(RuntimeError, match='failure of the prefill'):
            late.result(30)
        assert late.tokens == []
        for _req, stream, got in residents:
            with pytest.raises(RuntimeError, match='failure of the prefill'):
                for tok in stream:
                    got.append(tok)
            assert len(got) < 40
        out = eng.generate(_prompt(5, seed=43), max_new_tokens=4,
                           deadline_s=60.0)
        assert list(out) == ref
    delta = monitor.counter_delta(before)
    assert delta['generate_step_error_total'] == 1
    assert delta['generate_request_total{outcome=error}'] == 3  # once each
    assert delta['generate_request_total{outcome=ok}'] == 1
    assert eng.stats()['active'] == 0
    assert eng.stats()['blocks']['in_use'] == 0
    assert eng._firsts == [] and eng._flights == []


@pytest.mark.parametrize('pool', POOLS)
def test_a_deadline_and_a_stop_with_a_first_token_pending(pool):
    """A request whose deadline passes between its prefill's dispatch
    and the pick-up is evicted like any resident: the token is never
    fetched, the slot and the blocks go to the next tenant, whose stream
    is generate_once's. stop() with a first token pending lands what is
    on the device — the steps, then the prefill no step took over — and
    leaves nothing behind."""
    eng = GenerateEngine(_pool_cfg(pool, slots=2))
    pa, pb, pn = _prompt(9, seed=61), _prompt(6, seed=62), _prompt(5, seed=63)
    ref_b = eng.generate_once(pb, max_new_tokens=6)
    ref_n = eng.generate_once(pn, max_new_tokens=12)
    log = []
    _watch(eng, log)
    n = eng.submit(pn, max_new_tokens=12, deadline_s=60.0)
    eng._admit()
    k = eng._step_dispatch()            # the neighbour's step, in flight
    del log[:]
    doomed = eng.submit(pa, max_new_tokens=8, deadline_s=0.05)
    eng._admit()
    assert eng._firsts and doomed.tokens == []
    time.sleep(0.06)
    eng._evict_expired()
    with pytest.raises(DeadlineExceededError):
        doomed.result(5)
    b = eng.submit(pb, max_new_tokens=6, deadline_s=60.0)
    eng._admit()                        # the slot's next tenant
    assert [st.req for st in eng._slots] == [n, b]
    eng._step_complete(k)
    while b.finish_reason is None or n.finish_reason is None:
        eng._step()
    assert list(b.result(5)) == ref_b and list(n.result(5)) == ref_n
    # one fetch of a prefill's output, b's: the evicted row's never came
    assert doomed.tokens == [] and log.count(('fetch', 1)) == 1
    assert eng.stats()['active'] == 0 and eng._firsts == []

    split = eng._split_load

    def slow(out, n):
        if n == 1:
            time.sleep(0.3)             # the pick-up: stop() comes inside
        return split(out, n)
    eng.start()
    _req, stream, got_n = _resident(eng, pn)
    eng._split_load = slow
    req = eng.submit(pa, max_new_tokens=30, deadline_s=60.0)
    time.sleep(0.1)
    eng.stop()
    with pytest.raises(generate_mod.EngineStoppedError):
        req.result(30)
    with pytest.raises(generate_mod.EngineStoppedError):
        for tok in stream:
            got_n.append(tok)
    assert len(req.tokens) <= 3
    assert req.tokens == eng.generate_once(
        pa, max_new_tokens=8)[:len(req.tokens)]
    assert got_n == ref_n[:len(got_n)]
    assert eng._flights == [] and eng._firsts == []
    assert eng.stats()['active'] == 0
    assert eng.stats()['blocks']['in_use'] == 0
    assert not any(t.name == 'paddle-generate' for t in threading.enumerate())


# ---------------------------------------------------------------------------
# (f) a chunked prefill takes a chunk a pass behind a step in flight

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'benchmark_tests', 'configs', 'toy-kexaone.json')) as _f:
    TOY_KEXAONE = json.load(_f)
CHUNK_POOLS = ['dense', 'lfm2-tails', 'kexaone-rings']


def _chunk_cfg(pool, **kw):
    """Buckets of 8 and 16 under prompts of 35: two chunks of 16 and a
    last dispatch of 3 rows in the 8 bucket. `kexaone-rings`: window
    layers of 12 keys, their K/V in a ring a slot beside the global
    layer's pool (tests/test_kexaone_serving.py's toy)."""
    if pool == 'kexaone-rings':
        from benchmark.models import kexaone
        kw.setdefault('model', kexaone.lm_config(TOY_KEXAONE, MAX_LEN, False))
        kw.setdefault('prefix_sharing', False)
        return _cfg(**kw)
    return _pool_cfg(pool, **kw)


def _pass(eng, k):
    """One pass of the loop behind step `k`, by hand: the next step out,
    `k` landed and delivered, then the admissions."""
    nxt = eng._step_dispatch(prev=k)
    eng._step_complete(k, nxt)
    eng._admit()
    return nxt


@pytest.mark.parametrize('pool', CHUNK_POOLS)
def test_f_a_chunked_prefill_takes_one_chunk_a_pass(pool):
    """A is resident with a step in flight; B's prompt is three
    dispatches wide. Each pass dispatches ONE of them behind the step
    just sent, so the device runs step, chunk, step, chunk: A's token gap
    holds a chunk and never the whole prompt. B's slot is neither free
    nor resident meanwhile, C waits in the queue until B's last chunk is
    out, the step behind a chunk waits for the chunk before its own
    fetch (and every gap with a chunk in it reads as one that held an
    admission), and all three streams are generate_once's."""
    eng = GenerateEngine(_chunk_cfg(pool))
    work = [(_prompt(6, seed=91), 12), (_prompt(35, seed=92), 5),
            (_prompt(7, seed=93), 4)]
    ref = [eng.generate_once(p, max_new_tokens=n) for p, n in work]
    log = []
    _watch(eng, log)
    before = monitor.counters()
    a = eng.submit(work[0][0], max_new_tokens=work[0][1])
    eng._admit()
    k = eng._step_dispatch()
    b, c = [eng.submit(p, max_new_tokens=n) for p, n in work[1:]]
    del log[:]
    eng._admit()
    # one chunk and no more; B under way, C not popped
    assert log == [('prefill', 16)]
    assert eng._chunking is not None and eng._chunking.req is b
    assert eng.queue.depth() == 1 and eng.stats()['active'] == 1
    assert len(eng._free) == eng.config.slots - 2
    assert [f.st for f in eng._firsts] == [None]
    k = _pass(eng, k)
    # step, A's tokens, then the second chunk behind the step just sent
    assert log[1:] == [('step', 0), ('fetch', eng.config.slots),
                       ('prefill', 16)]
    assert eng._chunking.off == 32 and b.tokens == []
    del log[:]
    k = _pass(eng, k)
    # the step that went out behind the FIRST chunk landed: no fetch of
    # the chunk's output, the loop only waited for it; B's last dispatch
    assert log == [('step', 0), ('fetch', eng.config.slots),
                   ('prefill', 8)]
    assert eng._chunking is None and eng.stats()['active'] == 2
    assert b.tokens == [] and eng.queue.depth() == 1
    del log[:]
    k = _pass(eng, k)
    # B's row joins on its token as it is on the device; C's turn
    assert log == [('step', 1), ('fetch', eng.config.slots),
                   ('prefill', 8)]
    assert b.tokens == [] and eng.queue.depth() == 0
    k = _pass(eng, k)
    # the first token picked up before the fetch of the step it fed
    assert b.tokens == ref[1][:2]
    eng._step_complete(k)
    reqs = (a, b, c)
    while any(r.finish_reason is None for r in reqs):
        eng._step()
    assert [list(r.result(5)) for r in reqs] == ref
    delta = monitor.counter_delta(before)
    assert delta['generate_admit_total'] == 3
    # A's gaps behind B's two chunks, B's last dispatch and C's prefill
    assert a.admissions_waited == 4
    assert eng._firsts == [] and eng.stats()['active'] == 0
    if not eng.config.prefix_sharing:   # the index keeps B's full blocks
        assert eng.stats()['blocks']['in_use'] == 0


@pytest.mark.parametrize('pool', CHUNK_POOLS)
def test_f_chunked_admissions_under_the_loop_and_a_stop_between_chunks(pool):
    """Under the running loop, long and short prompts mixed: every stream
    is generate_once's, `prefill_seconds` has one observation an
    admission. Then stop() between two chunks of a prefill: the request
    fails by name, its slot and blocks go back."""
    eng = GenerateEngine(_chunk_cfg(pool))
    work = [(_prompt(n, seed=100 + n), m)
            for n, m in ((35, 6), (5, 9), (33, 4), (17, 8), (40, 5), (9, 7))]
    ref = [eng.generate_once(p, max_new_tokens=n) for p, n in work]
    before = monitor.snapshot()['histograms'].get(
        'prefill_seconds', {}).get('count', 0)
    eng.start()
    _req, stream, _got = _resident(eng, _prompt(4, seed=99))
    reqs = [eng.submit(p, max_new_tokens=n, deadline_s=60.0)
            for p, n in work]
    assert [list(r.result(60)) for r in reqs] == ref
    after = monitor.snapshot()['histograms']['prefill_seconds']['count']
    assert after - before == len(work) + 1

    calls = eng._prefill_call
    gate = threading.Event()

    def slow(bound, feed):
        out = calls(bound, feed)
        if eng._flights and feed['gen_len'][0, 0] == 16:
            gate.set()
            time.sleep(0.3)             # stop() comes between two chunks
        return out
    eng._prefill_call = slow
    doomed = eng.submit(_prompt(38, seed=77), max_new_tokens=5,
                        deadline_s=60.0)
    assert gate.wait(30.0)
    eng.stop()
    with pytest.raises(generate_mod.EngineStoppedError,
                       match='between two chunks'):
        doomed.result(30)
    with pytest.raises(generate_mod.EngineStoppedError):
        list(stream)
    assert eng._chunking is None and eng._firsts == []
    assert eng.stats()['active'] == 0
    assert sorted(eng._free) == list(range(eng.config.slots))
    assert eng.stats()['blocks']['in_use'] == 0
