"""monitor.phase and what is built on it: the decode loop's and
Executor.run's phase counters add up to the wall time they split, spans
and phases land in a profiler trace as `paddle_tpu:<name>`, compiled
programs carry their program's name, and a phase costs little with no
profiler session. Since PR 37 also the admission taken apart — since PR
38 `prefill.dispatch` nested in `prefill`, and `prefill.fetch`, the
loop's wait for the first token where it picks it up, a pass or two
later; `prefill.drain` is gone with the wait it timed — and every
delivered token gap booked by whether another request's admission
completed inside it."""
import gc
import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.core import lowering
from paddle_tpu.models.transformer import LMConfig, build_lm_decode_step
from paddle_tpu.serving import GenerateConfig, GenerateEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOOP = 'generate_loop_seconds_total'
ADMISSION = ('prefill', 'prefill.dispatch', 'prefill.fetch')
RUN = 'executor_run_phase_seconds_total'


def _engine():
    # wide enough that a decode step takes the CPU a few milliseconds:
    # the microseconds between two phases are then well under the 5 %
    return GenerateEngine(GenerateConfig(
        model=LMConfig(vocab_size=64, seq_len=32, d_model=256, n_head=4,
                       n_layer=4, d_ff=1024, dropout=0.0, attn_dropout=0.0,
                       use_flash_attention=False),
        slots=4, max_len=48, prompt_buckets=[8, 16], eos_id=None, seed=0))


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(2, 64, size=n) \
        .astype('int64')


def _phases(delta, counter):
    prefix = counter + '{phase='
    return {k[len(prefix):-1]: v for k, v in delta.items()
            if k.startswith(prefix)}


def _hist_sum(name):
    return monitor.snapshot()['histograms'].get(name, {}).get('sum', 0.0)


# ---------------------------------------------------------------------------
# the primitive


def test_a_nested_phase_is_taken_out_of_its_parents_self_time():
    before = monitor.counters()
    with monitor.phase('t.outer', 't_phase_seconds_total', {'phase': 'o'}):
        time.sleep(0.02)
        with monitor.phase('t.inner', 't_phase_seconds_total',
                           {'phase': 'i'}):
            time.sleep(0.03)
    got = _phases(monitor.counter_delta(before), 't_phase_seconds_total')
    assert got['i'] == pytest.approx(0.03, abs=0.008)
    assert got['o'] == pytest.approx(0.02, abs=0.008)
    assert 't.outer' not in [s['name'] for s in monitor.spans()]


def test_a_phase_counts_also_when_its_body_raises():
    before = monitor.counters()
    with pytest.raises(KeyError):
        with monitor.phase('t.raises', 't_raises_seconds_total'):
            time.sleep(0.005)
            raise KeyError('x')
    assert monitor.counter_delta(before)['t_raises_seconds_total'] >= 0.005
    assert not monitor._open_phase      # nothing left open on this thread


def test_monitor_alone_imports_no_jax():
    """`import paddle_tpu` brings jax in through the package's own
    __init__; the module itself must not, spans and phases included, so a
    parent that loads it alone stays off jax."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('mon', %r)\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "with m.span('s'):\n"
        "    with m.phase('p', 'p_seconds_total', {'phase': 'p'}):\n"
        "        pass\n"
        "assert m.counters()['p_seconds_total{phase=p}'] >= 0\n"
        "assert 'jax' not in sys.modules, 'monitor imported jax'\n"
        % os.path.join(REPO, 'paddle_tpu', 'monitor.py'))
    done = subprocess.run([sys.executable, '-c', code], timeout=120,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# the decode loop


def test_loop_phases_add_up_to_the_loops_wall_time():
    eng = _engine()
    eng.warmup()
    work = [(_prompt(4, 1), 9), (_prompt(7, 2), 14), (_prompt(12, 3), 6),
            (_prompt(16, 4), 11), (_prompt(5, 5), 8), (_prompt(9, 6), 13)]
    before = monitor.counters()
    prefill0 = _hist_sum('prefill_seconds')
    with eng:
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        for r, (_p, n) in zip(reqs, work):
            assert len(r.result(timeout=60)) == n
    delta = monitor.counter_delta(before)
    phases = _phases(delta, LOOP)
    wall = delta['generate_loop_wall_seconds_total']
    assert {'admit', 'prefill', 'prefill.dispatch', 'prefill.fetch',
            'feed', 'dispatch', 'admit_overlapped', 'wait', 'deliver',
            'yield', 'idle'} <= set(phases)
    assert 'prefill.drain' not in phases    # no admission waits for a step
    # self times: nothing counts twice, and little is left uncovered
    assert sum(phases.values()) <= wall * 1.001
    assert sum(phases.values()) >= wall * 0.95
    # `admit` holds admissions without their prefills: those are
    # `prefill`, the bound call nested in it, and the wait at the
    # pick-up — the loop's own time for an admission, which is what
    # prefill_seconds observes. A request's `prefill` stage runs from the
    # admission's start to the token on the host, ACROSS the passes in
    # between where a step was in flight: no shorter
    prefill_s = _hist_sum('prefill_seconds') - prefill0
    parts = [phases[p] for p in ADMISSION]
    assert sum(parts) == pytest.approx(prefill_s, rel=0.2)
    assert min(parts) > 0
    assert sum(r.result().timing['prefill_s'] for r in reqs) \
        >= prefill_s * 0.999
    assert phases['admit'] + phases['admit_overlapped'] < wall - prefill_s
    assert delta['generate_admit_total'] == len(work)
    assert delta['generate_queue_wait_seconds_total'] > 0
    loop = eng.stats()['loop']
    flat = monitor.counters()
    assert loop['admitted'] == flat['generate_admit_total']
    assert loop['wall_s'] == flat['generate_loop_wall_seconds_total']
    assert loop['phase_s']['feed'] == flat[LOOP + '{phase=feed}']


def test_an_admission_with_a_step_in_flight_blocks_for_nothing():
    """A step is in flight when B is admitted: the admission is its
    prefill's bound call and returns with the step still unfetched —
    nothing is fetched, `prefill.drain` never opens, and no phase counter
    of the admission moves yet. The wait is where the token is picked up,
    before the fetch of the first step that carries B: `prefill.fetch`,
    and that step's `decode_step_seconds` runs from the pick-up's end,
    not from the fetch before: what lies between was the prefill's.
    There the admission is booked whole, counters and histogram at one
    moment: `prefill_seconds` observes the three phases' seconds."""
    eng = _engine()
    eng.warmup()
    a = eng.submit(_prompt(5, 1), max_new_tokens=8)
    eng._admit()
    eng._step()
    k = eng._step_dispatch()                    # step k, in flight
    before = monitor.counters()
    prefill0 = _hist_sum('prefill_seconds')
    b = eng.submit(_prompt(9, 2), max_new_tokens=4)
    t0 = time.perf_counter()
    eng._admit()
    admit_s = time.perf_counter() - t0
    assert _phases(monitor.counter_delta(before), LOOP) == {}
    assert eng._flights == [k] and b.tokens == []
    nxt = eng._step_dispatch(prev=k)            # carries B on the device
    eng._step_complete(k, nxt)
    between = _phases(monitor.counter_delta(before), LOOP)
    assert not set(between) & set(ADMISSION) and b.tokens == []
    split, step0 = eng._split_load, _hist_sum('decode_step_seconds')
    eng._split_load = lambda out, n: (time.sleep(0.05 if n == 1 else 0.0),
                                      split(out, n))[1]
    t0 = time.perf_counter()
    eng._step_complete(nxt)                     # the pick-up, then k + 1
    whole_s = time.perf_counter() - t0
    eng._split_load = split
    got = _phases(monitor.counter_delta(before), LOOP)
    assert 'prefill.drain' not in got and set(ADMISSION) <= set(got)
    assert got['prefill.fetch'] >= 0.05 and len(b.tokens) == 2
    # the step's observation begins where the first token landed
    assert _hist_sum('decode_step_seconds') - step0 \
        <= whole_s - got['prefill.fetch']
    assert 0 < got['prefill'] + got['prefill.dispatch'] <= admit_s
    assert _hist_sum('prefill_seconds') - prefill0 == pytest.approx(
        sum(got[p] for p in ADMISSION), rel=1e-6)
    # with nothing in flight the wait is the prefill's own: no step's
    picked = eng._picked_t
    lone = eng.submit(_prompt(4, 3), max_new_tokens=1)
    _drive(eng, a, b, lone)
    assert eng._picked_t == picked


def test_a_chunked_prefill_books_every_chunk():
    """A prompt wider than the widest bucket prefills in chunks, each
    through `_prefill_call`: the three dispatches of a 40-token prompt
    (16 + 16 + 8) are all inside the one phase, and only the last one's
    output is fetched — the pick-up's."""
    eng = _engine()
    eng.warmup()
    calls, fetched = [], []
    for b, f in list(eng._prefill_bound.items()):
        eng._prefill_bound[b] = (
            lambda feed, return_numpy, _b=b, _f=f:
            calls.append(_b) or _f(feed, return_numpy=return_numpy))
    split = eng._split_load
    eng._split_load = lambda out, n: (fetched.append(n), split(out, n))[1]
    before = monitor.counters()
    req = eng.submit(_prompt(40, 2), max_new_tokens=1)
    eng._admit()
    got = _phases(monitor.counter_delta(before), LOOP)
    assert calls == [16, 16, 8] and fetched == [1]
    assert set(got) == set(ADMISSION) and len(req.result(5)) == 1
    assert got['prefill.dispatch'] > got['prefill'] > 0


def _drive(eng, *reqs):
    """The loop's pass inline, no thread: a step, then admission."""
    while any(r.finish_reason is None for r in reqs):
        eng._step()
        eng._admit()


def _gaps(delta):
    return {held: (delta.get('generate_token_gaps_total{held=%s}' % held, 0),
                   delta.get('generate_token_gap_seconds_total{held=%s}'
                             % held, 0.0))
            for held in ('admission', 'none')}


def test_a_gap_that_held_an_admission_is_booked_apart():
    """A is resident; B is admitted between A's second and third token.
    Exactly that gap of A's is `held=admission`; B's own prefill is
    before B's first gap, which is plain like every other; the request's
    timing says the same."""
    eng = _engine()
    eng.warmup()
    a = eng.submit(_prompt(6, 1), max_new_tokens=6)
    eng._admit()
    before = monitor.counters()
    eng._step()                                 # A's second token
    assert _gaps(monitor.counter_delta(before))['admission'] == (0, 0.0)
    b = eng.submit(_prompt(9, 2), max_new_tokens=4)
    eng._admit()                                # B's prefill
    mid = monitor.counters()
    eng._step()                                 # A's third, B's second
    one = _gaps(monitor.counter_delta(mid))
    assert one['admission'][0] == 1 and one['none'][0] == 1
    assert one['admission'][1] > one['none'][1] > 0     # A's held B's prefill
    _drive(eng, a, b)
    got = _gaps(monitor.counter_delta(before))
    assert len(a.tokens) == 6 and len(b.tokens) == 4
    # every token a decode step delivered closed one gap
    assert got['admission'][0] == 1
    assert got['none'][0] == (6 - 1) + (4 - 1) - 1
    ta, tb = a.result().timing, b.result().timing
    assert ta['admissions_waited'] == 1 and tb['admissions_waited'] == 0
    assert ta['admission_wait_s'] == pytest.approx(got['admission'][1])
    assert tb['admission_wait_s'] == 0.0
    assert ta['admission_wait_s'] < ta['decode_step_s']


def test_two_admissions_in_one_gap_are_one_gap_and_a_lone_row_has_none():
    eng = _engine()
    eng.warmup()
    before = monitor.counters()
    a = eng.submit(_prompt(5, 3), max_new_tokens=4)
    eng._admit()
    eng._step()
    b = eng.submit(_prompt(7, 4), max_new_tokens=3)
    c = eng.submit(_prompt(8, 5), max_new_tokens=3)
    eng._admit()            # both, one after the other
    _drive(eng, a, b, c)
    got = _gaps(monitor.counter_delta(before))
    # A's third token waited for B and C: one gap. B's first gap held
    # C's admission, C's own held none
    assert got['admission'][0] == 2
    assert a.result().timing['admissions_waited'] == 1
    assert b.result().timing['admissions_waited'] == 1
    assert c.result().timing['admissions_waited'] == 0
    assert got['admission'][0] + got['none'][0] == 3 + 2 + 2
    lone = eng.submit(_prompt(4, 6), max_new_tokens=5)
    mid = monitor.counters()
    eng._admit()
    _drive(eng, lone)
    alone = _gaps(monitor.counter_delta(mid))
    assert alone['admission'] == (0, 0.0) and alone['none'][0] == 4


def test_the_loops_gap_counts_add_up_to_its_decode_tokens():
    """Under the loop thread, whatever the schedule: the gaps counted are
    the tokens decode steps delivered (every token but a request's
    first), and the seconds booked per request add up to the counter."""
    eng = _engine()
    eng.warmup()
    work = [(_prompt(4 + i, 10 + i), 5 + i % 4) for i in range(7)]
    before = monitor.counters()
    with eng:
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        outs = [r.result(timeout=60) for r in reqs]
    delta = monitor.counter_delta(before)
    got = _gaps(delta)
    assert got['admission'][0] + got['none'][0] \
        == delta['decode_tokens_total'] - delta['generate_admit_total'] \
        == sum(n - 1 for _p, n in work)
    # 7 requests through 4 slots: some admission found a neighbour
    assert got['admission'][0] >= 1
    assert sum(o.timing['admissions_waited'] for o in outs) \
        == got['admission'][0]
    assert sum(o.timing['admission_wait_s'] for o in outs) \
        == pytest.approx(got['admission'][1])
    assert sum(o.timing['decode_step_s'] for o in outs) \
        == pytest.approx(got['admission'][1] + got['none'][1], rel=1e-3)


def test_decode_step_seconds_is_one_observation_a_step_inside_the_wall():
    """With the pipeline full a step's observation runs from the fetch
    before it to its own: one a decode step, so the count is the step
    count `decode_sampled_step_share` and `decode_overlapped_step_share`
    divide by; their sum stays inside the loop's wall time (no stretch
    is counted twice); and the mean is not below what the bound call
    itself takes — an observation round a fetch that is already there
    would read nothing, and `decode_hbm_share` over it more than the
    device can do."""
    eng = _engine()
    eng.warmup()
    S = eng.config.slots
    feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
            'gen_pos': np.zeros((S, 1), 'int64'),
            'gen_btab': np.zeros((S, eng._max_blocks), 'int64')}
    feed.update(eng._sample_feed(S))
    call_s = []
    for _ in range(8):      # all-zero tables: the trash block
        t0 = time.perf_counter()
        out = eng._step_bound(feed, return_numpy=False)
        call_s.append(time.perf_counter() - t0)     # the call, not the step
        out[0].block_until_ready()
    before = monitor.counters()
    hist0 = monitor.snapshot()['histograms'].get('decode_step_seconds', {})
    with eng:
        reqs = [eng.submit(_prompt(5 + i, i), max_new_tokens=24)
                for i in range(S)]      # every slot resident throughout
        assert [len(r.result(timeout=60)) for r in reqs] == [24] * S
    delta = monitor.counter_delta(before)
    hist1 = monitor.snapshot()['histograms']['decode_step_seconds']
    count = hist1['count'] - hist0.get('count', 0)
    total = hist1['sum'] - hist0.get('sum', 0.0)
    st = eng.stats()
    assert count == st['decode_steps'] == 23
    assert st['overlapped_steps'] == delta['generate_overlapped_steps_total']
    assert st['overlapped_steps'] >= 21         # all but the start's
    assert total <= delta['generate_loop_wall_seconds_total']
    assert total / count >= min(call_s)


# ---------------------------------------------------------------------------
# Executor.run


def _mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[256], dtype='float32')
        h = x
        for _ in range(4):
            h = fluid.layers.fc(h, size=256, act='relu')
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGD(0.01).minimize(loss)
    return main, startup, loss


def test_run_phases_add_up_to_executor_run_seconds():
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {'x': np.ones((64, 256), 'float32')}
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)    # compiles
    before, run0 = monitor.counters(), _hist_sum('executor_run_seconds')
    for _ in range(50):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    delta = monitor.counter_delta(before)
    phases = _phases(delta, RUN)
    assert set(phases) == {'prepare', 'dispatch', 'commit', 'fetch'}
    assert delta['executor_run_total'] == 50
    run_s = _hist_sum('executor_run_seconds') - run0
    assert sum(phases.values()) == pytest.approx(run_s, rel=0.10)


def test_a_first_run_is_the_compile_phase():
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    before = monitor.counters()
    exe.run(main, feed={'x': np.ones((3, 256), 'float32')},
            fetch_list=[loss], scope=scope)
    phases = _phases(monitor.counter_delta(before), RUN)
    assert 'dispatch' not in phases
    assert phases['compile'] > phases['prepare']


# ---------------------------------------------------------------------------
# names


def test_a_program_lowers_to_a_module_of_its_name():
    import jax

    def build(name=None):
        prog = fluid.Program(name)
        with fluid.program_guard(prog, fluid.Program()):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name='x', shape=[4], dtype='float32')
                y = fluid.layers.scale(x, scale=2.0)
        return prog, y

    named, y = build('lm_decode_step')
    plain, _ = build()
    assert plain.name == 'program'
    assert named._fingerprint() == plain._fingerprint()
    assert named.clone().name == 'lm_decode_step'
    fn, _ro, _rw = lowering.build_callable(named, [y.name], [], [])
    text = fn.lower({'x': np.ones((2, 4), 'float32')}, {}, {},
                    jax.random.PRNGKey(0)).as_text()
    assert 'module @jit_lm_decode_step ' in text


def test_builders_and_the_engine_name_their_programs():
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        with fluid.unique_name.guard():
            build_lm_decode_step(LMConfig(
                vocab_size=64, seq_len=32, d_model=32, n_head=2, n_layer=1,
                d_ff=64, dropout=0.0), 2, 16, block_size=8, num_blocks=4)
    assert prog.name == 'lm_decode_step'
    eng = _engine()
    assert eng._step_prog.name == 'lm_decode_step'
    assert {b: p.name for b, (p, _v) in eng._prefill.items()} == \
        {8: 'lm_prefill_paged_b8', 16: 'lm_prefill_paged_b16'}


# ---------------------------------------------------------------------------
# the profiler's trace


def test_spans_and_phases_land_in_the_profiler_trace(tmp_path):
    import jax
    main, startup, loss = _mlp()
    exe, scope = fluid.Executor(), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {'x': np.ones((8, 256), 'float32')}
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    eng = _engine()
    eng.warmup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        with eng:
            assert len(eng.submit(_prompt(5, 1), max_new_tokens=4)
                       .result(timeout=60)) == 4
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*'
                          / '*.xplane.pb'))
    names = {e.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith('/host:')
             for line in plane.lines for e in line.events
             if e.name.startswith(monitor.ANNOTATION_PREFIX)}
    assert {'paddle_tpu:run', 'paddle_tpu:run.prepare',
            'paddle_tpu:run.dispatch', 'paddle_tpu:run.commit',
            'paddle_tpu:run.fetch', 'paddle_tpu:generate.admit',
            'paddle_tpu:generate.prefill',
            'paddle_tpu:generate.prefill.dispatch',
            'paddle_tpu:generate.prefill.fetch', 'paddle_tpu:generate.feed',
            'paddle_tpu:generate.dispatch', 'paddle_tpu:generate.wait',
            'paddle_tpu:generate.deliver'} <= names


# ---------------------------------------------------------------------------
# cost with no profiler session


def _best_call_us(hook, n=6000, rounds=5):
    """Min of per-call timings over interleaved rounds, gc off: the
    method of the repo's other overhead guards (a preempted timeslice
    poisons a block average, but only one call)."""
    pc, best = time.perf_counter, float('inf')
    gc.disable()
    try:
        for _ in range(rounds):
            for _ in range(n):
                t0 = pc()
                hook()
                dt = pc() - t0
                if dt < best:
                    best = dt
    finally:
        gc.enable()
    return best * 1e6


def test_phase_overhead_within_the_step_and_run_budgets():
    """What the phases add with no profiler session: at most 30 us a
    decode step (its phases and the pass's wall counter) and 10 us
    an Executor.run (its four)."""
    import jax
    assert not jax.profiler.TraceAnnotation.is_enabled()
    from paddle_tpu.executor import _run_phase
    from paddle_tpu.serving.generate import _loop_phase

    def step():
        for name in ('admit', 'feed', 'dispatch', 'admit_overlapped',
                     'wait', 'deliver', 'yield'):
            with _loop_phase(name):
                pass
        monitor.inc('generate_loop_wall_seconds_total', 0.0)

    def run():
        for name in ('prepare', 'dispatch', 'commit', 'fetch'):
            with _run_phase(name):
                pass

    spans_before = monitor.span_seq()
    step_us, run_us = _best_call_us(step), _best_call_us(run)
    assert step_us <= 30.0, 'phases of a decode step: %.1f us' % step_us
    assert run_us <= 10.0, 'phases of an Executor.run: %.1f us' % run_us
    assert monitor.span_seq() == spans_before   # nothing went to the ring
