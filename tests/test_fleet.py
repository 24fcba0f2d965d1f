"""Multi-tenant fleet layer (serving/fleet.py + serving/router.py +
kv_blocks.QuotaBlockAllocator): shared-budget residency, goodput-priced
admission by priority/deadline, per-tenant paged-block quotas with
structural prefix-eviction isolation, and zero-downtime hot-swap under
live traffic.

Router policy tests run against a STUB fleet (requests are plain
event/timing records) with SYNTHETIC goodput costs — the admission math
is pure bookkeeping and must be testable without engines or sleeps.
Fleet lifecycle tests load real ServingEngines over the same tiny
2-fc model test_serving.py builds (fingerprint compile cache keeps the
warmups at milliseconds after the first compile). The paged two-tenant
test drives two GenerateEngines INLINE (loop threads never started)
over ONE shared BlockAllocator pool — the test_paged_generate.py
determinism idiom. The measure_fleet macro bench is @slow
(tests/conftest.py asserts this file's marker split)."""
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import goodput, monitor
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import (FleetError, GenerateConfig,
                                GenerateEngine, LoadShedError, ModelFleet,
                                Router, TenantConfig)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


# ---------------------------------------------------------------------------
# shared-budget block accounting (host-side, no programs)


def test_quota_view_accounting():
    fleet = ModelFleet(block_budget=10, block_size=8)
    pool = fleet.block_pool
    a = fleet.block_view('a', 4)
    b = fleet.block_view('b', 6)
    assert pool.capacity == 10
    assert a.capacity == 4 and a.available() == 4
    assert a.block_size == 8

    got = a.alloc(4)
    assert got is not None and len(got) == 4
    assert a.alloc(1) is None               # over quota, pool NOT touched
    assert a.in_use() == 4 and a.available() == 0
    assert pool.in_use() == 4
    assert b.available() == 6               # a's quota is invisible to b

    # within-tenant extra refs (the prefix-sharing case) hold the same
    # physical block — one unit of quota, not two
    a.ref(got[0])
    assert a.in_use() == 4
    assert not a.deref(got[0])              # still held once -> not freed
    assert a.in_use() == 4

    got_b = b.alloc(6)
    assert got_b is not None and b.alloc(1) is None
    with pytest.raises(ValueError):
        b.ref(got[0])                       # un-owned block at quota
    with pytest.raises(ValueError):
        b.deref(got[0])                     # never held through this view

    # conservation: every deref lands back in the ONE free list
    assert a.deref_many(got) == 4
    assert b.deref_many(got_b) == 6
    assert a.in_use() == 0 and b.in_use() == 0
    assert pool.in_use() == 0 and pool.available() == 10


def test_quota_view_validation():
    fleet = ModelFleet(block_budget=4, block_size=8)
    with pytest.raises(ValueError):
        fleet.block_view('t', 0)
    with pytest.raises(FleetError):
        ModelFleet().block_view('t', 1)     # no shared pool configured


def test_shared_pool_concurrent_tenants_conserve_blocks():
    """Three tenants' decode threads hammer ONE pool through their
    views: the pool lock makes every check-then-mutate atomic, so the
    free list never underflows (an unsynchronized allocator IndexErrors
    here) and refcounts conserve exactly."""
    fleet = ModelFleet(block_budget=8, block_size=8)
    pool = fleet.block_pool
    views = [fleet.block_view('t%d' % i, 4) for i in range(3)]
    errors = []

    def hammer(view, seed):
        rng = np.random.RandomState(seed)
        try:
            for _ in range(300):
                got = view.alloc(int(rng.randint(1, 4)))
                if got is None:             # quota or pool dry — legal
                    continue
                view.ref(got[0])            # within-tenant prefix share
                view.deref(got[0])
                view.deref_many(got)
        except Exception as e:              # noqa: BLE001 — any crash
            errors.append(e)                # is the regression

    threads = [threading.Thread(target=hammer, args=(v, i))
               for i, v in enumerate(views)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30.0)
    assert errors == []
    assert all(v.in_use() == 0 for v in views)
    assert pool.in_use() == 0 and pool.available() == 8
    assert all(r == 0 for r in pool._ref)


# ---------------------------------------------------------------------------
# live cost estimates (goodput)


def _seed_cost(name, device_s, n=1):
    """Synthetic goodput stream: `n` dispatches of `device_s` busy each
    for model `name` (disjoint windows — busy attribution is serial)."""
    fp = (name + '-fp').ljust(40, '0')[:40]
    goodput.name_model(fp, name)
    t = 100.0
    for _ in range(n):
        goodput.note_dispatch(fp, 'serve', t, t + device_s)
        t += 2.0 * device_s


def test_cost_estimate_from_live_goodput():
    goodput.reset()
    try:
        assert goodput.cost_estimate('fleet_nobody') is None
        _seed_cost('fleet_billing', 0.02, n=3)
        est = goodput.cost_estimate('fleet_billing')
        assert est['model'] == 'fleet_billing'
        assert est['dispatches'] == 3
        assert est['device_s_per_dispatch'] == pytest.approx(0.02,
                                                             rel=1e-6)
        assert est['device_s'] == pytest.approx(0.06, rel=1e-6)
        assert 'serve' in est['by_kind']
        assert goodput.cost_estimate('fleet_billing',
                                     kind='other') is None
    finally:
        goodput.reset()


# ---------------------------------------------------------------------------
# router admission policy (stub fleet — no engines)


class _FakeReq(object):
    def __init__(self):
        self._event = threading.Event()
        self.timing = {}

    def finish(self, queue_s=None):
        if queue_s is not None:
            self.timing['queue_s'] = queue_s
        self._event.set()


class _StubFleet(object):
    def __init__(self):
        self.submitted = []

    def submit(self, name, feed, deadline_s=None, **kw):
        req = _FakeReq()
        self.submitted.append((name, req))
        return req


def test_router_tenant_quota_shed():
    goodput.reset()
    r = Router(_StubFleet(), tenants={
        't': TenantConfig('rq_model', max_outstanding=2)})
    r.submit('t', {})
    r.submit('t', {})
    with pytest.raises(LoadShedError) as ei:
        r.submit('t', {})
    assert ei.value.reason == 'tenant_quota'
    with pytest.raises(KeyError):
        r.submit('nobody', {})


def test_router_concurrent_submits_respect_quota():
    """Racing submits must not overshoot max_outstanding: the
    provisional outstanding entry lands in the SAME locked section as
    the admission checks, so concurrent threads charge each other's
    quota even though the fleet dispatch runs unlocked."""
    goodput.reset()
    fleet = _StubFleet()
    r = Router(fleet, tenants={
        't': TenantConfig('rq_conc', max_outstanding=3)})
    n_threads = 8
    barrier = threading.Barrier(n_threads)
    outcomes = []

    def rush():
        barrier.wait()
        try:
            r.submit('t', {})
        except LoadShedError:
            outcomes.append('shed')
        else:
            outcomes.append('admitted')

    threads = [threading.Thread(target=rush) for _ in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30.0)
    assert outcomes.count('admitted') == 3      # never 4+
    assert outcomes.count('shed') == n_threads - 3
    assert len(fleet.submitted) == 3
    assert r.stats()['tenants']['t']['outstanding'] == 3


def test_router_submit_failure_releases_provisional_entry():
    """A fleet.submit that raises must roll back the provisional
    outstanding entry, or the tenant's quota leaks away permanently."""
    goodput.reset()

    class _BoomFleet(object):
        def submit(self, name, feed, deadline_s=None, **kw):
            raise RuntimeError('engine gone')

    r = Router(_BoomFleet(), tenants={
        't': TenantConfig('rq_boom', max_outstanding=1)})
    for _ in range(3):                  # quota 1, yet every retry admits
        with pytest.raises(RuntimeError):
            r.submit('t', {})
    assert r.stats()['tenants']['t']['outstanding'] == 0


def test_router_deadline_unmeetable_priced_by_goodput():
    goodput.reset()
    try:
        _seed_cost('rq_dl', 0.5)
        fleet = _StubFleet()
        r = Router(fleet, tenants={
            't': TenantConfig('rq_dl', deadline_s=0.4)})
        # own cost alone (0.5s, measured not configured) blows the 0.4s
        # deadline — admitting would burn device time for nothing
        with pytest.raises(LoadShedError) as ei:
            r.submit('t', {})
        assert ei.value.reason == 'deadline_unmeetable'
        # a roomier per-request deadline admits; the SECOND request then
        # sees the first's estimated backlog and sheds again
        r.submit('t', {}, deadline_s=0.6)
        with pytest.raises(LoadShedError) as ei:
            r.submit('t', {}, deadline_s=0.6)
        assert ei.value.reason == 'deadline_unmeetable'
        fleet.submitted[0][1].finish()
        r.submit('t', {}, deadline_s=0.6)   # reaped -> admits again
    finally:
        goodput.reset()


def test_router_priority_backlog_protects_deadline_tenant():
    goodput.reset()
    try:
        _seed_cost('rq_hi', 0.05)
        _seed_cost('rq_lo', 0.6)
        r = Router(_StubFleet(), tenants={
            'hi': TenantConfig('rq_hi', priority=10, deadline_s=1.0),
            'lo': TenantConfig('rq_lo', priority=0),
        })
        before = monitor.counters()
        r.submit('lo', {})                  # 0.6 fits inside hi's 1.0
        with pytest.raises(LoadShedError) as ei:
            r.submit('lo', {})              # 1.2 total would starve hi
        assert ei.value.reason == 'priority_backlog'
        # the asymmetry: hi ignores lo's backlog entirely and admits
        r.submit('hi', {})
        delta = monitor.counter_delta(before)
        assert any('shed_priority_backlog' in k and 'lo' in k
                   for k in delta)
        assert any('admitted' in k and 'hi' in k for k in delta)
    finally:
        goodput.reset()


def test_router_scale_hint_callback_and_slo_burn(monkeypatch):
    goodput.reset()
    bundles = []
    from paddle_tpu import blackbox
    monkeypatch.setattr(
        blackbox, 'record',
        lambda kind, **kw: bundles.append((kind, kw)))
    hints = []
    fleet = _StubFleet()
    r = Router(fleet,
               tenants={'t': TenantConfig('rq_slo', slo_ms=10.0,
                                          min_samples=2)},
               on_scale_hint=lambda tenant, hint, state:
               hints.append((tenant, hint, state)),
               hint_cooldown_s=0.0)
    for _ in range(3):
        r.submit('t', {})
    # 50 ms observed queue waits against a 10 ms SLO: hint ~5x
    for _name, req in fleet.submitted:
        req.finish(queue_s=0.05)
    r.stats()                               # reaps -> EWMA -> burn
    gauges = monitor.snapshot()['gauges']
    hint_vals = [v for k, v in gauges.items()
                 if 'fleet_scale_hint' in k and 't' in k]
    assert hint_vals and hint_vals[0] > 1.0
    assert hints and hints[0][0] == 't' and hints[0][1] > 1.0
    assert 't' in hints[0][2]               # full per-tenant queue state
    kinds = [k for k, _ in bundles]
    assert 'fleet_slo_burn' in kinds
    _, fields = bundles[kinds.index('fleet_slo_burn')]
    assert fields['cause'] == 'queue_burn' and 'tenants' in fields
    goodput.reset()


def test_router_scale_hint_callback_may_reenter(monkeypatch):
    """Burn delivery (bundle + callback) happens AFTER _lock drops, so
    a replica-manager hook that reads router.stats() — the natural
    thing for a manager deciding placement — must not deadlock."""
    goodput.reset()
    from paddle_tpu import blackbox
    monkeypatch.setattr(blackbox, 'record', lambda kind, **kw: None)
    seen = []
    fleet = _StubFleet()
    r = Router(fleet,
               tenants={'t': TenantConfig('rq_reent', slo_ms=10.0,
                                          min_samples=2)},
               on_scale_hint=lambda tenant, hint, state:
               seen.append(r.stats()),
               hint_cooldown_s=30.0)
    for _ in range(3):
        r.submit('t', {})
    for _name, req in fleet.submitted:
        req.finish(queue_s=0.05)
    r.stats()                           # reap -> burn -> re-entrant hook
    assert seen and 't' in seen[0]['tenants']
    goodput.reset()


def test_router_shed_storm_publishes_bundle(monkeypatch):
    goodput.reset()
    bundles = []
    from paddle_tpu import blackbox
    monkeypatch.setattr(
        blackbox, 'record',
        lambda kind, **kw: bundles.append((kind, kw)))
    r = Router(_StubFleet(),
               tenants={'s': TenantConfig('rq_storm',
                                          max_outstanding=1)},
               storm_n=3, storm_window_s=60.0)
    r.submit('s', {})
    for _ in range(3):
        with pytest.raises(LoadShedError):
            r.submit('s', {})
    causes = [kw.get('cause') for k, kw in bundles
              if k == 'fleet_slo_burn']
    assert 'shed_storm' in causes


# ---------------------------------------------------------------------------
# fleet lifecycle (real engines over a tiny saved model)


@pytest.fixture(scope='module')
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('fleet_model'))
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[6], dtype='float32')
            h = fluid.layers.fc(x, size=12, act='relu')
            y = fluid.layers.fc(h, size=3)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        fluid.save_inference_model(d, ['x'], [y], exe,
                                   main_program=main_p)
    return d


def _rows(n, seed=0):
    return np.random.RandomState(seed).randn(n, 6).astype('float32')


_ENGINE_KW = dict(max_batch_size=4, max_wait_ms=1.0, num_workers=2,
                  queue_cap=64)


def test_fleet_hot_swap_zero_dropped_inflight(model_dir):
    fleet = ModelFleet()
    warm = {'x': _rows(1)}
    r1 = fleet.deploy('m', model_dir, warm_feed=warm, **_ENGINE_KW)
    assert r1['version'] == 1 and not r1['swapped']
    assert r1['resident_bytes'] > 0
    errs, oks = [], [0]
    stop_evt = threading.Event()

    def traffic():
        i = 0
        while not stop_evt.is_set():
            try:
                fleet.run('m', {'x': _rows(1 + i % 3, seed=i)},
                          timeout=10.0)
            except Exception as e:      # noqa: BLE001 — any drop counts
                errs.append(e)
            else:
                oks[0] += 1
            i += 1

    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    time.sleep(0.05)                    # traffic is flowing
    r2 = fleet.deploy('m', model_dir, warm_feed=warm, **_ENGINE_KW)
    assert r2['version'] == 2 and r2['swapped'] and r2['drained_ok']
    # same program structure -> the warmfarm re-warms from its AOT
    # executables: ZERO fresh compiles on the hot path
    assert r2['warm']['compiles'] == 0 and r2['warm']['reused'] > 0
    time.sleep(0.05)                    # traffic over the NEW version
    stop_evt.set()
    th.join(10.0)
    assert fleet.version('m') == 2
    # admission prices now come from live accounting, labeled by the
    # STABLE fleet name across both versions
    est = goodput.cost_estimate('m')
    assert est is not None and est['device_s_per_dispatch'] > 0
    fleet.stop()
    assert errs == [] and oks[0] > 0
    assert fleet.models() == []


def test_fleet_failed_deploy_keeps_old_version(model_dir, tmp_path):
    fleet = ModelFleet()
    fleet.deploy('m', model_dir, **_ENGINE_KW)
    before = monitor.counters()
    with pytest.raises(Exception):
        fleet.deploy('m', str(tmp_path / 'missing'), **_ENGINE_KW)
    delta = monitor.counter_delta(before)
    assert any('fleet_deploy_total' in k and 'failed' in k
               for k in delta)
    assert fleet.version('m') == 1      # old version untouched...
    assert fleet.run('m', {'x': _rows(2)}, timeout=10.0) is not None
    fleet.stop()


def test_fleet_hbm_budget_refuses_overflow(model_dir):
    fleet = ModelFleet(hbm_budget_bytes=64)     # smaller than any model
    with pytest.raises(FleetError):
        fleet.deploy('m', model_dir, **_ENGINE_KW)
    assert fleet.models() == []
    roomy = ModelFleet(hbm_budget_bytes=10 << 20)
    roomy.deploy('m', model_dir, **_ENGINE_KW)
    assert roomy.models() == ['m']
    assert roomy.stats()['resident_bytes_total'] > 0
    roomy.stop()


# ---------------------------------------------------------------------------
# two paged decode tenants on ONE shared block pool


def _lm():
    # same shape family as test_paged_generate.py — the process-wide
    # fingerprint compile cache makes the second engine's compiles free
    return LMConfig(vocab_size=64, seq_len=32, d_model=32, n_head=2,
                    n_layer=2, d_ff=64, dropout=0.0, attn_dropout=0.0,
                    use_flash_attention=False)


def _paged_engine(view, **kw):
    kw.setdefault('model', _lm())
    kw.setdefault('slots', 4)
    kw.setdefault('max_len', 48)
    kw.setdefault('prompt_buckets', [8, 16])
    kw.setdefault('eos_id', None)
    kw.setdefault('seed', 0)
    kw.setdefault('block_size', 8)
    return GenerateEngine(GenerateConfig(**kw), block_allocator=view)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 64, size=n) \
        .astype('int64')


def _drive(eng, *reqs):
    """Run the engine loop inline (no thread) until every request
    finishes, then sweep finished slots."""
    eng._admit()
    while any(r.finish_reason is None and r._error is None
              for r in reqs):
        eng._step()
        eng._evict_expired()
        eng._admit()
    eng._evict_expired()


def test_two_paged_tenants_quota_and_prefix_isolation():
    fleet = ModelFleet(block_budget=12, block_size=8)
    pool = fleet.block_pool
    va = fleet.block_view('a', 3)
    vb = fleet.block_view('b', 9)
    ea = _paged_engine(va)
    eb = _paged_engine(vb)
    ea.warmup()
    eb.warmup()                             # fingerprint cache: ~free
    fleet.attach('gen_a', ea)
    fleet.attach('gen_b', eb)
    with pytest.raises(FleetError):
        fleet.attach('gen_a', ea)       # deploy() is the swap path
    try:
        # b populates its prefix cache: 16-token prompt = 2 full blocks
        rb = eb.submit(_prompt(16, seed=1), max_new_tokens=4)
        _drive(eb, rb)
        assert rb.finish_reason == 'length'
        assert eb._prefix is not None
        assert len(eb._prefix._entries) == 2
        b_blocks = sorted(e[0] for e in eb._prefix._entries.values())
        assert all(pool.refcount(bid) >= 1 for bid in b_blocks)
        b_held = vb.in_use()
        assert b_held >= 2                  # prefix residency survives rb

        # a: 3-block quota. Its 16-token prompt (2 blocks) admits and
        # decode grows a 3rd; the next block crossing finds the QUOTA
        # dry — finish_reason 'cache_full' — while the pool itself still
        # has free blocks (b's untouched share)
        ra = ea.submit(_prompt(16, seed=2), max_new_tokens=24)
        _drive(ea, ra)
        assert ra.finish_reason == 'cache_full'
        assert pool.available() > 0

        # a's allocation pressure ran a's evict_for — b's prefix blocks
        # are STRUCTURALLY out of reach (b's cache lives over b's view)
        assert sorted(e[0] for e in eb._prefix._entries.values()) \
            == b_blocks
        assert all(pool.refcount(bid) >= 1 for bid in b_blocks)
        assert vb.in_use() == b_held
    finally:
        fleet.stop()
    # refcount conservation: every block of both tenants came back
    assert va.in_use() == 0 and vb.in_use() == 0
    assert pool.in_use() == 0 and pool.available() == 12


# ---------------------------------------------------------------------------
# macro bench smoke (@slow: real fp32 + PTQ-int8 fleet under mixed load)


@pytest.mark.slow
def test_measure_fleet_smoke():
    from tools.servebench import measure_fleet
    row = measure_fleet(high_clients=2, low_clients=2,
                        requests_per_client=8, low_quota=2)
    hp = row['high_priority']
    assert hp['errors'] == 0 and hp['p99_under_deadline']
    assert row['hot_swap']['performed']
    assert row['hot_swap']['dropped_inflight'] == 0
    assert row['recompiles_after_warmup'] == 0
    assert row['low_priority']['shed'] > 0
    assert row['low_priority']['errors'] == 0
    assert row['int8_programs_loaded'] >= 1
    costs = [m['cost_s_per_dispatch'] for m in row['models'].values()]
    assert len(costs) == 2
    assert all(c is not None and c > 0 for c in costs)
