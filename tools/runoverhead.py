"""Per-run host-overhead micro-bench for the Executor hot path.

Answers two questions the residency/compile-cache contract (docs/
executor_performance.md) makes measurable promises about:

- run_overhead_us: host time of ONE steady-state `Executor.run` dispatch on
  a 1-op program (`w <- w + 1` on a small device-resident persistable) —
  after the first call this is pure per-run tax (cache-key computation,
  state staging from the scope, jit dispatch), with no host<->device
  parameter traffic: the per-`run()` latency an un-fused serving loop
  pays.
- cache_hit_compile_s: time-to-first-run of a FRESH Executor on a REBUILT
  (structurally identical, new `_uid`) program. The process-wide
  fingerprint cache must answer it without retracing, so this should be
  milliseconds against a first_compile_s of seconds.
- bound_overhead_us: host time of ONE steady `Executor.bind` call on a
  program that reads N read-only persistables and adds them up, at N = 2
  and N = 300 (a served model's decode step reads ~300 weights): the
  handle stages them once, so what is left a variable is the compiled
  call's own argument handling. bound_overhead_us_per_var is the slope
  between the two.

- run_overhead_us_rw: host time of ONE steady `Executor.run` on a program
  that increments N read-written persistables, at N = 2 and N = 1 200 (a
  24-layer AMP + Adam train step reads and writes ~1 460 leaves), and of
  its `prepare` phase alone (executor_run_phase_seconds_total). A steady
  run takes its state from the run before, so `prepare` does not grow
  with N: run_overhead_us_per_rw_var, the slope between the two, is the
  compiled call's own argument, donation and output handling a leaf, and
  run_prepare_us_per_rw_var reads ~0 (a walk of the scope a run would
  read 2-3 us a leaf there).

Usage: python tools/runoverhead.py [rounds]   (prints one JSON line)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build():
    import paddle_tpu as fluid
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            w = fluid.layers.create_global_var(
                [256], value=0.0, dtype='float32', persistable=True,
                name='runoverhead_w')
            fluid.layers.increment(w)
    return main_p, startup


def measure_bound_overhead(n_vars, rounds=300):
    """Host microseconds of one steady bound call on a program that reads
    `n_vars` read-only persistables of 256 floats and adds them up."""
    import jax
    import numpy as np
    import paddle_tpu as fluid
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[256], dtype='float32')
            total = fluid.layers.sums([x] + [
                fluid.layers.create_global_var(
                    [256], value=float(i), dtype='float32',
                    persistable=True, name='boundoverhead_w%d' % i)
                for i in range(n_vars)])
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    feed = {'x': np.zeros((1, 256), 'float32')}
    exe.run(startup, scope=scope)
    bound = exe.bind(main_p, feed, fetch_list=[total], scope=scope)
    assert len(bound._entry.ro_names) == n_vars
    out = bound(feed, return_numpy=False)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(rounds):
        out = bound(feed, return_numpy=False)
    jax.block_until_ready(out)
    return (time.time() - t0) / rounds * 1e6


def measure_rw_overhead(n_vars, rounds=100):
    """(host microseconds of one steady `Executor.run`, of its `prepare`
    phase alone) on a program that increments `n_vars` read-written
    persistables of 16 floats."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            for i in range(n_vars):
                fluid.layers.increment(fluid.layers.create_global_var(
                    [16], value=0.0, dtype='float32', persistable=True,
                    name='rwoverhead_w%d' % i))
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    for _ in range(3):
        exe.run(main_p, scope=scope)
    jax.block_until_ready(scope.get('rwoverhead_w0'))
    series = 'executor_run_phase_seconds_total{phase=prepare}'
    before = monitor.counters().get(series, 0.0)
    t0 = time.time()
    for _ in range(rounds):
        exe.run(main_p, scope=scope)
    jax.block_until_ready(scope.get('rwoverhead_w0'))
    run_us = (time.time() - t0) / rounds * 1e6
    prepare_us = (monitor.counters().get(series, 0.0) - before) \
        / rounds * 1e6
    return run_us, prepare_us


def measure_rw_slope(rounds=100, few=2, many=1200):
    """{'run_overhead_us_rw', 'run_prepare_us_rw',
    'run_overhead_us_per_rw_var', 'run_prepare_us_per_rw_var'}: a steady
    run at `few` and at `many` read-written leaves and the slopes."""
    us = {n: measure_rw_overhead(n, rounds) for n in (few, many)}
    slope = [round((us[many][i] - us[few][i]) / (many - few), 3)
             for i in (0, 1)]
    return {'run_overhead_us_rw': {str(n): round(v[0], 1)
                                   for n, v in us.items()},
            'run_prepare_us_rw': {str(n): round(v[1], 1)
                                  for n, v in us.items()},
            'run_overhead_us_per_rw_var': slope[0],
            'run_prepare_us_per_rw_var': slope[1]}


def measure_run_overhead(rounds=300):
    """Returns {'run_overhead_us', 'first_compile_s', 'cache_hit_compile_s',
    'bound_overhead_us', 'bound_overhead_us_per_var', 'rounds'} and
    measure_rw_slope's four; importable."""
    import jax
    import paddle_tpu as fluid

    main_p, startup = _build()
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t0 = time.time()
        exe.run(startup, scope=scope)
        exe.run(main_p, scope=scope)                 # compile
        jax.block_until_ready(scope.get('runoverhead_w'))
        first_compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(rounds):
            exe.run(main_p, scope=scope)
        jax.block_until_ready(scope.get('runoverhead_w'))
        overhead_us = (time.time() - t0) / rounds * 1e6

    # fresh Executor + rebuilt identical program: the process-wide
    # fingerprint cache (and, cross-process, JAX's persistent compilation
    # cache) must make this a hit, not a recompile
    main2, startup2 = _build()
    exe2 = fluid.Executor(fluid.TPUPlace(0))
    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        exe2.run(startup2, scope=scope2)
        t0 = time.time()
        exe2.run(main2, scope=scope2)
        jax.block_until_ready(scope2.get('runoverhead_w'))
        cache_hit_compile_s = time.time() - t0

    few, many = 2, 300
    bound_us = {n: measure_bound_overhead(n, rounds) for n in (few, many)}
    return dict(measure_rw_slope(min(rounds, 100)),
                run_overhead_us=round(overhead_us, 1),
                first_compile_s=round(first_compile_s, 3),
                cache_hit_compile_s=round(cache_hit_compile_s, 4),
                bound_overhead_us={str(n): round(us, 1)
                                   for n, us in bound_us.items()},
                bound_overhead_us_per_var=round(
                    (bound_us[many] - bound_us[few]) / (many - few), 3),
                rounds=rounds)


if __name__ == '__main__':
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 300
    print(json.dumps(measure_run_overhead(n)))
