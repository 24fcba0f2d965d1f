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


def measure_run_overhead(rounds=300):
    """Returns {'run_overhead_us', 'first_compile_s', 'cache_hit_compile_s',
    'rounds'}; importable."""
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

    return {'run_overhead_us': round(overhead_us, 1),
            'first_compile_s': round(first_compile_s, 3),
            'cache_hit_compile_s': round(cache_hit_compile_s, 4),
            'rounds': rounds}


if __name__ == '__main__':
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 300
    print(json.dumps(measure_run_overhead(n)))
