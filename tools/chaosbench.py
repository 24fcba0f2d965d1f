"""Chaos drill bench: time-to-recover after a mid-run kill, with elastic
(reshard-on-load) resume.

Measures the contract docs/resilience.md makes for
``resilience.elastic_train_loop`` + topology-independent checkpoints: a
``PADDLE_FAULT_SPEC``-style fatal fault kills a training step mid-run;
the loop rebuilds a mesh over a SHRUNKEN device set (half the visible
devices — the 8 -> 4 simulated-host drill on the CPU test mesh),
restores the newest valid checkpoint resharded onto it, and replays.
Reported:

- time_to_recover_s: wall clock from the kill to the completion of the
  first successful post-resume step (checkpoint restore + reshard +
  recompile for the new device set + one step);
- steps_lost: how many optimizer steps had to be replayed (kill step -
  resume step; bounded by the checkpoint cadence);
- trajectory_parity: the elastic run's per-step losses bit-match an
  uninterrupted same-math baseline (contract: True);
- devices '8->4', checkpoint cadence, and the elastic_resume /
  ckpt_reshard counter deltas;
- bundles / bundle_write_ms: the drill runs with the blackbox flight
  recorder ON (scoped env) and ASSERTS the kill published an incident
  bundle — the recorder's cost is on the perf record from day one
  (docs/observability.md "Incident flight recorder").

Usage: python tools/chaosbench.py [steps] [kill_at]   (prints one JSON
line; PADDLE_FAULT_SPEC-equivalent faults are installed
programmatically so the drill is self-contained). `--grow` runs the
shrink-THEN-grow drill instead (kill halves the fleet, capacity later
returns and the loop re-expands onto the full mesh); it forces an
8-way CPU mesh and reports time-to-recover both directions.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_model(seed):
    import paddle_tpu as fluid
    fluid.unique_name.switch()          # same var names on every build
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[16], dtype='float32')
        y = fluid.layers.data(name='y', shape=[1], dtype='int64')
        h = fluid.layers.fc(x, size=32, act='relu')
        p = fluid.layers.fc(h, size=4, act='softmax')
        loss = fluid.layers.mean(fluid.layers.cross_entropy(p, y))
        fluid.optimizer.Adam(0.05).minimize(loss)
    return main, startup, loss


def _batches(n, batch=32, dim=16, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = rng.randn(batch, dim).astype('float32')
        y = rng.randint(0, 4, (batch, 1)).astype('int64')
        out.append({'x': x, 'y': y})
    return out


def measure_elastic_resume(steps=10, kill_at=7, every_steps=2,
                           ckpt_dir=None, seed=31):
    """One full drill; returns the bench row dict. kill_at is the 0-based
    step whose dispatch is killed (a fatal run-site fault — exactly what
    PADDLE_FAULT_SPEC='run:nth=<k>,kind=fatal' would inject)."""
    import numpy as np
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import blackbox, monitor, resilience
    from paddle_tpu.parallel.mesh import data_mesh

    import shutil
    import tempfile
    own_dir = ckpt_dir is None
    ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix='chaosbench_')
    bundle_dir = tempfile.mkdtemp(prefix='chaosbench_blackbox_')
    feeds = _batches(steps, seed=seed)

    def _run(exe, main, loss, scope, feed):
        return np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                  scope=scope)[0]).copy()

    # uninterrupted same-math baseline
    main, startup, loss = _build_model(seed)
    exe = fluid.Executor()
    s0 = fluid.Scope()
    base = []
    with fluid.scope_guard(s0):
        exe.run(startup, scope=s0)
        for i in range(steps):
            base.append(_run(exe, main, loss, s0, feeds[i]))

    devices = jax.devices()
    shrink = max(1, len(devices) // 2)
    main, startup, loss = _build_model(seed)
    s1 = fluid.Scope()
    t_fail = [None]
    t_first_ok = [None]
    resumed_at = [None]
    before = monitor.counters()
    try:
        with fluid.scope_guard(s1):
            exe.run(startup, scope=s1)
            mgr = fluid.CheckpointManager(ckpt_dir, main, scope=s1,
                                          every_steps=every_steps,
                                          keep_last_n=3)

            def step_fn(step, mesh):
                try:
                    out = _run(exe, main, loss, s1, feeds[step])
                except BaseException:
                    t_fail[0] = time.perf_counter()
                    raise
                if resumed_at[0] is not None and t_first_ok[0] is None:
                    t_first_ok[0] = time.perf_counter()
                return out

            def on_resume(step, mesh, exc):
                resumed_at[0] = step

            # the kill: (kill_at+1)-th run-site check after the startup
            # run, fatal so the retry layer steps aside. The flight
            # recorder is ON for the drill (scoped env): the kill's
            # elastic_resume must publish a bundle, and its write cost
            # goes on the bench row.
            resilience.install_fault('run', 'nth', kill_at + 1,
                                     fatal=True)
            bb_env = {'PADDLE_BLACKBOX': '1',
                      'PADDLE_BLACKBOX_DIR': bundle_dir,
                      'PADDLE_BLACKBOX_RATE': '0'}
            bb_saved = {k: os.environ.get(k) for k in bb_env}
            os.environ.update(bb_env)
            blackbox.reset()
            t0 = time.perf_counter()
            try:
                out = resilience.elastic_train_loop(
                    step_fn, mgr, steps, mesh=data_mesh(len(devices)),
                    devices_fn=lambda: devices[:shrink],
                    on_resume=on_resume)
                wall = time.perf_counter() - t0
                blackbox.flush(10.0)
                bundles = blackbox.bundles(bundle_dir)
            finally:
                for k, v in bb_saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
    finally:
        resilience.clear_faults()
        if own_dir:     # a caller-supplied dir is theirs to keep/inspect
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    delta = monitor.counter_delta(before)
    parity = all(np.array_equal(a, b) for a, b in zip(base, out))
    bundle_write_ms = blackbox.last_write_ms()
    kinds = [os.path.basename(b).split('_', 1)[1].rsplit('_', 3)[0]
             for b in bundles]
    shutil.rmtree(bundle_dir, ignore_errors=True)
    if 'elastic_resume' not in kinds:
        raise AssertionError(
            'chaosbench: the kill published no elastic_resume bundle '
            '(got %s) — the flight recorder missed the incident' % kinds)
    return {
        'steps': steps,
        'kill_at_step': kill_at,
        'ckpt_every_steps': every_steps,
        'devices': '%d->%d' % (len(devices), shrink),
        'time_to_recover_s': round(t_first_ok[0] - t_fail[0], 3)
        if t_first_ok[0] and t_fail[0] else None,
        'steps_lost': (kill_at - resumed_at[0])
        if resumed_at[0] is not None else None,
        'resumed_at_step': resumed_at[0],
        'trajectory_parity': bool(parity),
        'elastic_wall_s': round(wall, 3),
        'bundles': len(bundles),
        'bundle_write_ms': round(bundle_write_ms, 3)
        if bundle_write_ms is not None else None,
        'counters': {k: v for k, v in delta.items()
                     if k.startswith(('elastic_', 'ckpt_reshard',
                                      'ckpt_fallback', 'fault_injected'))},
    }


def _ensure_cpu_mesh(n=8):
    """Force an n-device CPU mesh for the grow drill. Only effective
    before jax's first import — growth needs a real multi-device
    reshard, which the default 1-device CPU host can't express."""
    if 'jax' in sys.modules:
        return
    os.environ['JAX_PLATFORMS'] = 'cpu'
    flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + ' --xla_force_host_platform_device_count=%d' % n
        ).strip()


def measure_shrink_grow(steps=12, kill_at=4, grow_at=8, every_steps=2,
                        seed=37):
    """The shrink-THEN-grow drill: a fatal kill at `kill_at` halves the
    fleet (elastic shrink resume), capacity returns after step `grow_at`
    completes and the loop re-expands onto the full device set
    (checkpoint-publish barrier + reshard, no replay). Reports
    time-to-recover BOTH directions plus the bitwise-parity contract vs
    an uninterrupted run. Async saves are ON — the grow barrier also
    exercises the writer flush."""
    import numpy as np
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import blackbox, monitor, resilience
    from paddle_tpu.parallel.mesh import data_mesh

    import shutil
    import tempfile
    ckpt_dir = tempfile.mkdtemp(prefix='chaosbench_grow_')
    bundle_dir = tempfile.mkdtemp(prefix='chaosbench_grow_blackbox_')
    feeds = _batches(steps, seed=seed)

    def _run(exe, main, loss, scope, feed):
        return np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                  scope=scope)[0]).copy()

    main, startup, loss = _build_model(seed)
    exe = fluid.Executor()
    s0 = fluid.Scope()
    base = []
    with fluid.scope_guard(s0):
        exe.run(startup, scope=s0)
        for i in range(steps):
            base.append(_run(exe, main, loss, s0, feeds[i]))

    devices = jax.devices()
    if len(devices) < 2:
        raise RuntimeError(
            'shrink-then-grow needs >=2 devices (got %d); run '
            '`python tools/chaosbench.py --grow`, which forces an '
            '8-way CPU mesh before jax initializes' % len(devices))
    shrink = max(1, len(devices) // 2)
    half = devices[:shrink]
    phase = ['full']
    t_fail = [None]
    t_first_ok = [None]
    t_grow_req = [None]
    t_grow_ok = [None]
    resumed = [None]            # 'shrink' after the kill, 'grow' after
    main, startup, loss = _build_model(seed)
    s1 = fluid.Scope()
    before = monitor.counters()
    try:
        with fluid.scope_guard(s1):
            exe.run(startup, scope=s1)
            mgr = fluid.CheckpointManager(ckpt_dir, main, scope=s1,
                                          every_steps=every_steps,
                                          keep_last_n=3, async_save=True)

            def step_fn(step, mesh):
                try:
                    out = _run(exe, main, loss, s1, feeds[step])
                except BaseException:
                    phase[0] = 'half'   # the kill took half the fleet
                    t_fail[0] = time.perf_counter()
                    raise
                if resumed[0] == 'shrink' and t_first_ok[0] is None:
                    t_first_ok[0] = time.perf_counter()
                if resumed[0] == 'grow' and t_grow_ok[0] is None:
                    t_grow_ok[0] = time.perf_counter()
                if step == grow_at and phase[0] == 'half':
                    phase[0] = 'full'   # capacity returned; the loop's
                    t_grow_req[0] = time.perf_counter()  # probe fires
                    # at the top of the next iteration
                return out

            def on_resume(step, mesh, exc):
                resumed[0] = 'shrink' if exc is not None else 'grow'

            resilience.install_fault('run', 'nth', kill_at + 1,
                                     fatal=True)
            bb_env = {'PADDLE_BLACKBOX': '1',
                      'PADDLE_BLACKBOX_DIR': bundle_dir,
                      'PADDLE_BLACKBOX_RATE': '0'}
            bb_saved = {k: os.environ.get(k) for k in bb_env}
            os.environ.update(bb_env)
            blackbox.reset()
            t0 = time.perf_counter()
            try:
                out = resilience.elastic_train_loop(
                    step_fn, mgr, steps, mesh=data_mesh(len(devices)),
                    devices_fn=lambda: (half if phase[0] == 'half'
                                        else devices),
                    on_resume=on_resume)
                wall = time.perf_counter() - t0
                blackbox.flush(10.0)
                bundles = blackbox.bundles(bundle_dir)
            finally:
                for k, v in bb_saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
    finally:
        resilience.clear_faults()
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    delta = monitor.counter_delta(before)
    parity = all(np.array_equal(a, b) for a, b in zip(base, out))
    kinds = [os.path.basename(b).split('_', 1)[1].rsplit('_', 3)[0]
             for b in bundles]
    shutil.rmtree(bundle_dir, ignore_errors=True)
    for want in ('elastic_resume', 'elastic_grow'):
        if want not in kinds:
            raise AssertionError(
                'chaosbench grow drill: no %s bundle published (got %s)'
                % (want, kinds))
    return {
        'steps': steps,
        'kill_at_step': kill_at,
        'grow_at_step': grow_at,
        'ckpt_every_steps': every_steps,
        'devices': '%d->%d->%d' % (len(devices), shrink, len(devices)),
        'time_to_recover_shrink_s': round(t_first_ok[0] - t_fail[0], 3)
        if t_first_ok[0] and t_fail[0] else None,
        'time_to_recover_grow_s': round(t_grow_ok[0] - t_grow_req[0], 3)
        if t_grow_ok[0] and t_grow_req[0] else None,
        'trajectory_parity': bool(parity),
        'elastic_wall_s': round(wall, 3),
        'bundles': len(bundles),
        'counters': {k: v for k, v in delta.items()
                     if k.startswith(('elastic_', 'ckpt_reshard',
                                      'ckpt_async', 'fault_injected'))},
    }


def main(argv):
    if '--grow' in argv:
        argv = [a for a in argv if a != '--grow']
        _ensure_cpu_mesh(8)
        steps = int(argv[1]) if len(argv) > 1 else 12
        kill_at = int(argv[2]) if len(argv) > 2 else 4
        row = measure_shrink_grow(steps=steps, kill_at=kill_at)
        print(json.dumps({'metric': 'elastic_grow_back', **row}))
        return 0 if row['trajectory_parity'] else 1
    steps = int(argv[1]) if len(argv) > 1 else 10
    kill_at = int(argv[2]) if len(argv) > 2 else 7
    row = measure_elastic_resume(steps=steps, kill_at=kill_at)
    print(json.dumps({'metric': 'elastic_resume', **row}))
    return 0 if row['trajectory_parity'] else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv))
