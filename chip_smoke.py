"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # on a machine with a TPU; no CPU mode

One process, no subprocess, no platform override, no try/except around a
phase: any failure is a traceback and a non-zero exit. It drives the repo's
main path once at the full width of the widest LM the repo has run
(L8 d1024 ff4096 V32000 seq512, batch 32, bf16 AMP; depth is the only cut,
weights are random from a seed):

  A  trainer  — build_lm + AMP fused Adam through fluid.Executor: startup,
                3 run() steps, one run_fused(steps=4) window.
  B  server   — the paged GenerateEngine on the trained scope: warmup,
                8 concurrent submit()s over both prompt buckets, two of
                them sharing a 64-token prefix; greedy parity against
                generate_once.
  C  4 chips  — the Phase A program under CompiledProgram(...)
                .with_data_parallel over data_mesh(4), when the host has
                four devices.

After each phase it prints the fused_kernel_dispatch_total{op,impl,mesh}
table and fails unless every fused unit landed on the tier declared for it
below; Phase A also checks the compiled train step for the Mosaic custom
calls of the units declared `pallas`.

Every time printed here is set-up/diagnostic wall time (compilation
included) and is not a speed result. The run's detail (phases run, steps,
losses, tokens generated, cold compile seconds per phase) is one
`report: {json}` line; the last stdout line is the verdict alone,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Exit code 0 only if every phase that ran passed.

The phases are importable functions of a SmokeConfig, so
tests/test_chip_smoke.py drives them at toy width on the virtual CPU mesh.
"""
import json
import math
import re
import sys
import time

import numpy as np


class SmokeConfig(object):
    """One smoke run: the model, the traffic, and the tier each fused unit
    is DECLARED to land on at this width (ops/kernel_tier.py)."""

    def __init__(self):
        self.lm = dict(vocab_size=32000, seq_len=512, d_model=1024,
                       n_head=16, n_layer=8, d_ff=4096, dropout=0.1,
                       attn_dropout=0.0, use_flash_attention=True)
        self.batch = 32
        self.seed = 7
        # server
        self.slots = 32
        self.max_len = 512
        self.block_size = 16
        self.prompt_buckets = [64, 256]
        self.max_new_tokens = 16
        # six independent prompts over both buckets + two that share a
        # `shared_prefix`-token prefix (prefix + tail lengths)
        self.prompt_lens = [24, 60, 100, 180, 250, 40]
        self.shared_prefix = 64
        self.shared_tails = [30, 100]
        # four chips
        self.dp_devices = 4
        # bf16 matmuls reduce in another order once the batch is split
        # four ways (measured on the v5e: 5e-5 over these three steps)
        self.dp_loss_tol = 0.01
        # declared tiers. fused_ffn_tail lands on `xla` in both phases:
        # AMP stands the kernel down in training (it is written for f32
        # row tiles), and the f32 serving panels (32 MB at d1024 ff4096)
        # exceed its VMEM predicate (ops/ffn_ops.ffn_shapes_ok).
        # lookup_table has one lowering, XLA's gather of the table where
        # it lies (PR 33), and counts as `off` at every tier. A prefill's
        # attention lands on `xla`: 16 heads x 256 rows x 512 keys of
        # float32 scores are 8 MB, under what its kernel takes
        # (ops/prefix_attention.shapes_ok).
        self.train_tiers = {
            'lookup_table': 'off', 'fused_ln_residual': 'pallas',
            'flash_attention': 'pallas', 'fused_ffn_tail': 'xla',
            'softmax_with_cross_entropy': 'pallas', 'fused_adam': 'pallas'}
        self.serve_tiers = {
            'lookup_table': 'off', 'fused_ln_residual': 'pallas',
            'fused_ffn_tail': 'xla', 'kv_decode_attention_paged': 'pallas',
            'kv_prefix_attention': 'xla'}
        # Mosaic kernel names the compiled train step must contain for the
        # units declared pallas (the `name=` of their pallas_call)
        self.mosaic_kernels = {
            'fused_ln_residual': 'fused_ln_residual_fwd',
            'flash_attention': 'flash_attention_fwd',
            'softmax_with_cross_entropy': 'softmax_ce_fwd',
            'fused_adam': 'fused_adam'}
        self.platform = 'tpu'


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def _check(cond, msg):
    if not cond:
        raise AssertionError('chip_smoke: ' + msg)


def _build_train_program(cfg):
    """The examples/train_lm.py recipe. unique_name.guard +
    fixed seeds: two builds give identical names, init and dropout keys,
    so Phase C replays Phase A's trajectory."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.transformer import build_lm, LMConfig

    lm = LMConfig(**cfg.lm)
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = cfg.seed
    with fluid.unique_name.guard():
        with fluid.program_guard(main_p, startup):
            _tokens, _labels, _logits, avg_loss = build_lm(lm)
            opt = mp.decorate(
                fluid.optimizer.Adam(learning_rate=1e-4, fuse=True))
            opt.minimize(avg_loss)
    return lm, main_p, startup, avg_loss


def _batches(cfg, n):
    rng = np.random.RandomState(cfg.seed)
    shape = (cfg.batch, cfg.lm['seq_len'])
    v = cfg.lm['vocab_size']
    return [{'tokens': rng.randint(0, v, shape).astype('int64'),
             'labels': rng.randint(0, v, shape).astype('int64')}
            for _ in range(n)]


def _scalar(x):
    return float(np.asarray(x).reshape(-1)[0])


def _misses(delta):
    return sum(v for k, v in delta.items()
               if k.startswith('compile_cache_miss'))


def check_dispatch(phase, delta, declared, mesh):
    """Print the fused_kernel_dispatch_total rows that moved in `delta` and
    fail unless every unit landed on exactly its declared tier under the
    expected mesh label, and every declared unit dispatched at all."""
    rows = {}
    for key, n in sorted(delta.items()):
        m = re.match(r'fused_kernel_dispatch_total\{(.*)\}$', key)
        if m:
            lab = dict(kv.split('=', 1) for kv in m.group(1).split(','))
            rows[(lab['op'], lab['impl'], lab['mesh'])] = int(n)
    print('[%s] fused_kernel_dispatch_total' % phase)
    for (op, impl, msh), n in sorted(rows.items()):
        print('    op=%-28s impl=%-9s mesh=%s  %d' % (op, impl, msh, n))
    for (op, impl, msh) in rows:
        _check(op in declared,
               '%s: unit %r dispatched but no tier is declared for it'
               % (phase, op))
        _check(impl == declared[op],
               '%s: unit %r landed on impl=%s, declared %s'
               % (phase, op, impl, declared[op]))
        _check(msh == mesh, '%s: unit %r dispatched under mesh=%s, '
               'expected mesh=%s' % (phase, op, msh, mesh))
    missing = set(declared) - {op for (op, _i, _m) in rows}
    _check(not missing, '%s: declared units never dispatched: %s'
           % (phase, sorted(missing)))
    return {'%s/%s' % (op, impl): n for (op, impl, _m), n in rows.items()}


# ---------------------------------------------------------------------------
# Phase A — trainer, one chip
# ---------------------------------------------------------------------------

def phase_train(cfg):
    """Returns (report, lm_config, trained_scope)."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import analysis, monitor

    lm, main_p, startup, avg_loss = _build_train_program(cfg)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    batches = _batches(cfg, 7)
    c0 = monitor.counters()

    t0 = time.perf_counter()
    exe.run(startup, scope=scope)
    startup_s = time.perf_counter() - t0

    losses, step_s, step_miss = [], [], []
    for i in range(3):
        before = monitor.counters()
        t0 = time.perf_counter()
        out, = exe.run(main_p, feed=batches[i], fetch_list=[avg_loss],
                       scope=scope)
        losses.append(_scalar(out))
        step_s.append(time.perf_counter() - t0)
        step_miss.append(_misses(monitor.counter_delta(before)))
    print('[A] losses %s  (ln V = %.4f)'
          % (['%.4f' % v for v in losses], math.log(lm.vocab_size)))
    _check(all(np.isfinite(losses)), 'A: non-finite loss %r' % (losses,))
    _check(abs(losses[0] - math.log(lm.vocab_size)) < 1.5,
           'A: step-0 loss %.4f is not within 1.5 of ln(V)=%.4f'
           % (losses[0], math.log(lm.vocab_size)))
    _check(step_miss[0] >= 1 and step_miss[1:] == [0, 0],
           'A: compile_cache_miss per step %r — expected a compile on '
           'step 1 only' % (step_miss,))

    # the bench / example path: K steps scanned in ONE compiled call over
    # batches pre-staged on the device — a different compiled program
    stacked = {k: jax.device_put(np.stack([b[k] for b in batches[3:7]]))
               for k in batches[0]}
    t0 = time.perf_counter()
    out, = exe.run_fused(main_p, stacked, fetch_list=[avg_loss],
                         scope=scope, steps=4)
    fused_loss = _scalar(out)
    fused_s = time.perf_counter() - t0
    print('[A] run_fused(steps=4) last loss %.4f' % fused_loss)
    _check(np.isfinite(fused_loss), 'A: run_fused loss not finite')

    # parameters and Adam moments live on the device
    resident = 0
    for p in main_p.global_block().all_parameters():
        names = [p.name] + [n for n in scope.names()
                            if n.startswith(p.name + '_moment')]
        _check(len(names) == 3, 'A: %s has moments %r' % (p.name, names[1:]))
        for n in names:
            v = scope.get(n)
            _check(isinstance(v, jax.Array),
                   'A: %s is %s, not a device array' % (n, type(v).__name__))
            plats = {d.platform for d in v.sharding.device_set}
            _check(plats == {cfg.platform},
                   'A: %s lives on %s, expected %s'
                   % (n, sorted(plats), cfg.platform))
            resident += v.nbytes
    print('[A] parameters + Adam moments on %s: %.1f MB'
          % (cfg.platform, resident / 1e6))

    delta = monitor.counter_delta(c0)
    fallbacks = {k: v for k, v in delta.items()
                 if k.startswith('donation_fallback_total')}
    _check(delta.get('donation_run_total', 0) >= 4 and not fallbacks,
           'A: donation_run_total moved %s, fallbacks %r'
           % (delta.get('donation_run_total', 0), fallbacks))
    table = check_dispatch('A', delta, cfg.train_tiers, '1')

    # back the counter with the compiled program itself
    wanted = {op: k for op, k in cfg.mosaic_kernels.items()
              if cfg.train_tiers.get(op) == 'pallas'}
    if wanted:
        text = analysis.lookup(main_p, kind='run').hlo_text()
        _check(text is not None, 'A: no lowered text for the train step')
        n_calls = text.count('tpu_custom_call')
        absent = sorted(k for k in wanted.values()
                        if 'kernel_name = "%s"' % k not in text)
        print('[A] train step lowered text: %d Mosaic custom calls'
              % n_calls)
        _check(n_calls > 0 and not absent,
               'A: Mosaic kernels missing from the train step: %s' % absent)

    report = {
        'steps': 3 + 4, 'losses': [round(v, 4) for v in losses],
        'fused_window_loss': round(fused_loss, 4),
        'dispatch': table,
        'setup_wall_s': {   # diagnostic: compile + first execution
            'startup': round(startup_s, 1),
            'first_step': round(step_s[0], 1),
            'warm_steps': [round(v, 3) for v in step_s[1:]],
            'fused_window_first_call': round(fused_s, 1)},
        'cold_compile_s': round(startup_s + step_s[0] + fused_s, 1),
    }
    return report, lm, scope


# ---------------------------------------------------------------------------
# Phase B — server, same process, trained scope
# ---------------------------------------------------------------------------

def phase_serve(cfg, lm, scope):
    from paddle_tpu import monitor
    from paddle_tpu.serving.generate import GenerateEngine, GenerateConfig

    rng = np.random.RandomState(cfg.seed + 1)
    v = lm.vocab_size
    prompts = [rng.randint(1, v, n) for n in cfg.prompt_lens]
    prefix = rng.randint(1, v, cfg.shared_prefix)
    prompts += [np.concatenate([prefix, rng.randint(1, v, n)])
                for n in cfg.shared_tails]

    c0 = monitor.counters()
    eng = GenerateEngine(GenerateConfig(
        model=lm, slots=cfg.slots, max_len=cfg.max_len,
        block_size=cfg.block_size, prompt_buckets=cfg.prompt_buckets,
        max_new_tokens=cfg.max_new_tokens), scope=scope)
    warm = eng.warmup()
    print('[B] warmup: %d buckets, %d compiles, %.1f s (set-up)'
          % (warm['buckets'], warm['compiles'], warm['seconds']))
    _check(warm['buckets'] == len(cfg.prompt_buckets),
           'B: warmed %d buckets of %d'
           % (warm['buckets'], len(cfg.prompt_buckets)))

    after_warm = monitor.counters()
    # sequential reference for two of the independent prompts (one per
    # bucket), on the same compiled programs, before the loop starts
    ref_ids = [0, 3]
    refs = {i: eng.generate_once(prompts[i]) for i in ref_ids}

    eng.start()
    try:
        reqs = [eng.submit(p) for p in prompts]
        # read EVERY result: a failed decode step surfaces here
        results = [r.result(timeout=600) for r in reqs]
    finally:
        eng.stop()

    for i, res in enumerate(results):
        _check(len(res) == cfg.max_new_tokens
               and res.finish_reason == 'length',
               'B: request %d returned %d tokens, finish_reason=%r'
               % (i, len(res), res.finish_reason))
    for i in ref_ids:
        _check(list(results[i]) == list(refs[i]),
               'B: request %d diverges from generate_once:\n  %r\n  %r'
               % (i, list(results[i]), list(refs[i])))
    traffic = monitor.counter_delta(after_warm)
    _check(_misses(traffic) == 0,
           'B: %d compiles after warmup' % _misses(traffic))
    hits = traffic.get('kv_prefix_hit_total{outcome=hit}', 0)
    _check(hits >= 1, 'B: no prefix hit (kv_prefix_hit_total %r)'
           % {k: n for k, n in traffic.items() if 'prefix' in k})
    tokens = sum(len(r) for r in results)
    print('[B] %d/%d requests complete, %d tokens, greedy parity on '
          'requests %s, %d prefix hit(s), 0 compiles after warmup'
          % (len(results), len(prompts), tokens, ref_ids, hits))
    table = check_dispatch('B', monitor.counter_delta(c0),
                           cfg.serve_tiers, '1')
    return {'requests': len(results), 'tokens_generated': tokens,
            'prefix_hits': int(hits), 'dispatch': table,
            'cold_compile_s': warm['seconds']}


# ---------------------------------------------------------------------------
# Phase C — four chips, data parallel
# ---------------------------------------------------------------------------

def phase_dp(cfg, ref_losses):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import monitor

    n = cfg.dp_devices
    lm, main_p, startup, avg_loss = _build_train_program(cfg)
    compiled = fluid.CompiledProgram(main_p).with_data_parallel(
        loss_name=avg_loss.name, places=fluid.tpu_places(range(n)))
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    batches = _batches(cfg, 3)
    c0 = monitor.counters()
    exe.run(startup, scope=scope)

    losses, step_s = [], []
    for b in batches:
        t0 = time.perf_counter()
        out, = exe.run(compiled, feed=b, fetch_list=[avg_loss.name],
                       scope=scope)
        losses.append(_scalar(out))
        step_s.append(time.perf_counter() - t0)
    print('[C] data_mesh(%d) losses %s  (Phase A: %s)'
          % (n, ['%.4f' % v for v in losses],
             ['%.4f' % v for v in ref_losses]))
    _check(all(np.isfinite(losses)), 'C: non-finite loss %r' % (losses,))
    worst = max(abs(a - b) for a, b in zip(losses, ref_losses))
    _check(worst <= cfg.dp_loss_tol,
           'C: losses differ from Phase A by %.4f > %.4f'
           % (worst, cfg.dp_loss_tol))

    mesh_devs = set(jax.devices()[:n])
    param_bytes = 0
    for p in main_p.global_block().all_parameters():
        v = scope.get(p.name)
        _check(set(v.sharding.device_set) == mesh_devs,
               'C: %s sits on %d device(s), expected the %d of the mesh'
               % (p.name, len(v.sharding.device_set), n))
        param_bytes += v.nbytes
    # plain data parallelism replicates the parameters: every device of
    # the mesh must hold at least one full copy (nothing parked on
    # device 0 alone)
    in_use = []
    for d in sorted(mesh_devs, key=lambda d: d.id):
        stats = d.memory_stats()      # None where the backend keeps none
        if stats is not None:
            in_use.append(stats['bytes_in_use'])
    if in_use:
        print('[C] bytes_in_use per device: %s  (parameters: %.2f GB)'
              % (['%.2f GB' % (b / 1e9) for b in in_use],
                 param_bytes / 1e9))
        _check(min(in_use) >= param_bytes,
               'C: a device holds %.2f GB, less than one copy of the '
               'parameters (%.2f GB)' % (min(in_use) / 1e9,
                                         param_bytes / 1e9))
    table = check_dispatch('C', monitor.counter_delta(c0),
                           cfg.train_tiers, 'n')
    return {'devices': n, 'steps': len(losses),
            'losses': [round(v, 4) for v in losses],
            'max_abs_diff_vs_A': round(worst, 5), 'dispatch': table,
            'cold_compile_s': round(step_s[0], 1)}


# ---------------------------------------------------------------------------

def verdict_line(ok, devs):
    """The last stdout line: exactly these keys and no others, the device
    as JAX reports it. Everything else the run learned goes on the
    `report:` line above it."""
    return json.dumps({'ok': bool(ok), 'device': {
        'platform': devs[0].platform, 'kind': devs[0].device_kind,
        'count': len(devs)}})


def main():
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != 'tpu':
        sys.exit('chip_smoke: no TPU — jax.devices()[0].platform is %r; '
                 'this script has no CPU mode' % dev.platform)

    import importlib.metadata as md
    import jaxlib
    from paddle_tpu import goodput
    from paddle_tpu.executor import _wire_persistent_cache

    peak = goodput.peak_flops_for(dev.device_kind)
    print('device: platform=%s kind=%r count=%d' % (
        dev.platform, dev.device_kind, len(devs)))
    print('versions: jax %s jaxlib %s libtpu %s' % (
        jax.__version__, jaxlib.__version__, md.version('libtpu')))
    print('compile cache dir: %s' % _wire_persistent_cache())
    print('peak_flops_for(%r) = %s' % (dev.device_kind, peak))
    _check(peak is not None,
           'goodput.peak_flops_for knows no peak for %r' % dev.device_kind)

    # jax's own count of compiles answered from the on-disk cache
    pcache = {'requests': 0, 'hits': 0}

    def _on_event(event, **_kw):
        if event == '/jax/compilation_cache/compile_requests_use_cache':
            pcache['requests'] += 1
        elif event == '/jax/compilation_cache/cache_hits':
            pcache['hits'] += 1
    jax.monitoring.register_event_listener(_on_event)

    cfg = SmokeConfig()
    t_start = time.perf_counter()
    phases = {}
    phases['A'], lm, scope = phase_train(cfg)
    phases['B'] = phase_serve(cfg, lm, scope)
    del scope
    if len(devs) >= cfg.dp_devices:
        phases['C'] = phase_dp(cfg, phases['A']['losses'])
    else:
        print('devices=%d: four-chip phase not run' % len(devs))
    print('persistent compile cache: %(hits)d hits of %(requests)d '
          'compile requests' % pcache)

    print('report: ' + json.dumps({
        'phases_run': sorted(phases),
        'steps': sum(p.get('steps', 0) for p in phases.values()),
        'losses': {k: p['losses'] for k, p in phases.items()
                   if 'losses' in p},
        'tokens_generated': phases['B']['tokens_generated'],
        'cold_compile_s': {k: p['cold_compile_s']
                           for k, p in phases.items()},
        'persistent_cache': pcache,
        'wall_s': round(time.perf_counter() - t_start, 1),
        'phases': phases,
    }))
    print(verdict_line(True, devs))


if __name__ == '__main__':
    main()
