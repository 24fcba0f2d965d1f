"""Driver of `kind: train` traffic: the trainer's main path — one
Executor.run per step, fed numpy from a host-side generator, the loss
fetched every step. The recipe is chip_smoke.py's (bench.py /
examples/train_lm.py): build_lm + AMP-decorated Adam through
fluid.Executor with donation."""
import math
import time

import numpy as np

from benchmark import traffic_gen
from benchmark.drivers.common import (PROGRAM_SEED, compile_misses,
                                      scalar as _scalar)


def run(ctx):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.contrib import mixed_precision as mp

    m, tr, model = ctx.config, ctx.traffic, ctx.model
    seqs, seq_len = int(tr['sequences_per_step']), int(tr['seq_len'])
    if tr['optimizer'] != 'adam' or tr['amp'] not in ('bf16', 'none'):
        raise ValueError('train driver knows optimizer adam and amp '
                         'bf16|none, got %r / %r'
                         % (tr['optimizer'], tr['amp']))
    lm, build = model.build_train(m, seq_len)
    main_p, startup = fluid.Program(), fluid.Program()
    main_p.random_seed = startup.random_seed = PROGRAM_SEED
    with fluid.unique_name.guard():
        with fluid.program_guard(main_p, startup):
            _t, _l, _logits, avg_loss = build(lm)
            opt = fluid.optimizer.Adam(learning_rate=float(tr['lr']),
                                       fuse=bool(tr['fuse']))
            if tr['amp'] == 'bf16':
                opt = mp.decorate(opt)
            opt.minimize(avg_loss)
    # the same model, inference mode, float32, in a program of its own on
    # the trainer's scope: what the reference is compared with
    eval_p = fluid.Program()
    eval_p.random_seed = PROGRAM_SEED
    with fluid.unique_name.guard():
        with fluid.program_guard(eval_p, fluid.Program()):
            _t, _l, _logits, eval_loss = build(
                model.lm_config(m, seq_len, False), is_test=True)

    ctx.note('programs built: %.1f s' % ctx.since_start())
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    exe.run(startup, scope=scope)       # optimizer state, lr, loss scale
    ctx.note('startup program run: %.1f s' % ctx.since_start())
    for name, value in model.init_params(m, ctx.seed).items():
        scope.set(name, value)
    ctx.note('startup + weights from the seed: %.1f s' % ctx.since_start())

    # ---- correct, part 1: the system's forward against the reference, on
    # one seeded sequence, while no train step holds the device's memory
    ref = model.reference()
    check = next(traffic_gen.train_batches(ctx.seed + 1, 1, seq_len,
                                           m['vocab_size']))
    sys_loss = _scalar(exe.run(eval_p, feed=check, fetch_list=[eval_loss],
                               scope=scope)[0])
    ctx.note('system forward (eval program) done: %.1f s' % ctx.since_start())
    ref_loss = ref.loss(scope, m, check['tokens'][0], check['labels'][0])
    rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    ctx.note('forward vs reference: system loss %.6f, reference %.6f, '
             'relative difference %.2e (tolerance %.0e)'
             % (sys_loss, ref_loss, rel, ref.LOSS_RTOL))
    correct = rel <= ref.LOSS_RTOL

    # ---- warm-up: the step compiles (or loads) on its first call
    batches = traffic_gen.train_batches(ctx.seed, seqs, seq_len,
                                        m['vocab_size'])
    losses = []
    for _ in range(int(tr['warmup_steps'])):
        losses.append(_scalar(exe.run(main_p, feed=next(batches),
                                      fetch_list=[avg_loss],
                                      scope=scope)[0]))
    ctx.note('%d warm-up steps done: %.1f s'
             % (len(losses), ctx.since_start()))
    first_gap = abs(losses[0] - sys_loss)
    ctx.note('first train step loss %.4f (|difference| to the forward '
             'loss %.4f, allowed %.2f; ln V = %.4f)'
             % (losses[0], first_gap, ref.FIRST_STEP_ATOL,
                math.log(m['vocab_size'])))
    correct = correct and first_gap <= ref.FIRST_STEP_ATOL

    def step():
        with ctx.span('feed'):
            feed = next(batches)
        with ctx.span('run'):
            out, = exe.run(main_p, feed=feed, fetch_list=[avg_loss],
                           scope=scope)
        losses.append(_scalar(out))

    # ---- traced window, in a traced run only, before the measured one
    traced_steps = 0
    if ctx.trace:
        ctx.start_trace()
        t_end = time.perf_counter() + float(tr['trace_seconds'])
        while time.perf_counter() < t_end:
            step()
            traced_steps += 1
        ctx.stop_trace()

    # ---- the measured window
    before = monitor.counters()
    n0 = len(losses)
    t0 = ctx.open_window()
    while time.perf_counter() - t0 < ctx.seconds:
        step()
    jax.block_until_ready(scope.get('lm_head.w'))
    t1 = time.perf_counter()
    delta = monitor.counter_delta(before)

    steps = len(losses) - n0
    window_losses = losses[n0:]
    finite = bool(np.all(np.isfinite(window_losses)))
    misses = compile_misses(delta)
    ctx.note('window: %d steps in %.3f s, loss %.4f -> %.4f, all finite: '
             '%s, compile_cache_miss in the window: %d'
             % (steps, t1 - t0, window_losses[0], window_losses[-1], finite,
                misses))
    correct = correct and finite and misses == 0
    tokens = steps * seqs * seq_len
    return {
        'correct': bool(correct), 'attempted': steps,
        'failed': int(sum(1 for v in window_losses if not np.isfinite(v))),
        'end_to_end': {'train_tokens_per_s': tokens / (t1 - t0)},
        'facts': {
            'kind': 'train', 'steps': steps, 'tokens': tokens,
            'window_s': t1 - t0, 'traced_steps': traced_steps,
            'tokens_per_step': seqs * seq_len, 'seq_len': seq_len,
            'flops_per_token': model.train_flops_per_token(m, seq_len),
            'counters': delta},
    }
