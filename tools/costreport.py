"""Compile-time cost/memory report for a program — the CLI twin of
`Executor.explain` (docs/observability.md).

Builds the mnist-mlp reference program (train + inference clones), pulls
XLA's cost analysis (flops, transcendentals, bytes accessed) and buffer
assignment memory stats (argument/output/temp/alias -> peak bytes) for
each, and prints a side-by-side report plus the contrib
`memory_usage(program, batch)` band the numbers back.

Usage:
    python tools/costreport.py [--batch 64] [--hidden 64] [--json]

Importable: ``measure_costreport(batch=...)`` returns the dict (flops /
peak_bytes columns per program).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(batch, hidden):
    import numpy as np
    import paddle_tpu as fluid

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            img = fluid.layers.data(name='img', shape=[784],
                                    dtype='float32')
            label = fluid.layers.data(name='label', shape=[1],
                                      dtype='int64')
            h = fluid.layers.fc(input=img, size=hidden, act='relu')
            h = fluid.layers.fc(input=h, size=hidden, act='relu')
            pred = fluid.layers.fc(input=h, size=10, act='softmax')
            cost = fluid.layers.cross_entropy(input=pred, label=label)
            avg = fluid.layers.mean(cost)
            # the true serving program: forward only, pruned to the
            # prediction (what save_inference_model would persist)
            infer_p = main_p.clone(for_test=True)._prune([pred])
            fluid.optimizer.Adam(learning_rate=0.01).minimize(avg)
    rng = np.random.RandomState(0)
    feed = {'img': rng.randn(batch, 784).astype('float32'),
            'label': rng.randint(0, 10, (batch, 1)).astype('int64')}
    return main_p, startup, infer_p, avg, pred, feed


def measure_costreport(batch=64, hidden=64, memory=True):
    """Explain the mnist-mlp train + inference programs; returns
    {'train': explain dict, 'infer': explain dict, 'memory_usage_mb':
    (low, high)} with flops/peak_bytes per program."""
    import paddle_tpu as fluid

    main_p, startup, infer_p, avg, pred, feed = _build(batch, hidden)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        train = exe.explain(main_p, feed=feed, fetch_list=[avg],
                            scope=scope, memory=memory)
        infer = exe.explain(infer_p, feed={'img': feed['img']},
                            fetch_list=[pred], scope=scope, memory=memory)
        from paddle_tpu.contrib import memory_usage
        lo, hi = memory_usage(main_p, batch_size=batch)
    keep = ('flops', 'transcendentals', 'bytes_accessed', 'argument_bytes',
            'output_bytes', 'temp_bytes', 'alias_bytes', 'peak_bytes',
            'op_count', 'fingerprint')
    return {
        'batch': batch,
        'train': {k: train.get(k) for k in keep},
        'infer': {k: infer.get(k) for k in keep},
        'memory_usage_mb': [round(lo, 3), round(hi, 3)],
    }


def _fmt_bytes(n):
    if n is None:
        return '-'
    for unit in ('B', 'KiB', 'MiB', 'GiB'):
        if abs(n) < 1024 or unit == 'GiB':
            return '%.1f%s' % (n, unit) if unit != 'B' else '%d%s' % (n, unit)
        n /= 1024.0
    return '%d' % n


def _fmt_flops(n):
    if n is None:
        return '-'
    for unit in ('', 'K', 'M', 'G', 'T'):
        if abs(n) < 1000 or unit == 'T':
            return '%.2f%sFLOP' % (n, unit)
        n /= 1000.0
    return '%g' % n


def print_report(rep, out=sys.stdout):
    w = out.write
    w('costreport (mnist-mlp, batch=%d)\n\n' % rep['batch'])
    w('%-22s %18s %18s\n' % ('', 'train', 'infer'))
    rows = [
        ('flops', _fmt_flops),
        ('transcendentals', _fmt_flops),
        ('bytes_accessed', _fmt_bytes),
        ('argument_bytes', _fmt_bytes),
        ('output_bytes', _fmt_bytes),
        ('temp_bytes', _fmt_bytes),
        ('alias_bytes', _fmt_bytes),
        ('peak_bytes', _fmt_bytes),
        ('op_count', lambda v: '%d' % v),
    ]
    for name, fmt in rows:
        w('%-22s %18s %18s\n' % (
            name, fmt(rep['train'].get(name)), fmt(rep['infer'].get(name))))
    lo, hi = rep['memory_usage_mb']
    w('\ncontrib.memory_usage(train, batch=%d): %.3f .. %.3f MB\n'
      % (rep['batch'], lo, hi))


def main(argv=None):
    p = argparse.ArgumentParser(
        description='XLA cost/memory report for the mnist-mlp reference '
                    'program (Executor.explain CLI twin)')
    p.add_argument('--batch', type=int, default=64)
    p.add_argument('--hidden', type=int, default=64)
    p.add_argument('--no-memory', action='store_true',
                   help='skip the buffer-assignment pass (one extra XLA '
                        'compile per program)')
    p.add_argument('--json', action='store_true', help='print one JSON line')
    args = p.parse_args(argv)
    rep = measure_costreport(batch=args.batch, hidden=args.hidden,
                             memory=not args.no_memory)
    if args.json:
        print(json.dumps(rep, sort_keys=True))
    else:
        print_report(rep)


if __name__ == '__main__':
    main()
