"""Dump + histogram the TPU-optimized HLO of one framework train step
(resnet50) to find what the compiled program actually spends ops on.
The LM train step, device-less, with XLA's own cycles and tiling a GEMM
fusion: tools/fusioncost.py."""
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.core import lowering
    from paddle_tpu.models.resnet import build as build_resnet

    batch = int(os.environ.get('HLO_BATCH', '64'))
    use_amp = os.environ.get('HLO_AMP', '1') == '1'
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        img, label, pred, avg_cost, acc = build_resnet('imagenet', depth=50)
        opt = fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        if use_amp:
            opt = mp.decorate(opt, keep_bf16_activations=True)
        opt.minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        read, written = lowering.analyze_state(main_p, [avg_cost.name])
        needed = exe._read_before_write(main_p, read, written, {'img',
                                                                'label'},
                                        [avg_cost.name])
        fn, ro, rw = lowering.build_fn(main_p, [avg_cost.name], needed,
                                       written)
        feed = {'img': np.zeros((batch, 3, 224, 224), 'float32'),
                'label': np.zeros((batch, 1), 'int64')}
        ro_v = {n: scope.get(n) for n in ro}
        rw_v = {n: scope.get(n) for n in rw}
        lowered = jax.jit(fn, donate_argnums=(2,)).lower(
            feed, ro_v, rw_v, jax.random.PRNGKey(0))
        txt = lowered.compile().as_text()
    path = os.environ.get('HLO_OUT', '/tmp/rn50_tpu.hlo')
    with open(path, 'w') as f:
        f.write(txt)
    print("bytes:", len(txt), "->", path)

    # histogram op kinds with total output element sizes
    kind_count = collections.Counter()
    kind_bytes = collections.Counter()
    dt_size = {'f32': 4, 'bf16': 2, 's32': 4, 'u32': 4, 'pred': 1,
               'f16': 2, 's64': 8, 'u8': 1, 's8': 1}
    for m in re.finditer(
            r'=\s+(\w+)\[([0-9,]*)\][^ ]*\s+(\w+)\(', txt):
        dt, shape, kind = m.group(1), m.group(2), m.group(3)
        n = 1
        for d in shape.split(','):
            if d:
                n *= int(d)
        kind_count[kind] += 1
        kind_bytes[kind] += n * dt_size.get(dt, 4)
    print("\ntop op kinds by count:")
    for k, c in kind_count.most_common(18):
        print("  %-24s %5d   %8.1f MB" % (k, c, kind_bytes[k] / 1e6))
    # fusion vs standalone convolutions, and their layouts
    convs = re.findall(r'convolution\([^\n]*dim_labels=([^ ,}]*)', txt)
    print("\nconv dim_labels histogram:", collections.Counter(convs))
    # transposes with big outputs
    big_t = [m.group(0)[:120] for m in re.finditer(
        r'= \w+\[[0-9,]{12,}\][^ ]* transpose\([^\n]*', txt)]
    print("\nbig transposes:", len(big_t))
    for t in big_t[:8]:
        print("  ", t)
    copies = len(re.findall(r'\bcopy\(', txt))
    print("copies:", copies)


if __name__ == '__main__':
    main()
