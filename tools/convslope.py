"""Slope-timed (per-call-constant-free) step rates for the conv bench rows.

NOTE: the build recipe (model + AMP-decorated Momentum + staged feeds)
mirrored `_bench_image_model` of the second benchmark (`bench.py`,
deleted at PR 30): the slope numbers describe that configuration."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def slope(model, batch, s1=60, s2=240):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        if model == 'resnet50':
            from paddle_tpu.models.resnet import build as b
            img, label, pred, cost, acc = b('imagenet', depth=50)
        elif model == 'se':
            from paddle_tpu.models.se_resnext import build as b
            img, label, pred, cost, acc = b()
        else:
            from paddle_tpu.models.vgg import build as b
            img, label, pred, cost, acc = b(class_dim=10,
                                            image_shape=(3, 32, 32))
        opt = mp.decorate(
            fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9),
            keep_bf16_activations=True)
        opt.minimize(cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    shape = (3, 32, 32) if model == 'vgg' else (3, 224, 224)
    ncls = 10 if model == 'vgg' else 1000
    stacked = {'img': jax.device_put(np.stack(
        [rng.randn(batch, *shape).astype('float32') for _ in range(4)])),
        'label': jax.device_put(np.stack(
            [rng.randint(0, ncls, (batch, 1)).astype('int64')
             for _ in range(4)]))}
    jax.block_until_ready(stacked)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for st in (s1, s2):
            exe.run_fused(main_p, stacked, fetch_list=[cost], scope=scope,
                          return_numpy=True, steps=st)
        t1s, t2s = [], []
        for _ in range(3):
            for arr, st in ((t1s, s1), (t2s, s2)):
                t0 = time.time()
                out = exe.run_fused(main_p, stacked, fetch_list=[cost],
                                    scope=scope, return_numpy=False,
                                    steps=st)
                float(np.asarray(out[0]).reshape(-1)[0])
                arr.append(time.time() - t0)
    sec = (min(t2s) - min(t1s)) / (s2 - s1)
    return {'img_per_sec_slope': round(batch / sec, 1),
            'step_ms_slope': round(sec * 1000, 2),
            'overhead_s': round(min(t1s) - s1 * sec, 2),
            't1': [round(t, 2) for t in t1s],
            't2': [round(t, 2) for t in t2s]}


def main():
    for name, model, batch in (('resnet50_b128', 'resnet50', 128),
                               ('se_resnext_b64', 'se', 64),
                               ('vgg16_b128', 'vgg', 128)):
        t0 = time.time()
        try:
            r = slope(model, batch)
        except Exception as e:
            r = {'error': '%s: %s' % (type(e).__name__, str(e)[:200])}
        r['wall_s'] = round(time.time() - t0, 1)
        print(json.dumps({name: r}), flush=True)


if __name__ == '__main__':
    main()
