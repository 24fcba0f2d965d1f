"""Conv-net MFU experiments (round 4, VERDICT #1).

Each experiment measures a fused multi-step window ending in one real
fetch (per-call dispatch + fetch cost stays out of the per-step number)
and reports best-of-rounds.

Experiments (select with CONVEXP=name,name,... env; default all):
  base64 / base128 / base256   resnet50 through the framework at b64/128/256
  rawjax128                    pure-JAX NHWC-resident resnet50 train step,
                               b128 — the layout roofline the framework
                               should approach
  se32 / se64                  se_resnext50 through the framework
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _measure(fn, stacked, state, rounds=3):
    """fn(stacked, state) -> (loss, new_state); jitted, donates state."""
    import jax
    t0 = time.time()
    loss, state2 = fn(stacked, state)
    float(loss)
    compile_s = time.time() - t0
    best = float('inf')
    for _ in range(rounds):
        t0 = time.time()
        loss, state2 = fn(stacked, state2)
        lv = float(loss)
        best = min(best, time.time() - t0)
    return best, lv, compile_s


def bench_framework_resnet(batch, k=8, steps=24, model='resnet50'):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        if model == 'resnet50':
            from paddle_tpu.models.resnet import build as build_resnet
            img, label, pred, avg_cost, acc = build_resnet('imagenet',
                                                           depth=50)
        else:
            from paddle_tpu.models.se_resnext import build as build_se
            img, label, pred, avg_cost, acc = build_se()
        opt = fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt = mp.decorate(opt, keep_bf16_activations=True)
        opt.minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    batches = [{'img': rng.randn(batch, 3, 224, 224).astype('float32'),
                'label': rng.randint(0, 1000, (batch, 1)).astype('int64')}
               for _ in range(k)]
    stacked = {name: jax.device_put(
        np.stack([b[name] for b in batches])) for name in batches[0]}
    jax.block_until_ready(stacked)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        t0 = time.time()
        exe.run_fused(main_p, stacked, fetch_list=[avg_cost], scope=scope,
                      return_numpy=True, steps=steps)
        compile_s = time.time() - t0
        best = float('inf')
        loss = None
        for _ in range(3):
            t0 = time.time()
            out = exe.run_fused(main_p, stacked, fetch_list=[avg_cost],
                                scope=scope, return_numpy=False,
                                steps=steps)
            loss = float(np.asarray(out[0]).reshape(-1)[0])
            best = min(best, time.time() - t0)
    sec_step = best / steps
    return {'img_per_sec': round(batch / sec_step, 1),
            'step_ms': round(sec_step * 1000, 2),
            'compile_s': round(compile_s, 1), 'loss': round(loss, 4)}


# ---------------------------------------------------------------------------
# pure-JAX NHWC resnet50 (roofline probe)
# ---------------------------------------------------------------------------

def _rn50_params(rng, dtype):
    import jax.numpy as jnp
    P = {}

    def conv(name, cin, cout, k):
        P[name + '/w'] = jnp.asarray(
            rng.randn(k, k, cin, cout).astype('float32') * 0.05)
        P[name + '/g'] = jnp.ones((cout,), jnp.float32)
        P[name + '/b'] = jnp.zeros((cout,), jnp.float32)

    conv('stem', 3, 64, 7)
    cin = 64
    blocks = [(3, 64), (4, 128), (6, 256), (3, 512)]
    for si, (n, w) in enumerate(blocks):
        for bi in range(n):
            pre = 's%d_b%d' % (si, bi)
            conv(pre + '/c1', cin, w, 1)
            conv(pre + '/c2', w, w, 3)
            conv(pre + '/c3', w, w * 4, 1)
            if bi == 0:
                conv(pre + '/sc', cin, w * 4, 1)
            cin = w * 4
    P['fc/w'] = jnp.asarray(rng.randn(2048, 1000).astype('float32') * 0.02)
    P['fc/b'] = jnp.zeros((1000,), jnp.float32)
    return P


def _rn50_fwd(P, x, dtype):
    """NHWC-resident resnet50 forward; BN folded to scale+shift (inference
    -style stats — the FLOP/byte profile of fused train BN without the
    separate stats pass)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def conv(name, x, stride):
        w = P[name + '/w'].astype(dtype)
        # bf16 in/out (MXU accumulates f32 internally); a f32
        # preferred_element_type would make the conv vjp mix dtypes
        y = lax.conv_general_dilated(
            x, w, (stride, stride), 'SAME',
            dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
        g = P[name + '/g'].astype(dtype)
        b = P[name + '/b'].astype(dtype)
        return y * g + b

    x = conv('stem', x, 2)
    x = jax.nn.relu(x)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                          (1, 2, 2, 1), 'SAME')
    blocks = [(3, 64), (4, 128), (6, 256), (3, 512)]
    for si, (n, w) in enumerate(blocks):
        for bi in range(n):
            pre = 's%d_b%d' % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            sc = conv(pre + '/sc', x, stride) if bi == 0 else x
            y = jax.nn.relu(conv(pre + '/c1', x, 1))
            y = jax.nn.relu(conv(pre + '/c2', y, stride))
            y = conv(pre + '/c3', y, 1)
            x = jax.nn.relu(y + sc)
    x = jnp.mean(x, axis=(1, 2))
    return x.astype(jnp.float32) @ P['fc/w'] + P['fc/b']


def bench_rawjax(batch, steps=24, dtype_name='bfloat16'):
    import jax
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if dtype_name == 'bfloat16' else jnp.float32
    rng = np.random.RandomState(0)
    P = _rn50_params(rng, dtype)
    x = jax.device_put(jnp.asarray(
        rng.randn(batch, 224, 224, 3).astype('float32')).astype(dtype))
    labels = jax.device_put(jnp.asarray(
        rng.randint(0, 1000, (batch,)).astype('int32')))

    def loss_fn(P, x):
        logits = _rn50_fwd(P, x, dtype)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))

    @jax.jit
    def train_steps(P, x):
        def body(i, carry):
            P, _ = carry
            l, g = jax.value_and_grad(loss_fn)(P, x)
            P = jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, P, g)
            return P, l
        return jax.lax.fori_loop(0, steps, body,
                                 (P, jnp.zeros((), jnp.float32)))

    t0 = time.time()
    P2, l = train_steps(P, x)
    float(l)
    compile_s = time.time() - t0
    best = float('inf')
    for _ in range(3):
        t0 = time.time()
        P2, l = train_steps(P2, x)
        lv = float(l)
        best = min(best, time.time() - t0)
    sec_step = best / steps
    return {'img_per_sec': round(batch / sec_step, 1),
            'step_ms': round(sec_step * 1000, 2),
            'compile_s': round(compile_s, 1), 'loss': round(lv, 4)}


def bench_ab(batch=64, steps=24):
    """Interleaved A/B: framework resnet50 vs raw-JAX NHWC resnet50 in
    alternating timed windows — contention-immune RATIO measurement."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.resnet import build as build_resnet
    import jax.numpy as jnp

    # --- framework side
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        img, label, pred, avg_cost, acc = build_resnet('imagenet', depth=50)
        opt = fluid.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
        opt = mp.decorate(opt, keep_bf16_activations=True)
        opt.minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    stacked = {'img': jax.device_put(np.stack(
        [rng.randn(batch, 3, 224, 224).astype('float32')
         for _ in range(4)])),
        'label': jax.device_put(np.stack(
            [rng.randint(0, 1000, (batch, 1)).astype('int64')
             for _ in range(4)]))}
    jax.block_until_ready(stacked)

    # --- raw side
    P = _rn50_params(rng, jnp.bfloat16)
    xr = jax.device_put(jnp.asarray(
        rng.randn(batch, 224, 224, 3).astype('float32')).astype(
        jnp.bfloat16))
    labels = jax.device_put(jnp.asarray(
        rng.randint(0, 1000, (batch,)).astype('int32')))

    def loss_fn(P, x):
        logits = _rn50_fwd(P, x, jnp.bfloat16)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, labels[:, None], 1))

    @jax.jit
    def raw_steps(P, x):
        def body(i, carry):
            P, _ = carry
            l, g = jax.value_and_grad(loss_fn)(P, x)
            P = jax.tree_util.tree_map(lambda p, gg: p - 1e-4 * gg, P, g)
            return P, l
        return jax.lax.fori_loop(0, steps, body,
                                 (P, jnp.zeros((), jnp.float32)))

    @jax.jit
    def raw_steps3(P, x):
        def body(i, carry):
            P, _ = carry
            l, g = jax.value_and_grad(loss_fn)(P, x)
            P = jax.tree_util.tree_map(lambda p, gg: p - 1e-4 * gg, P, g)
            return P, l
        return jax.lax.fori_loop(0, 3 * steps, body,
                                 (P, jnp.zeros((), jnp.float32)))

    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        exe.run_fused(main_p, stacked, fetch_list=[avg_cost], scope=scope,
                      return_numpy=True, steps=steps)     # compile fw S
        exe.run_fused(main_p, stacked, fetch_list=[avg_cost], scope=scope,
                      return_numpy=True, steps=3 * steps)  # compile fw 3S
        P2, l = raw_steps(P, xr)
        float(l)                                          # compile raw
        P2, l = raw_steps3(P2, xr)
        float(l)
        # slope timing: (t_3S - t_S) / 2S cancels the constant
        # launch+fetch overhead that otherwise pollutes both sides
        fw1, fw3, raw1, raw3 = [], [], [], []
        for _ in range(4):
            for arr, n_st in ((fw1, steps), (fw3, 3 * steps)):
                t0 = time.time()
                out = exe.run_fused(main_p, stacked,
                                    fetch_list=[avg_cost], scope=scope,
                                    return_numpy=False, steps=n_st)
                float(np.asarray(out[0]).reshape(-1)[0])
                arr.append(time.time() - t0)
            t0 = time.time()
            P2, l = raw_steps(P2, xr)
            float(l)
            raw1.append(time.time() - t0)
            t0 = time.time()
            P2, l = raw_steps3(P2, xr)
            float(l)
            raw3.append(time.time() - t0)
    fw = (min(fw3) - min(fw1)) / (2 * steps)
    raw = (min(raw3) - min(raw1)) / (2 * steps)
    return {'fw_img_per_sec': round(batch / fw, 1),
            'fw_step_ms': round(fw * 1000, 2),
            'raw_img_per_sec': round(batch / raw, 1),
            'raw_step_ms': round(raw * 1000, 2),
            'ratio_fw_over_raw': round(fw / raw, 3),
            'overhead_fw_s': round(min(fw1) - steps * fw, 2),
            'overhead_raw_s': round(min(raw1) - steps * raw, 2),
            'fw_times': [round(t, 2) for t in fw1 + fw3],
            'raw_times': [round(t, 2) for t in raw1 + raw3]}


EXPS = {
    'ab64': lambda: bench_ab(64),
    'ab128': lambda: bench_ab(128, steps=12),
    'base64': lambda: bench_framework_resnet(64),
    'base128': lambda: bench_framework_resnet(128),
    'base256': lambda: bench_framework_resnet(256, k=4, steps=12),
    'rawjax128': lambda: bench_rawjax(128),
    'rawjax256': lambda: bench_rawjax(256, steps=12),
    'se32': lambda: bench_framework_resnet(32, model='se'),
    'se64': lambda: bench_framework_resnet(64, model='se'),
}


def main():
    names = [n for n in os.environ.get(
        'CONVEXP', 'base64,base128,rawjax128').split(',') if n]
    for n in names:
        t0 = time.time()
        try:
            r = EXPS[n]()
        except Exception as e:
            r = {'error': '%s: %s' % (type(e).__name__, str(e)[:300])}
        r['wall_s'] = round(time.time() - t0, 1)
        print(json.dumps({n: r}), flush=True)


if __name__ == '__main__':
    main()
