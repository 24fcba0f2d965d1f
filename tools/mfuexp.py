"""MFU frontier experiments (VERDICT r4 #4): lm_large and BERT variants,
slope-timed ((t(S2)-t(S1))/(S2-S1)) so the per-call constant cancels, plus a
pure-JAX probe of each model's exact GEMM mix that yields its
shape-limited ceiling (ROADMAP S3 cites the per-GEMM rates it found).

Usage:
  python tools/mfuexp.py gemm          # model-shape matmul rooflines
  python tools/mfuexp.py lm_large [batch]
  python tools/mfuexp.py bert [batch]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

PEAK = 197e12      # v5e dense bf16


def _slope(fn, s1=20, s2=60, reps=3):
    fn(s1)
    fn(s2)                       # compile both
    best = float('inf')
    for _ in range(reps):
        t0 = time.time()
        fn(s1)
        t1 = time.time() - t0
        t0 = time.time()
        fn(s2)
        t2 = time.time() - t0
        best = min(best, (t2 - t1) / (s2 - s1))
    return best


def gemm_probe():
    """Time the exact GEMM shapes of lm_large (L8 d1024 ff4096 b32
    seq512) and bert-base (L12 d768 seq128 b128/b256) in bf16: each
    model's weighted mix = its shape-limited matmul ceiling."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def time_mm(m, k, n, iters=40):
        """Ping-pong chain a->(m,n)->(m,k): a real data dependency that
        stays matrix-shaped (a scalar-feedback chain drains the MXU
        pipeline every step and under-measures by 3-5x). NOTE the
        reported rate is the PAIR average of (m,k)@(k,n) and its
        transposed sibling (m,n)@(n,k) — which is the quantity the
        training-mix ceiling needs, because the backward pass runs
        exactly that sibling as the data-gradient GEMM (dX = dY @ W^T)."""
        b = jnp.full((k, n), 0.01, jnp.bfloat16)
        bt = jnp.full((n, k), 0.01, jnp.bfloat16)

        def chain(s):
            def body(i, a):
                y = (a @ b) * jnp.bfloat16(0.01)
                return (y @ bt) * jnp.bfloat16(0.01)
            return lax.fori_loop(0, s, body,
                                 jnp.full((m, k), 0.5, jnp.bfloat16))

        f = jax.jit(chain, static_argnums=0)
        float(jnp.sum(f(iters))[None][0])      # compile+run sync
        t0 = time.time()
        float(jnp.sum(f(iters))[None][0])
        dt = time.time() - t0
        return 4 * m * k * n * iters / dt

    out = {}
    # lm_large token matmuls: B*L = 16384 rows
    for name, (m, k, n) in {
        'lm_large qkv   16384x1024x3072': (16384, 1024, 3072),
        'lm_large proj  16384x1024x1024': (16384, 1024, 1024),
        'lm_large ffn1  16384x1024x4096': (16384, 1024, 4096),
        'lm_large ffn2  16384x4096x1024': (16384, 4096, 1024),
        'lm_large head  16384x1024x32000': (16384, 1024, 32000),
        'bert256 qkv    32768x768x2304': (32768, 768, 2304),
        'bert256 ffn1   32768x768x3072': (32768, 768, 3072),
        'bert256 ffn2   32768x3072x768': (32768, 3072, 768),
        'bert256 mlm    5120x768x30522': (5120, 768, 30522),
        'bert128 qkv    16384x768x2304': (16384, 768, 2304),
        # weight-gradient shapes: K = B*L, the best-utilized GEMMs in the
        # backward pass (2/3 of training FLOPs run at shapes like these)
        'lm_large dWffn 1024x16384x4096': (1024, 16384, 4096),
        'lm_large dWqkv 1024x16384x3072': (1024, 16384, 3072),
        'bert256 dWffn  768x32768x3072': (768, 32768, 3072),
    }.items():
        tf = time_mm(m, k, n)
        out[name] = round(tf / 1e12, 1)
        print("%s: %.1f TF/s (%.2f of peak)" % (name, tf / 1e12,
                                                tf / PEAK), flush=True)
    print(json.dumps(out))


def _lm_flops(cfg, batch):
    B, L, d, V, dff = batch, cfg.seq_len, cfg.d_model, cfg.vocab_size, \
        cfg.d_ff
    per_layer = (2 * B * L * d * 3 * d + 2 * B * L * L * d * 2
                 + 2 * B * L * d * d + 2 * B * L * d * dff * 2)
    return 3 * (cfg.n_layer * per_layer + 2 * B * L * d * V)


def lm_large(batch=32, remat=False):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.transformer import build_lm, LMConfig

    cfg = LMConfig(vocab_size=32000, seq_len=512, d_model=1024, n_head=16,
                   n_layer=8, d_ff=4096, dropout=0.1, attn_dropout=0.0,
                   use_flash_attention=True)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        tokens, labels, logits, avg_loss = build_lm(cfg)
        opt = mp.decorate(fluid.optimizer.Adam(learning_rate=1e-4))
        opt.minimize(avg_loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    k = 8
    stacked = {
        'tokens': jax.device_put(rng.randint(
            0, cfg.vocab_size, (k, batch, cfg.seq_len)).astype('int64')),
        'labels': jax.device_put(rng.randint(
            0, cfg.vocab_size, (k, batch, cfg.seq_len)).astype('int64'))}
    jax.block_until_ready(stacked)
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)

        def run(steps):
            out = exe.run_fused(main_p, stacked, fetch_list=[avg_loss],
                                scope=scope, return_numpy=False,
                                steps=steps)
            float(np.asarray(out[0]).reshape(-1)[0])

        sec = _slope(run)
    mfu = _lm_flops(cfg, batch) / sec / PEAK
    print(json.dumps({
        'model': 'lm_large', 'batch': batch,
        'bq': os.environ.get('PADDLE_FLASH_BQ', '512'),
        'bk': os.environ.get('PADDLE_FLASH_BK', '512'),
        'step_ms': round(sec * 1000, 2),
        'tokens_per_sec': round(batch * cfg.seq_len / sec, 1),
        'mfu': round(mfu, 4)}))


def bert(batch=128):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)

    cfg = BertConfig(seq_len=128, max_predictions=20)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        total, mlm_loss, nsp_loss = build_bert_pretrain(cfg)
        opt = mp.decorate(fluid.optimizer.Adam(learning_rate=1e-4))
        opt.minimize(total)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    k = 8
    import jax.numpy as jnp
    raw = [make_pretrain_batch(cfg, batch, rng) for _ in range(k)]
    stacked = {n: jax.device_put(np.stack([b[n] for b in raw]))
               for n in raw[0]}
    jax.block_until_ready(stacked)
    B, L, d, V, dff = batch, cfg.seq_len, cfg.d_model, cfg.vocab_size, \
        cfg.d_ff
    per_layer = (2 * B * L * d * 3 * d + 2 * B * L * L * d * 2
                 + 2 * B * L * d * d + 2 * B * L * d * dff * 2)
    fwd = cfg.n_layer * per_layer + 2 * B * cfg.max_predictions * d * V \
        + 2 * B * d * d + 2 * B * L * d * d
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)

        def run(steps):
            out = exe.run_fused(main_p, stacked, fetch_list=[total],
                                scope=scope, return_numpy=False,
                                steps=steps)
            float(np.asarray(out[0]).reshape(-1)[0])

        sec = _slope(run)
    print(json.dumps({
        'model': 'bert', 'batch': batch,
        'step_ms': round(sec * 1000, 2),
        'samples_per_sec': round(batch / sec, 1),
        'mfu': round(3 * fwd / sec / PEAK, 4)}))


if __name__ == '__main__':
    which = sys.argv[1] if len(sys.argv) > 1 else 'gemm'
    arg = int(sys.argv[2]) if len(sys.argv) > 2 else None
    if which == 'gemm':
        gemm_probe()
    elif which == 'lm_large':
        lm_large(arg or 32)
    elif which == 'bert':
        bert(arg or 128)
