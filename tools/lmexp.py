"""Flagship-LM fused-window experiments: donation off + window-size sweep
(slope timing cancels the per-call constant)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.transformer import build_lm, LMConfig

    cfg = LMConfig(vocab_size=32000, seq_len=512, d_model=512, n_head=8,
                   n_layer=6, d_ff=2048, dropout=0.1, attn_dropout=0.0,
                   use_flash_attention=True)
    batch = 64
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
    s1, s2 = 30, 120
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for st in (s1, s2):
            exe.run_fused(main_p, stacked, fetch_list=[avg_loss],
                          scope=scope, return_numpy=True, steps=st)
        t1s, t2s = [], []
        for _ in range(4):
            for arr, st in ((t1s, s1), (t2s, s2)):
                t0 = time.time()
                out = exe.run_fused(main_p, stacked,
                                    fetch_list=[avg_loss], scope=scope,
                                    return_numpy=False, steps=st)
                float(np.asarray(out[0]).reshape(-1)[0])
                arr.append(time.time() - t0)
    slope = (min(t2s) - min(t1s)) / (s2 - s1)
    toks = batch * cfg.seq_len
    print(json.dumps({
        'step_ms_slope': round(slope * 1000, 2),
        'tokens_per_sec_slope': round(toks / slope, 1),
        'overhead_s': round(min(t1s) - s1 * slope, 2),
        'window30_eff_tok_s': round(toks * s1 / min(t1s), 1),
        'window120_eff_tok_s': round(toks * s2 / min(t2s), 1),
        't30': [round(t, 2) for t in t1s],
        't120': [round(t, 2) for t in t2s]}))


if __name__ == '__main__':
    main()
