"""Training-dynamics appendix runs (VERDICT r4 #3): long multi-window
convergence on a conv net and on CTR (the flagship LM got the same
treatment in a 2000-step run). Loss is reported at every fused
window boundary, on teacher tasks with fresh batches per step inside a
window — the loss can only fall by LEARNING the teacher structure.

Usage:  python tools/convergence.py [resnet|ctr|bert|both]
Writes one JSON line per model: {"model", "steps", "losses": [...]}.
'both' runs all three ('bert' was added round 5: MLM on a Markov
teacher corpus).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def run_resnet(windows=40, k=24, batch=64):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.resnet import build as build_resnet

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        img, label, pred, avg_cost, acc = build_resnet('imagenet',
                                                       depth=50)
        opt = mp.decorate(
            fluid.optimizer.Momentum(learning_rate=0.02, momentum=0.9),
            keep_bf16_activations=True)
        opt.minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    teacher_dev = jax.device_put(rng.randn(192, 1000).astype('float32'))

    # fresh batches generated ON DEVICE each window: the earlier host-side
    # version uploaded 350 MB of images per 24-step
    # window; device generation makes the run
    # compute-bound, so 1000 steps take minutes
    @jax.jit
    def gen_window(key):
        imgs = jax.random.normal(key, (k, batch, 3, 224, 224),
                                 jnp.float32)
        pooled = imgs.reshape(k * batch, 3, 8, 28, 8, 28).mean(axis=(3, 5))
        lbl = jnp.argmax(pooled.reshape(k * batch, -1) @ teacher_dev, 1)
        return imgs, lbl.astype(jnp.int64).reshape(k, batch, 1)

    def make_window(idx):
        imgs, lbl = gen_window(jax.random.PRNGKey(idx + 1))
        return {'img': imgs, 'label': lbl}

    losses = []
    t0 = time.time()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for w in range(windows):
            stacked = make_window(w)
            jax.block_until_ready(stacked)
            out = exe.run_fused(main_p, stacked, fetch_list=[avg_cost],
                                scope=scope, steps=k)
            losses.append(round(float(np.asarray(out[0]).reshape(-1)[0]),
                                4))
            print("resnet window %d (step %d): loss %.4f" %
                  (w, (w + 1) * k, losses[-1]), flush=True)
    print(json.dumps({'model': 'resnet50_teacher1000',
                      'steps': windows * k, 'batch': batch,
                      'losses': losses,
                      'wall_s': round(time.time() - t0, 1)}))


def run_ctr(windows=10, k=200, batch=512, vocab=100000, dim=16):
    import jax
    import paddle_tpu as fluid

    slots = 26
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        ids = fluid.layers.data(name='ids', shape=[slots], dtype='int64')
        label = fluid.layers.data(name='label', shape=[1],
                                  dtype='float32')
        emb = fluid.layers.embedding(
            input=fluid.layers.reshape(ids, [-1, slots, 1]),
            size=[vocab, dim], is_sparse=True)
        flat = fluid.layers.reshape(emb, [-1, slots * dim])
        h = fluid.layers.fc(flat, size=400, act='relu')
        h = fluid.layers.fc(h, size=400, act='relu')
        p = fluid.layers.fc(h, size=1, act='sigmoid')
        loss = fluid.layers.mean(fluid.layers.log_loss(p, label))
        fluid.optimizer.Adagrad(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    id_score = rng.randn(vocab).astype('float32')

    def make_window():
        idsv = rng.randint(0, vocab, (k, batch, slots)).astype('int64')
        lbl = (id_score[idsv].sum(2) > 0).astype('float32')
        return {'ids': jax.device_put(idsv),
                'label': jax.device_put(lbl.reshape(k, batch, 1))}

    losses = []
    t0 = time.time()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for w in range(windows):
            stacked = make_window()
            jax.block_until_ready(stacked)
            out = exe.run_fused(main_p, stacked, fetch_list=[loss],
                                scope=scope, steps=k)
            losses.append(round(float(np.asarray(out[0]).reshape(-1)[0]),
                                4))
            print("ctr window %d (step %d): loss %.4f" %
                  (w, (w + 1) * k, losses[-1]), flush=True)
    print(json.dumps({'model': 'ctr_teacher', 'steps': windows * k,
                      'batch': batch, 'vocab': vocab, 'losses': losses,
                      'wall_s': round(time.time() - t0, 1)}))


def run_bert(windows=30, k=50, batch=64, teacher_vocab=4096, lr=3e-4,
             n_layer=12, d_model=768, n_head=12, d_ff=3072, amp=True):
    """BERT-base MLM on a MARKOV teacher corpus: tok[i+1] = perm[tok[i]]
    with prob 0.9 (random otherwise), so a masked token is predictable
    from either neighbor through a learnable vocab transition — MLM loss
    can fall only by learning the corpus structure (the uniform
    make_pretrain_batch corpus is unlearnable noise, right for
    throughput rows, wrong for convergence evidence). The teacher lives
    on a `teacher_vocab`-id subset of the full 30522 vocab (the full
    model/softmax is unchanged): descent has two stages — support
    (ln 30522 = 10.33 -> ln tv, learned in <50 steps) then transitions
    (-> ~0.1*ln(tv) + H(0.9)). MEASURED (round 5, on chip):
    BERT-base completes the support stage and then plateaus at the
    unigram floor for >=10^4 steps regardless of size/AMP/attention
    path — the long attention-binding plateau of BERT-scale
    pretraining — while the same program at toy scale (vocab 64,
    L2 d32) descends through the floor within 15 steps on both CPU and
    chip. Bench-budget runs therefore evidence the support stage and
    numeric health, not full contextual convergence."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)

    cfg = BertConfig(seq_len=128, max_predictions=20, n_layer=n_layer,
                     d_model=d_model, n_head=n_head, d_ff=d_ff)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        total, mlm, nsp = build_bert_pretrain(cfg)
        # minimize MLM ONLY: the synthetic nsp labels are random noise,
        # and this tool's purpose is convergence evidence — training the
        # nsp head against coin flips would push unlearnable gradient
        # into the shared encoder. (The bench throughput row keeps
        # `total`, matching real pretraining cost.)
        # plain AMP (fp32 activations) — the bench's proven BERT config;
        # keep_bf16_activations NaNs bert's layer_norm/softmax stack
        opt = fluid.optimizer.Adam(learning_rate=lr)
        if amp:
            opt = mp.decorate(opt)
        opt.minimize(mlm)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    V, L, P = cfg.vocab_size, cfg.seq_len, cfg.max_predictions
    tv = min(teacher_vocab, V - 4)
    perm = rng.permutation(np.arange(4, 4 + tv)).astype('int64')

    def gen_tokens(n):
        toks = np.empty((n, L), 'int64')
        toks[:, 0] = rng.randint(4, 4 + tv, n)
        for i in range(L - 1):
            follow = rng.rand(n) < 0.9
            toks[:, i + 1] = np.where(follow, perm[toks[:, i] - 4],
                                      rng.randint(4, 4 + tv, n))
        return toks

    def make_window():
        # per-step batches through the model's own masking/flat-position
        # contract (make_pretrain_batch owns the [MASK] id and the
        # positions-into-[batch*L] convention), stacked for run_fused
        steps = [make_pretrain_batch(cfg, batch, rng,
                                     toks=gen_tokens(batch))
                 for _ in range(k)]
        return {kk: jax.device_put(np.stack([s[kk] for s in steps]))
                for kk in steps[0]}

    losses = []
    t0 = time.time()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for w in range(windows):
            stacked = make_window()
            jax.block_until_ready(stacked)
            out = exe.run_fused(main_p, stacked, fetch_list=[mlm],
                                scope=scope, steps=k)
            losses.append(round(float(np.asarray(out[0]).reshape(-1)[0]),
                                4))
            print("bert window %d (step %d): mlm loss %.4f" %
                  (w, (w + 1) * k, losses[-1]), flush=True)
    print(json.dumps({'model': 'bert_markov_teacher',
                      'config': 'L%d d%d h%d ff%d' % (n_layer, d_model,
                                                      n_head, d_ff),
                      'steps': windows * k, 'batch': batch,
                      'teacher_vocab': tv, 'lr': lr, 'amp': bool(amp),
                      'losses': losses,
                      'wall_s': round(time.time() - t0, 1)}))


if __name__ == '__main__':
    which = sys.argv[1] if len(sys.argv) > 1 else 'both'
    if which in ('resnet', 'both'):
        run_resnet()
    if which in ('ctr', 'both'):
        run_ctr()
    if which in ('bert', 'both'):
        run_bert()
