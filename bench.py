"""Benchmark suite: training throughput on one chip, multiple models.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.
The headline metric stays the flagship Transformer-LM (so vs_baseline is
comparable across rounds); additional model rows (larger LM, ResNet-50,
CTR sparse-embedding) ride in the "models" extra — the bench-suite shape
of the reference (benchmark/fluid/fluid_benchmark.py: mnist/resnet/...
with examples/sec = num_samples / elapsed, :297-301).

Measurement contract (round-3 redesign):
- steady state is measured with Executor.run_fused — K steps scanned
  on-device per call over pre-staged DISTINCT batches — so per-launch
  host latency and the device->host fetch are paid once per window, not
  per step (a per-step-fetch loop under-measures the machine: BENCH_r02
  95.5k tok/s vs 275k+ measured fused on the same model).
- compile/warmup time is reported separately (compile_s), never mixed into
  throughput; the one trailing sync per measurement is included in the
  timed window and its standalone cost reported as sync_ms.
- the measurement runs in ONE child process with a timeout (the parent
  never touches JAX, so the child owns the chip). No TPU means a non-zero
  exit and no number; a row that raised carries {'error': ...} and makes
  the exit code non-zero after the JSON line is printed.
- every row must end its FIRST pass at a NON-DEGENERATE loss (VERDICT r4
  weak #3): labels come from a fixed random TEACHER function of the
  inputs (learnable structure, not memorizable noise), sequence/CTR rows
  stage one DISTINCT batch per step, image rows train at lr 0.02 (0.005
  for resnet50, which fits the teacher fastest), and
  final_loss is taken from the first (compile) pass — the timing rounds
  that follow re-train over the same staged stream, so any loss taken
  after them measures memorization of the stage. Long-run convergence
  is tools/convergence.py's job (fresh data every window).
"""
import glob
import json
import os
import subprocess
import sys
import time

TPU_TIMEOUT_S = 2400          # compile times under chip contention vary 5x
TPU_MODEL_BUDGET_S = 1700     # leave headroom for JSON emission

def _peak_for(kind):
    # one source of truth for the per-chip peak table: the goodput layer
    # (paddle_tpu/goodput.py PEAK_FLOPS) — the live step_mfu gauge and
    # this offline column must divide by the SAME denominator
    from paddle_tpu.goodput import peak_flops_for
    return peak_flops_for(kind)


def _lm_train_flops_per_step(cfg, batch):
    """Model FLOPs of one train step (fwd matmuls+attention, x3 for bwd)."""
    B, L, d, V, dff = batch, cfg.seq_len, cfg.d_model, cfg.vocab_size, cfg.d_ff
    per_layer = (2 * B * L * d * 3 * d       # qkv proj
                 + 2 * B * L * L * d         # scores
                 + 2 * B * L * L * d         # context
                 + 2 * B * L * d * d         # out proj
                 + 2 * B * L * d * dff * 2)  # ffn1 + ffn2
    fwd = cfg.n_layer * per_layer + 2 * B * L * d * V  # + lm head
    return 3 * fwd


def _measure_steps(exe, program, scope, batches, loss_var, k_per_call,
                   rounds, steps=None):
    """Steady-state timing: `rounds` fused calls of k_per_call steps each
    over distinct batches pre-staged ON DEVICE (what a prefetching input
    pipeline provides — upload is not part of step time, exactly like the
    reference's reader threads double-buffering to the GPU,
    operators/reader/buffered_reader.h:30); returns (sec_per_step,
    last_loss, compile_s)."""
    import numpy as np
    import jax
    if any(isinstance(v, tuple) for b in batches for v in b.values()):
        # LoD feeds can't pre-stack on device; run_fused stages them
        # (identical-LoD contract) — feeds are small for ragged models
        stacked = batches
    else:
        stacked = {name: jax.device_put(
            np.stack([np.asarray(b[name]) for b in batches]))
            for name in batches[0]}
        jax.block_until_ready(stacked)
    steps = steps or k_per_call
    t0 = time.time()
    out = exe.run_fused(program, stacked, fetch_list=[loss_var],
                        scope=scope, return_numpy=True,
                        steps=steps)                     # compile + sync
    compile_s = time.time() - t0
    # the reported loss comes from THIS first pass over the staged stream
    # — the timing rounds below re-train over the same staged batches, so
    # their loss measures memorization of the stage, not learning
    loss = float(np.asarray(out[0]).reshape(-1)[0])
    # each round is timed separately (call + its own sync); the BEST round
    # is reported — the chip may be time-shared with other tenants, and the
    # fastest window estimates the uncontended machine. The goodput layer
    # accounts the SAME rounds live: per-round (device-busy, flops)
    # deltas give the live MFU of the best window — the cross-check
    # column against this file's offline formula.
    from paddle_tpu import goodput as _goodput
    from paddle_tpu import analysis as _analysis
    # warm the one-time XLA cost analysis BEFORE the measured window so
    # the first round's stats() read doesn't pay it inside the wall
    _analysis.lookup(program, kind='fused')
    _goodput.reset()
    best = float('inf')
    best_rate = 0.0
    prev = _goodput.stats()
    for r in range(rounds):
        t0 = time.time()
        last = exe.run_fused(program, stacked, fetch_list=[loss_var],
                             scope=scope, return_numpy=False, steps=steps)
        float(np.asarray(last[0]).reshape(-1)[0])        # sync
        best = min(best, time.time() - t0)
        cur = _goodput.stats()
        d_busy = cur['productive_s'] - prev['productive_s']
        d_flops = cur['flops'] - prev['flops']
        prev = cur
        if d_busy > 0:
            best_rate = max(best_rate, d_flops / d_busy)
    final = _goodput.stats()
    peak, _bw = _goodput.device_peaks()
    gp_cols = {
        'goodput_frac': round(final['goodput_frac'], 4),
        'live_flops_per_s': round(best_rate, 1),
        'live_mfu': round(best_rate / peak, 4) if peak else None,
    }
    return best / steps, loss, compile_s, gp_cols


def _program_cost_row(program, memory=False):
    """XLA analytics columns for one bench row: per-STEP flops / bytes
    accessed from the registered executable, plus buffer-assignment peak
    bytes when `memory` (costs one extra XLA compile — CPU rows only;
    TPU compiles are minutes). XLA's HloCostAnalysis counts a while-loop
    BODY once regardless of trip count (measured: identical flops for a
    4-step and an 8-step fused scan of the same program), so the
    registered flops are ALREADY per step — rows before r08 divided by
    the scan length again and under-reported these columns by k x."""
    try:
        from paddle_tpu import analysis
        rec = analysis.lookup(program, memory=memory)
        if rec is None:
            return {}
        out = {}
        if rec.flops is not None:
            out['flops'] = rec.flops
            out['bytes_accessed'] = rec.bytes_accessed
        if rec.peak_bytes is not None:
            out['peak_bytes'] = rec.peak_bytes
        return out
    except Exception as e:  # noqa: BLE001 — advisory columns only
        return {'analytics_error': '%s: %s' % (type(e).__name__,
                                               str(e)[:120])}


def _bench_lm(cfg_kwargs, batch, k_per_call, rounds, amp,
              steps_per_call=None):
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.transformer import build_lm, LMConfig

    cfg = LMConfig(**cfg_kwargs)
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        tokens, labels, logits, avg_loss = build_lm(cfg)
        # fuse=True: one fused_adam unit over the whole parameter set
        # (kernel tier applies per PADDLE_FUSED_TIER; 'off' is bitwise
        # per-param adam, so the row is comparable across tiers)
        opt = fluid.optimizer.Adam(learning_rate=1e-4, fuse=True)
        if amp:
            opt = mp.decorate(opt)
        opt.minimize(avg_loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    batches = [{'tokens': rng.randint(0, cfg.vocab_size,
                                      (batch, cfg.seq_len)).astype('int64'),
                'labels': rng.randint(0, cfg.vocab_size,
                                      (batch, cfg.seq_len)).astype('int64')}
               for _ in range(k_per_call)]
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        sec_step, loss, compile_s, gp_cols = _measure_steps(
            exe, main_p, scope, batches, avg_loss, k_per_call, rounds,
            steps=steps_per_call or max(120, k_per_call))
    row = {
        'tokens_per_sec': round(batch * cfg.seq_len / sec_step, 1),
        'step_ms': round(sec_step * 1000, 2),
        'compile_s': round(compile_s, 1),
        'final_loss': round(loss, 4),
        'flops_per_step': _lm_train_flops_per_step(cfg, batch),
        'config': 'L%d d%d ff%d V%d seq%d b%d' % (
            cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.vocab_size,
            cfg.seq_len, batch),
    }
    row.update(_program_cost_row(main_p))
    row.update(gp_cols)
    return row


def _bench_image_model(build_fn, label_str, batch, k_per_call, rounds,
                       amp, img_shape=(3, 224, 224), n_class=1000,
                       dataset='imagenet', lr=0.02):
    """Shared image-model measurement (resnet50 / se_resnext / vgg rows):
    Momentum + keep-bf16-activations AMP (+13% images/sec measured on
    v5e), 24+-step fused windows."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        img, label, pred, avg_cost, acc = build_fn()
        # low lr (not the reference harness's 0.1): with 4 staged batches
        # a 240-step window at 0.1 memorizes to ~0 loss, which proves
        # nothing about training dynamics; resnet50 fits the teacher fast
        # enough to need 0.005
        opt = fluid.optimizer.Momentum(learning_rate=lr, momentum=0.9)
        if amp:
            opt = mp.decorate(opt, keep_bf16_activations=True)
        opt.minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    # teacher labels: class = argmax of a fixed random projection of the
    # 8x8-downsampled image — learnable structure rather than pure noise
    c, h, w = img_shape
    pool = (h % 8 == 0 and w % 8 == 0)   # exact 8x8 pooling when possible

    def _features(imgs):
        if pool:
            imgs = imgs.reshape(imgs.shape[0], c, 8, h // 8, 8, w // 8) \
                .mean(axis=(3, 5))
        return imgs.reshape(imgs.shape[0], -1)

    feat_dim = _features(np.zeros((1,) + tuple(img_shape),
                                  'float32')).shape[1]
    teacher = rng.randn(feat_dim, n_class).astype('float32')

    def _teacher_label(imgs):
        return np.argmax(_features(imgs) @ teacher, 1) \
            .astype('int64').reshape(-1, 1)

    batches = []
    for _ in range(k_per_call):
        imgs = rng.randn(batch, *img_shape).astype('float32')
        batches.append({'img': imgs, 'label': _teacher_label(imgs)})
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        sec_step, loss, compile_s, gp_cols = _measure_steps(
            exe, main_p, scope, batches, avg_cost, k_per_call, rounds,
            steps=max(240, k_per_call))
    row = {
        'images_per_sec': round(batch / sec_step, 1),
        'step_ms': round(sec_step * 1000, 2),
        'compile_s': round(compile_s, 1),
        'final_loss': round(loss, 4),
        'config': '%s %s b%d' % (label_str, dataset, batch),
    }
    row.update(_program_cost_row(main_p))
    row.update(gp_cols)
    return row


def _bench_resnet50(batch, k_per_call, rounds, amp):
    from paddle_tpu.models.resnet import build as build_resnet
    return _bench_image_model(
        lambda: build_resnet('imagenet', depth=50), 'resnet50', batch,
        k_per_call, rounds, amp, lr=0.005)


def _bench_bert(batch, k_per_call, rounds, amp):
    """BERT-base pretraining samples/sec (BASELINE.json north-star row)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)

    cfg = BertConfig(seq_len=128, max_predictions=20)   # BERT-base
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        total, mlm_loss, nsp_loss = build_bert_pretrain(cfg)
        opt = fluid.optimizer.Adam(learning_rate=1e-4, fuse=True)
        if amp:
            opt = mp.decorate(opt)
        opt.minimize(total)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    batches = [make_pretrain_batch(cfg, batch, rng)
               for _ in range(k_per_call)]
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        sec_step, loss, compile_s, gp_cols = _measure_steps(
            exe, main_p, scope, batches, total, k_per_call, rounds,
            steps=max(120, k_per_call))
    # model FLOPs: encoder matmuls+attention (x3 for bwd) + MLM head over
    # the P masked positions + NSP head
    B, L, d, V, dff = batch, cfg.seq_len, cfg.d_model, cfg.vocab_size, \
        cfg.d_ff
    per_layer = (2 * B * L * d * 3 * d + 2 * B * L * L * d * 2
                 + 2 * B * L * d * d + 2 * B * L * d * dff * 2)
    fwd = cfg.n_layer * per_layer \
        + 2 * B * cfg.max_predictions * d * V \
        + 2 * B * d * d + 2 * B * L * d * d   # mlm transform + pooler-ish
    row = {
        'samples_per_sec': round(batch / sec_step, 1),
        'step_ms': round(sec_step * 1000, 2),
        'compile_s': round(compile_s, 1),
        'final_loss': round(loss, 4),
        'flops_per_step': 3 * fwd,
        'config': 'bert-base L%d d%d seq%d b%d' % (
            cfg.n_layer, cfg.d_model, cfg.seq_len, batch),
    }
    row.update(gp_cols)
    return row


def _bench_stacked_lstm(batch, seq_len, k_per_call, rounds):
    """Stacked dynamic-LSTM sentiment model over ragged (LoD) input — the
    reference benchmark/fluid/models/stacked_dynamic_lstm.py row; exercises
    the static-LoD ragged pipeline + lax.scan recurrences.

    A realistic stream is MIXED-length, and run_fused binds one LoD per
    compiled window (VERDICT r4 weak #5), so this row measures a
    bucketed stream the way reader/bucketing.py serves one:
    BUCKET-MAJOR — three bucket shapes (seq/2, 3seq/4, seq) measured as
    separate fused windows, each its own compile, with the reported rate
    = total samples / total time blended across buckets. (Interleaved
    mixed-LoD lists are also supported by run_fused itself via
    consecutive-segment splitting, with trajectory parity — see
    tests/test_run_fused.py — but bucket-major is how a throughput
    pipeline would actually serve the stream.)"""
    import numpy as np
    import paddle_tpu as fluid

    vocab, emb_dim, hid = 5000, 128, 128
    layers_n = 3
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        words = fluid.layers.data(name='words', shape=[1], dtype='int64',
                                  lod_level=1)
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        emb = fluid.layers.embedding(words, size=[vocab, emb_dim])
        h = emb
        for _ in range(layers_n):
            proj = fluid.layers.fc(h, size=hid * 4)
            h, _ = fluid.layers.dynamic_lstm(input=proj, size=hid * 4)
        last = fluid.layers.sequence_last_step(h)
        pred = fluid.layers.fc(last, size=2, act='softmax')
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    tok_score = rng.randn(vocab).astype('float32')
    n_steps = max(30, k_per_call)
    buckets = sorted({seq_len // 2, 3 * seq_len // 4, seq_len})

    def make_batches(sl):
        lod = [list(range(0, (batch + 1) * sl, sl))]
        out = []
        for _ in range(n_steps):
            w = rng.randint(0, vocab, (batch * sl, 1)).astype('int64')
            sent = (tok_score[w.reshape(batch, sl)].mean(1) > 0)
            out.append({'words': (w, lod),
                        'label': sent.astype('int64').reshape(-1, 1)})
        return out

    per_bucket = {}
    total_time = total_samples = total_tokens = 0.0
    compile_total = 0.0
    lossv = None
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        for sl in buckets:
            sec_step, lossv, compile_s, gp_cols = _measure_steps(
                exe, main_p, scope, make_batches(sl), loss, n_steps,
                rounds, steps=n_steps)
            per_bucket['seq%d' % sl] = {
                'samples_per_sec': round(batch / sec_step, 1),
                'step_ms': round(sec_step * 1000, 2),
                'compile_s': round(compile_s, 1)}
            total_time += sec_step * n_steps
            total_samples += batch * n_steps
            total_tokens += batch * sl * n_steps
            compile_total += compile_s
    return {
        'samples_per_sec': round(total_samples / total_time, 1),
        'tokens_per_sec': round(total_tokens / total_time, 1),
        'step_ms': round(total_time / (len(buckets) * n_steps) * 1000, 2),
        'compile_s': round(compile_total, 1),
        'final_loss': round(lossv, 4),
        'buckets': per_bucket,
        'config': 'stacked_lstm L%d h%d mixed-seq%s b%d' % (
            layers_n, hid, buckets, batch),
        # goodput columns from the LAST bucket's measured window (each
        # bucket resets the live accounting window)
        **gp_cols,
    }


def _bench_se_resnext(batch, k_per_call, rounds, amp):
    """SE-ResNeXt-50 (reference benchmark/fluid/models/se_resnext.py)."""
    from paddle_tpu.models.se_resnext import build as build_se
    return _bench_image_model(build_se, 'se_resnext50', batch,
                              k_per_call, rounds, amp)


def _bench_vgg(batch, k_per_call, rounds, amp):
    """VGG16-BN cifar10 (reference benchmark/fluid/models/vgg.py:28
    vgg16_bn_drop; fluid_benchmark default data_set cifar10)."""
    from paddle_tpu.models.vgg import build as build_vgg
    return _bench_image_model(
        lambda: build_vgg(class_dim=10, image_shape=(3, 32, 32)),
        'vgg16', batch, k_per_call, rounds, amp,
        img_shape=(3, 32, 32), n_class=10, dataset='cifar10')


def _bench_nmt(batch, seq_len, k_per_call, rounds):
    """Attention seq2seq NMT train + beam-search generation timing
    (reference benchmark/fluid/models/machine_translation.py:186:
    emb/enc/dec 512, dict 30000; its harness trains only, is_generating=
    False — the generation timing is our addition). Train feeds are
    ragged LoD batches with one shared bucket shape per fused window."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models.seq2seq import (Seq2SeqConfig, build_nmt_train,
                                           build_nmt_generate)

    cfg = Seq2SeqConfig()       # reference scale: 512/512/512, V=30000
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        feeds, avg_cost, _pred = build_nmt_train(cfg)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(avg_cost)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    lod = [list(range(0, (batch + 1) * seq_len, seq_len))]
    total = batch * seq_len
    batches = [{
        'source_sequence': (rng.randint(
            1, cfg.dict_size, (total, 1)).astype('int64'), lod),
        'target_sequence': (rng.randint(
            1, cfg.dict_size, (total, 1)).astype('int64'), lod),
        'label_sequence': (rng.randint(
            1, cfg.dict_size, (total, 1)).astype('int64'), lod),
    } for _ in range(k_per_call)]
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        sec_step, loss, compile_s, gp_cols = _measure_steps(
            exe, main_p, scope, batches, avg_cost, k_per_call, rounds)
    out = {
        'samples_per_sec': round(batch / sec_step, 1),
        'tokens_per_sec': round(total / sec_step, 1),
        'step_ms': round(sec_step * 1000, 2),
        'compile_s': round(compile_s, 1),
        'final_loss': round(loss, 4),
        **gp_cols,
        'config': 'nmt emb%d enc%d dec%d V%d seq%d b%d' % (
            cfg.embedding_dim, cfg.encoder_size, cfg.decoder_size,
            cfg.dict_size, seq_len, batch),
    }
    # beam-search generation, measured at a CACHED COMPILED STEP: bind()
    # compiles the While decode once and the timing loop re-dispatches
    # that executable directly — no per-sentence program re-trace, no
    # per-call feed re-preparation or cache-key hashing (the timing
    # includes one dispatch + fetch round-trip; reported per sentence)
    try:
        from paddle_tpu.contrib.decoder import BeamSearchDecoder
        gmain, gstart = fluid.Program(), fluid.Program()
        gcfg = Seq2SeqConfig(beam_size=3)
        with fluid.program_guard(gmain, gstart):
            gfeeds, (ids_v, sc_v) = build_nmt_generate(gcfg, max_len=50)
        gb = 8
        src = (rng.randint(1, cfg.dict_size,
                           (gb * seq_len, 1)).astype('int64'),
               [list(range(0, (gb + 1) * seq_len, seq_len))])
        init_ids, init_scores = BeamSearchDecoder.make_initial_beams(
            gb, gcfg.beam_size, 0)
        gscope = fluid.Scope()
        with fluid.scope_guard(gscope):
            exe.run(gstart, scope=gscope)
            feed = {'source_sequence': src, 'init_ids': init_ids,
                    'init_scores': init_scores}
            bound = exe.bind(gmain, feed, fetch_list=[ids_v, sc_v],
                             scope=gscope)             # compiles once
            best = float('inf')
            for _ in range(max(1, rounds)):
                t0 = time.time()
                bound(bound.example_feed)
                best = min(best, time.time() - t0)
        out['beam_decode_ms_per_sentence'] = round(best * 1000 / gb, 2)
        out['beam_config'] = 'beam%d maxlen50 b%d cached-step' % (
            gcfg.beam_size, gb)
    except Exception as e:
        out['beam_error'] = '%s: %s' % (type(e).__name__, str(e)[:150])
    return out


def _bench_ctr(batch, k_per_call, rounds, vocab=100000, dim=16,
               is_distributed=False):
    """Wide&deep-style CTR: multi-slot embedding lookups + MLP, the sparse
    workload BASELINE.json's north-star configs name (DeepFM/CTR).
    is_distributed=True sizes the table for the vocab-sharded path
    (reference lookup_table is_distributed / parameter_prefetch) — on the
    single bench chip the shard is the whole table; the 8-way sharded
    placement itself is validated by dryrun_multichip's V=1M mesh case."""
    import numpy as np
    import paddle_tpu as fluid

    slots = 26
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        ids = fluid.layers.data(name='ids', shape=[slots], dtype='int64')
        label = fluid.layers.data(name='label', shape=[1], dtype='float32')
        emb = fluid.layers.embedding(
            input=fluid.layers.reshape(ids, [-1, slots, 1]),
            size=[vocab, dim], is_sparse=True,
            is_distributed=is_distributed)
        flat = fluid.layers.reshape(emb, [-1, slots * dim])
        h = fluid.layers.fc(flat, size=400, act='relu')
        h = fluid.layers.fc(h, size=400, act='relu')
        p = fluid.layers.fc(h, size=1, act='sigmoid')
        loss = fluid.layers.mean(fluid.layers.log_loss(p, label))
        fluid.optimizer.Adagrad(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    # one DISTINCT batch per step (ids are tiny; nothing repeats, so the
    # window measures online learning, not memorization) with teacher
    # labels: click iff the ids' fixed random scores sum positive —
    # exactly the per-id structure the embedding model can learn
    n_steps = max(150, k_per_call)
    id_score = rng.randn(vocab).astype('float32')
    batches = []
    for _ in range(n_steps):
        ids = rng.randint(0, vocab, (batch, slots)).astype('int64')
        lbl = (id_score[ids].sum(1) > 0).astype('float32').reshape(-1, 1)
        batches.append({'ids': ids, 'label': lbl})
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        sec_step, loss, compile_s, gp_cols = _measure_steps(
            exe, main_p, scope, batches, loss, n_steps, rounds,
            steps=n_steps)
    row = {
        'samples_per_sec': round(batch / sec_step, 1),
        'step_ms': round(sec_step * 1000, 2),
        'compile_s': round(compile_s, 1),
        'final_loss': round(loss, 4),
        'config': 'ctr v%d s%d d%d b%d' % (vocab, slots, dim, batch),
    }
    row.update(gp_cols)
    return row


def _machine_window(pred, feed, over_fn):
    """Shared differential-window device-resident rate (the lstmroof.py
    slope method): machine_ms = (t(k2) - t(k1)) / (k2 - k1), best-of-3
    per window. A single fixed-k window divides the per-call constant
    (dispatch + fetch) by k and leaks it into the number; the slope
    cancels the constant term entirely. LARGE float feeds are
    generated ON device (uploading K image batches is
    not serving latency) while small float feeds keep their real values
    (BERT's input_mask is a 0/1 contract; noise would corrupt the
    attention bias). Returns one of {'ms': float},
    {'unstable': [t1, t2]}, {'skipped': 'time budget'} — ONE
    implementation so the fp32 and int8 rows can never drift apart on
    method."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    k1, k2 = 8, 40

    def _stage(v):
        arr = np.asarray(v)
        if arr.dtype.kind == 'f' and arr.nbytes > (1 << 20):
            return jax.random.normal(jax.random.PRNGKey(0),
                                     (k1,) + arr.shape, jnp.float32)
        return jax.device_put(np.stack([arr] * k1))
    stacked = {kk: _stage(v) for kk, v in feed.items()}

    def _timed(n_steps):
        with fluid.scope_guard(pred.scope):
            pred.executor.run_fused(
                pred.program, stacked, fetch_list=pred.fetch_vars,
                steps=n_steps)                            # compile
            best = float('inf')
            for _ in range(3):
                t0 = time.time()
                pred.executor.run_fused(
                    pred.program, stacked, fetch_list=pred.fetch_vars,
                    steps=n_steps)
                best = min(best, time.time() - t0)
        return best
    t1 = _timed(k1)
    if over_fn():
        # mark the cut so a consumer can tell 'metric cut by budget'
        # from 'bench version without the metric'
        return {'skipped': 'time budget'}
    t2 = _timed(k2)
    # best-of-3 only rejects jitter when at least one sample per window
    # is clean; a non-positive slope means the host moved under us —
    # re-measure the pair once, and if it is STILL unstable publish the
    # raw windows instead of a negative "serving rate"
    if t2 <= t1 and not over_fn():
        t1, t2 = _timed(k1), _timed(k2)
    if t2 > t1:
        return {'ms': round((t2 - t1) * 1000 / (k2 - k1), 2)}
    return {'unstable': [round(t1, 3), round(t2, 3)]}


def _bench_inference(rounds=9, deadline=None):
    """Predictor (deploy-path) latency: save_inference_model ->
    load_inference_model -> Predictor.run at batch 1 and 128, p50 ms per
    call (the reference inference/tests/api/analyzer_resnet50_tester.cc /
    analyzer_bert_tester pattern). The per-call number includes the
    host dispatch + fetch round-trip, so a device-resident
    `machine_ms` is also reported for b128: K forwards scanned in ONE
    compiled call on the predictor's own pruned program (what an
    on-device serving loop would see). `deadline` (epoch seconds) bounds
    the row — each part needs a fresh XLA compile, and compile time under
    chip contention is the budget risk."""
    import shutil
    import tempfile
    import numpy as np
    import paddle_tpu as fluid

    out = {}

    def _over():
        return deadline is not None and time.time() > deadline

    def _row(name, build_prog, make_feed, fetch_pick):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            feeds, targets = build_prog(main)
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        d = tempfile.mkdtemp(prefix='bench_infer_')
        try:
            with fluid.scope_guard(scope):
                exe.run(startup, scope=scope)
                fluid.io.save_inference_model(d, feeds, targets, exe,
                                              main_program=main)
            pred = fluid.create_predictor(d)
            row = {}
            for b in (1, 128):
                if _over():
                    row['skipped_b%d' % b] = 'time budget'
                    continue
                feed = make_feed(b)
                pred.run(feed)                       # compile
                # a >8 MB feed makes each call upload-bound:
                # fewer rounds, same p50 story
                n_bytes = sum(np.asarray(v).nbytes for v in feed.values())
                n_rounds = min(rounds, 5) if n_bytes > (8 << 20) else rounds
                times = []
                for _ in range(n_rounds):
                    t0 = time.time()
                    pred.run(feed)
                    times.append((time.time() - t0) * 1000)
                times.sort()
                row['p50_ms_b%d' % b] = round(times[len(times) // 2], 2)
                # device-resident serving rate: K forwards, one call.
                # LARGE float feeds (images) are generated ON device —
                # uploading K image batches is not
                # serving latency — but small float feeds keep their real
                # values (BERT's input_mask is a 0/1 contract; feeding it
                # noise would corrupt the attention bias).
                # b128 only: each machine window is another full compile.
                if b != 128:
                    continue
                if _over():
                    row['skipped_machine_b%d' % b] = 'time budget'
                    continue
                win = _machine_window(pred, feed, _over)
                if 'ms' in win:
                    row['machine_ms_b%d' % b] = win['ms']
                elif 'unstable' in win:
                    row['machine_unstable_b%d' % b] = win['unstable']
                else:
                    row['skipped_machine_b%d' % b] = win['skipped']
            out[name] = row
        finally:
            shutil.rmtree(d, ignore_errors=True)

    rng = np.random.RandomState(0)

    def _resnet_prog(main):
        from paddle_tpu.models.resnet import build as build_resnet
        img, label, pred_v, avg_cost, acc = build_resnet('imagenet',
                                                         depth=50)
        return ['img'], [pred_v]

    def _resnet_feed(b):
        return {'img': rng.randn(b, 3, 224, 224).astype('float32')}

    from paddle_tpu.models.bert import (BertConfig, build_bert_pretrain,
                                        make_pretrain_batch)
    bcfg = BertConfig(seq_len=128, max_predictions=20)

    def _bert_prog(main):
        total, mlm, nsp = build_bert_pretrain(bcfg, is_test=True)
        return ['tokens', 'segments', 'input_mask', 'mlm_positions',
                'mlm_labels', 'nsp_labels'], [total]

    def _bert_feed(b):
        return make_pretrain_batch(bcfg, b, rng)

    for name, fns in (('resnet50_infer', (_resnet_prog, _resnet_feed)),
                      ('bert_infer', (_bert_prog, _bert_feed))):
        if _over():
            out[name] = {'skipped': 'time budget'}
            continue
        try:
            _row(name, fns[0], fns[1], None)
        except Exception as e:
            out[name] = {'error': '%s: %s' % (type(e).__name__,
                                              str(e)[:200])}

    # int8 BERT inference: the SAME program post-training-quantized
    # (contrib.quantize.post_training_quantize — calibrated int8 GEMMs,
    # int8 weight blobs in the artifact). Contract: machine_ms_b128 beats
    # the fp32 bert_infer row at equal accuracy (loss_int8 within 1% of
    # loss_fp32 on the shared eval batch; the convergence harness
    # (tools/convergence.py) carries the long-run accuracy evidence), and
    # the quantized program serves with zero recompiles after warmup.
    if not _over():
        try:
            out['bert_infer_int8'] = _bert_int8_row(
                bcfg, rng, rounds, deadline,
                fp32_row=out.get('bert_infer'))
        except Exception as e:
            out['bert_infer_int8'] = {'error': '%s: %s' % (
                type(e).__name__, str(e)[:200])}
    else:
        out['bert_infer_int8'] = {'skipped': 'time budget'}
    return out


def _bert_int8_row(bcfg, rng, rounds, deadline, fp32_row=None):
    """PTQ int8 BERT: quantize -> export -> Predictor -> timed like the
    fp32 row (same differential-window machine_ms method)."""
    import shutil
    import tempfile
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.contrib.quantize import post_training_quantize
    from paddle_tpu.models.bert import build_bert_pretrain, \
        make_pretrain_batch

    def _over():
        return deadline is not None and time.time() > deadline

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        total, mlm, nsp = build_bert_pretrain(bcfg, is_test=True)
    feed_names = ['tokens', 'segments', 'input_mask', 'mlm_positions',
                  'mlm_labels', 'nsp_labels']
    exe = fluid.Executor(fluid.TPUPlace(0))
    scope = fluid.Scope()
    d = tempfile.mkdtemp(prefix='bench_int8_')
    row = {}
    try:
        with fluid.scope_guard(scope):
            exe.run(startup, scope=scope)
            infer = main.clone(for_test=True)
            eval_feed = make_pretrain_batch(bcfg, 128, rng)
            ref, = exe.run(infer, feed=eval_feed, fetch_list=[total],
                           scope=scope)
            row['loss_fp32'] = round(
                float(np.asarray(ref).reshape(-1)[0]), 4)
            calib = [make_pretrain_batch(bcfg, 16, rng) for _ in range(2)]
            n_q = post_training_quantize(exe, infer, scope, calib)
            row['quantized_matmuls'] = len(n_q)
            fluid.io.save_inference_model(
                d, feed_names, [infer.global_block().var(total.name)],
                exe, main_program=infer)
        pred = fluid.create_predictor(d)
        got, = pred.run(eval_feed)                    # compile
        row['loss_int8'] = round(
            float(np.asarray(got).reshape(-1)[0]), 4)
        denom = abs(row['loss_fp32']) or 1.0
        row['loss_rel_err'] = round(
            abs(row['loss_int8'] - row['loss_fp32']) / denom, 5)
        # zero-recompile serving contract after the warmup call above
        before = monitor.counters()
        times = []
        for _ in range(min(rounds, 5)):
            t0 = time.time()
            pred.run(eval_feed)
            times.append((time.time() - t0) * 1000)
        times.sort()
        row['p50_ms_b128'] = round(times[len(times) // 2], 2)
        row['recompiles_after_warmup'] = int(monitor.counter_delta(
            before).get('compile_cache_miss', 0))
        if _over():
            row['skipped_machine_b128'] = 'time budget'
            return row
        # the SAME _machine_window as the fp32 bert_infer row — shared
        # implementation, so the vs_fp32 ratio can never become a
        # methodology artifact
        win = _machine_window(pred, eval_feed, _over)
        if 'ms' in win:
            row['machine_ms_b128'] = win['ms']
            fp32_ms = (fp32_row or {}).get('machine_ms_b128')
            if fp32_ms:
                row['vs_fp32'] = round(fp32_ms / win['ms'], 3)
        elif 'unstable' in win:
            row['machine_unstable_b128'] = win['unstable']
        else:
            row['skipped_machine_b128'] = win['skipped']
        return row
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _child():
    """Run the measurement on the chip; print the JSON line. Exits non-zero
    without a TPU, and — after the line is printed — when any row raised."""
    import jax
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != 'tpu':
        sys.exit('bench.py: no TPU (jax.devices()[0].platform == %r); '
                 'this benchmark has no CPU mode' % dev.platform)
    kind = getattr(dev, 'device_kind', '') or ''
    start = time.time()

    # attach monitor counter DELTAS (cache hits, donations, bytes moved)
    # to each row so BENCH_*.json carries causal context, not just timings
    from paddle_tpu import monitor as _monitor
    _COUNTER_PREFIXES = ('compile_cache', 'donation', 'feed_host_bytes',
                         'fetch_host_bytes', 'nan_check',
                         'fused_kernel_dispatch', 'quantized_program',
                         'kv_prefix_hit', 'kv_prefix_tokens_saved',
                         'kv_block_cow')

    def _with_counters(fn, *args, **kw):
        before = _monitor.counters()
        row = fn(*args, **kw)
        if isinstance(row, dict):
            row['counters'] = {
                k: v for k, v in _monitor.counter_delta(before).items()
                if k.startswith(_COUNTER_PREFIXES)}
        return row

    # standalone device->host sync cost, for transparency
    t0 = time.time()
    float(jax.numpy.zeros(()))
    sync_ms = round((time.time() - t0) * 1000, 1)

    # steady-state per-run host overhead (residency + donation contract:
    # after warmup, a run() dispatch must not re-stage state through the
    # host) and compile-cache reuse for a rebuilt identical program in a
    # fresh Executor — measured, not asserted
    try:
        from tools.runoverhead import measure_run_overhead
        run_overhead = measure_run_overhead(30)
    except Exception as e:
        run_overhead = {'error': '%s: %s' % (type(e).__name__,
                                             str(e)[:200])}

    # serving-engine row: dynamic-batching request throughput vs
    # sequential Predictor.run on a mixed-shape concurrent load, p50/p99
    # latency, recompiles-after-warmup (contract: 0), shed behavior.
    # best-of-rounds minima on both sides (tools/servebench.py)
    try:
        from tools.servebench import measure_serving
        serving = measure_serving(rounds=3, requests_per_client=20)
    except Exception as e:
        serving = {'error': '%s: %s' % (type(e).__name__, str(e)[:200])}

    # multi-tenant fleet row: fp32 + PTQ-int8 models co-resident in one
    # ModelFleet behind the goodput-priced Router — premium closed-loop
    # deadline traffic (contract: p99 under deadline, 0 errors) next to
    # a flooding quota'd batch tenant (contract: sheds structured, never
    # starves the deadline class), with a mid-bench hot-swap of the
    # premium model under live load (contract: dropped_inflight == 0,
    # recompiles_after_warmup == 0) and LIVE goodput.cost_estimate
    # pricing per model (tools/servebench.py measure_fleet / --fleet)
    try:
        from tools.servebench import measure_fleet
        serving_fleet = measure_fleet(requests_per_client=20)
    except Exception as e:
        serving_fleet = {'error': '%s: %s'
                         % (type(e).__name__, str(e)[:200])}

    # generative-decode row: continuous-batching GenerateEngine with the
    # device-resident KV cache vs the sequential re-traced greedy
    # baseline — tokens/sec, ENGINE-attributed per-token p50/p99 (step
    # time charged to each token the step emitted), recompiles-after-
    # warmup (contract: 0), kv occupancy, and the PAGED columns: the
    # same workload at the same KV HBM budget through the block-table
    # cache (block utilization, prefix-share hit rate, peak concurrent
    # sequences — contract: >= 2x the contiguous slots — and exact
    # greedy parity vs the contiguous engine). The companion
    # shared-prefix row (one system prompt, N clients) proves physical
    # block sharing (refcounts) + measurably reduced prefill
    # (tools/servebench.py measure_generate / measure_shared_prefix;
    # contract: >=10x sentences/s vs re-trace).
    # ROW-SCHEMA NOTE (per-token latency attribution): rounds up to and
    # including BENCH_r06 computed ms_per_token_p50/p99 from CLIENT
    # ARRIVAL GAPS — tokens buffered in the stream queue drain in ~0
    # time, so those rows carry a bogus p50 (e.g. 0.003 ms against a
    # 72 ms p99 in r06). PR 12 switched the attribution to engine step
    # time charged per emitted token; r07+ rows are comparable to each
    # other but NOT to the p50 column of older rows (p99 was dominated
    # by real step time and remains roughly comparable).
    try:
        from tools.servebench import measure_generate
        generate = measure_generate(rounds=2)
    except Exception as e:
        generate = {'error': '%s: %s' % (type(e).__name__, str(e)[:200])}
    try:
        from tools.servebench import measure_shared_prefix
        generate_shared_prefix = measure_shared_prefix()
    except Exception as e:
        generate_shared_prefix = {'error': '%s: %s'
                                  % (type(e).__name__, str(e)[:200])}

    # speculative-decode row: the decode-heavy greedy workload through
    # the paged engine plain vs SPECULATIVE (draft = target: accept
    # rate 1.0 — one drafter dispatch + one spec_k+1-wide verify
    # replace spec_k+1 sequential steps; contract: >= 1.5x engine
    # tokens/sec, exact greedy parity, 0 recompiles), plus the
    # chunked-prefill proof: a prompt past the widest bucket admitted
    # with a bit-exact continuation (tools/servebench.py
    # measure_speculative / --speculative)
    try:
        from tools.servebench import measure_speculative
        generate_speculative = measure_speculative(rounds=3)
    except Exception as e:
        generate_speculative = {'error': '%s: %s'
                                % (type(e).__name__, str(e)[:200])}

    # async-pipeline row: overlapped input pipeline (DevicePrefetcher ->
    # run_async, bounded in-flight window) vs the synchronous step loop
    # on an input-bound workload (tools/pipebench.py; contract: >=1.3x
    # steps/sec at recompiles_after_warmup=0 with exact trajectory
    # parity)
    try:
        from tools.pipebench import measure_pipeline
        async_pipeline = measure_pipeline(rounds=2)
    except Exception as e:
        async_pipeline = {'error': '%s: %s' % (type(e).__name__,
                                               str(e)[:200])}

    # parameter-server CTR row: the ctr_sharded_v1m shape with the
    # embedding table PS-RESIDENT on live socket shards (paddle_tpu/ps)
    # — samples/s with the pull-prefetch overlap vs the serialized
    # pull->run->push loop, pull/push counter + byte deltas, and
    # recompiles_after_warmup (contract: overlap > no_overlap at 0
    # recompiles; tools/psbench.py)
    try:
        from tools.psbench import measure_ctr_ps
        ctr_ps = measure_ctr_ps(rounds=2)
    except Exception as e:
        ctr_ps = {'error': '%s: %s' % (type(e).__name__, str(e)[:200])}

    # elastic-resume chaos row: a fatal fault kills a training step
    # mid-run; elastic_train_loop restores the latest checkpoint
    # RESHARDED onto half the devices and replays
    # (tools/chaosbench.py; contract: trajectory_parity True — the
    # recovered run bit-matches the uninterrupted one)
    try:
        from tools.chaosbench import measure_elastic_resume
        elastic_resume = measure_elastic_resume()
    except Exception as e:
        elastic_resume = {'error': '%s: %s' % (type(e).__name__,
                                               str(e)[:200])}

    # shrink-THEN-grow chaos row: the kill halves the fleet, capacity
    # later returns and the loop re-expands onto the full mesh via a
    # checkpoint-publish barrier (time_to_recover both directions;
    # contract: trajectory_parity True). Runs as a subprocess — the
    # drill needs an 8-way CPU mesh forced before jax initializes,
    # which this process's jax can no longer do. The child pins itself
    # to the CPU backend before importing jax (tools/chaosbench.py
    # main), so it never reaches for the chip this process holds.
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          'tools', 'chaosbench.py'), '--grow'],
            capture_output=True, text=True, timeout=600)
        line = [l for l in res.stdout.splitlines()
                if l.startswith('{')][-1]
        elastic_grow_back = json.loads(line)
        elastic_grow_back.pop('metric', None)
    except Exception as e:
        elastic_grow_back = {'error': '%s: %s' % (type(e).__name__,
                                                  str(e)[:200])}

    # XLA cost/memory analytics smoke (tools/costreport.py — the
    # Executor.explain CLI): flops + buffer-assignment peak for the
    # mnist-mlp reference programs. Memory stats cost one extra XLA
    # compile per program — minutes on TPU, so this line keeps cost
    # analysis only.
    try:
        from tools.costreport import measure_costreport
        costreport = measure_costreport(batch=64, memory=False)
    except Exception as e:
        costreport = {'error': '%s: %s' % (type(e).__name__,
                                           str(e)[:200])}

    # mesh-partitioned fused-kernel smoke (tools/kernbench.py --mesh 2):
    # each fused unit must dispatch its PARTITIONED impl under
    # mesh(data=2) — the mesh_dispatch sub-dicts carry the
    # fused_kernel_dispatch_total{...,mesh=n} proof rows. Tiny configs:
    # this is a dispatch/coverage row, not a timing row. It needs two
    # devices and runs in THIS process: a kernbench child would reach
    # for the chip this process holds. On a one-chip host the row is
    # recorded as not run; its virtual-CPU form is tier-1's
    # (tests/test_fused_mesh.py).
    if len(jax.devices()) >= 2:
        try:
            from tools.kernbench import measure_kernbench
            kernbench_mesh = measure_kernbench(
                tiers=['off', 'pallas'], rounds=1, k=2, size='small',
                mesh=2)
        except Exception as e:
            kernbench_mesh = {'error': '%s: %s' % (type(e).__name__,
                                                   str(e)[:200])}
    else:
        kernbench_mesh = {'skipped': 'not run on this topology: 1 device'}

    flagship_cfg = dict(vocab_size=32000, seq_len=512, d_model=512,
                        n_head=8, n_layer=6, d_ff=2048, dropout=0.1,
                        attn_dropout=0.0, use_flash_attention=True)
    flag = _with_counters(_bench_lm, flagship_cfg, batch=64,
                          k_per_call=30, rounds=3, amp=True)

    peak = _peak_for(kind)
    mfu = None
    if peak:
        mfu = round(flag['flops_per_step']
                    / (flag['step_ms'] / 1000) / peak, 4)

    # live-vs-offline MFU cross-check on the flagship row: the goodput
    # layer's best-window live flops rate vs this file's analytic
    # formula at the best step time (the ratio is peak-independent).
    goodput_xcheck = None
    if flag.get('live_flops_per_s') and flag.get('flops_per_step'):
        offline_rate = flag['flops_per_step'] / (flag['step_ms'] / 1000.0)
        ratio = flag['live_flops_per_s'] / offline_rate
        goodput_xcheck = {
            'live_mfu': flag.get('live_mfu'),
            'offline_mfu': mfu,
            'live_flops_per_s': flag['live_flops_per_s'],
            'offline_flops_per_s': round(offline_rate, 1),
            'live_vs_offline': round(ratio, 4),
            'within_10pct': bool(abs(ratio - 1.0) <= 0.10),
            'goodput_frac': flag.get('goodput_frac'),
        }

    models = {}

    def _try(name, fn, *args, **kw):
        if time.time() - start > TPU_MODEL_BUDGET_S:
            models[name] = {'skipped': 'time budget'}
            return
        try:
            models[name] = _with_counters(fn, *args, **kw)
        except Exception as e:  # the line still prints; the exit code tells
            models[name] = {'error': '%s: %s' % (
                type(e).__name__, str(e)[:200])}

    def _set_mfu(name):
        r = models.get(name)
        if isinstance(r, dict) and peak and 'flops_per_step' in r:
            r['mfu'] = round(r['flops_per_step']
                             / (r['step_ms'] / 1000) / peak, 4)

    _try('lm_large', _bench_lm,
         dict(vocab_size=32000, seq_len=512, d_model=1024, n_head=16,
              n_layer=8, d_ff=4096, dropout=0.1, attn_dropout=0.0,
              use_flash_attention=True),
         32, 20, 2, True)
    _set_mfu('lm_large')
    _try('lm_long_seq8k', _bench_lm,
         dict(vocab_size=32000, seq_len=8192, d_model=512, n_head=8,
              n_layer=4, d_ff=2048, dropout=0.0, attn_dropout=0.0,
              use_flash_attention=True),
         2, 10, 2, True)
    _set_mfu('lm_long_seq8k')
    _try('resnet50', _bench_resnet50, 128, 4, 2, True)
    _try('bert_base', _bench_bert, 128, 10, 2, True)
    _set_mfu('bert_base')
    _try('se_resnext', _bench_se_resnext, 128, 4, 2, True)
    _try('vgg16', _bench_vgg, 128, 10, 3, True)
    _try('ctr_sharded_v1m', _bench_ctr, 512, 20, 2,
         vocab=1 << 20, dim=32, is_distributed=True)
    _try('stacked_lstm', _bench_stacked_lstm, 32, 128, 10, 2)
    _try('ctr_sparse', _bench_ctr, 512, 50, 3)
    # inference (~6 fresh compiles, 2 models) runs BEFORE nmt: its two
    # rows are required deliverables, while nmt's ~500 s while-loop
    # train compile is the budget whale — nmt goes last so the
    # elapsed-budget guard above makes IT the row that absorbs
    # chip-contention overruns, not everything after it. Bounded at
    # ~600 s so a slow compile can't starve nmt in the good case.
    _try('inference', _bench_inference,
         deadline=min(start + TPU_MODEL_BUDGET_S - 120,
                      time.time() + 600))
    _try('machine_translation', _bench_nmt, 32, 30, 6, 2)
    for r in models.values():
        r.pop('flops_per_step', None)
    flag.pop('flops_per_step', None)

    tokens_per_sec = flag['tokens_per_sec']
    rec = {
        'metric': 'transformer_lm_train_throughput',
        'value': round(tokens_per_sec, 2),
        'unit': 'tokens/sec',
        'vs_baseline': _vs_baseline(tokens_per_sec, dev.platform),
        'platform': dev.platform,
        'device_kind': kind,
        'mfu': mfu,
        'step_ms': flag['step_ms'],
        'compile_s': flag['compile_s'],
        'sync_ms': sync_ms,
        'run_overhead': run_overhead,
        'serving': serving,
        'serving_fleet': serving_fleet,
        'generate': generate,
        'generate_shared_prefix': generate_shared_prefix,
        'generate_speculative': generate_speculative,
        'async_pipeline': async_pipeline,
        'ctr_ps': ctr_ps,
        'elastic_resume': elastic_resume,
        'elastic_grow_back': elastic_grow_back,
        'costreport': costreport,
        'kernbench_mesh': kernbench_mesh,
        'goodput': goodput_xcheck,
        'flops': flag.get('flops'),
        'peak_bytes': flag.get('peak_bytes'),
        'final_loss': flag['final_loss'],
        'amp': True,
        'flash_attention': True,
        'fused_steps_per_call': 120,
        'config': flag['config'],
        'counters': flag.get('counters'),
        'models': models,
    }
    print(json.dumps(rec))
    failed = _error_rows(rec)
    if failed:
        sys.exit('bench.py: rows failed: %s' % ', '.join(failed))


def _error_rows(rec, path=''):
    """Dotted paths of every row in `rec` that carries an 'error' key."""
    if not isinstance(rec, dict):
        return []
    if 'error' in rec:
        return [path or '.']
    return [p for k, v in rec.items()
            for p in _error_rows(v, (path + '.' + k) if path else k)]


def _vs_baseline(value, platform):
    """Ratio vs the newest prior round's recorded throughput on the SAME
    platform (the kept driver records BENCH_r02.json ... BENCH_r05.json)."""
    best = None
    for path in sorted(glob.glob('BENCH_r*.json')):
        try:
            with open(path) as f:
                rec = json.load(f)
        except Exception:
            continue
        parsed = rec.get('parsed') if isinstance(rec, dict) else None
        if not isinstance(parsed, dict):
            parsed = rec if isinstance(rec, dict) and 'value' in rec else None
        if not parsed or not parsed.get('value'):
            continue
        if str(parsed.get('platform', 'tpu')) != platform:
            continue
        best = float(parsed['value'])  # sorted() => last one wins
    return round(value / best, 4) if best else 1.0


def main():
    if os.environ.get('BENCH_CHILD'):
        return _child()
    # ONE measuring child under a timeout; this parent never imports jax,
    # so the child is the only process that reaches for the chip. Its
    # stdout (the JSON line) and exit code pass through unchanged.
    env = dict(os.environ, BENCH_CHILD='1')
    try:
        res = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             env=env, timeout=TPU_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        sys.exit('bench.py: child timed out after %ds' % TPU_TIMEOUT_S)
    sys.exit(res.returncode)


if __name__ == '__main__':
    main()
