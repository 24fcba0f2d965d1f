"""Serving-engine load generator: closed-loop + open-loop measurement,
plus a streaming-decode client mode for the generative engine.

Answers the questions the serving layer (paddle_tpu/serving/,
docs/serving.md) makes measurable promises about:

- batching win: request throughput of a mixed-shape CONCURRENT load
  (requests spanning >= 3 bucket sizes) through the engine vs the same
  requests as sequential single-request `Predictor.run` calls. The
  contract is >= 3x at batchable concurrency; both sides report the
  best-of-`rounds` window (the CI box is noisy — compare minima).
- warm steady state: `compile_cache_miss` delta across the measured
  window after `warmup()` — the bucket ladder's whole point is that this
  is 0.
- overload behavior: an OPEN-LOOP burst past the queue bound must shed
  (structured LoadShedError, counted) while every accepted request still
  completes within its deadline — never unbounded queueing.
- decode win (`measure_generate`): streaming clients drive mixed
  prompt/output-length greedy generation through the continuous-batching
  `GenerateEngine` (tokens/sec, sentences/sec, per-token p50/p99,
  kv-slot occupancy, recompiles_after_warmup == 0) against the
  sequential RE-TRACED baseline — one full-context forward re-built and
  re-run per generated token, the only decode path the repo had before
  the KV-cache engine. The contract is >= 10x sentences/sec. Per-token
  latency is ENGINE-attributed: each decode step's wall time is charged
  to every token that step emitted (`GenerateRequest.step_s`). Client
  arrival gaps are NOT used — tokens buffered in the stream queue drain
  in ~0 time, which used to report a nonsense sub-microsecond p50
  against a tens-of-ms p99 (BENCH_r06).
- block-pool columns (same row, under `paged`): block utilization,
  prefix-share hit rate, copy-on-write count and peak concurrent
  sequences of that run. Greedy parity is held against the re-traced
  baseline, which has no cache.
- shared-prefix win (`measure_shared_prefix`, `--shared-prefix`): N
  clients sending ONE system prompt + tiny unique suffixes through the
  paged engine with prefix sharing on vs off. Reports physical-sharing
  proof (peak refcount on the system prompt's blocks, prefix-hit /
  tokens-saved counters) and the prefill-compute reduction (suffix
  bucketing: a hit prefills 8 tokens instead of 64).
- speculative win (`measure_speculative`, `--speculative`): the same
  decode-heavy greedy workload through the plain paged engine vs the
  SPECULATIVE engine (draft proposes spec_k tokens in one dispatch,
  target verifies spec_k + 1 positions in one batched step). Reports
  spec-vs-plain engine tokens/sec (contract: >= 1.5x at a high-accept
  draft on a quiet box), accept rate (1.0 at the default
  draft = target), recompiles_after_warmup == 0, and exact greedy
  parity. `--draft-config '{"n_layer": 1, ...}'` swaps in a custom
  draft LMConfig (fresh-initialized — accept rate then measures that
  draft's real agreement). The same row drives a LONG-PROMPT workload
  (prompts past the widest bucket) exercising CHUNKED prefill, with a
  bit-exactness check against a single-shot wide-bucket reference.

- fleet win (`measure_fleet`, `--fleet`): an fp32 model + its PTQ-int8
  variant co-resident in one `ModelFleet` behind a goodput-priced
  `Router`. Premium closed-loop deadline traffic (p99 under deadline)
  shares the process with a flooding low-priority tenant (quota sheds,
  never starves the deadline class), a mid-bench hot-swap redeploys the
  premium model under the live load (zero dropped in-flight,
  recompiles_after_warmup == 0), and the row carries the LIVE
  `goodput.cost_estimate` device-seconds per dispatch per model.

Usage: python tools/servebench.py [rounds] (prints one JSON line);
       python tools/servebench.py --generate   (streaming-decode mode);
       python tools/servebench.py --shared-prefix [clients];
       python tools/servebench.py --speculative [rounds]
                                  [--draft-config JSON] [--spec-k K];
       python tools/servebench.py --fleet [requests_per_client]
importable `measure_serving()` / `measure_generate()` /
`measure_shared_prefix()` / `measure_speculative()` / `measure_fleet()`
(the four `@slow` tests of tests/test_generate.py,
test_paged_generate.py, test_speculative.py and test_fleet.py reuse
them).
"""
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Committed serving-row baseline (BENCH_r08, the PR 14-sentinel era
# box): engine/sequential speedup 1.77. The r06/r07 0.84-0.85x readings
# were TRIAGED as sequential-BASELINE drift, not an engine regression:
# sequential_rps swings 3.7x across CPU-only rounds on identical
# code (720 r08 / 1712 r07 / 1886 r06 / 2673 standalone 2026-08) while
# the engine re-measures >= 1.6x standalone on the same tree, and the
# ratio IMPROVES under both external CPU load (4.3x) and in-process GIL
# contention (20x) — the single-threaded tiny-dispatch sequential loop
# is the noisy term. measure_serving feeds the measured speedup to the
# goodput sentinel against this baseline so a REAL engine collapse
# (below baseline * PADDLE_PERFWATCH_ROW_DRIFT) trips
# perf_regression_total{kind=bench_row_drift} instead of hiding in
# round-to-round noise.
SERVING_ROW_BASELINE = {'speedup': 1.77, 'source': 'BENCH_r08'}


def _build_model(dirname):
    """Small 3-layer MLP saved as an inference model: big enough that a
    batched dispatch does real work, small enough to compile in ~100 ms
    per bucket on CPU."""
    import numpy as np
    import paddle_tpu as fluid
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[64], dtype='float32')
            h = fluid.layers.fc(x, size=128, act='relu')
            h = fluid.layers.fc(h, size=128, act='relu')
            y = fluid.layers.fc(h, size=16)
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        fluid.save_inference_model(dirname, ['x'], [y], exe,
                                   main_program=main_p)
    return 'x', 64


def _build_int8_model(dirname, seed=0):
    """The `_build_model` MLP post-training-quantized to int8 (quantize ->
    quantized_matmul rewrite over calibration batches) and saved as a
    `load_inference_model` artifact — the cheap-tier fleet variant.
    Loading it in a serving process counts
    `quantized_program_total{kind=loaded}`."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.contrib.quantize import post_training_quantize
    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup):
        with fluid.unique_name.guard():
            x = fluid.layers.data(name='x', shape=[64], dtype='float32')
            h = fluid.layers.fc(x, size=128, act='relu')
            h = fluid.layers.fc(h, size=128, act='relu')
            y = fluid.layers.fc(h, size=16)
    exe = fluid.Executor()
    scope = fluid.Scope()
    rng = np.random.RandomState(seed)
    calib = [{'x': rng.randn(4, 64).astype('float32')} for _ in range(4)]
    with fluid.scope_guard(scope):
        exe.run(startup, scope=scope)
        infer = main_p.clone(for_test=True)
        post_training_quantize(exe, infer, scope, calib)
        fluid.save_inference_model(dirname, ['x'], [y], exe,
                                   main_program=infer)
    return 'x', 64


def _mixed_requests(feed_name, width, n, seed=0):
    """Request stream spanning 3 batch-bucket sizes (1/2/4 rows)."""
    import numpy as np
    rng = np.random.RandomState(seed)
    rows_cycle = (1, 2, 4)
    return [{feed_name: rng.randn(rows_cycle[i % 3], width)
             .astype('float32')} for i in range(n)]


def _quantile(sorted_vals, q):
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


def measure_serving(rounds=5, clients=8, requests_per_client=40,
                    max_batch_size=64, max_wait_ms=2.0, num_workers=2,
                    model_dir=None):
    """Returns the serving-row dict (see module docstring). A model is
    built in a temp dir unless `model_dir` points at a saved one with a
    single 2-D float32 feed.

    Clients are PIPELINED: each client thread submits its whole request
    stream and then drains the futures in order — the "batchable
    concurrency" shape (an async frontend keeping its pipeline full, not
    one blocked caller per thread whose turnaround is dominated by
    python thread wakeup latency under the GIL)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import monitor
    from paddle_tpu.serving import ServingConfig, ServingEngine, \
        LoadShedError

    tmp = None
    if model_dir is None:
        tmp = tempfile.mkdtemp(prefix='servebench_')
        feed_name, width = _build_model(tmp)
        model_dir = tmp
    else:
        pred0 = fluid.Predictor(model_dir)
        feed_name = pred0.get_input_names()[0]
        width = None          # caller-provided model: derive from program
        for v in pred0.program.global_block().vars.values():
            if v.name == feed_name and v.shape:
                width = int(v.shape[-1])
        if width is None or width < 1:
            raise ValueError(
                "servebench cannot derive the feed width of %r from %s "
                "(var missing or dynamic last dim %r) — it drives models "
                "with one 2-D float32 feed of static width"
                % (feed_name, model_dir, width))
        del pred0

    n_requests = clients * requests_per_client
    reqs = _mixed_requests(feed_name, width, n_requests)

    # --- sequential baseline: the same rows, one Predictor.run each ---
    pred = fluid.Predictor(model_dir)
    pred.run(reqs[0])                                   # compile
    seq_best = float('inf')
    for _ in range(rounds):
        t0 = time.perf_counter()
        for r in reqs:
            pred.run(r)
        seq_best = min(seq_best, time.perf_counter() - t0)
    seq_rps = n_requests / seq_best

    # --- engine closed loop: `clients` pipelined submitter threads ---
    cfg = ServingConfig(model_dir, max_batch_size=max_batch_size,
                        max_wait_ms=max_wait_ms, num_workers=num_workers,
                        queue_cap=n_requests + clients)
    engine = ServingEngine(cfg)
    warm = engine.warmup({feed_name: reqs[0][feed_name][:1]})
    lat_lock = threading.Lock()
    latencies = []
    errors = [0]

    def client(cid, barrier):
        mine = reqs[cid::clients]
        barrier.wait()
        futs = []
        for r in mine:
            try:
                futs.append((time.perf_counter(),
                             engine.submit(r, deadline_s=60.0)))
            except Exception:
                with lat_lock:
                    errors[0] += 1
        for t0, f in futs:
            try:
                f.result(60.0)
            except Exception:
                with lat_lock:
                    errors[0] += 1
                continue
            dt = time.perf_counter() - t0
            with lat_lock:
                latencies.append(dt)

    eng_best, miss_delta = float('inf'), 0
    engine.start()
    try:
        # latencies ACCUMULATE across rounds (p50/p99 over every measured
        # request); throughput is still best-of-rounds — reporting the
        # last round's percentiles next to the best round's rps would mix
        # windows and read as a latency regression on a noisy box
        for _ in range(rounds):
            before = monitor.counters()
            barrier = threading.Barrier(clients + 1)
            threads = [threading.Thread(target=client, args=(c, barrier),
                                        daemon=True)
                       for c in range(clients)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            delta = monitor.counter_delta(before)
            miss_delta = max(miss_delta, sum(
                v for k, v in delta.items()
                if k.startswith('compile_cache_miss')))
            eng_best = min(eng_best, elapsed)
        lat = sorted(latencies)

        # --- open loop: burst 4x the queue bound, expect sheds, and no
        # accepted request may outlive its deadline ---
        shed, ok, max_lat = 0, 0, 0.0
        burst_cfg = ServingConfig(model_dir, max_batch_size=max_batch_size,
                                  max_wait_ms=max_wait_ms, num_workers=1,
                                  queue_cap=8)
        burst = ServingEngine(burst_cfg, predictor=pred)
        burst.start()
        try:
            # one waiter thread per accepted future records COMPLETION
            # latency (draining sequentially would charge a request the
            # time spent blocked on earlier futures and fake a deadline
            # violation)
            stats_lock = threading.Lock()

            def waiter(t0, f):
                nonlocal ok, max_lat
                try:
                    f.result(15.0)
                except Exception:
                    return
                dt = time.perf_counter() - t0
                with stats_lock:
                    ok += 1
                    max_lat = max(max_lat, dt)

            # submit the WHOLE burst back-to-back first (spawning a
            # thread per accept would yield the GIL and let the worker
            # drain, hiding the overload), then start the waiters
            accepted = []
            for i in range(64):
                try:
                    accepted.append((time.perf_counter(),
                                     burst.submit(reqs[i % len(reqs)],
                                                  deadline_s=10.0)))
                except LoadShedError:
                    shed += 1
            waiters = [threading.Thread(target=waiter, args=(t0, f),
                                        daemon=True)
                       for t0, f in accepted]
            for t in waiters:
                t.start()
            for t in waiters:
                t.join(20.0)
        finally:
            burst.stop()
    finally:
        engine.stop()
        if tmp is not None:
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)

    eng_rps = n_requests / eng_best
    from paddle_tpu import goodput
    speedup = eng_rps / seq_rps
    goodput.note_bench_row('serving_speedup', speedup,
                           SERVING_ROW_BASELINE['speedup'])
    return {
        'requests': n_requests,
        'clients': clients,
        'bucket_sizes_spanned': 3,
        'sequential_rps': round(seq_rps, 1),
        'engine_rps': round(eng_rps, 1),
        'speedup': round(speedup, 2),
        'baseline': dict(SERVING_ROW_BASELINE),
        'latency_p50_ms': round(1e3 * (_quantile(lat, 0.5) or 0), 2),
        'latency_p99_ms': round(1e3 * (_quantile(lat, 0.99) or 0), 2),
        'errors': errors[0],
        'warmup': warm,
        'recompiles_after_warmup': int(miss_delta),
        'open_loop': {'submitted': 64, 'ok': ok, 'shed': shed,
                      'max_latency_ms': round(1e3 * max_lat, 1)},
        'rounds': rounds,
    }


def _decode_lm():
    """Decode-bench LM: big enough that a full-context forward does real
    work per token, small enough that ~50 distinct context lengths of the
    re-traced baseline all compile inside the bench budget on CPU.
    Deterministic (dropout 0) and dense-masked so the baseline full
    forward and the engine's prefill run the same attention math."""
    from paddle_tpu.models.transformer import LMConfig
    return LMConfig(vocab_size=256, seq_len=64, d_model=64, n_head=4,
                    n_layer=2, d_ff=128, dropout=0.0, attn_dropout=0.0,
                    use_flash_attention=False)


def _gen_workload(n, seed=0):
    """Mixed prompt/output-length traffic: prompt lengths span 3 prompt
    buckets (<=8 / <=16 / <=32) and output lengths interleave short and
    long, so slots churn at token boundaries instead of draining in
    lockstep."""
    import numpy as np
    rng = np.random.RandomState(seed)
    p_lens = (4, 7, 12, 16, 24, 30)
    n_new = (6, 14, 10, 18, 8, 12)
    return [(rng.randint(2, 256, size=p_lens[i % len(p_lens)])
             .astype('int64'), n_new[i % len(n_new)]) for i in range(n)]


def _retrace_greedy(exe, scope, base, prompt, n_new, seed):
    """The pre-engine decode path: ONE full-context forward re-BUILT and
    re-run per generated token (exactly how the repo's beam decode
    generates — re-trace the whole loop, argmax, extend, repeat). The
    PR 1 fingerprint cache still de-duplicates XLA compiles per context
    length; what this path pays per token is graph rebuild + full-T
    forward + host round-trip."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models.transformer import LMConfig, build_lm

    ids = list(int(t) for t in prompt)
    out_toks = []
    for _ in range(n_new):
        cfg_t = LMConfig(
            vocab_size=base.vocab_size, seq_len=len(ids),
            d_model=base.d_model, n_head=base.n_head,
            n_layer=base.n_layer, d_ff=base.d_ff, dropout=0.0,
            attn_dropout=0.0, use_flash_attention=False)
        main, start = fluid.Program(), fluid.Program()
        main.random_seed = seed
        with fluid.program_guard(main, start):
            with fluid.unique_name.guard():
                _t, _l, logits, _loss = build_lm(cfg_t, is_test=True)
        arr = np.array(ids, 'int64')[None, :]
        out = exe.run(main, feed={'tokens': arr,
                                  'labels': np.zeros_like(arr)},
                      fetch_list=[logits], scope=scope)
        nxt = int(np.asarray(out[0])[0, -1].argmax())
        ids.append(nxt)
        out_toks.append(nxt)
    return out_toks


def measure_generate(rounds=3, sentences=24, slots=8, clients=6):
    """Returns the generate-row dict (see module docstring): continuous-
    batching `GenerateEngine` throughput on mixed prompt/output-length
    greedy traffic vs the sequential re-traced baseline, with per-token
    streaming latency percentiles measured client-side. Both sides share
    ONE scope (identical weights), so the row also cross-checks greedy
    parity between the KV-cache decode path and the full-context
    forward."""
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.serving import GenerateConfig, GenerateEngine

    base = _decode_lm()
    work = _gen_workload(sentences)
    total_new = sum(n for _, n in work)
    cfg = GenerateConfig(model=base, slots=slots, max_len=96,
                         prompt_buckets=[8, 16, 32], eos_id=None,
                         max_new_tokens=64, seed=0,
                         queue_cap=sentences + clients)
    engine = GenerateEngine(cfg)
    warm = engine.warmup()

    # --- sequential re-traced baseline (shared weights) ---------------
    refs = [None] * sentences
    for i, (p, n_new) in enumerate(work):      # compile pass, unmeasured
        refs[i] = _retrace_greedy(engine.executor, engine.scope, base,
                                  p, n_new, cfg.seed)
    seq_best = float('inf')
    for _ in range(rounds):
        t0 = time.perf_counter()
        for p, n_new in work:
            _retrace_greedy(engine.executor, engine.scope, base,
                            p, n_new, cfg.seed)
        seq_best = min(seq_best, time.perf_counter() - t0)

    # --- continuous-batching engine: streaming clients ----------------
    def run_engine_rounds(eng):
        """Drive `rounds` of the workload; returns (best wall, max
        compile-miss delta, outputs, engine-attributed per-token ms,
        errors). Token latency = each decode step's wall time charged
        to every token it emitted (GenerateRequest.step_s) — client
        arrival gaps are meaningless for same-step tokens (they drain a
        queue in ~0 time)."""
        lat_lock = threading.Lock()
        token_ms = []
        outs = [None] * sentences
        errors = [0]

        def client(cid, barrier):
            mine = list(range(cid, sentences, clients))
            barrier.wait()
            reqs = [(i, eng.submit(work[i][0], max_new_tokens=work[i][1],
                                   deadline_s=120.0)) for i in mine]
            for i, req in reqs:
                got = []
                try:
                    for tok in req.stream(timeout=120.0):
                        got.append(tok)
                except Exception:
                    with lat_lock:
                        errors[0] += 1
                with lat_lock:
                    token_ms.extend(1e3 * s for s in req.step_s)
                outs[i] = got

        best, miss = float('inf'), 0
        eng.start()
        try:
            for _ in range(rounds):
                before = monitor.counters()
                barrier = threading.Barrier(clients + 1)
                threads = [threading.Thread(target=client,
                                            args=(c, barrier),
                                            daemon=True)
                           for c in range(clients)]
                for t in threads:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                best = min(best, time.perf_counter() - t0)
                delta = monitor.counter_delta(before)
                miss = max(miss, sum(
                    v for k, v in delta.items()
                    if k.startswith('compile_cache_miss')))
        finally:
            eng.stop()
        return best, miss, outs, token_ms, errors[0]

    before_run = monitor.counters()
    eng_best, miss_delta, outs, token_ms, errors = \
        run_engine_rounds(engine)
    run_delta = monitor.counter_delta(before_run)

    stats = engine.stats()
    lat = sorted(token_ms)
    parity = sum(1 for r, o in zip(refs, outs) if o == r)
    hits = run_delta.get('kv_prefix_hit_total{outcome=hit}', 0)
    misses = run_delta.get('kv_prefix_hit_total{outcome=miss}', 0)
    return {
        'sentences': sentences,
        'tokens_generated': total_new,
        'clients': clients,
        'sequential_sentences_per_sec': round(sentences / seq_best, 2),
        'engine_sentences_per_sec': round(sentences / eng_best, 2),
        'speedup': round(seq_best / eng_best, 2),
        'sequential_tokens_per_sec': round(total_new / seq_best, 1),
        'engine_tokens_per_sec': round(total_new / eng_best, 1),
        'ms_per_token_p50': round(_quantile(lat, 0.5) or 0, 3),
        'ms_per_token_p99': round(_quantile(lat, 0.99) or 0, 3),
        'recompiles_after_warmup': int(miss_delta),
        'kv_slot_occupancy': {
            'mean': stats['mean_slot_occupancy'],
            'peak': stats['peak_slot_occupancy']},
        'greedy_parity_sentences': '%d/%d' % (parity, sentences),
        'errors': errors,
        'warmup': warm,
        'paged': {
            'block_size': cfg.block_size,
            'hbm_budget_rows': cfg.num_blocks * cfg.block_size,
            'concurrent_seqs_peak': stats['peak_active'],
            'block_utilization_peak': round(
                stats['blocks']['peak_in_use']
                / float(stats['blocks']['capacity']), 3),
            'prefix_hit_rate': round(hits / float(hits + misses), 3)
            if hits + misses else 0.0,
            'cow_total': int(run_delta.get('kv_block_cow_total', 0)),
        },
        'rounds': rounds,
        'config': 'lm v%d d%d h%d L%d slots%d maxlen%d' % (
            base.vocab_size, base.d_model, base.n_head, base.n_layer,
            slots, cfg.max_len),
    }


def measure_shared_prefix(clients=8, system_len=48, suffix_len=8,
                          new_tokens=8, block_size=16):
    """The millions-of-users shape: every client sends the SAME system
    prompt plus a tiny unique suffix. Drives the workload through a
    paged engine twice — prefix sharing ON vs OFF — and reports the
    physical-sharing proof (peak refcount on the system prompt's
    blocks, hit/saved counters, blocks stored once) and the
    prefill-compute reduction (a hit prefills the suffix bucket, not
    the whole prompt; `prefill_s_total` is the engine-attributed sum)."""
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.serving import GenerateConfig, GenerateEngine

    base = _decode_lm()
    rng = np.random.RandomState(0)
    system = rng.randint(2, 256, size=system_len).astype('int64')
    prompts = [np.concatenate([
        system, rng.randint(2, 256, size=suffix_len).astype('int64')])
        for _ in range(clients)]

    def run(sharing):
        cfg = GenerateConfig(
            model=base, slots=8, max_len=96,
            prompt_buckets=[8, 16, 32, 64], eos_id=None, seed=0,
            queue_cap=clients + 1, block_size=block_size,
            prefix_sharing=sharing)
        eng = GenerateEngine(cfg)
        eng.warmup()
        before = monitor.counters()
        peak_ref = [0]
        shared_blocks = [0]
        with eng:
            # every request after the first should hit the registered
            # system-prompt blocks; refcounts are sampled DURING
            # residency (they drop back to the cache's single reference
            # once a sharer finishes)
            reqs = [eng.submit(p, max_new_tokens=new_tokens,
                               deadline_s=120.0) for p in prompts]
            pending = list(reqs)
            while pending:
                if sharing and eng._prefix is not None:
                    for b, *_rest in list(eng._prefix._entries.values()):
                        peak_ref[0] = max(peak_ref[0],
                                          eng._alloc.refcount(b))
                    shared_blocks[0] = max(shared_blocks[0],
                                           len(eng._prefix))
                pending = [r for r in pending
                           if r.finish_reason is None and
                           r._error is None]
                time.sleep(0.001)
            outs = [r.result(120.0) for r in reqs]
        delta = monitor.counter_delta(before)
        pf_total = sum(r.timing['prefill_s'] for r in reqs
                       if r.timing is not None)
        return {
            'outs': [list(o) for o in outs],
            'prefill_s_total': round(pf_total, 4),
            'hits': int(delta.get('kv_prefix_hit_total{outcome=hit}', 0)),
            'tokens_saved': int(delta.get(
                'kv_prefix_tokens_saved_total', 0)),
            'cow': int(delta.get('kv_block_cow_total', 0)),
            'peak_blocks': eng.stats()['blocks']['peak_in_use'],
            'prefix_entries_peak': shared_blocks[0],
            'peak_refcount': peak_ref[0],
        }

    on = run(True)
    off = run(False)
    assert on['outs'] == off['outs'], \
        "prefix sharing changed greedy outputs — COW/masking bug"
    full_blocks = system_len // block_size
    return {
        'clients': clients,
        'system_len': system_len,
        'suffix_len': suffix_len,
        'system_full_blocks': full_blocks,
        'prefix_hits': on['hits'],
        'prefill_tokens_saved': on['tokens_saved'],
        'cow_total': on['cow'],
        'peak_refcount_on_shared_blocks': on['peak_refcount'],
        'prefix_entries': on['prefix_entries_peak'],
        'peak_blocks': {'sharing_on': on['peak_blocks'],
                        'sharing_off': off['peak_blocks']},
        'prefill_s_total': {'sharing_on': on['prefill_s_total'],
                            'sharing_off': off['prefill_s_total']},
        'prefill_speedup': round(
            off['prefill_s_total'] / on['prefill_s_total'], 2)
        if on['prefill_s_total'] else None,
        'greedy_parity_on_vs_off': True,
    }


def measure_speculative(rounds=4, sentences=8, slots=8, spec_k=6,
                        new_tokens=48, draft_config=None):
    """Speculative-decode row: the same decode-heavy greedy workload
    through the plain paged engine and the speculative engine, best-of
    `rounds` minima on both sides (interleaved — this box's load comes
    in phases). Default draft is the target itself (accept rate 1.0 by
    construction — the upper bound of the draft-quality axis, and the
    honest measure of the WINDOW mechanics: one drafter dispatch + one
    wide verify replacing spec_k + 1 sequential steps). `draft_config`
    (LMConfig kwargs dict) swaps in a fresh-initialized draft instead.

    The `chunked_prefill` sub-dict drives prompts LONGER than the
    widest warmup bucket through the same engine geometry and pins the
    continuation bit-exact against a single-shot wide-bucket
    reference — the admission-limit lift costs zero new signatures."""
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.models.transformer import LMConfig
    from paddle_tpu.serving import GenerateConfig, GenerateEngine

    base = _decode_lm()
    rng = np.random.RandomState(0)
    p_lens = (4, 7, 12, 16)
    work = [(rng.randint(2, 256, size=p_lens[i % len(p_lens)])
             .astype('int64'), new_tokens) for i in range(sentences)]
    total = sum(n for _, n in work)
    kw = dict(model=base, slots=slots, max_len=96,
              prompt_buckets=[8, 16, 32], eos_id=None, max_new_tokens=64,
              seed=0, queue_cap=sentences + 2, block_size=16)
    draft = LMConfig(**dict(dict(vocab_size=base.vocab_size,
                                 seq_len=base.seq_len), **draft_config)) \
        if draft_config else None

    plain = GenerateEngine(GenerateConfig(**kw))
    plain.warmup()
    spec = GenerateEngine(GenerateConfig(speculative=True, spec_k=spec_k,
                                         draft_model=draft, **kw))
    warm = spec.warmup()

    def drive(eng):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new_tokens=n, deadline_s=120.0)
                for p, n in work]
        outs = [list(r.result(120)) for r in reqs]
        return time.perf_counter() - t0, outs

    plain.start()
    spec.start()
    try:
        drive(plain), drive(spec)               # warm both loops
        before = monitor.counters()
        tb = ts = float('inf')
        outs_p = outs_s = None
        for _ in range(rounds):                  # interleaved minima
            t, outs_p = drive(plain)
            tb = min(tb, t)
            t, outs_s = drive(spec)
            ts = min(ts, t)
        delta = monitor.counter_delta(before)
    finally:
        plain.stop()
        spec.stop()
    miss = sum(v for k, v in delta.items()
               if k.startswith('compile_cache_miss'))
    st = spec.stats()['spec']

    # --- chunked prefill: prompts past the widest bucket --------------
    long_p = rng.randint(2, 256, size=56).astype('int64')   # > bucket 32
    wide = GenerateEngine(GenerateConfig(
        model=base, slots=slots, max_len=96, prompt_buckets=[64],
        eos_id=None, seed=0, block_size=16))
    ref = wide.generate_once(long_p, max_new_tokens=16)
    chunk = GenerateEngine(GenerateConfig(**kw))
    chunk.warmup()
    t0 = time.perf_counter()
    with chunk:
        creq = chunk.submit(long_p, max_new_tokens=16, deadline_s=120.0)
        cout = list(creq.result(120))
    chunk_s = time.perf_counter() - t0

    return {
        'sentences': sentences,
        'tokens_generated': total,
        'spec_k': spec_k,
        'draft': 'target' if draft is None else 'custom',
        'plain_tokens_per_sec': round(total / tb, 1),
        'spec_tokens_per_sec': round(total / ts, 1),
        'speculative': {
            'vs_plain_tokens_per_sec': round(tb / ts, 2),
            'accept_rate': st['accept_rate'],
            'proposed': st['proposed'],
            'accepted': st['accepted'],
            'rounds': st['rounds'],
            'greedy_parity': outs_p == outs_s,
            'recompiles_after_warmup': int(miss),
            'warmup': warm,
        },
        'chunked_prefill': {
            'prompt_len': int(long_p.size),
            'widest_bucket': 32,
            'admitted': creq.finish_reason is not None,
            'bitexact_vs_single_shot': cout == ref,
            'wall_s': round(chunk_s, 3),
        },
        'rounds': rounds,
        'config': 'lm v%d d%d h%d L%d slots%d maxlen%d' % (
            base.vocab_size, base.d_model, base.n_head, base.n_layer,
            slots, 96),
    }


def measure_fleet(high_clients=3, low_clients=3, requests_per_client=40,
                  deadline_ms=2000.0, low_quota=8):
    """Returns the serving_fleet row dict: an fp32 model AND its PTQ-int8
    variant resident in ONE `ModelFleet`, a goodput-priced `Router` in
    front, and a mixed-priority workload driving both at once:

    - premium tenant (priority 10, per-request deadline) runs CLOSED-LOOP
      clients against the fp32 model; every admitted request must
      complete, and p99 under the deadline is the headline.
    - batch tenant (priority 0, `max_outstanding` quota) FLOODS the int8
      model open-loop; overload sheds structured (tenant_quota) instead
      of queueing unboundedly — shed count proves the policy bit.
    - mid-bench, a hot-swap redeploys the premium model (v2 artifact)
      UNDER the live closed loop. The zero-downtime contract:
      `dropped_inflight == 0` (no premium request fails across the flip)
      and `recompiles_after_warmup == 0` (the v2 warmup reuses the
      warmfarm's AOT executables — same program structure, cache-hit
      warm).
    - admission prices come from LIVE `goodput.cost_estimate` — the row
      carries the measured device-seconds per dispatch per model, primed
      by a handful of direct requests before the window opens.
    """
    import paddle_tpu as fluid  # noqa: F401 — predictor deps
    from paddle_tpu import monitor
    from paddle_tpu.serving import (LoadShedError, ModelFleet, Router,
                                    TenantConfig)

    tmp = tempfile.mkdtemp(prefix='fleetbench_')
    d_fp32_v1 = os.path.join(tmp, 'fp32_v1')
    d_fp32_v2 = os.path.join(tmp, 'fp32_v2')
    d_int8 = os.path.join(tmp, 'int8')
    feed_name, width = _build_model(d_fp32_v1)
    _build_model(d_fp32_v2)
    _build_int8_model(d_int8)

    reqs = _mixed_requests(feed_name, width, 64)
    warm = {feed_name: reqs[0][feed_name][:1]}
    cfg_kw = dict(max_batch_size=16, max_wait_ms=1.0, num_workers=2,
                  queue_cap=256)
    deadline_s = deadline_ms / 1e3

    fleet = ModelFleet()
    before_all = monitor.counters()
    try:
        fleet.deploy('fleet_fp32', d_fp32_v1, warm_feed=warm, **cfg_kw)
        fleet.deploy('fleet_int8', d_int8, warm_feed=warm, **cfg_kw)
        int8_loaded = sum(
            v for k, v in monitor.counter_delta(before_all).items()
            if k.startswith('quantized_program_total') and 'loaded' in k)

        router = Router(fleet, tenants={
            'premium': TenantConfig('fleet_fp32', priority=10,
                                    deadline_s=deadline_s,
                                    slo_ms=deadline_ms / 2),
            'batch': TenantConfig('fleet_int8', priority=0,
                                  deadline_s=30.0,
                                  max_outstanding=low_quota),
        })
        # prime the live cost estimates — the router admits-and-learns
        # at default_cost_s until goodput has dispatches for a model
        for r in reqs[:6]:
            fleet.run('fleet_fp32', r, timeout=10.0)
            fleet.run('fleet_int8', r, timeout=10.0)

        lock = threading.Lock()
        hi_lat, hi_err, hi_n = [], [0], [0]
        lo_ok, lo_err, lo_shed, lo_sub = [0], [0], [0], [0]
        half = threading.Event()
        swap_done = threading.Event()
        swap_result = {}
        t_end = time.monotonic() + 60.0
        barrier = threading.Barrier(high_clients + low_clients + 1)

        def premium_client(cid):
            barrier.wait()
            n = 0
            # closed loop, one request in flight per client; clients keep
            # looping until the hot-swap lands so the flip happens UNDER
            # live deadline traffic (t_end backstops a stuck swap)
            while (n < requests_per_client or not swap_done.is_set()) \
                    and time.monotonic() < t_end:
                t0 = time.perf_counter()
                try:
                    f = router.submit('premium', reqs[n % len(reqs)])
                    f.result(deadline_s)
                except Exception:   # noqa: BLE001 — any failure counts
                    with lock:
                        hi_err[0] += 1
                else:
                    with lock:
                        hi_lat.append(time.perf_counter() - t0)
                n += 1
                if cid == 0 and n == max(1, requests_per_client // 2):
                    half.set()
            with lock:
                hi_n[0] += n

        def batch_client(cid):
            barrier.wait()
            futs = []
            for i in range(requests_per_client * 3):
                try:
                    futs.append(router.submit(
                        'batch', reqs[(cid + i) % len(reqs)]))
                except LoadShedError:
                    with lock:
                        lo_shed[0] += 1
                except Exception:   # noqa: BLE001
                    with lock:
                        lo_err[0] += 1
            with lock:
                lo_sub[0] += requests_per_client * 3
            for f in futs:
                try:
                    f.result(30.0)
                except Exception:   # noqa: BLE001
                    with lock:
                        lo_err[0] += 1
                else:
                    with lock:
                        lo_ok[0] += 1

        def swapper():
            half.wait(30.0)
            try:
                swap_result.update(fleet.deploy(
                    'fleet_fp32', d_fp32_v2, warm_feed=warm, **cfg_kw))
            except Exception as e:  # noqa: BLE001 — reported in the row
                swap_result['error'] = '%s: %s' % (type(e).__name__, e)
            finally:
                swap_done.set()

        before = monitor.counters()
        threads = [threading.Thread(target=premium_client, args=(c,),
                                    daemon=True)
                   for c in range(high_clients)]
        threads += [threading.Thread(target=batch_client, args=(c,),
                                     daemon=True)
                    for c in range(low_clients)]
        sw = threading.Thread(target=swapper, daemon=True)
        for t in threads:
            t.start()
        sw.start()
        barrier.wait()
        for t in threads:
            t.join(90.0)
        sw.join(90.0)
        delta = monitor.counter_delta(before)
        miss = sum(v for k, v in delta.items()
                   if k.startswith('compile_cache_miss'))
        rstats = router.stats()
        fstats = fleet.stats()
    finally:
        fleet.stop()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)

    lat = sorted(hi_lat)
    p99 = 1e3 * (_quantile(lat, 0.99) or 0)
    costs = {m: (c or {}).get('device_s_per_dispatch')
             for m, c in (rstats.get('costs') or {}).items()}
    return {
        'models': {
            name: {'version': m['version'],
                   'resident_bytes': m['resident_bytes'],
                   'cost_s_per_dispatch': costs.get(name)}
            for name, m in fstats['models'].items()},
        'high_priority': {
            'clients': high_clients,
            'requests': hi_n[0],
            'ok': len(hi_lat),
            'errors': hi_err[0],
            'p50_ms': round(1e3 * (_quantile(lat, 0.5) or 0), 2),
            'p99_ms': round(p99, 2),
            'deadline_ms': deadline_ms,
            'p99_under_deadline': bool(lat) and p99 < deadline_ms,
        },
        'low_priority': {
            'clients': low_clients,
            'submitted': lo_sub[0],
            'ok': lo_ok[0],
            'errors': lo_err[0],
            'shed': lo_shed[0],
            'quota': low_quota,
        },
        'hot_swap': {
            'performed': swap_result.get('swapped', False),
            'result': swap_result,
            'dropped_inflight': hi_err[0],
        },
        'recompiles_after_warmup': int(miss),
        'int8_programs_loaded': int(int8_loaded),
        'tenants': rstats.get('tenants'),
    }


if __name__ == '__main__':
    argv = [a for a in sys.argv[1:]]
    draft_cfg = None
    spec_k = 6
    if '--draft-config' in argv:
        i = argv.index('--draft-config')
        draft_cfg = json.loads(argv[i + 1])
        del argv[i:i + 2]
    if '--spec-k' in argv:
        i = argv.index('--spec-k')
        spec_k = int(argv[i + 1])
        del argv[i:i + 2]
    if (draft_cfg is not None or spec_k != 6) and \
            '--speculative' not in argv:
        raise SystemExit(
            "--spec-k / --draft-config only apply to --speculative — "
            "they would be silently ignored by this mode")
    if '--generate' in argv:
        argv.remove('--generate')
        n = int(argv[0]) if argv else 3
        print(json.dumps(measure_generate(rounds=n)))
    elif '--shared-prefix' in argv:
        argv.remove('--shared-prefix')
        n = int(argv[0]) if argv else 8
        print(json.dumps(measure_shared_prefix(clients=n)))
    elif '--speculative' in argv:
        argv.remove('--speculative')
        n = int(argv[0]) if argv else 4
        print(json.dumps(measure_speculative(rounds=n, spec_k=spec_k,
                                             draft_config=draft_cfg)))
    elif '--fleet' in argv:
        argv.remove('--fleet')
        n = int(argv[0]) if argv else 40
        print(json.dumps(measure_fleet(requests_per_client=n)))
    else:
        n = int(argv[0]) if argv else 5
        print(json.dumps(measure_serving(rounds=n)))
