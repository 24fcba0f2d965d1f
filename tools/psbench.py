"""Parameter-server CTR micro-bench: PS-resident table, overlap on/off.

Measures the contract docs/parameter_server.md makes for the prefetch
overlap (ps/worker.py `PSTrainerSession.train`): on the ctr_sharded_v1m
shape (vocab 2^20, dim 32, 26 slots — the table is PS-RESIDENT on live
socket shards, the trainer process never holds [2^20, 32]) the
overlapped loop hides the host half of every step — the next batch's
row pull (crc32 sharding + 2 shard RPCs + row reassembly) and the
previous step's grad push — behind the device step, while the
non-overlapped loop pays host + device serially. Reported:

- samples_per_sec_no_overlap: pull -> run -> push, serialized
  (``train(overlap=False)`` — the trajectory-exact mode);
- samples_per_sec_overlap:    ``train(overlap=True)`` — staleness-1
  prefetch riding the executor's bounded async window;
- speedup (contract: > 1 — the pull wait is real and the overlap hides
  it), pull/push counter + byte deltas, rows resident per shard, and
  recompiles_after_warmup (contract: 0 — the rows feed [batch*slots,
  dim] is shape-stable, so the PS path compiles exactly once).

Both modes run the same pre-generated batches from the same loaded
table state; best-of-`rounds` minima on both sides (this box's noise
calls for comparing minima — see BASELINE notes).

Usage: python tools/psbench.py [rounds]        (prints one JSON line)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB, DIM, SLOTS = 1 << 20, 32, 26


def _build_ctr(hidden=400):
    import paddle_tpu as fluid
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            ids = fluid.layers.data(name='ids', shape=[SLOTS],
                                    dtype='int64')
            label = fluid.layers.data(name='label', shape=[1],
                                      dtype='float32')
            emb = fluid.layers.embedding(
                input=fluid.layers.reshape(ids, [-1, SLOTS, 1]),
                size=[VOCAB, DIM], is_sparse=True, is_distributed=True)
            flat = fluid.layers.reshape(emb, [-1, SLOTS * DIM])
            h = fluid.layers.fc(flat, size=hidden, act='relu')
            h = fluid.layers.fc(h, size=hidden, act='relu')
            p = fluid.layers.fc(h, size=1, act='sigmoid')
            loss = fluid.layers.mean(fluid.layers.log_loss(p, label))
            fluid.optimizer.Adam(0.001).minimize(loss)
    return main, startup, loss


def measure_ctr_ps(rounds=3, n_batches=12, batch=512, num_shards=2):
    """Returns the ctr_ps bench row (importable)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu import monitor, ps

    main, startup, loss = _build_ctr()
    t = fluid.transpiler.DistributeTranspiler()
    eps = ['127.0.0.1:0'] * num_shards
    t.transpile(0, program=main, pservers=eps, startup_program=startup,
                mode='pserver')
    servers = [t.get_pserver_programs(e).serve(port=0) for e in eps]
    client = ps.PSClient(endpoints=[s.endpoint for s in servers])
    table = list(t.ps_info.tables)[0]

    rng = np.random.RandomState(0)
    batches = [{'ids': rng.randint(0, VOCAB,
                                   (batch, SLOTS)).astype('int64'),
                'label': rng.randint(0, 2, (batch, 1)).astype('float32')}
               for _ in range(n_batches)]

    exe = fluid.Executor(fluid.TPUPlace(0))

    def fresh():
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(t.get_startup_program(), scope=scope)
        return ps.PSTrainerSession(exe, main, client, scope=scope)

    def run_mode(overlap):
        sess = fresh()
        try:
            with fluid.scope_guard(sess.scope):
                t0 = time.perf_counter()
                outs = sess.train(batches, fetch_list=[loss],
                                  overlap=overlap)
                dt = time.perf_counter() - t0
            last = float(np.asarray(outs[-1][0]).reshape(-1)[0])
        finally:
            sess.close(close_client=False)
        return dt, last

    try:
        # un-timed warmup: compiles the one PS step signature (run and
        # run_async stage feeds identically here) and materializes the
        # touched rows server-side, so every timed round re-touches
        # resident rows — steady-state training, not first-touch fill
        run_mode(False)
        run_mode(True)
        before = monitor.counters()
        sync_best = over_best = None
        last_loss = None
        for _ in range(max(1, rounds)):
            dt, last_loss = run_mode(False)
            sync_best = dt if sync_best is None else min(sync_best, dt)
            dt, _ = run_mode(True)
            over_best = dt if over_best is None else min(over_best, dt)
        delta = monitor.counter_delta(before)
        stats = client.stats()
        rows_resident = {
            'shard%d' % s: sum(tt['rows_resident']
                               for tt in stats[s].values())
            for s in sorted(stats)}
        n_samples = n_batches * batch
        return {
            'steps': n_batches,
            'batch': batch,
            'rounds': rounds,
            'num_shards': num_shards,
            'table': '%s v%d d%d (PS-resident)' % (table, VOCAB, DIM),
            'samples_per_sec_no_overlap': round(n_samples / sync_best, 1),
            'samples_per_sec_overlap': round(n_samples / over_best, 1),
            'speedup': round(sync_best / over_best, 3),
            'final_loss': round(last_loss, 4),
            'rows_resident': rows_resident,
            'ps_pull_total': delta.get('ps_pull_total{table=%s}' % table,
                                       0),
            'ps_push_total': delta.get('ps_push_total{table=%s}' % table,
                                       0),
            'ps_pull_rows_total': delta.get('ps_pull_rows_total', 0),
            'ps_push_rows_total': delta.get('ps_push_rows_total', 0),
            'ps_pull_mb': round(delta.get('ps_pull_bytes', 0) / 1e6, 1),
            'ps_push_mb': round(delta.get('ps_push_bytes', 0) / 1e6, 1),
            'recompiles_after_warmup': int(delta.get('compile_cache_miss',
                                                     0)),
        }
    finally:
        client.close()
        for s in servers:
            s.close()


if __name__ == '__main__':
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    print(json.dumps(measure_ctr_ps(rounds=n)))
