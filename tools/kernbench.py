"""Per-op fused-vs-unfused microbench for the kernel tier.

For each fused unit (softmax_ce / fused_adam / embedding_gather /
layernorm_residual / ffn_tail / ln_sites — the last two are the PR 16
FFN-tail epilogue and the block-entry/final-LN residual-threading
sites) this builds a small program that isolates the op,
compiles it under each requested PADDLE_FUSED_TIER, and reports
steady-state wall time (best-of-rounds minima over k dispatches — the
box-noise protocol from BASELINE notes) next to the XLA cost-analysis
columns mined from the analysis registry (flops / bytes_accessed per
compiled program), so a tier's win or loss shows up with its bandwidth
story attached.

``--mesh N`` runs every case SPMD over a mesh(data=N) MeshRunner — the
fused units then dispatch their PARTITIONED (shard_map) kernels, so
fused-vs-unfused numbers exist for the sharded case too (the
``fused_kernel_dispatch_total{...,mesh=n}`` counter rows prove which
impl actually ran). Needs >= N local devices; as a CLI this file forces
an 8-device virtual CPU host when no accelerator is attached.

Usage: python tools/kernbench.py [--tiers off,xla,interpret]
       [--cases softmax_ce,fused_adam,embedding_gather,
                layernorm_residual,ffn_tail,ln_sites]
       [--rounds 5] [--size small|bench] [--mesh N]
       (prints one JSON line)

On CPU the 'pallas' tier runs through the interpreter (pass 'interpret');
its wall time is NOT meaningful — the interpret rows exist to check the
kernels dispatch and to carry the analytics columns. Real pallas timing
needs the TPU box (tools/tpu_smoke.py environment).
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build_softmax_ce(size):
    import numpy as np
    import paddle_tpu as fluid
    n, v = (256, 512) if size == 'small' else (4096, 32000)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='kx', shape=[v], dtype='float32')
        y = fluid.layers.data(name='ky', shape=[1], dtype='int64')
        # a [v] bias parameter makes the backward run THROUGH the CE unit
        # without adding a matmul that would swamp the measurement
        b = fluid.layers.create_parameter([v], 'float32')
        logits = fluid.layers.elementwise_add(x, b)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'kx': rng.randn(n, v).astype('float32'),
            'ky': rng.randint(0, v, (n, 1)).astype('int64')}
    return main, startup, feed, loss


def _build_fused_adam(size):
    import numpy as np
    import paddle_tpu as fluid
    d = 64 if size == 'small' else 1024
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='ax', shape=[d], dtype='float32')
        h = x
        for _ in range(4):
            h = fluid.layers.fc(h, size=d, act='relu')
        loss = fluid.layers.mean(h)
        fluid.optimizer.Adam(1e-3, fuse=True).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'ax': rng.randn(32, d).astype('float32')}
    return main, startup, feed, loss


def _build_embedding_gather(size):
    import numpy as np
    import paddle_tpu as fluid
    v, d, n = (1024, 128, 512) if size == 'small' else (100000, 256, 8192)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        ids = fluid.layers.data(name='ei', shape=[1], dtype='int64')
        emb = fluid.layers.embedding(ids, size=[v, d])
        out = fluid.layers.reduce_sum(emb)
    rng = np.random.RandomState(0)
    feed = {'ei': rng.randint(0, v, (n, 1)).astype('int64')}
    return main, startup, feed, out


def _build_layernorm_residual(size):
    import numpy as np
    import paddle_tpu as fluid
    n, d = (256, 128) if size == 'small' else (4096, 1024)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='lx', shape=[d], dtype='float32')
        # a linear branch gives the pair a real residual input and routes
        # the backward through both of the op's outputs
        h = fluid.layers.fc(x, size=d)
        y, s = fluid.layers.fused_layer_norm_residual(x, h,
                                                      begin_norm_axis=1)
        loss = fluid.layers.mean(fluid.layers.elementwise_add(y, s))
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'lx': rng.randn(n, d).astype('float32')}
    return main, startup, feed, loss


def _build_ffn_tail(size):
    import numpy as np
    import paddle_tpu as fluid
    n, d, d_ff = (2048, 128, 512) if size == 'small' else (4096, 1024, 4096)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.layers.data(name='fx', shape=[d], dtype='float32')
        # the whole FFN sublayer as one op; tier 'off' lowers the
        # unfused fc->gelu->fc composition — the vs_off column IS the
        # fused-vs-unfused story. Train-mode dropout included so the
        # fused epilogue (mask multiply) is part of what gets timed.
        out = fluid.layers.fused_ffn_tail(x, d_ff, d, num_flatten_dims=1,
                                          dropout_prob=0.1, is_test=False)
        loss = fluid.layers.mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'fx': rng.randn(n, d).astype('float32')}
    return main, startup, feed, loss


def _build_ln_sites(size):
    import numpy as np
    import paddle_tpu as fluid
    n, d = (256, 128) if size == 'small' else (4096, 1024)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        # the PR 16 residual-threading sites: a block-ENTRY ln1
        # resolving the previous block's pending FFN delta, then a
        # final_ln resolving the last delta — two chained
        # residual-add + LN pairs on one stream, exactly the shape the
        # LM/BERT towers lower after the deferral rewrite
        x = fluid.layers.data(name='sx', shape=[d], dtype='float32')
        delta = fluid.layers.fc(x, size=d)
        ln1, stream = fluid.layers.fused_layer_norm_residual(
            x, delta, begin_norm_axis=1)
        delta2 = fluid.layers.fc(ln1, size=d)
        final, _ = fluid.layers.fused_layer_norm_residual(
            stream, delta2, begin_norm_axis=1)
        loss = fluid.layers.mean(final)
        fluid.optimizer.SGD(0.1).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {'sx': rng.randn(n, d).astype('float32')}
    return main, startup, feed, loss


_CASES = {
    'softmax_ce': _build_softmax_ce,
    'fused_adam': _build_fused_adam,
    'embedding_gather': _build_embedding_gather,
    'layernorm_residual': _build_layernorm_residual,
    'ffn_tail': _build_ffn_tail,
    'ln_sites': _build_ln_sites,
}


def _measure(build, tier, rounds, k, size, mesh_n=1):
    import numpy as np
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import analysis

    prev = os.environ.get('PADDLE_FUSED_TIER')
    if tier is None:
        os.environ.pop('PADDLE_FUSED_TIER', None)
    else:
        os.environ['PADDLE_FUSED_TIER'] = tier
    try:
        main, startup, feed, fetch = build(size)
        exe = fluid.Executor(fluid.TPUPlace(0))
        scope = fluid.Scope()
        runner = None
        if mesh_n and mesh_n > 1:
            if len(jax.devices()) < mesh_n:
                raise RuntimeError(
                    'mesh=%d needs %d local devices, have %d'
                    % (mesh_n, mesh_n, len(jax.devices())))
            from jax.sharding import PartitionSpec as P
            from paddle_tpu.parallel import make_mesh, MeshRunner
            mesh = make_mesh([('data', mesh_n)])
            runner = MeshRunner(main, mesh,
                                feed_specs={n: P('data') for n in feed})

        def run_step(return_numpy=True):
            if runner is not None:
                return runner.run(feed, [fetch], scope,
                                  return_numpy=return_numpy)
            return exe.run(main, feed=feed, fetch_list=[fetch],
                           scope=scope, return_numpy=return_numpy)

        with fluid.scope_guard(scope):
            t0 = time.time()
            exe.run(startup, scope=scope)
            out = run_step()
            jax.block_until_ready(
                [np.asarray(o, copy=False) if not hasattr(o, 'block_until_ready')
                 else o for o in out])
            compile_s = time.time() - t0
            best = float('inf')
            for _ in range(rounds):
                t0 = time.time()
                for _ in range(k):
                    out = run_step(return_numpy=False)
                jax.block_until_ready(list(out))
                best = min(best, (time.time() - t0) / k)
        row = {'wall_us': round(best * 1e6, 1),
               'compile_s': round(compile_s, 3)}
        rec = analysis.lookup(main)
        if rec is not None and rec.flops is not None:
            row['flops'] = rec.flops
            row['bytes_accessed'] = rec.bytes_accessed
        return row
    finally:
        if prev is None:
            os.environ.pop('PADDLE_FUSED_TIER', None)
        else:
            os.environ['PADDLE_FUSED_TIER'] = prev


def measure_kernbench(cases=None, tiers=None, rounds=5, k=10,
                      size='small', mesh=1):
    """Importable entry (the tier-1 smoke test runs one tiny case;
    ``mesh=N`` runs every case through a mesh(data=N) MeshRunner so the
    partitioned fused kernels are what gets timed)."""
    from paddle_tpu import monitor
    cases = list(cases or _CASES)
    tiers = list(tiers or ['off', 'xla', 'interpret'])
    out = {}
    for case in cases:
        out[case] = {}
        for tier in tiers:
            before = monitor.counters()
            try:
                out[case][tier] = _measure(_CASES[case], tier, rounds, k,
                                           size, mesh_n=mesh)
            except Exception as e:      # noqa: BLE001 — advisory tool
                out[case][tier] = {'error': '%s: %s' % (
                    type(e).__name__, str(e)[:200])}
            if mesh and mesh > 1:
                # which impl ACTUALLY ran under the mesh — the sharded
                # rows' proof (fused_kernel_dispatch_total{...,mesh=n})
                out[case][tier]['mesh_dispatch'] = {
                    kk: v for kk, v in
                    monitor.counter_delta(before).items()
                    if kk.startswith('fused_kernel_dispatch_total')
                    and 'mesh=n' in kk}
        off = out[case].get('off', {}).get('wall_us')
        for tier, row in out[case].items():
            if off and row.get('wall_us'):
                row['vs_off'] = round(off / row['wall_us'], 3)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--cases', default=','.join(_CASES))
    ap.add_argument('--tiers', default='off,xla,interpret')
    ap.add_argument('--rounds', type=int, default=5)
    ap.add_argument('--k', type=int, default=10)
    ap.add_argument('--size', default='small',
                    choices=('small', 'bench'))
    ap.add_argument('--mesh', type=int, default=1,
                    help='run each case SPMD over mesh(data=N)')
    args = ap.parse_args()
    if args.mesh > 1 and 'jax' not in sys.modules and \
            '--xla_force_host_platform_device_count' not in \
            os.environ.get('XLA_FLAGS', ''):
        # CLI convenience: a virtual multi-device CPU host (must happen
        # before jax initializes). The flag only shapes the CPU backend:
        # on a TPU host set JAX_PLATFORMS=cpu too, or this process takes
        # the chip
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '') +
            ' --xla_force_host_platform_device_count=%d'
            % max(8, args.mesh)).strip()
    res = measure_kernbench(args.cases.split(','), args.tiers.split(','),
                            args.rounds, args.k, args.size,
                            mesh=args.mesh)
    print(json.dumps(res))


if __name__ == '__main__':
    main()
