"""Per-op fused-vs-unfused microbench for the kernel tier.

For each fused unit (softmax_ce / fused_adam / layernorm_residual /
ffn_tail / ln_sites — the last two are the PR 16 FFN-tail epilogue and
the block-entry/final-LN residual-threading sites) this builds a small
program that isolates the op,
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

The `embedding_gather` case is not a tier comparison: the lookup has
one lowering (XLA's gather, ops/embedding_ops.py). It times that gather
and the in-place DMA kernel it was chosen over (`gather_in_place`, kept
here only) alone, at the benchmark cells' tables and row counts with
`--size bench`: ms a call and GB/s, a candidate a column.

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


def gather_in_place(w, flat_ids, interpret=False, ring=16):
    """The lookup's OTHER candidate (PERF.md, PR 33), kept here to be
    measured against XLA's gather, which `ops/embedding_ops.py` runs: a
    Pallas kernel that leaves the table an HBM ref and fetches each row
    by its own DMA, `ring` in flight. Mosaic refuses a one-row slice of
    an (8, 128)-tiled ref ("Slice shape along dimension 0 must be
    aligned to tiling (8)"), so a DMA takes the row's whole 8-row tile
    group into VMEM and the kernel copies the one row out: 8 times the
    bytes. V % 8 == 0, D % 128 == 0, float32."""
    import jax
    import jax.numpy as jnp
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, d = flat_ids.shape[0], w.shape[1]
    rows = 8 if n % 8 == 0 else n           # an output block
    ring = min(ring, n)

    def kernel(ids_ref, w_hbm, o_ref, buf, sems):
        g = pl.program_id(0)

        def fetch(i):
            group = pl.multiple_of(ids_ref[i] // 8 * 8, 8)
            return pltpu.make_async_copy(
                w_hbm.at[pl.ds(group, 8), :], buf.at[i % ring],
                sems.at[i % ring])

        @pl.when(g == 0)
        def _():
            for i in range(ring):
                fetch(i).start()

        for j in range(rows):
            i = g * rows + j
            fetch(i).wait()
            o_ref[pl.ds(j, 1), :] = buf[i % ring,
                                        pl.ds(ids_ref[i] % 8, 1), :]

            @pl.when(i + ring < n)
            def _():
                fetch(i + ring).start()

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, d), lambda g, ids: (g, 0)),
            scratch_shapes=[pltpu.VMEM((ring, 8, d), w.dtype),
                            pltpu.SemaphoreType.DMA((ring,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), w.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name='embedding_gather_in_place',
    )(jnp.clip(flat_ids.astype(jnp.int32), 0, w.shape[0] - 1), w)


# the cells' `tok_emb.w` (float32) and the rows a dispatch looks up: a
# decode step's 4 / 16 / 32 / 64 slots, a prefill bucket, a train step
GATHER_TABLES = {
    'small': {'toy': (1024, 128)},
    'bench': {'fd355m chat, train-2k': (50264, 1024),
              'fd1.3b doc, train-4chip': (50264, 2048),
              'olmoe': (50304, 2048),
              'joyai': (129280, 2048)}}
GATHER_ROWS = {'small': (4, 64), 'bench': (4, 32, 64, 2048, 8192)}


def gather_candidates():
    import functools
    import jax
    from paddle_tpu.ops.embedding_ops import embedding_gather
    return {'xla_gather': embedding_gather,
            'dma_in_place': functools.partial(
                gather_in_place,
                interpret=jax.default_backend() != 'tpu')}


def measure_embedding_gather(size, rounds, k, candidates=None):
    """The lookup alone, each candidate at each of the cells' tables and
    row counts: ms a call and GB/s of rows moved (read + written), best
    of `rounds` runs of ONE program that makes `k` dependent calls (ids
    shifted by the loop index, and by a bit of the call before, so
    neither the host's dispatch nor a hoisted gather is what is timed).
    Every candidate's rows are checked against numpy.take."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    candidates = candidates or gather_candidates()
    out = {}
    rng = np.random.RandomState(0)
    for label, (v, d) in GATHER_TABLES[size].items():
        w = jax.random.normal(jax.random.PRNGKey(0), (v, d), jnp.float32)
        for n in GATHER_ROWS[size]:
            ids = jnp.asarray(rng.randint(0, v, n).astype('int32'))
            want = np.take(np.asarray(w), np.asarray(ids), axis=0)
            row = out.setdefault('%s [%d, %d]' % (label, v, d), {}) \
                .setdefault('rows=%d' % n, {})
            for name, fn in candidates.items():
                try:
                    def calls(w, ids, fn=fn):
                        def body(i, acc):
                            moved = (acc[0, 0] != acc[0, 0]).astype(ids.dtype)
                            return fn(w, (ids + i + moved) % v)
                        return lax.fori_loop(0, k, body, fn(w, ids))
                    np.testing.assert_array_equal(
                        np.asarray(jax.jit(fn)(w, ids)), want)
                    loop = jax.jit(calls)
                    loop(w, ids).block_until_ready()
                    best = float('inf')
                    for _ in range(rounds):
                        t0 = time.perf_counter()
                        loop(w, ids).block_until_ready()
                        best = min(best, (time.perf_counter() - t0) / (k + 1))
                    row[name] = {'ms': round(best * 1e3, 4),
                                 'gb_per_s': round(2 * n * d * 4 / best / 1e9,
                                                   2)}
                except Exception as e:      # noqa: BLE001 — advisory tool
                    row[name] = {'error': '%s: %s' % (
                        type(e).__name__, str(e)[:200])}
    return out


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
    'layernorm_residual': _build_layernorm_residual,
    'ffn_tail': _build_ffn_tail,
    'ln_sites': _build_ln_sites,
}
# every case by name: the tier comparisons and the lookup's candidates
_CASE_NAMES = list(_CASES) + ['embedding_gather']


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
    cases = list(cases or _CASE_NAMES)
    tiers = list(tiers or ['off', 'xla', 'interpret'])
    out = {}
    for case in cases:
        if case == 'embedding_gather':      # candidates, not tiers
            out[case] = measure_embedding_gather(size, rounds, k)
            continue
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
    ap.add_argument('--cases', default=','.join(_CASE_NAMES))
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
