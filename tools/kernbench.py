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

The `grouped_matmul` case is none either: `jax.lax.ragged_dot` alone (the
expert matmul of `ops/moe_ops.py`'s `grouped_ffn`) at the expert cells'
shapes and group sizes with `--size bench`, a tiling a column — XLA's
own choice, the rule's (`grouped_matmul_tiling`) and the candidates of
`--tilings`: ms a call, GB/s of the weights of the groups that hold a row,
and the largest error against a float64 product. The sweep that set the
rule's constants (PERF.md, PR 50), to be run again on another chip or
another libtpu.

The `flash_attention` case is the third of that kind: the three kernels
of `ops/attention_ops.py` each alone (forward, dQ, dKV), the
`custom_vjp`'s kernels behind one another and the whole between the fused
QKV product and `attn.proj`, at the train cells' shapes with `--size
bench` (`bh` 64 / 32, L 2048, `dh` 64, bfloat16, causal; `dh` 128 beside
them), a tiling a column PAIR -- the rule's (`flash_attention_tiling`) and
the `bq,bk` candidates of `--tilings`, each with head-major operands and
with the packed product (PR 57): ms a call and TFLOP/s over the causal
FLOPs (2 matmuls forward, 5 backward, half of L x L). The installed
JAX's `pallas.ops.tpu.flash_attention` is a yardstick column, never a
dependency of the package. The sweep that set the rule's constants
(PERF.md, PR 52) and chose the packed view's head split (PR 57).

The `mla_prefix_attention` case is the fourth: a prefill's expanded latent
attention from the scores on (`ops/mla_ops.py`) at the buckets of
`joyai-serve-longchat64` with `--size bench`, a form a column -- the plain
composition, the op's kernel (two products a key tile), one key of 128 + 64
lanes laid a call, that key padded to 256 -- and each `rows` of `--tilings`
a column of the op's kernel at that query tile: ms a call and TFLOP/s over
the causal half's FLOPs (PERF.md, PR 61).

Usage: python tools/kernbench.py [--tiers off,xla,interpret]
       [--cases softmax_ce,fused_adam,embedding_gather,grouped_matmul,
                flash_attention,mla_prefix_attention,layernorm_residual,
                ffn_tail,ln_sites]
       [--rounds 5] [--size small|bench] [--mesh N]
       [--tilings 64,896,512:32,896,512]   (flash_attention: 512,512:256,256;
                                            mla_prefix_attention: 512:256)
       [--shapes 'nemotron up,nemotron down']
       (prints one JSON line)

On CPU the 'pallas' tier runs through the interpreter (pass 'interpret');
its wall time is NOT meaningful — the interpret rows exist to check the
kernels dispatch and to carry the analytics columns. Real pallas timing
needs the TPU box (tools/tpu_smoke.py environment).
"""
import argparse
import contextlib
import functools
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


# the expert cells' grouped matmuls: (rows GIVEN, groups, K, N, the
# precision the cell's programs multiply at, assignments that fall in a
# group). Rows given = `grouped_ffn`'s `cap` (or all n * k assignments
# where every expert is held); a share of the experts gets its share of
# the assignments, the rest of the rows lie in no group.
GROUPED_MATMULS = {
    'small': {'toy up': (16, 4, 128, 256, 'highest', 8),
              'toy down': (16, 4, 256, 128, None, 16)},
    'bench': {
        # nemotron3-serve-reason128: 16 of 128 experts held, 6 a row; the
        # step and b128 give 768 assignments (cap 256), b256 / b512 cap 384
        # / 640; 'all' the other branch of the cond, '128' a cap of one tile
        'nemotron up': (256, 16, 2688, 1856, 'highest', 96),
        'nemotron down': (256, 16, 1856, 2688, 'highest', 96),
        'nemotron up b256': (384, 16, 2688, 1856, 'highest', 192),
        'nemotron up b512': (640, 16, 2688, 1856, 'highest', 384),
        'nemotron down b512': (640, 16, 1856, 2688, 'highest', 384),
        'nemotron up all': (768, 16, 2688, 1856, 'highest', 96),
        'nemotron up 128': (128, 16, 2688, 1856, 'highest', 96),
        # lfm2-serve-agent64: all 32 experts, 4 a row
        'lfm2 up': (256, 32, 2048, 1792, None, 256),
        'lfm2 down': (256, 32, 1792, 2048, None, 256),
        'lfm2 up b128': (512, 32, 2048, 1792, None, 512),
        'lfm2 up b512': (2048, 32, 2048, 1792, None, 2048),
        'lfm2 down b512': (2048, 32, 1792, 2048, None, 2048),
        # joyai-serve-longchat64: 64 of 256 held, 8 a row
        'joyai up': (256, 64, 2048, 768, None, 128),
        'joyai down': (256, 64, 768, 2048, None, 128),
        'joyai up all': (512, 64, 2048, 768, None, 128),
        'joyai up b512': (1536, 64, 2048, 768, None, 1024),
        'joyai up b2048': (6144, 64, 2048, 768, None, 4096),
        'joyai down b2048': (6144, 64, 768, 2048, None, 4096),
        # kexaone-serve-mixed64: 8 of 128 held, 8 a row
        'kexaone up': (128, 8, 6144, 2048, None, 32),
        'kexaone down': (128, 8, 2048, 6144, None, 32),
        'kexaone up b512': (384, 8, 6144, 2048, None, 256),
        'kexaone up all': (512, 8, 6144, 2048, None, 32),
        # olmoe-serve-chat16: all 64 experts, 8 a row
        'olmoe up': (128, 64, 2048, 1024, None, 128),
        'olmoe down': (128, 64, 1024, 2048, None, 128),
        'olmoe up b128': (1024, 64, 2048, 1024, None, 1024),
        'olmoe down b128': (1024, 64, 1024, 2048, None, 1024),
        'olmoe up b768': (6144, 64, 2048, 1024, None, 6144),
        'olmoe down b768': (6144, 64, 1024, 2048, None, 6144)}}


def measure_grouped_matmul(size, rounds, k, tilings=(), shapes=None):
    """`lax.ragged_dot` alone at each of the cells' shapes, XLA's tiling
    ('xla'), the rule's ('rule: tm,tk,tn', where it states one) and each
    of `tilings` ('tm,tk,tn' strings): ms a call (best of `rounds` runs
    of ONE program that makes `k` dependent calls), GB/s of the weights
    of the groups that hold a row, and the largest |error| against the
    float64 product of the same operands over the rows in a group, as a
    share of the largest |product|. `shapes`: only these labels."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental.xla_metadata import set_xla_metadata
    from paddle_tpu.ops.moe_ops import (grouped_matmul_tiling, matmul_passes,
                                        tiling_label)
    out = {}
    for label, (rows, groups, kk, n, precision, held) in \
            GROUPED_MATMULS[size].items():
        if shapes and label not in shapes:
            continue
        rng = np.random.RandomState(len(label))
        sizes = np.bincount(rng.randint(0, groups, held), minlength=groups)
        x = jax.random.normal(jax.random.PRNGKey(1), (rows, kk), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(2), (groups, kk, n),
                              jnp.float32) * kk ** -0.5
        gs = jnp.asarray(sizes.astype('int32'))
        xh, ends = np.asarray(x, np.float64), np.cumsum(sizes)
        want = np.concatenate(
            [xh[e - c:e] @ np.asarray(w[g], np.float64)
             for g, (c, e) in enumerate(zip(sizes, ends)) if c])
        touched = int(np.count_nonzero(sizes)) * kk * n * 4
        with jax.default_matmul_precision(precision) if precision \
                else contextlib.nullcontext():
            rule = grouped_matmul_tiling(rows, kk, n, matmul_passes())
            named = [('xla', None)]
            if rule:
                named.append(('rule: ' + tiling_label(rule),
                              tiling_label(rule)))
            named += [(t, t) for t in tilings]
            row = out.setdefault('%s %d x [%d, %d, %d] %s' % (
                label, rows, groups, kk, n, precision or 'default'), {})
            for name, tiling in named:
                def dot(x, w, gs, tiling=tiling):
                    with set_xla_metadata(ragged_dot_tiling=tiling) \
                            if tiling else contextlib.nullcontext():
                        return lax.ragged_dot(x, w, gs)

                def calls(x, w, gs, dot=dot):
                    def body(i, acc):
                        # the next call's rows wait for this call's
                        return dot(x + (acc[0, 0] != acc[0, 0]), w, gs)
                    return lax.fori_loop(0, k, body, dot(x, w, gs))
                try:
                    got = np.asarray(jax.jit(dot)(x, w, gs))[:int(ends[-1])]
                    loop = jax.jit(calls)
                    loop(x, w, gs).block_until_ready()
                    best = float('inf')
                    for _ in range(rounds):
                        t0 = time.perf_counter()
                        loop(x, w, gs).block_until_ready()
                        best = min(best, (time.perf_counter() - t0) / (k + 1))
                    row[name] = {
                        'ms': round(best * 1e3, 4),
                        'gb_per_s': round(touched / best / 1e9, 1),
                        'max_err': float(np.max(np.abs(got - want))
                                         / np.max(np.abs(want)))}
                except Exception as e:      # noqa: BLE001 — advisory tool
                    row[name] = {'error': '%s: %s' % (
                        type(e).__name__, str(e)[:200])}
    return out


# the train cells' attention: (batch x heads a chip, L, head size, dtype,
# heads of a batch row -- how the packed column splits batch x heads)
FLASH_SHAPES = {
    'small': {'toy': (2, 256, 64, 'float32', 2)},
    'bench': {'fd355m-train-2k': (64, 2048, 64, 'bfloat16', 16),
              'fd1.3b-train-4chip, a chip': (32, 2048, 64, 'bfloat16', 32),
              'head size 128': (32, 2048, 128, 'bfloat16', 8),
              'fd355m eval forward': (64, 2048, 64, 'float32', 16)}}


def measure_flash_attention(size, rounds, k, tilings=(), shapes=None):
    """The three flash kernels each alone, the `custom_vjp`'s kernels
    behind one another ('vjp': forward + both backward kernels + the delta
    row sum) and the WHOLE of what a layer runs between the fused QKV
    product and `attn.proj` ('whole': [B, L, 3 x H x dh] and dO [B, L, H x
    dh] in, the context and the product's cotangent out), causal, at each
    of the cells' shapes. A tiling a column pair -- the rule's ('rule: bq,bk
    / bq,bk / bq,bk' for forward / dQ / dKV) and each 'bq,bk' of `tilings`
    given to all three -- head-major ([BH, L, dh] operands; its 'whole'
    pays the two transposes each way that the model paid before PR 57) and
    'packed ...' (the kernels read the product itself; its 'whole' pays one
    concatenate of dQ, dK, dV), so both columns time the same work. The
    operands are ARGUMENTS of every timed program (closed over they are
    baked into the executable: PR 52). `jax.experimental.pallas.ops.tpu.
    flash_attention` ('jax') is a yardstick. ms a call (best of `rounds`
    runs of ONE program that makes `k` dependent calls) and TFLOP/s over
    the causal FLOPs: 2 x 2 x L x L x dh / 2 a (batch x head) forward, 5 /
    2 of it backward (dQ 3 matmuls of it, dKV 4: the scores are recomputed
    in both; each of the two also pays the delta row sum, an XLA fusion
    over dO and O). Off the chip the kernels run through the interpreter:
    the times mean nothing there, and the yardstick has no column."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as A
    on_chip = jax.default_backend() == 'tpu'
    interpret = not on_chip
    out = {}
    for label, (bh, ln, dh, dtype, heads) in FLASH_SHAPES[size].items():
        if shapes and label not in shapes:
            continue
        scale = dh ** -0.5
        b = bh // heads
        qkv, do_p = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                       jnp.dtype(dtype))
                     for i, shape in enumerate([(b, ln, 3 * heads * dh),
                                                (b, ln, heads * dh)]))

        def unpack(x):      # [B, L, n x H x dh] -> n of [BH, L, dh]
            x = x.reshape(b, ln, -1, heads, dh).transpose(2, 0, 3, 1, 4)
            return tuple(x.reshape(-1, bh, ln, dh))

        def pack(*xs):      # n of [BH, L, dh] -> [B, L, n x H x dh]
            x = jnp.stack(xs).reshape(-1, b, heads, ln, dh)
            return x.transpose(1, 3, 0, 2, 4).reshape(b, ln, -1)
        q, kk, v = jax.jit(unpack)(qkv)
        do, = jax.jit(unpack)(do_p)
        matmul = 2 * bh * ln * ln * dh / 2          # one causal matmul
        row = out.setdefault('%s [%d, %d, %d] %s' % (label, bh, ln, dh,
                                                     dtype), {})
        rule = {kern: A.flash_attention_tiling(ln, dh, q.dtype, kern)
                for kern in ('fwd', 'bwd_dq', 'bwd_dkv')}
        named = [('rule: ' + ' / '.join('%d,%d' % t[:2]
                                        for t in rule.values()), rule)]
        for t in tilings:
            bq, bk = (int(x) for x in t.split(','))
            named.append((t, {kern: (bq, bk) + r[2:]
                              for kern, r in rule.items()}))
        for name, til in named:
            def fwd(q, kk, v, til=til, **kw):
                return A._flash_fwd_pallas(q, kk, v, scale, True, interpret,
                                           tiling=til['fwd'], **kw)

            def bwd(q, kk, v, o, lse, do, til=til, **kw):
                return A._flash_bwd_pallas(
                    q, kk, v, o, lse, do, scale, True, interpret,
                    tiling_dq=til['bwd_dq'], tiling_dkv=til['bwd_dkv'], **kw)

            def whole(qkv, do_p):
                q, kk, v = unpack(qkv)
                o, lse = fwd(q, kk, v)
                return pack(o), pack(*bwd(q, kk, v, o, lse,
                                          *unpack(do_p)))

            def fwd_p(x):
                return fwd(x, None, None, heads=heads)

            def bwd_p(x, o, lse, do):
                return bwd(x, None, None, o, lse, do, heads=heads)

            def whole_p(x, do):
                o, lse = fwd_p(x)
                return o, jnp.concatenate(bwd_p(x, o, lse, do), axis=-1)
            # a view a column: (its name, the kernels' operands ahead of
            # (o, lse, dO), its forward, backward and whole, its dO)
            views = [(name, (q, kk, v), fwd, bwd, whole, do)]
            if dh in (64, 128):
                views.append(('packed ' + name, (qkv,), fwd_p, bwd_p,
                              whole_p, do_p))
            for col, x, f, b_, w, d in views:
                n = len(x)
                try:
                    o, lse = jax.jit(f)(*x)
                except Exception as e:      # noqa: BLE001 -- advisory tool
                    row[col] = {'error': '%s: %s' % (type(e).__name__,
                                                     str(e)[:200])}
                    continue
                # XLA drops the kernel whose results a program does not
                # return
                row[col] = _time_flash({
                    'fwd': (2, lambda *a, f=f, n=n: f(*a[:n])[0]),
                    'bwd_dq': (3, lambda *a, b_=b_: b_(*a)[0]),
                    'bwd_dkv': (4, lambda *a, b_=b_: b_(*a)[1:]),
                    'vjp': (7, lambda *a, f=f, b_=b_, n=n: b_(
                        *a[:n], *f(*a[:n]), a[-1]))},
                    x + (o, lse, d), k, rounds, matmul)
                row[col].update(_time_flash(
                    {'whole': (7, w)}, (qkv, do_p), k, rounds, matmul))
        if on_chip:
            from jax.experimental.pallas.ops.tpu import flash_attention as U
            blocks = U.BlockSizes(
                block_q=512, block_k_major=512, block_k=512, block_b=1,
                block_q_major_dkv=512, block_k_major_dkv=512,
                block_k_dkv=512, block_q_dkv=512, block_k_major_dq=512,
                block_k_dq=512, block_q_dq=512)

            def up(q, kk, v):           # [1, bh, L, dh]
                return U.flash_attention(q[None], kk[None], v[None],
                                         causal=True, sm_scale=scale,
                                         block_sizes=blocks)[0]
            row['jax 512 x 512'] = _time_flash({
                'fwd': (2, lambda q, kk, v, *_: up(q, kk, v)),
                'vjp': (7, lambda q, kk, v, o, lse, do: jax.vjp(
                    up, q, kk, v)[1](do))},
                (q, kk, v, o, lse, do), k, rounds, matmul)
    return out


# a prefill's expanded latent attention: (heads, bucket rows, nope, rope,
# value width, the table's keys)
MLA_PREFIX_SHAPES = {
    'small': {'toy': (2, 64, 128, 64, 128, 160)},
    'bench': {'joyai b%d' % t: (32, t, 128, 64, 128, 2816)
              for t in (512, 1024, 2048)}}


def measure_mla_prefix_attention(size, rounds, k, tilings=(), shapes=None):
    """`mla_prefix_attention` (ops/mla_ops.py) from the scores on, at each
    bucket of `joyai-serve-longchat64` with `--size bench`: q ``[T, H, nope
    + rope]`` at positions 0 .. T - 1 (the cell shares no prefix), k_nope
    and the values ``[H, M, .]`` and the one rotary key ``[M, rope]`` in,
    the context ``[T, H, v]`` out — what differs between the tiers; the
    two einsums that rebuild k_nope and v are the same program in both.
    A form a column, each paying what it re-lays: 'composition' (the
    `off` / `xla` tier), 'two products' (the op's kernel: a head's own
    lanes against its keys and the rotary lanes against a key block whose
    index ignores the head), 'one wide key' (the rotary key repeated a
    head behind k_nope, ``[H, M, nope + rope]``, laid once a call), 'padded
    key' (that key and q in whole vregs, zeros behind the rotary lanes);
    'kernel alone' is 'two products' with q and the context heads-major
    already. Each `rows` of `tilings` is 'two products' again with query
    tiles of that many rows at most (the op's own: `mla_ops.
    _KERNEL_QUERY_ROWS`). ms a call, TFLOP/s over the FLOPs of the causal
    half (2 x H x T x T / 2 x (nope + rope + v)) and a form's largest
    distance from the composition, as a share of the largest value."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import mla_ops, prefix_attention as pfa
    interpret = jax.default_backend() != 'tpu'
    out = {}
    for label, (H, T, nope, rope, dv, M) in MLA_PREFIX_SHAPES[size].items():
        if shapes and label not in shapes:
            continue
        scale = (nope + rope) ** -0.5
        q, k_nope, k_rope, value = (
            jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
            for i, shape in enumerate([(T, H, nope + rope), (H, M, nope),
                                       (M, rope), (H, M, dv)]))
        pos, at = jnp.arange(T), jnp.arange(M)

        def kernel(q, k, v, *shared, rows=mla_ops._KERNEL_QUERY_ROWS):
            # heads-major q and context
            return pfa.prefix_attention(q, k, v, at, pos, *shared,
                                        scale=scale, interpret=interpret,
                                        name='mla_prefix_attention',
                                        rows=rows)

        def composition(q, k_nope, k_rope, value):
            return mla_ops._expanded_attention_scores(q, k_nope, k_rope,
                                                      value, pos, scale)

        def two_products(q, k_nope, k_rope, value, **kw):
            return jnp.swapaxes(kernel(jnp.swapaxes(q, 0, 1), k_nope, value,
                                       k_rope, **kw), 0, 1)

        def wide_key(q, k_nope, k_rope, value, fill=0):
            zeros = jnp.zeros(q.shape[:2] + (fill,), q.dtype)
            key = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope, (H, M, rope)),
                 jnp.zeros((H, M, fill), q.dtype)], axis=-1)
            return jnp.swapaxes(kernel(jnp.swapaxes(
                jnp.concatenate([q, zeros], axis=-1), 0, 1), key, value),
                0, 1)

        def alone(q, k_nope, k_rope, value):    # q [H, T, .] already
            return kernel(q, k_nope, value, k_rope)
        fill = -(nope + rope) % 128
        forms = {'composition': composition, 'two products': two_products,
                 'one wide key': wide_key,
                 'padded key': lambda *a: wide_key(*a, fill=fill)}
        operands = (q, k_nope, k_rope, value)
        causal = 2 * H * T * T / 2 * (nope + rope + dv)
        row = out.setdefault('%s [%d, %d, %d + %d | %d] x %d keys' % (
            label, H, T, nope, rope, dv, M), {})
        want = jax.jit(composition)(*operands)
        for name, fn in forms.items():
            row[name] = _time_flash({'call': (1, fn)}, operands, k, rounds,
                                    causal)['call']
            if 'error' not in row[name]:
                row[name]['max_err'] = float(
                    jnp.max(jnp.abs(jax.jit(fn)(*operands) - want))
                    / jnp.max(jnp.abs(want)))
        row['kernel alone'] = _time_flash(
            {'call': (1, alone)}, (jnp.swapaxes(q, 0, 1),) + operands[1:],
            k, rounds, causal)['call']
        for t in tilings:
            row['two products, rows %s' % t] = _time_flash(
                {'call': (1, functools.partial(two_products, rows=int(t)))},
                operands, k, rounds, causal)['call']
    return out


def _time_flash(kernels, operands, k, rounds, matmul):
    """{kernel: {ms, tflops}} of `kernels` {name: (causal matmuls, fn(q, k,
    v, o, lse, do))}: each the best of `rounds` runs of one program of `k`
    dependent calls (the next call's first operand waits for a bit of this
    call's result)."""
    import jax
    from jax import lax
    out = {}
    for kern, (n_mm, fn) in kernels.items():
        def calls(q, *rest, fn=fn):
            # q rides the loop so that the bit lands in place: a pass over
            # the operand would be timed with the kernel (the packed
            # product is three times q)
            def body(i, carry):
                q, acc = carry
                bit = jax.tree_util.tree_leaves(acc)[0][0, 0, 0]
                q = q.at[0, 0, 0].add((bit != bit).astype(q.dtype))
                return q, fn(q, *rest)
            return lax.fori_loop(0, k, body, (q, fn(q, *rest)))[1]
        try:
            loop = jax.jit(calls)
            jax.block_until_ready(loop(*operands))
            best = float('inf')
            for _ in range(rounds):
                t0 = time.perf_counter()
                jax.block_until_ready(loop(*operands))
                best = min(best, (time.perf_counter() - t0) / (k + 1))
            out[kern] = {'ms': round(best * 1e3, 4),
                         'tflops': round(n_mm * matmul / best / 1e12, 2)}
        except Exception as e:      # noqa: BLE001 -- advisory tool
            out[kern] = {'error': '%s: %s' % (type(e).__name__,
                                              str(e)[:200])}
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
_CASE_NAMES = list(_CASES) + ['embedding_gather', 'grouped_matmul',
                                 'flash_attention', 'mla_prefix_attention']


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
                      size='small', mesh=1, tilings=(), shapes=None):
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
        if case == 'grouped_matmul':        # tilings, not tiers
            out[case] = measure_grouped_matmul(size, rounds, k, tilings,
                                               shapes)
            continue
        if case == 'flash_attention':       # tilings, not tiers
            out[case] = measure_flash_attention(size, rounds, k, tilings,
                                                shapes)
            continue
        if case == 'mla_prefix_attention':  # forms, not tiers
            out[case] = measure_mla_prefix_attention(size, rounds, k,
                                                     tilings, shapes)
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
    ap.add_argument('--tilings', default='',
                    help="grouped_matmul: 'tm,tk,tn' candidates, ':' between; "
                         "flash_attention: 'bq,bk'; mla_prefix_attention: "
                         "'rows' a query tile")
    ap.add_argument('--shapes', default='',
                    help='grouped_matmul, flash_attention, mla_prefix_attention: '
                         'only these labels of GROUPED_MATMULS / FLASH_SHAPES '
                         '/ MLA_PREFIX_SHAPES (comma '
                         'between)')
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
                            mesh=args.mesh,
                            tilings=[t for t in args.tilings.split(':') if t],
                            shapes=[t for t in args.shapes.split(',') if t])
    print(json.dumps(res))


if __name__ == '__main__':
    main()
