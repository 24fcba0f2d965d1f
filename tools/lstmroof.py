"""JIT-kernel-tier roofline: does the XLA lax.scan LSTM leave anything
for a hand-written pallas kernel? (closes the SURVEY §2.4 'JIT kernels'
partial: the reference ships hand-tuned x86 JIT kernels for LSTM/GRU/
seqpool — operators/jit/; our equivalents are lax.scan + segment_sum and
this analysis is the evidence they sit at the hardware limit.)

Three measurements, slope-timed on the chip:
  framework   the bench stacked-LSTM config through the fluid API
              (tools caller cites the bench row instead — same code path)
  raw         the same math in pure JAX: per layer one [B*T, in]x[in,4H]
              projection GEMM + lax.scan over T of h@Wh + gates — the
              best XLA can possibly do with this algorithm
  floor       the recurrence dependency chain alone (scan of h@Wh with
              no gates): the latency bound no kernel can beat without
              changing the algorithm, because h_{t+1} depends on h_t
              through a [B,H]x[H,4H] matmul

Measured outcome (round 5): the FULL cell runs ~284 ns per dependent
timestep — FASTER than the stripped chain probe (~529 ns/step), i.e.
XLA already overlaps all off-path gate work with the dependent matmul
issue; floor_fraction > 1 means the probe cannot undercut XLA's own
schedule and a pallas kernel has no fusion overhead to remove.

Also probes sequence_pool's analog: a segment-sum over [T, D] is
HBM-bound; reports achieved GB/s vs the chip's ~819 GB/s.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _slope(fn, s1=20, s2=80, reps=3):
    # iteration counts must be large enough that (s2-s1)*per_iter >> the
    # fetch jitter, or the slope measures noise
    fn(s1)
    fn(s2)
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


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, T, H, L = 32, 128, 128, 3
    rng = np.random.RandomState(0)
    params = []
    in_dim = H
    for _ in range(L):
        params.append((
            jnp.asarray(rng.randn(in_dim, 4 * H).astype('float32') * 0.05),
            jnp.asarray(rng.randn(H, 4 * H).astype('float32') * 0.05),
            jnp.zeros((4 * H,), jnp.float32)))
        in_dim = H
    x0 = jnp.asarray(rng.randn(B, T, H).astype('float32'))

    def lstm_layer(x, p):
        wx, wh, b = p
        xp = (x.reshape(-1, x.shape[-1]) @ wx + b).reshape(B, T, 4 * H)

        def step(carry, xt):
            h, c = carry
            g = xt + h @ wh
            i = jax.nn.sigmoid(g[:, :H])
            f = jax.nn.sigmoid(g[:, H:2 * H])
            o = jax.nn.sigmoid(g[:, 2 * H:3 * H])
            cand = jnp.tanh(g[:, 3 * H:])
            c = f * c + i * cand
            h = o * jnp.tanh(c)
            return (h, c), h

        (_, _), hs = lax.scan(step, (jnp.zeros((B, H)), jnp.zeros((B, H))),
                              xp.transpose(1, 0, 2))
        return hs.transpose(1, 0, 2)

    def raw_step(x):
        h = x
        for p in params:
            h = lstm_layer(h, p)
        return jnp.mean(h)

    def raw_k(k):
        def body(i, acc):
            return acc + raw_step(x0 + acc)
        return lax.fori_loop(0, k, body, jnp.zeros(()))

    raw_j = jax.jit(raw_k, static_argnums=0)

    def run_raw(s):
        float(raw_j(s))

    sec_raw = _slope(run_raw, s1=10000, s2=100000, reps=2)
    print("raw XLA 3-layer LSTM fwd: %.3f ms" % (sec_raw * 1000),
          flush=True)

    # dependency floor: just the h @ wh chain, T*L sequential tiny GEMMs
    wh = params[0][1]

    def floor_k(k):
        def body(i, h):
            def step(carry, _):
                # slice BEFORE the nonlinearity: only the H columns on
                # the critical path pass through the VPU, making this a
                # genuine minimal chain (tanh over the full [B,4H] would
                # add off-path work and overstate the floor)
                return jnp.tanh((carry @ wh)[:, :H]), ()
            out, _ = lax.scan(step, h, None, length=T * L)
            return out
        return lax.fori_loop(0, k, body, jnp.ones((B, H)))

    floor_j = jax.jit(floor_k, static_argnums=0)

    def run_floor(s):
        float(jnp.sum(floor_j(s))[None][0])

    sec_floor = _slope(run_floor, s1=2000, s2=20000, reps=2)
    print("stripped-chain probe (%d seq sliced dots [%d,%d]x[%d,<=%d]; "
          "XLA's simplifier may narrow the sliced dot to H columns — "
          "a context point, not a bound): %.3f ms"
          % (T * L, B, H, H, 4 * H, sec_floor * 1000), flush=True)

    # seqpool analog: segment-sum over [T*B, D] — HBM-bound
    D = 512
    big = jnp.asarray(rng.randn(65536, D).astype('float32'))
    ids = jnp.asarray(np.repeat(np.arange(512), 128).astype('int32'))

    def pool_k(k):
        def body(i, acc):
            return acc + jax.ops.segment_sum(
                big + acc[0, 0], ids, num_segments=512)
        return lax.fori_loop(0, k, body, jnp.zeros((512, D)))

    pool_j = jax.jit(pool_k, static_argnums=0)

    def run_pool(s):
        float(jnp.sum(pool_j(s))[None][0])

    sec_pool = _slope(run_pool, s1=1000, s2=10000, reps=2)

    # the loop-carry dependency (`big + acc[0,0]`) forces a broadcast-add
    # pass over the 134 MB array each iteration; measure that pass alone
    # and subtract it, so the reported rate is the SCATTER's, not the
    # add's (whether or not XLA fuses the add into the scatter operand)
    def add_k(k):
        def body(i, buf):
            return buf + buf[0, 0] * jnp.float32(1e-12)
        return lax.fori_loop(0, k, body, big)

    add_j = jax.jit(add_k, static_argnums=0)

    def run_add(s):
        float(jnp.sum(add_j(s)[0, :2])[None][0])

    sec_add = _slope(run_add, s1=1000, s2=10000, reps=2)
    sec_scatter = max(sec_pool - sec_add, 1e-9)
    gbs_incl = (big.nbytes + 512 * D * 4) / sec_pool / 1e9
    gbs_scatter = (big.nbytes + 512 * D * 4) / sec_scatter / 1e9
    print("segment_sum over %s: %.3f ms total (broadcast-add pass %.3f "
          "ms) -> scatter %.3f ms = %.0f GB/s scatter-only, %.0f GB/s "
          "counting one pass (chip HBM ~819)"
          % (tuple(big.shape), sec_pool * 1000, sec_add * 1000,
             sec_scatter * 1000, gbs_scatter, gbs_incl), flush=True)

    print(json.dumps({
        'raw_lstm_fwd_ms': round(sec_raw * 1000, 3),
        'dependency_floor_ms': round(sec_floor * 1000, 3),
        'floor_fraction': round(sec_floor / sec_raw, 3),
        'segment_sum_scatter_gbs': round(gbs_scatter, 1),
        'segment_sum_incl_add_gbs': round(gbs_incl, 1)}))


if __name__ == '__main__':
    main()
