"""Model step (decode program, ops/kv_cache_ops.py). The bytes one decode
step has to move (benchmark/flops.py lm_decode_bytes_per_step: every
weight once + the live K/V rows of the active slots, the live rows from
GenerateEngine.stats() blocks in use) / peak HBM bytes/s / the mean
decode_step_seconds of the window, in percent. The bound is hbm. The step
time is the host clock's until a tracing PR names the decode program in
the device trace. Moves serve_tokens_per_s."""


def read(facts):
    need = facts.get('decode_bytes_per_step')
    n, total = facts.get('histograms', {}).get('decode_step_seconds', (0, 0))
    if not need or not n:
        return None
    least_s = need / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / (total / n)
