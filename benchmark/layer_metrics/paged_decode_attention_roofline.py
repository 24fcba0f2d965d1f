"""Kernels (ops/paged_decode_attention.py: the paged one-query decode
attention over per-head K/V pages, grouped queries included). The kernel's
share of its roofline, which is HBM: the K and V bytes it has to read a
second / peak bytes/s / the share of the traced window it runs in, in
percent.

- Bytes (benchmark/flops_lfm2.py `paged_decode_attention_bytes`):
  kv_tokens_read_total (serving/generate.py: per decode step the live
  positions of the active slots x ATTENTION layers, over the measured
  window) x K and V of the K/V heads (2 x num_key_value_heads x head_dim
  x 4), per second of the window — a page is read ONCE for the query
  heads of each of its K/V heads. The kernel copies whole pages, a slot's
  last one too: what it moves beyond the live positions is its overhead,
  and lowers this share.
- Time: the device operation `mosaic:paged_decode_attention` as the trace
  prints it, over the traced window.

Beside it, the same call's share of the chip's peak FLOP/s (not reported:
the bound is bytes at 4 query heads a K/V head, 4 FLOP a byte):
`flops_lfm2.paged_decode_attention_flops(config, rows)` a second / peak
FLOP/s / the same share of the traced window.

A program with no such operation or counter (the parent commit, a latent
cache, a CPU run), or a configuration without this file's keys, reads
nothing. Moves serve_tokens_per_s."""
from benchmark import flops_lfm2

OP = 'mosaic:paged_decode_attention'


def read(facts):
    t = facts.get('trace')
    tokens = facts.get('counters', {}).get('kv_tokens_read_total')
    m = facts.get('config', {})
    if not t or not tokens or not facts.get('window_s') \
            or 'num_key_value_heads' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds:
        return None
    need = flops_lfm2.paged_decode_attention_bytes(m, tokens)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
