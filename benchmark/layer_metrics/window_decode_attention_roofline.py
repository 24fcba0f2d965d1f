"""Kernels (ops/paged_decode_attention.py called with a bound: the window
layers' one-query decode attention, from the page of the first key seen).
The call's share of its roofline, which is HBM: the K and V bytes it has
to read a second / peak bytes/s / the share of the traced window it runs
in, in percent.

- Bytes (benchmark/flops_kexaone.py `window_decode_attention_bytes`):
  kv_window_tokens_read_total (serving/generate.py: per decode step, over
  the active slots, min(position + 1, sliding_window) x WINDOW layers,
  over the measured window) x K and V of the K/V heads (2 x
  num_key_value_heads x head_dim x 4), per second of the window. The
  kernel copies whole pages, five for a window of four pages that starts
  inside one: what it moves beyond the keys seen is its overhead, and
  lowers this share — as does the DMA's latency, which so few pages a slot
  leave bare.
- Time: the device operation `mosaic:paged_window_decode_attention` as the
  trace prints it, over the traced window. (The global layers' calls are
  `mosaic:paged_decode_attention`: paged_decode_attention_roofline.)

A program with no such operation or counter (the parent commit, a model
without window layers, a CPU run), or a configuration without this
family's keys, reads nothing. Moves serve_tokens_per_s."""
from benchmark import flops_kexaone

OP = 'mosaic:paged_window_decode_attention'


def read(facts):
    t = facts.get('trace')
    tokens = facts.get('counters', {}).get('kv_window_tokens_read_total')
    m = facts.get('config', {})
    if not t or not tokens or not facts.get('window_s') \
            or 'sliding_window' not in m or 'num_key_value_heads' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds:
        return None
    need = flops_kexaone.window_decode_attention_bytes(m, tokens)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
