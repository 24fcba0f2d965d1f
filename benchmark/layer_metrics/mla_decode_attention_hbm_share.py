"""Kernels (ops/mla_paged_decode_attention.py: the absorbed latent decode
attention). The kernel's share of its roofline, which is HBM: the latent
bytes it has to read a second / peak bytes/s / the share of the traced
window it runs in, in percent.

- Bytes: kv_latent_tokens_read_total (serving/generate.py: per decode
  step the live positions of the active slots x layers, over the measured
  window) x the numbers a token caches a layer (benchmark/flops_joyai.py
  `latent_row_width`: kv_lora_rank + qk_rope_head_dim = 576) x 4, per
  second of the window. The pool stores a row in 640 lanes (whole
  128-lane tiles) and the kernel copies whole pages, the last one of a
  slot too: what it moves beyond the 576 numbers of the live positions is
  its overhead, and lowers this share.
- Time: the device operation `mosaic:mla_paged_decode_attention` as the
  trace prints it, over the traced window.

Beside it, the same call's share of the chip's peak FLOP/s (not reported:
the kernel is bound by bytes, ~2 x heads FLOP a byte):
`flops_joyai.mla_decode_flops(config, latent tokens)` a second / peak
FLOP/s / the same share of the traced window.

A program with no such operation or counter (another configuration, the
parent commit, a CPU run) reads nothing. Moves serve_tokens_per_s."""
from benchmark import flops_joyai

OP = 'mosaic:mla_paged_decode_attention'


def read(facts):
    t = facts.get('trace')
    tokens = facts.get('counters', {}).get('kv_latent_tokens_read_total')
    if not t or not tokens or not facts.get('window_s'):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds:
        return None
    need = tokens * flops_joyai.latent_row_width(facts['config']) * 4
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
