"""Kernels (ops/ssm_ops.py `decode_update` and `decode_conv`: every live
slot's state row, and its convolution tail, read, advanced one position and
written back in place, a call of each a Mamba layer). The two kernels'
share of their roofline, which is HBM: the state and tail bytes they have
to move a second / peak bytes/s / the share of the traced window they run
in, in percent.

- Bytes (benchmark/flops_jamba.py `ssm_decode_state_bytes`):
  ssm_state_rows_updated_total (serving/generate.py: per decode step, the
  active rows x Mamba layers, over the measured window) x (mamba_d_state +
  mamba_d_conv - 1) x d_inner x 4 B, read once and written once, per
  second of the window. The tails' kernel moves the whole sublane tile of
  8 rows a layer keeps for its 3 (ops/ssm_ops.py `TAIL_ROWS`): what it
  moves beyond the rows that count is its overhead, and lowers this share.
- Time: the device operations `mosaic:ssm_decode_update` and
  `mosaic:ssm_decode_conv` as the trace prints them, over the traced
  window.

A program with no such operation or counter (the parent commit, a model
without state-space layers, the xla tier, a CPU run), or a configuration
without this family's keys, reads nothing. Moves serve_tokens_per_s."""
from benchmark import flops_jamba

OPS = ('mosaic:ssm_decode_update', 'mosaic:ssm_decode_conv')


def read(facts):
    t = facts.get('trace')
    rows = facts.get('counters', {}).get('ssm_state_rows_updated_total')
    m = facts.get('config', {})
    if not t or not rows or not facts.get('window_s') \
            or 'mamba_d_state' not in m or 'mamba_expand' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds:
        return None
    need = flops_jamba.ssm_decode_state_bytes(m, rows)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
