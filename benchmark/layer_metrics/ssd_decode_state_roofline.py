"""Kernels (ops/ssd_ops.py `decode_update`, and ops/ssm_ops.py
`decode_conv` at this model's width: every live slot's Mamba-2 state row,
and its convolution tail, read, advanced one position and written back in
place, a call of each a Mamba-2 layer). The two kernels' share of their
roofline, which is HBM: the state and tail bytes they have to move a
second / peak bytes/s / the share of the traced window they run in, in
percent.

- Bytes (benchmark/flops_nemotron.py `ssd_decode_state_bytes`):
  ssd_state_rows_updated_total (serving/generate.py: per decode step, the
  active rows x Mamba-2 layers, over the measured window) x (mamba_num_heads
  x mamba_head_dim x ssm_state_size + (conv_kernel - 1) x the convolution's
  channels) x 4 B, read once and written once, per second of the window.
  The tails' kernel moves the whole sublane tile of 8 rows a layer keeps
  for its 3, and the update's operands B and C come as columns padded to a
  lane tile: what they move beyond the bytes that count is their overhead,
  and lowers this share.
- Time: the device operations `mosaic:ssd_decode_update` and
  `mosaic:ssm_decode_conv` as the trace prints them, over the traced
  window.

A program with no such operation or counter (the parent commit, a model
without Mamba-2 layers, the xla tier, a CPU run), or a configuration
without this family's keys, reads nothing. Moves itl_p95_ms (a token gap is a
decode step, and the step is what these bytes take)."""
from benchmark import flops_nemotron

OPS = ('mosaic:ssd_decode_update', 'mosaic:ssm_decode_conv')
KEYS = ('mamba_num_heads', 'mamba_head_dim', 'ssm_state_size', 'n_groups')


def read(facts):
    t = facts.get('trace')
    rows = facts.get('counters', {}).get('ssd_state_rows_updated_total')
    m = facts.get('config', {})
    if not t or not rows or not facts.get('window_s') \
            or any(k not in m for k in KEYS):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds:
        return None
    need = flops_nemotron.ssd_decode_state_bytes(m, rows)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
