"""Kernels (ops/gdn_ops.py `decode_update`, and ops/ssm_ops.py
`decode_conv` at this model's width: every live slot's Gated DeltaNet
state row, and its convolution tail, read, advanced one position and
written back in place, a call of each a DeltaNet layer). The two kernels'
share of their roofline, which is HBM: the state and tail bytes they have
to move a second / peak bytes/s / the share of the trace's busy seconds they
run in, in percent.

- Bytes (benchmark/flops_qwen3next.py `gdn_decode_state_bytes`):
  gdn_state_rows_updated_total (serving/generate.py: per decode step, the
  active rows x DeltaNet layers, over the measured window) x
  (linear_num_value_heads x linear_key_head_dim x linear_value_head_dim +
  (linear_conv_kernel_dim - 1) x the convolution's channels) x 4 B, read
  once and written once, per second of the window. The tails' kernel moves
  the whole sublane tile of 8 rows a layer keeps for its 3, and the
  update's operands q and k come as columns padded to a lane tile: what
  they move beyond the bytes that count is their overhead, and lowers this
  share. The update walks a head's 128 x 128 tile twice on the VPU: where
  that takes longer than the bytes do, the share says so.
- Time: the device operations `mosaic:gdn_decode_update` and
  `mosaic:ssm_decode_conv` as the trace prints them, over the trace's
  busy seconds.

The time is the kernels' share of the trace's BUSY seconds, not of its
window: the counters are the measured window's, and a stall of the
machine's host inside the 8 s trace (one of three traced runs of PR 55 held
a gap of 2.99 s: PERF.md section 6) leaves the kernels a smaller share of
the trace's wall time than of the window's, which read 86 % where the two
other seeds read 43 and 58. Over busy seconds a stall in the trace moves
nothing, and idle time in the WINDOW lowers the reading: the share can be
under-read by the device's idle share (~1 % in this cell), never over-read
by it.

A program with no such operation or counter (the parent commit, a model
without DeltaNet layers, the xla tier, a CPU run), or a configuration
without this family's keys, reads nothing. Moves itl_p95_ms (a token gap
is a decode step, and the step is what these bytes take)."""
from benchmark import flops_qwen3next

OPS = ('mosaic:gdn_decode_update', 'mosaic:ssm_decode_conv')
KEYS = ('linear_num_value_heads', 'linear_key_head_dim',
        'linear_value_head_dim', 'linear_conv_kernel_dim',
        'full_attention_interval')


def read(facts):
    t = facts.get('trace')
    rows = facts.get('counters', {}).get('gdn_state_rows_updated_total')
    m = facts.get('config', {})
    if not t or not rows or not facts.get('window_s') \
            or any(k not in m for k in KEYS):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds or not t.get('busy_s'):
        return None
    need = flops_qwen3next.gdn_decode_state_bytes(m, rows)
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['busy_s'])
