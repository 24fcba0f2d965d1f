"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul over the
experts held here). `moe_ffn_hbm_share`'s formula on this configuration's
keys: the bytes the grouped matmuls have to move a second / peak bytes/s /
the share of the traced window they run in, in percent.

- Bytes (benchmark/flops_joyai.py `grouped_matmul_bytes`): the weights of
  the HELD experts the window's dispatches touched, once a touch
  (moe_experts_touched_total), and per COMPUTED assignment
  (moe_held_assignments_total: an assignment to an expert held elsewhere
  is neither computed nor read) the gathered row in, gate and up out,
  their product in, the result out; per second of the measured window.
- Time: `mosaic:ragged-dot*` in the trace (XLA:TPU's grouped-matmul kernel
  and its metadata kernel), over the traced window. The router, the
  top-k, the sort and the gathers are anonymous operations and are not in
  it; the shared expert is three plain matmuls and not in it either.

The bound is hbm at decode (two rows a held expert). A configuration
without these keys, or a program without the counters, reads nothing.
Moves serve_tokens_per_s."""
from benchmark import flops_joyai

OPS = 'mosaic:ragged-dot'


def read(facts):
    t = facts.get('trace')
    c = facts.get('counters', {})
    m = facts.get('config', {})
    touched = c.get('moe_experts_touched_total')
    if not t or not touched or not facts.get('window_s') \
            or 'moe_held_assignments_total' not in c \
            or 'moe_intermediate_size' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds:
        return None
    need = flops_joyai.grouped_matmul_bytes(
        m, touched, c['moe_held_assignments_total'])
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
