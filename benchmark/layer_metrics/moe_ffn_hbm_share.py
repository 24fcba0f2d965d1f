"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul). The share of
the chip's peak HBM bandwidth that the grouped matmuls reach: the bytes
they have to move a second / peak bytes/s / the share of the traced window
they run in, in percent.

- Bytes (benchmark/flops_moe.py `grouped_matmul_bytes`): the weights of
  the experts the window's dispatches touched, once a touch
  (moe_experts_touched_total, decode steps and prefills alike), and per
  assignment the gathered row in, gate and up out, their product in, the
  result out (moe_assignments_total); per second of the measured window.
- Time: the device operations of the grouped matmul as the trace prints
  them: `mosaic:ragged-dot*` (XLA:TPU lowers `jax.lax.ragged_dot` to a
  Mosaic grouped-matmul kernel, `ragged-dot-none`, and a small
  `ragged-dot-metadata` kernel ahead of it), over the traced window.
  moe_ffn's OTHER device operations — the router's matmul, softmax and
  top-k, the sort of the assignments, the two gathers, the silu-multiply,
  the weighted sum — are anonymous `fusion`s, `sort`s and `gather`s that
  the trace does not tell from the rest of the step; their bytes are not
  counted either, so this is the grouped matmul's share, not the op's.

The bound is hbm at decode (two rows an expert); a prefill's grouped
matmul is bound by compute, which pulls the reading down by the prefills'
share of the time. A program with no such operation or counter reads
nothing. Moves serve_tokens_per_s."""
from benchmark import flops_moe

OPS = 'mosaic:ragged-dot'


def read(facts):
    t = facts.get('trace')
    c = facts.get('counters', {})
    touched = c.get('moe_experts_touched_total')
    if not t or not touched or not facts.get('window_s'):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds:
        return None
    need = flops_moe.grouped_matmul_bytes(
        facts['config'], touched, c.get('moe_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
