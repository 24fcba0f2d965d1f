"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul, on an
LFM2-MoE configuration). As moe_ffn_hbm_share — the grouped matmuls'
share of their roofline, which is HBM at decode — with the byte count of
THIS family's keys: an expert's width is `moe_intermediate_size`
(`intermediate_size` is the dense layers' 7 168 here; flops_moe.py reads
it as an expert's, OLMoE's key, and would count four times the bytes).

- Bytes (benchmark/flops_lfm2.py `grouped_matmul_bytes`): the weights of
  the experts the window's dispatches touched, once a touch
  (moe_experts_touched_total, decode steps and prefills alike), and per
  assignment the gathered row in, gate and up out, their product in, the
  result out (moe_assignments_total); per second of the measured window.
- Time: `mosaic:ragged-dot*` as the trace prints them, over the traced
  window.

A prefill's grouped matmul is bound by compute, which pulls the reading
down by the prefills' share of the time. A program with no such operation
or counter, or a configuration without `moe_intermediate_size` and
`num_dense_layers` (another family), reads nothing. Moves
serve_tokens_per_s."""
from benchmark import flops_lfm2

OPS = 'mosaic:ragged-dot'


def read(facts):
    t = facts.get('trace')
    c = facts.get('counters', {})
    m = facts.get('config', {})
    touched = c.get('moe_experts_touched_total')
    if not t or not touched or not facts.get('window_s') \
            or 'moe_intermediate_size' not in m \
            or 'num_dense_layers' not in m:
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds:
        return None
    need = flops_lfm2.grouped_matmul_bytes(
        m, touched, c.get('moe_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
