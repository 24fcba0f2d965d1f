"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul, on a
Qwen3-Next configuration). As nemotron_moe_ffn_roofline -- the grouped
matmuls' share of their roofline, which is HBM at decode -- with the byte
count of THIS family's keys and form: an expert is SiLU-gated, three
matrices (`moe_intermediate_size` wide), and of the router's 10
assignments a row only those to one of the 64 experts held here are
computed.

- Bytes (benchmark/flops_qwen3next.py `grouped_matmul_bytes`): the three
  matrices of the held experts the window's dispatches touched, once a
  touch (moe_experts_touched_total, decode steps and prefills alike), and
  per COMPUTED assignment (moe_held_assignments_total) the gathered row
  in, gate and up out, their product in, the result out; per second of the
  measured window.
- Time: `mosaic:ragged-dot*` as the trace prints them, over the trace's
  busy seconds. The router, the top-k over 512, the sort and the gathers are
  anonymous operations and are not in it; the shared expert is three plain
  matmuls and not in it either.

A prefill's grouped matmul is bound by compute (at `highest`, six passes),
which pulls the reading down by the prefills' share of the time. A program
with no such operation or counter, or a configuration without
`shared_expert_intermediate_size`, `full_attention_interval` and
`linear_num_value_heads` (another family), reads nothing. Moves itl_p95_ms
(a token gap is a decode step, and the step is what these bytes take).

The time is the kernels' share of the trace's BUSY seconds, not of its
window (gdn_decode_state_roofline.py says why: a stall of the machine's
host inside the trace would read as a faster kernel): idle time in the
measured window lowers the reading, a stall in the trace moves nothing.
"""
from benchmark import flops_qwen3next

OPS = 'mosaic:ragged-dot'
KEYS = ('shared_expert_intermediate_size', 'full_attention_interval',
        'linear_num_value_heads', 'moe_intermediate_size')


def read(facts):
    t = facts.get('trace')
    c = facts.get('counters', {})
    m = facts.get('config', {})
    touched = c.get('moe_experts_touched_total')
    if not t or not touched or not facts.get('window_s') \
            or any(k not in m for k in KEYS):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OPS))
    if not seconds or not t.get('busy_s'):
        return None
    need = flops_qwen3next.grouped_matmul_bytes(
        m, touched, c.get('moe_held_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['busy_s'])
