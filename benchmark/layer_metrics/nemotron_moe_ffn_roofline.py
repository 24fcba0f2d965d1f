"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul, on a
Nemotron-H configuration). As kexaone_moe_ffn_roofline -- the grouped
matmuls' share of their roofline, which is HBM at decode -- with the byte
count of THIS family's keys and form: an expert is UNGATED, two matrices
(`moe_intermediate_size` wide), and of the router's assignments only those
to an expert held here are computed.

- Bytes (benchmark/flops_nemotron.py `grouped_matmul_bytes`): the two
  matrices of the held experts the window's dispatches touched, once a
  touch (moe_experts_touched_total, decode steps and prefills alike), and
  per COMPUTED assignment (moe_held_assignments_total) the gathered row in,
  up out, its square in, the result out; per second of the measured
  window.
- Time: `mosaic:ragged-dot*` as the trace prints them, over the traced
  window. NOT in it: the `copy` of each expert layer's up matrices that XLA
  puts in front of the grouped matmul every step (1856 columns are no whole
  number of lane tiles; 17 % of the device's time where this reader first
  read 20 %, PERF.md PR 48) -- time the expert layer costs and this share
  does not see.

A prefill's grouped matmul is bound by compute, which pulls the reading
down by the prefills' share of the time. A program with no such operation
or counter, or a configuration without `mlp_hidden_act`,
`moe_shared_expert_intermediate_size` and `hybrid_override_pattern`
(another family), reads nothing. Moves itl_p95_ms (a token gap is a
decode step, and the step is what these bytes take)."""
from benchmark import flops_nemotron

OPS = 'mosaic:ragged-dot'
KEYS = ('mlp_hidden_act', 'moe_shared_expert_intermediate_size',
        'hybrid_override_pattern')


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
    if not seconds:
        return None
    need = flops_nemotron.grouped_matmul_bytes(
        m, touched, c.get('moe_held_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
