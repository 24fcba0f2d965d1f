"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul, on a Mellum 2
configuration). As kexaone_moe_ffn_roofline -- the grouped matmuls' share
of their roofline, which is HBM at decode -- with the byte count of THIS
family's keys: an expert's width is `moe_intermediate_size`
(`intermediate_size` is a dense layer's 7 168, which no layer has), every
expert is held and there is no shared expert and no dense layer
(`kexaone_moe_ffn_roofline` asks for `first_k_dense_replace` and reads
nothing here).

- Bytes (benchmark/flops_mellum2.py `grouped_matmul_bytes`): the three
  matrices of the experts the window's dispatches touched, once a touch
  (moe_experts_touched_total, decode steps and prefills alike), and per
  assignment (moe_assignments_total) the gathered row in, gate and up out,
  their product in, the result out; per second of the measured window.
- Time: `mosaic:ragged-dot*` as the trace prints them, over the traced
  window.

A prefill chunk touches every expert for 512 rows where a step touches
them for 64: both are bound by the weights' bytes at these widths (6.2 MB
an expert against 8 rows' worth of operations), so the chunks do not pull
the reading down as they do where an expert is narrow. A program with no
such operation or counter, or a configuration without
`moe_intermediate_size`, `mlp_layer_types` and `use_sliding_window`
(another family), reads nothing. Moves itl_p95_ms (a token gap is a decode
step, or a step and a chunk, and both are mostly these bytes)."""
from benchmark import flops_mellum2

OPS = 'mosaic:ragged-dot'
KEYS = ('moe_intermediate_size', 'mlp_layer_types', 'use_sliding_window')


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
    need = flops_mellum2.grouped_matmul_bytes(
        m, touched, c.get('moe_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
