"""Kernels (ops/moe_ops.py: moe_ffn's grouped expert matmul, on a
K-EXAONE configuration). As lfm2_moe_ffn_roofline — the grouped matmuls'
share of their roofline, which is HBM at decode — with the byte count of
THIS family's keys and of the chip's SHARE of the experts: an expert's
width is `moe_intermediate_size` (`intermediate_size` is the dense
layer's 18 432 here), and of the router's assignments only those to an
expert held here are computed.

- Bytes (benchmark/flops_kexaone.py `grouped_matmul_bytes`): the weights
  of the held experts the window's dispatches touched, once a touch
  (moe_experts_touched_total, decode steps and prefills alike), and per
  COMPUTED assignment (moe_held_assignments_total) the gathered row in,
  gate and up out, their product in, the result out; per second of the
  measured window.
- Time: `mosaic:ragged-dot*` as the trace prints them, over the traced
  window.

A prefill's grouped matmul is bound by compute, which pulls the reading
down by the prefills' share of the time. A program with no such operation
or counter, or a configuration without `moe_intermediate_size`,
`first_k_dense_replace` and `sliding_window` (another family), reads
nothing. Moves serve_tokens_per_s."""
from benchmark import flops_kexaone

OPS = 'mosaic:ragged-dot'
KEYS = ('moe_intermediate_size', 'first_k_dense_replace', 'sliding_window')


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
    need = flops_kexaone.grouped_matmul_bytes(
        m, touched, c.get('moe_held_assignments_total', 0))
    least_share = need / facts['window_s'] \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_share / (seconds / t['window_s'])
