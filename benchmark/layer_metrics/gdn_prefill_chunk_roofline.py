"""Kernels (ops/gdn_ops.py `prefill_chunks`: the chunked delta rule over
one prompt suffix or chunk, a call a Gated DeltaNet layer a prefill
dispatch). The kernel's matmul operations a second / the chip's peak
FLOP/s / the share of the trace's busy seconds it runs in, in
percent.

- Operations (benchmark/flops_qwen3next.py `gdn_prefill_chunk_flops`):
  what the chunked form's equations need for the REAL rows
  (gdn_prefill_rows_total, serving/generate.py: a bucket's pad rows are not
  counted, the kernel walks them all the same), the triangular solve
  counted as one dense product; per second of the measured window.
- Time: the device operation `mosaic:gdn_prefill_chunk` as the trace
  prints it, over the trace's busy seconds.

The peak is the MXU's in bfloat16 (benchmark/peaks.json has no other), and
the kernel multiplies float32 operands at `Precision.HIGHEST`, six bfloat16
passes a product, on tiles of 64 rows: a sixth of the peak is the most it
could read, and it reads far under that. What is left is the finding a
later `perf_opt` of the kernel starts from, not a fault of the reader.

The operations are the 50 s window's and the time the trace's (ROADMAP
M10): the trace begins where the ramp ends and holds another share of
prefills than the window, so the reading swings with the seed by the
prefills' share (PERF.md section 6, PR 55, has three seeds' readings; 105
is far above them).

A program with no such operation or counter (the parent commit, a model
without DeltaNet layers, the xla tier, a CPU run), or a configuration
without this family's keys, reads nothing. Moves itl_p95_ms (a prefill
chunk sits in a token gap).

The time is the kernels' share of the trace's BUSY seconds, not of its
window (gdn_decode_state_roofline.py says why: a stall of the machine's
host inside the trace would read as a faster kernel): idle time in the
measured window lowers the reading, a stall in the trace moves nothing.
"""
from benchmark import flops_qwen3next

OP = 'mosaic:gdn_prefill_chunk'
KEYS = ('linear_num_value_heads', 'linear_key_head_dim',
        'linear_value_head_dim', 'full_attention_interval')


def read(facts):
    t = facts.get('trace')
    rows = facts.get('counters', {}).get('gdn_prefill_rows_total')
    m = facts.get('config', {})
    if not t or not rows or not facts.get('window_s') \
            or any(k not in m for k in KEYS):
        return None
    seconds = sum(s for name, s in t['op_seconds'].items()
                  if name.startswith(OP))
    if not seconds or not t.get('busy_s'):
        return None
    need = flops_qwen3next.gdn_prefill_chunk_flops(
        m, rows, int(m.get('gdn_chunk', 64)))
    least_share = need / facts['window_s'] \
        / facts['peaks']['bf16_flops_per_s']
    return 100.0 * least_share / (seconds / t['busy_s'])
