"""Model step (models/transformer.py `_loop_pass`: the ops of pass ``t`` of a
looped model lower under the scope ``paddle_tpu:loop_pass_<t>``, and the
decode attention's kernel takes the pass into its operation name). The
slowest pass's device time over the fastest's, from what the trace's
per-name sums can tell apart: `mosaic:paged_decode_attention_loop_pass_<t>`,
each pass's K/V walk over the traced span. Every pass of a step walks the
same live tokens in a cache layer of its own, so this reads 1.0 unless a
pass is laid out or scheduled unlike its twins (a pool whose later cache
layers lie differently, a pass whose calls wait on something the others do
not).

`reduce_trace.op_name` cuts a device event to its HLO instruction's name,
which XLA chooses for its own fusions: the weights' matmuls of a pass carry
the pass in their metadata alone, and are not among these sums (PERF.md
section 7, after PR 63).

A trace without two such names (a one-pass model, the parent commit, the xla
tier, a CPU run) reads nothing. Moves serve_tokens_per_s."""
PREFIX = 'mosaic:paged_decode_attention_loop_pass_'


def read(facts):
    t = facts.get('trace')
    if not t:
        return None
    passes = [s for name, s in t['op_seconds'].items()
              if name.startswith(PREFIX) and s > 0]
    if len(passes) < 2:
        return None
    return max(passes) / min(passes)
