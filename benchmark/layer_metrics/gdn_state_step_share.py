"""Model step (models/transformer.py 'gdn' layers, counted by
serving/generate.py). Of the bytes one decode step has to move
(`decode_bytes_per_step`: every weight outside the experts once, the held
experts the step's rows touch, the attention layers' live K/V rows, the
state), the share that is the Gated DeltaNet layers' state and tails, read
and written: 2 x the mean active slots x state_bytes_per_slot /
decode_bytes_per_step, in percent. The mean active slots are
gdn_state_rows_updated_total / DeltaNet layers / the window's decode steps
-- the rows the steps really advanced. It does not grow with the context,
where the attention layers' K/V share does. A program without the counter
(the parent commit, no DeltaNet layers), a configuration without this
family's keys or a run without `decode_bytes_per_step` (untraced) reads
nothing. Moves itl_p95_ms (a token gap is a decode step, and the step is
what these bytes take)."""
from benchmark import flops_qwen3next

KEYS = ('linear_num_value_heads', 'linear_key_head_dim',
        'linear_value_head_dim', 'linear_conv_kernel_dim',
        'full_attention_interval')


def read(facts):
    rows = facts.get('counters', {}).get('gdn_state_rows_updated_total')
    m = facts.get('config', {})
    steps, need = facts.get('decode_steps'), \
        facts.get('decode_bytes_per_step')
    if not rows or not steps or not need or any(k not in m for k in KEYS):
        return None
    active = rows / float(flops_qwen3next.n_gdn_layers(m)) / steps
    return 100.0 * 2 * active * flops_qwen3next.state_bytes_per_slot(m) \
        / need
