"""Model step (models/transformer.py 'ssm' layers, counted by
serving/generate.py). Of the bytes one decode step has to move
(`decode_bytes_per_step`: every weight once, the attention layers' live
K/V rows, the state), the share that is the Mamba layers' state and tails,
read and written: 2 x the mean active slots x state_bytes_per_slot /
decode_bytes_per_step, in percent. The mean active slots are
ssm_state_rows_updated_total / Mamba layers / the window's decode steps —
the rows the steps really advanced. ~17 % at 128 rows of Jamba2-3B; it
does not grow with the context, where an attention model's K/V share
does. A program without the counter (the parent commit, no state-space
layers), a configuration without this family's keys or a run without
`decode_bytes_per_step` (untraced) reads nothing. Moves
serve_tokens_per_s."""
from benchmark import flops_jamba


def read(facts):
    rows = facts.get('counters', {}).get('ssm_state_rows_updated_total')
    m = facts.get('config', {})
    steps, need = facts.get('decode_steps'), \
        facts.get('decode_bytes_per_step')
    if not rows or not steps or not need or 'mamba_d_state' not in m \
            or 'mamba_expand' not in m:
        return None
    active = rows / float(flops_jamba.n_ssm_layers(m)) / steps
    return 100.0 * 2 * active * flops_jamba.state_bytes_per_slot(m) / need
