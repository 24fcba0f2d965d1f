"""Model step (models/transformer.py 'ssd' layers, counted by
serving/generate.py). Of the bytes one decode step has to move
(`decode_bytes_per_step`: every weight once, the attention layers' live
K/V rows, the state), the share that is the Mamba-2 layers' state and
tails, read and written: 2 x the mean active slots x state_bytes_per_slot
/ decode_bytes_per_step, in percent. The mean active slots are
ssd_state_rows_updated_total / Mamba-2 layers / the window's decode steps
-- the rows the steps really advanced. ~38 % at 128 rows of the 20-layer
cut of Nemotron-3-Nano (Jamba2-3B: ~17 %); it does not grow with the
context, where an attention model's K/V share does. A program without the
counter (the parent commit, no Mamba-2 layers), a configuration without
this family's keys or a run without `decode_bytes_per_step` (untraced)
reads nothing. Moves itl_p95_ms (a token gap is a
decode step, and the step is what these bytes take)."""
from benchmark import flops_nemotron

KEYS = ('mamba_num_heads', 'mamba_head_dim', 'ssm_state_size', 'n_groups')


def read(facts):
    rows = facts.get('counters', {}).get('ssd_state_rows_updated_total')
    m = facts.get('config', {})
    steps, need = facts.get('decode_steps'), \
        facts.get('decode_bytes_per_step')
    if not rows or not steps or not need or any(k not in m for k in KEYS):
        return None
    active = rows / float(flops_nemotron.n_layers(m, 'M')) / steps
    return 100.0 * 2 * active * flops_nemotron.state_bytes_per_slot(m) / need
