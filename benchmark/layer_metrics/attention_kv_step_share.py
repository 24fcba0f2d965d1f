"""Model step (models/transformer.py 'attention' layers of a model whose
other layers keep a state, counted by serving/generate.py). Of the bytes one
decode step has to move (`decode_bytes_per_step`: every weight but the table
once, the full-attention layers' live K/V rows, the state), the share that
is the live K/V: the K/V rows a step READS x `kv_bytes_per_token` /
decode_bytes_per_step at those rows, in percent -- `gdn_state_step_share`'s
counterpart. It GROWS with the context where the state's share does not:
with 30 K/V heads of 128 in two layers (61 440 B a token) a 3.5 k-token
context is 12 state rows' worth of K/V, and this share is the larger of the
two.

The rows a step reads are kv_tokens_read_total / the full-attention layers /
the window's decode steps (serving/generate.py: per step, every resident's
position + 1, a layer) -- EACH SLOT'S OWN CONTEXT, a shared prefix's blocks
once a slot that reads them, as the kernel reads them. The driver's
`decode_bytes_per_step` is NOT used: it counts the blocks the allocator has
in use, a shared block once however many slots read it, and under-reads this
cell's K/V by the documents' share (PERF.md section 7); the step's bytes are
computed here from the same function at the rows read.

A program without the counter, a window without a decode step, or a
configuration without this family's keys (`layer_types` naming
``full_attention`` layers beside ``linear_attention`` ones) reads nothing.
Moves serve_tokens_per_s (a decode step gives every slot a token, and the
step is what these bytes take)."""
from benchmark import flops_olmohybrid

KEYS = ('layer_types', 'num_key_value_heads', 'num_attention_heads',
        'hidden_size')


def read(facts):
    m = facts.get('config', {})
    rows = facts.get('counters', {}).get('kv_tokens_read_total')
    steps, active = facts.get('decode_steps'), facts.get('active_slots_mean')
    if not rows or not steps or not active \
            or any(k not in m for k in KEYS) \
            or 'linear_attention' not in m['layer_types']:
        return None
    tokens = rows / float(flops_olmohybrid.n_full_layers(m)) / steps
    return 100.0 * tokens * flops_olmohybrid.kv_bytes_per_token(m) \
        / flops_olmohybrid.decode_bytes_per_step(m, tokens, active)
