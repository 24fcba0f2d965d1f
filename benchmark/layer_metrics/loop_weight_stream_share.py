"""Model step (models/transformer.py `LMConfig.passes`: the layer stack run
`total_ut_steps` times a token over one set of weights). The weights a decode
step has to stream, as a share of the step: benchmark/flops_ouro.py
`loop_weight_stream_bytes` -- the layers' weights ONCE A PASS (XLA keeps
nothing of a pass's 1.64 GB on the chip for the next), the final norm and the
exit gate with them, the head once -- / peak HBM bytes/s / the mean
decode_step_seconds of the window (host clock round a step that ends in a
fetch, as `decode_step_ms` reads it), in percent.

It is what the passes cost that a one-pass model of this depth would not
(three of the four streams), and what a narrower serving dtype (ROADMAP S4b)
or a pass fused over a resident layer would cut; `attention_kv_step_share.loop`
is its counterpart, the K/V the same step walks.

A window without a decode step, or a configuration without this family's
keys, reads nothing. Moves serve_tokens_per_s (a decode step gives every slot
a token)."""
from benchmark import flops_ouro

KEYS = ('total_ut_steps', 'num_hidden_layers', 'hidden_size', 'head_dim',
        'intermediate_size', 'num_attention_heads', 'num_key_value_heads',
        'vocab_size')


def read(facts):
    m = facts.get('config', {})
    n, total = facts.get('histograms', {}).get('decode_step_seconds', (0, 0))
    if not n or not total or any(k not in m for k in KEYS):
        return None
    least_s = flops_ouro.loop_weight_stream_bytes(m) \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / (total / n)
