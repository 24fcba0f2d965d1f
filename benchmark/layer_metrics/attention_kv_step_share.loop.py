"""Model step (models/transformer.py `LMConfig.passes`, counted by
serving/generate.py). The K and V a decode step has to walk, as a share of
the step: the live tokens a step reads x benchmark/flops_ouro.py
`kv_bytes_per_token` (K and V of every layer ONCE A PASS: 524 288 B in the
cut that is served) / peak HBM bytes/s / the mean decode_step_seconds of the
window, in percent -- `loop_weight_stream_share`'s counterpart. It GROWS with
the context where the weights' share does not.

The live tokens are kv_tokens_read_total / the pools' cache layers (passes x
layers: the engine books the series for the pool's ``shape[1]`` layers) / the
window's decode steps -- each slot's own context, as the kernel reads it, and
as `attention_kv_step_share` takes them (PERF.md section 7, after PR 58, k).

A program without the counter, a window without a decode step, or a
configuration without this family's keys reads nothing. Moves
serve_tokens_per_s (a decode step gives every slot a token)."""
from benchmark import flops_ouro

KEYS = ('total_ut_steps', 'num_hidden_layers', 'num_key_value_heads',
        'head_dim')


def read(facts):
    m = facts.get('config', {})
    rows = facts.get('counters', {}).get('kv_tokens_read_total')
    steps = facts.get('decode_steps')
    n, total = facts.get('histograms', {}).get('decode_step_seconds', (0, 0))
    if not rows or not steps or not n or not total \
            or any(k not in m for k in KEYS):
        return None
    tokens = rows / float(flops_ouro.cache_layers(m)) / steps
    least_s = tokens * flops_ouro.kv_bytes_per_token(m) \
        / facts['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / (total / n)
