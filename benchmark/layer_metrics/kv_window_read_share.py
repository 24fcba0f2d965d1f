"""Model step (models/transformer.py window layers, counted by
serving/generate.py). Of the K/V rows a decode step's attention would
read if every attention layer saw every key, the share it had to read:
(kv_tokens_read_total + kv_window_tokens_read_total) / (live tokens x
attention layers), both counters as they moved over the window, in
percent. kv_tokens_read_total is the live tokens x the GLOBAL layers, so
the live tokens are that count over the configuration's number of global
layers. One global layer in five and windows of 128 keys against contexts
of ~2 k read ~25 %; a model without window layers would read 100 and has
no such counter. A program without the counter (the parent commit, no
window layers) or a configuration without `sliding_window` reads nothing.
Lower is better. Moves serve_tokens_per_s."""
from benchmark import flops_kexaone


def read(facts):
    c = facts.get('counters', {})
    m = facts.get('config', {})
    seen, windowed = c.get('kv_tokens_read_total'), \
        c.get('kv_window_tokens_read_total')
    if not seen or windowed is None or 'sliding_window' not in m:
        return None
    n_global = flops_kexaone.n_global_layers(m)
    every = seen / n_global * (n_global + flops_kexaone.n_window_layers(m))
    return 100.0 * (seen + windowed) / every
