"""Operations and bytes of a K-EXAONE-shaped model (`model_type:
exaone_moe`: window and global attention layers mixed, grouped queries,
`first_k_dense_replace` dense layers, then sigmoid-routed experts beside a
shared one, an untied head), from shapes alone. `m` is a configuration
file with the keys of the source's config.json
(benchmark/configs/k-exaone-*.json): `intermediate_size` is the DENSE
layer's width and `moe_intermediate_size` an expert's; `num_experts` is
what THIS chip holds of `reduced_from.num_experts` (the router's width)
and `vocab_size` its slice of the vocabulary. benchmark/flops.py keeps
the dense LM's formulae and the table of peaks."""


def head_dim(m):
    return m['head_dim']


def layer_types(m):
    return list(m['layer_types'][:m['num_hidden_layers']])


def n_global_layers(m):
    return layer_types(m).count('full_attention')


def n_window_layers(m):
    return layer_types(m).count('sliding_attention')


def router_width(m):
    return m.get('reduced_from', {}).get('num_experts', m['num_experts'])


def n_moe_layers(m):
    return m['num_hidden_layers'] - m['first_k_dense_replace']


def expert_param_count(m):
    """One routed expert (and the shared expert): gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def attention_param_count(m):
    """q, k, v, o and the two per-head norm weights."""
    d, dh = m['hidden_size'], head_dim(m)
    q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
    return d * (q + 2 * kv) + q * d + 2 * dh


def layer_param_count(m, layer):
    """One layer as held here: attention, two RMSNorms, and the dense FFN
    or the router (all its outputs, with its bias), the experts held and
    the shared ones."""
    d = m['hidden_size']
    n = attention_param_count(m) + 2 * d
    if layer < m['first_k_dense_replace']:
        return n + 3 * d * m['intermediate_size']
    return n + d * router_width(m) + router_width(m) \
        + (m['num_experts'] + m['num_shared_experts']) \
        * expert_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head, the vocabulary's
    slice."""
    d, v = m['hidden_size'], m['vocab_size']
    return v * d + sum(layer_param_count(m, i)
                       for i in range(m['num_hidden_layers'])) + d + d * v


def expected_experts_touched(m, rows):
    """Held experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok of ALL the router's experts, in EXPECTATION UNDER
    EVEN ROUTING: held * (1 - (1 - k/E)^rows)."""
    e, k = float(router_width(m)), float(m['num_experts_per_tok'])
    return m['num_experts'] * (1.0 - (1.0 - k / e) ** rows)


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * head_dim(m) * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds for as long as its request
    lives: the GLOBAL layers' alone. (A window layer keeps a slot's last
    `sliding_window` rows whatever the context: `window_bytes_per_slot`.)"""
    return n_global_layers(m) * kv_row_bytes(m, dtype_bytes)


def window_bytes_per_slot(m, dtype_bytes=4):
    """What a decode step reads of the window layers' pools for one slot
    whose context has passed the window."""
    return n_window_layers(m) * m['sliding_window'] \
        * kv_row_bytes(m, dtype_bytes)


def window_decode_attention_bytes(m, window_tokens_read, dtype_bytes=4):
    """Bytes the window layers' paged decode attention has to read for
    `window_tokens_read` (token, window layer) rows (serving/generate.py
    kv_window_tokens_read_total): K and V of the K/V heads, once."""
    return window_tokens_read * kv_row_bytes(m, dtype_bytes)


def window_decode_attention_flops(m, window_tokens_read):
    """Its operations: every QUERY head's score and weighted sum over each
    row read."""
    return 4.0 * window_tokens_read * m['num_attention_heads'] * head_dim(m)


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """flops_moe.grouped_matmul_bytes on this configuration's keys: each
    touched (layer, held expert) pair's three matrices once, and per
    computed assignment the gathered row in, gate and up out, their
    product in, the result out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the routed
    experts and the embedding table once (the table gives up one row per
    active slot); per expert layer the weights of the held experts the
    step's `active_slots` rows touch, in expectation under even routing;
    the global layers' K/V rows of the live context; and a window's worth
    of the window layers' a slot."""
    dense = param_count(m) - m['vocab_size'] * m['hidden_size'] \
        - n_moe_layers(m) * m['num_experts'] * expert_param_count(m)
    experts = n_moe_layers(m) * expected_experts_touched(m, active_slots) \
        * expert_param_count(m)
    emb_rows = active_slots * m['hidden_size']
    return (dense + experts + emb_rows) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + active_slots * window_bytes_per_slot(m, dtype_bytes)
