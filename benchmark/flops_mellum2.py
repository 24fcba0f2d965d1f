"""Operations and bytes of a Mellum-2-shaped model (`model_type: mellum`:
window and full attention layers mixed, grouped queries, every layer's FFN
softmax-routed experts with no shared expert and no dense layer, an untied
head), from shapes alone. `m` is a configuration file with the keys of the
source's config.json (benchmark/configs/mellum2-*.json):
`moe_intermediate_size` is an expert's width (`intermediate_size`, a dense
layer's, is used by no layer) and every expert is held here.
benchmark/flops.py keeps the dense LM's formulae and the table of peaks."""


def layer_types(m):
    return list(m['layer_types'][:m['num_hidden_layers']])


def n_global_layers(m):
    return layer_types(m).count('full_attention')


def n_window_layers(m):
    return layer_types(m).count('sliding_attention')


def expert_param_count(m):
    """One expert: gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def attention_param_count(m):
    """q, k, v, o and the two per-head norm weights."""
    d, dh = m['hidden_size'], m['head_dim']
    q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
    return d * (q + 2 * kv) + q * d + 2 * dh


def layer_param_count(m):
    """One layer: attention, two RMSNorms, the router and every expert."""
    d = m['hidden_size']
    return attention_param_count(m) + 2 * d + d * m['num_experts'] \
        + m['num_experts'] * expert_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head."""
    d, v = m['hidden_size'], m['vocab_size']
    return v * d + m['num_hidden_layers'] * layer_param_count(m) + d + d * v


def expected_experts_touched(m, rows):
    """Experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok, in EXPECTATION UNDER EVEN ROUTING:
    E * (1 - (1 - k/E)^rows)."""
    e, k = float(m['num_experts']), float(m['num_experts_per_tok'])
    return e * (1.0 - (1.0 - k / e) ** rows)


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * m['head_dim'] * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds for as long as its request
    lives: the FULL-attention layers' alone. (A window layer keeps a slot's
    last `sliding_window` rows whatever the context.)"""
    return n_global_layers(m) * kv_row_bytes(m, dtype_bytes)


def window_bytes_per_slot(m, dtype_bytes=4):
    """What a decode step reads of the window layers' pools for one slot
    whose context has passed the window."""
    return n_window_layers(m) * m['sliding_window'] \
        * kv_row_bytes(m, dtype_bytes)


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """flops_moe.grouped_matmul_bytes on this configuration's keys: each
    touched (layer, expert) pair's three matrices once, and per assignment
    the gathered row in, gate and up out, their product in, the result
    out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def grouped_matmul_flops(m, assignments):
    """Its operations: three matmuls of one row by [d, w] an assignment."""
    return 2.0 * assignments * expert_param_count(m)


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the experts
    and the embedding table once (the table gives up one row per active
    slot); per layer the weights of the experts the step's `active_slots`
    rows touch, in expectation under even routing; the full layers' K/V
    rows of the live context; and a window's worth of the window layers' a
    slot. `live_tokens` counts the allocator's blocks in use, a shared
    prefix's ONCE: each slot that shares it reads it, so this under-reads
    by (sharers - 1) x the prefix (PERF.md section 7)."""
    dense = param_count(m) - m['vocab_size'] * m['hidden_size'] \
        - m['num_hidden_layers'] * m['num_experts'] * expert_param_count(m)
    experts = m['num_hidden_layers'] \
        * expected_experts_touched(m, active_slots) * expert_param_count(m)
    emb_rows = active_slots * m['hidden_size']
    return (dense + experts + emb_rows) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + active_slots * window_bytes_per_slot(m, dtype_bytes)
