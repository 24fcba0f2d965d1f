"""Operations and bytes of an LFM2-MoE-shaped model (gated short
convolutions, grouped-query attention in some layers, `num_dense_layers`
dense layers, then sigmoid-routed experts, a tied head), from shapes alone.
`m` is a configuration file with the keys of the source's config.json
(benchmark/configs/lfm2-*.json): `intermediate_size` is the DENSE layers'
width and `moe_intermediate_size` an expert's (flops_moe.py reads the
first as an expert's, OLMoE's key: not for this file). benchmark/flops.py
keeps the dense LM's formulae and the table of peaks."""


def head_dim(m):
    return m.get('head_dim') or m['hidden_size'] // m['num_attention_heads']


def layer_types(m):
    return list(m['layer_types'][:m['num_hidden_layers']])


def n_attn_layers(m):
    return layer_types(m).count('full_attention')


def n_conv_layers(m):
    return layer_types(m).count('conv')


def n_moe_layers(m):
    return m['num_hidden_layers'] - m['num_dense_layers']


def expert_param_count(m):
    """One expert: gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def mixer_param_count(m, kind):
    """A convolution mixer: in (D x 3D), the taps, out (D x D). An
    attention mixer: q, k, v, o and the two per-head norm weights."""
    d = m['hidden_size']
    if kind == 'conv':
        return 3 * d * d + d * m['conv_L_cache'] + d * d
    dh = head_dim(m)
    q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
    return d * (q + 2 * kv) + q * d + 2 * dh


def layer_param_count(m, layer):
    """One layer: its mixer, two RMSNorms, and the dense FFN or the router
    (with its bias) and every expert."""
    d = m['hidden_size']
    n = mixer_param_count(m, layer_types(m)[layer]) + 2 * d
    if layer < m['num_dense_layers']:
        return n + 3 * d * m['intermediate_size']
    return n + d * m['num_experts'] + m['num_experts'] \
        + m['num_experts'] * expert_param_count(m)


def param_count(m):
    """The embedding table (it is the head as well) + layers + the final
    RMSNorm."""
    d = m['hidden_size']
    return m['vocab_size'] * d + d + sum(
        layer_param_count(m, i) for i in range(m['num_hidden_layers']))


def expected_experts_touched(m, rows):
    """Experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok of num_experts, in EXPECTATION UNDER EVEN ROUTING:
    E * (1 - (1 - k/E)^rows)."""
    e, k = float(m['num_experts']), float(m['num_experts_per_tok'])
    return e * (1.0 - (1.0 - k / e) ** rows)


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * head_dim(m) * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds: the attention layers' alone.
    (The convolution layers' tails are a block's, not a token's:
    `tail_bytes_per_block`.)"""
    return n_attn_layers(m) * kv_row_bytes(m, dtype_bytes)


def tail_bytes_per_block(m, dtype_bytes=4):
    """The convolution layers' tails one block of the pool holds."""
    return n_conv_layers(m) * (m['conv_L_cache'] - 1) * m['hidden_size'] \
        * dtype_bytes


def paged_decode_attention_bytes(m, kv_tokens_read, dtype_bytes=4):
    """Bytes the paged decode attention has to read for `kv_tokens_read`
    (token, attention layer) rows (serving/generate.py
    kv_tokens_read_total): K and V of the K/V heads, once — not once a
    query head."""
    return kv_tokens_read * kv_row_bytes(m, dtype_bytes)


def paged_decode_attention_flops(m, kv_tokens_read):
    """Its operations: every QUERY head's score and weighted sum over each
    row read."""
    return 4.0 * kv_tokens_read * m['num_attention_heads'] * head_dim(m)


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """flops_moe.grouped_matmul_bytes on this configuration's keys: each
    touched (layer, expert) pair's three matrices once, and per computed
    assignment the gathered row in, gate and up out, their product in, the
    result out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the experts
    once (the table too: it is the head); per expert layer the weights of
    the experts the step's `active_slots` rows touch, in expectation under
    even routing; the K/V rows of the live context; and a slot's tails,
    read and written."""
    dense = param_count(m) \
        - n_moe_layers(m) * m['num_experts'] * expert_param_count(m)
    experts = n_moe_layers(m) * expected_experts_touched(m, active_slots) \
        * expert_param_count(m)
    return (dense + experts) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + 2 * active_slots * tail_bytes_per_block(m, dtype_bytes)
