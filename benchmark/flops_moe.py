"""Operations and bytes of an OLMoE-shaped model (RMSNorm, q/k-norm, no
bias, a top-k-of-E expert FFN), from shapes alone. `m` is a configuration
file with the keys of the source's config.json
(benchmark/configs/olmoe-*.json). benchmark/flops.py keeps the dense LM's
formulae and the table of peaks."""


def expert_param_count(m):
    """One expert: gate, up and down, each hidden_size x intermediate_size
    (the catalog reads intermediate_size as the width of ONE expert)."""
    return 3 * m['hidden_size'] * m['intermediate_size']


def layer_param_count(m):
    """One layer, experts included: qkv and output projections (no bias),
    q_norm and k_norm over the whole projected width, two RMSNorms, the
    router, the experts."""
    d = m['hidden_size']
    width = m['num_attention_heads'] * (d // m['num_attention_heads'])
    return d * 3 * width + width * d + 2 * width + 2 * d \
        + d * m['num_experts'] + m['num_experts'] * expert_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head."""
    d, v = m['hidden_size'], m['vocab_size']
    return v * d + m['num_hidden_layers'] * layer_param_count(m) + d + d * v


def expected_experts_touched(m, rows):
    """Experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok of num_experts, in EXPECTATION UNDER EVEN ROUTING
    (every expert equally likely, rows independent):
    E * (1 - (1 - k/E)^rows)."""
    e, k = float(m['num_experts']), float(m['num_experts_per_tok'])
    return e * (1.0 - (1.0 - k / e) ** rows)


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds over all layers."""
    width = m['num_attention_heads'] * (m['hidden_size']
                                        // m['num_attention_heads'])
    return 2 * m['num_hidden_layers'] * width * dtype_bytes


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """Bytes moe_ffn's grouped matmuls (gate, up, down) have to move for
    `assignments` (row, expert) pairs that touch `experts_touched`
    (layer, expert) pairs, summed over any number of layers and
    dispatches: each touched expert's three matrices once, and per
    assignment the gathered row in, gate and up out, their product in,
    the result out. The router, the sort and the gathers are other
    operations and are not counted."""
    d, w = m['hidden_size'], m['intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the experts
    and the embedding table once (the table gives up one row per active
    slot); per layer the weights of the experts the step's `active_slots`
    rows touch — `expected_experts_touched`, the expectation under even
    routing, NOT the step's own count (`moe_experts_touched_share` reads
    that) —; and the K/V rows of the live context. What the program reads
    beyond that is its overhead, which `decode_hbm_share` exposes."""
    n = m['num_hidden_layers']
    dense = param_count(m) - m['vocab_size'] * m['hidden_size'] \
        - n * m['num_experts'] * expert_param_count(m)
    experts = n * expected_experts_touched(m, active_slots) \
        * expert_param_count(m)
    emb_rows = active_slots * m['hidden_size']
    return (dense + experts + emb_rows) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes)
