"""Operations and bytes of a JoyAI-LLM-Flash-shaped model (the
DeepSeek-V3 layer: latent attention, `first_k_dense_replace` dense layers,
then sigmoid-routed experts beside shared ones), from shapes alone. `m` is
a configuration file with the keys of the source's config.json
(benchmark/configs/joyai-*.json); `n_routed_experts` is what THIS chip
holds of `reduced_from.n_routed_experts` (the router's width).
benchmark/flops.py keeps the dense LM's formulae and the table of
peaks."""
LANES = 128


def router_width(m):
    return m.get('reduced_from', {}).get('n_routed_experts',
                                         m['n_routed_experts'])


def expert_param_count(m):
    """One routed expert (and one shared expert): gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def attention_param_count(m):
    """q through its bottleneck, the latent down-projection, the two
    norms, both halves of the up-projection, the output projection."""
    d, h = m['hidden_size'], m['num_attention_heads']
    qk = m['qk_nope_head_dim'] + m['qk_rope_head_dim']
    rank = m['kv_lora_rank']
    return d * m['q_lora_rank'] + m['q_lora_rank'] \
        + m['q_lora_rank'] * h * qk \
        + d * (rank + m['qk_rope_head_dim']) + rank \
        + h * rank * (m['qk_nope_head_dim'] + m['v_head_dim']) \
        + h * m['v_head_dim'] * d


def layer_param_count(m, layer):
    """One layer as held here: attention, two RMSNorms, and the dense FFN
    or the router (all its outputs, with its bias), the experts held and
    the shared ones."""
    d = m['hidden_size']
    n = attention_param_count(m) + 2 * d
    if layer < m['first_k_dense_replace']:
        return n + 3 * d * m['intermediate_size']
    return n + d * router_width(m) + router_width(m) \
        + (m['n_routed_experts'] + m['n_shared_experts']) \
        * expert_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head."""
    d, v = m['hidden_size'], m['vocab_size']
    return v * d + sum(layer_param_count(m, i)
                       for i in range(m['num_hidden_layers'])) + d + d * v


def n_moe_layers(m):
    return m['num_hidden_layers'] - m['first_k_dense_replace']


def expected_experts_touched(m, rows):
    """Held experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok of ALL the router's experts, in EXPECTATION UNDER
    EVEN ROUTING: held * (1 - (1 - k/E)^rows)."""
    e, k = float(router_width(m)), float(m['num_experts_per_tok'])
    return m['n_routed_experts'] * (1.0 - (1.0 - k / e) ** rows)


def latent_row_width(m):
    """The numbers one token caches a layer: the latent and the one
    rotary key."""
    return m['kv_lora_rank'] + m['qk_rope_head_dim']


def kv_bytes_per_token(m, dtype_bytes=4):
    """The latent rows one cached token holds over all layers, as the
    algorithm needs them (the pool stores each row filled up to whole
    128-lane tiles: `pool_bytes_per_token`)."""
    return m['num_hidden_layers'] * latent_row_width(m) * dtype_bytes


def pool_bytes_per_token(m, dtype_bytes=4):
    return m['num_hidden_layers'] * dtype_bytes \
        * -(-latent_row_width(m) // LANES) * LANES


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """flops_moe.grouped_matmul_bytes on this configuration's keys: each
    touched (layer, expert) pair's three matrices once, and per computed
    assignment the gathered row in, gate and up out, their product in, the
    result out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def mla_decode_flops(m, latent_tokens):
    """Operations of the absorbed decode attention over `latent_tokens`
    (token, layer) rows read: every head's score over the whole row and
    its weighted sum over the latent."""
    return 2.0 * latent_tokens * m['num_attention_heads'] \
        * (latent_row_width(m) + m['kv_lora_rank'])


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the routed
    experts and the embedding table once (the table gives up one row per
    active slot); per expert layer the weights of the held experts the
    step's `active_slots` rows touch, in expectation under even routing;
    and the latent rows of the live context."""
    dense = param_count(m) - m['vocab_size'] * m['hidden_size'] \
        - n_moe_layers(m) * m['n_routed_experts'] * expert_param_count(m)
    experts = n_moe_layers(m) * expected_experts_touched(m, active_slots) \
        * expert_param_count(m)
    emb_rows = active_slots * m['hidden_size']
    return (dense + experts + emb_rows) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes)
