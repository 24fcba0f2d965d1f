"""Operations and bytes of a Qwen3-Next-shaped model (`model_type:
qwen3_next`: three Gated DeltaNet layers to every gated full-attention
layer, every layer's FFN softmax-routed experts beside a gated shared one,
an untied head), from shapes alone. `m` is a configuration file with the
keys of the source's config.json (benchmark/configs/qwen3-next-*.json):
`num_experts` is what THIS chip holds of `reduced_from.num_experts` (the
router's width), `vocab_size` its slice of the vocabulary.
benchmark/flops.py keeps the dense LM's formulae and the table of peaks."""


def is_full(m, i):
    return (i + 1) % m['full_attention_interval'] == 0


def n_full_layers(m):
    return sum(is_full(m, i) for i in range(m['num_hidden_layers']))


def n_gdn_layers(m):
    return m['num_hidden_layers'] - n_full_layers(m)


def router_width(m):
    return m.get('reduced_from', {}).get('num_experts', m['num_experts'])


def key_width(m):
    return m['linear_num_key_heads'] * m['linear_key_head_dim']


def value_width(m):
    return m['linear_num_value_heads'] * m['linear_value_head_dim']


def conv_width(m):
    """Channels of a DeltaNet layer's convolution: q, k and v."""
    return 2 * key_width(m) + value_width(m)


def expert_param_count(m):
    """One routed expert: gate, up and down."""
    return 3 * m['hidden_size'] * m['moe_intermediate_size']


def mixer_param_count(m, full):
    """A layer's mixer. Full attention: q with its gate, k, v, o and the
    two per-head norm weights. DeltaNet: W_in (q, k, v, z), W_ba, the taps,
    A_log and dt_bias a value head, the output norm's one weight, W_out."""
    d = m['hidden_size']
    if full:
        dh = m['head_dim']
        q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
        return d * (2 * q + 2 * kv) + q * d + 2 * dh
    hv = m['linear_num_value_heads']
    return d * (conv_width(m) + value_width(m)) + d * 2 * hv \
        + conv_width(m) * m['linear_conv_kernel_dim'] + 2 * hv \
        + m['linear_value_head_dim'] + value_width(m) * d


def ffn_param_count(m, experts=None):
    """A layer's FFN: the router (all its outputs), the shared expert with
    its gate, and `experts` routed experts (default: those held)."""
    d = m['hidden_size']
    held = m['num_experts'] if experts is None else experts
    return d * router_width(m) \
        + 3 * d * m['shared_expert_intermediate_size'] + d \
        + held * expert_param_count(m)


def layer_param_count(m, i, experts=None):
    """Layer `i` with its two RMSNorms."""
    return 2 * m['hidden_size'] + mixer_param_count(m, is_full(m, i)) \
        + ffn_param_count(m, experts)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head, the vocabulary's
    slice."""
    d, v = m['hidden_size'], m['vocab_size']
    return 2 * v * d + d + sum(layer_param_count(m, i)
                               for i in range(m['num_hidden_layers']))


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE full-attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * m['head_dim'] * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds: the full-attention layers'
    alone (8 192 B over two layers). The DeltaNet layers' state is a
    slot's, not a token's: `state_bytes_per_slot`."""
    return n_full_layers(m) * kv_row_bytes(m, dtype_bytes)


def state_row_bytes(m, dtype_bytes=4):
    """ONE DeltaNet layer's state and convolution tail of one slot: ``Hv x
    dk x dv`` numbers and ``K - 1`` rows of the convolution's channels."""
    return (m['linear_key_head_dim'] * value_width(m)
            + (m['linear_conv_kernel_dim'] - 1) * conv_width(m)) \
        * dtype_bytes


def state_bytes_per_slot(m, dtype_bytes=4):
    """What one slot keeps in the DeltaNet layers' pools, whatever its
    context: 6 x 2 195 456 = 13 172 736 B in the cut that is served."""
    return n_gdn_layers(m) * state_row_bytes(m, dtype_bytes)


def gdn_decode_state_bytes(m, state_rows_updated, dtype_bytes=4):
    """Bytes the decode update has to move for `state_rows_updated` (slot,
    DeltaNet layer) rows (serving/generate.py gdn_state_rows_updated_total):
    each row's state and tail read once and written once."""
    return 2 * state_rows_updated * state_row_bytes(m, dtype_bytes)


def gdn_prefill_chunk_flops(m, rows, chunk=64):
    """Matmul operations the chunked delta rule needs for `rows` (real row,
    DeltaNet layer) pairs (gdn_prefill_rows_total), from the chunked form's
    equations, a value head and a block of C rows: K K^T and Q K^T (2 C^2
    dk each), the triangular solve of [U | W] counted as ONE dense product
    T R (2 C^2 (dv + dk)), W S and Q S (2 C dk dv each), the inner product
    with V' (2 C^2 dv) and the state's update (2 C dk dv): per row 2 (2 C dk
    + C (dv + dk) + 3 dk dv + C dv). The kernel's own way to the inverse (a
    substitution on the VPU, rounds of a nilpotent product) costs more and
    is its overhead."""
    dk, dv = m['linear_key_head_dim'], m['linear_value_head_dim']
    c = chunk
    per_row = 2.0 * (2 * c * dk + c * (dv + dk) + 3 * dk * dv + c * dv)
    return rows * m['linear_num_value_heads'] * per_row


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """Bytes the expert layers' grouped matmuls have to move: each touched
    (layer, held expert) pair's three matrices once, and per computed
    assignment the gathered row in, gate and up out, their product in, the
    result out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 3 * w + d)) * dtype_bytes


def expected_experts_touched(m, rows):
    """HELD experts of one layer that `rows` rows route to, each picking
    num_experts_per_tok of the router's outputs, in EXPECTATION UNDER EVEN
    ROUTING: held x (1 - (1 - k/E)^rows)."""
    e, k = float(router_width(m)), float(m['num_experts_per_tok'])
    return m['num_experts'] * (1.0 - (1.0 - k / e) ** rows)


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight outside the routed
    experts and the embedding table once (the table gives up one row per
    active slot); per layer the held experts the step's `active_slots` rows
    touch, in expectation under even routing; the full-attention layers'
    K/V rows of the live context; and each active slot's state and tails,
    read and written."""
    n = m['num_hidden_layers']
    dense = param_count(m) - m['vocab_size'] * m['hidden_size'] \
        - n * m['num_experts'] * expert_param_count(m)
    experts = n * expected_experts_touched(m, active_slots) \
        * expert_param_count(m)
    return (dense + experts + active_slots * m['hidden_size']) \
        * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + 2 * active_slots * state_bytes_per_slot(m, dtype_bytes)
