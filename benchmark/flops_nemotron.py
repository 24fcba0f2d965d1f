"""Operations and bytes of a Nemotron-H-shaped model (`model_type:
nemotron_h`: every layer ONE sublayer by the letters of
`hybrid_override_pattern` -- `M` a Mamba-2 mixer, `*` grouped-query
attention without rotation, `E` sigmoid-routed ungated relu^2 experts
beside a shared one -- and an untied head), from shapes alone. `m` is a
configuration file with the keys of the source's config.json
(benchmark/configs/nemotron-*.json): `n_routed_experts` is what THIS chip
holds of `reduced_from.n_routed_experts` (the router's width),
`vocab_size` its slice of the vocabulary, `hybrid_override_pattern` the
letters of the layers held. benchmark/flops.py keeps the dense LM's
formulae and the table of peaks."""


def pattern(m):
    return m['hybrid_override_pattern'][:m['num_hidden_layers']]


def n_layers(m, letter):
    return pattern(m).count(letter)


def d_inner(m):
    """Channels of a Mamba-2 layer's recurrence: heads x head size (NOT
    `expand` x hidden_size: the modelling code leaves `expand` unused)."""
    return m['mamba_num_heads'] * m['mamba_head_dim']


def conv_width(m):
    """Channels of its convolution: x, and B and C of every group."""
    return d_inner(m) + 2 * m['n_groups'] * m['ssm_state_size']


def router_width(m):
    return m.get('reduced_from', {}).get('n_routed_experts',
                                         m['n_routed_experts'])


def expert_param_count(m):
    """One routed expert: up and down, no gate."""
    return 2 * m['hidden_size'] * m['moe_intermediate_size']


def layer_param_count(m, letter, experts=None):
    """One layer with its one RMSNorm. `M`: in (D x (2 d_inner + 2 G N +
    H)), the taps and their bias, dt's bias, A_log and D a head, the gated
    norm's weight, out. `*`: q, k, v, o. `E`: the router (all its outputs,
    with its correction bias), the shared expert's two matrices and
    `experts` routed experts (default: those held)."""
    d = m['hidden_size']
    if letter == 'M':
        di, h = d_inner(m), m['mamba_num_heads']
        return d + d * (di + conv_width(m) + h) \
            + conv_width(m) * (m['conv_kernel'] + 1) + 3 * h + di + di * d
    if letter == '*':
        dh = m['head_dim']
        q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
        return d + d * (q + 2 * kv) + q * d
    held = m['n_routed_experts'] if experts is None else experts
    return d + d * router_width(m) + router_width(m) \
        + 2 * d * m['moe_shared_expert_intermediate_size'] \
        + held * expert_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head, the vocabulary's
    slice."""
    d, v = m['hidden_size'], m['vocab_size']
    return 2 * v * d + d + sum(layer_param_count(m, letter)
                               for letter in pattern(m))


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * m['head_dim'] * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds: the attention layers' alone
    (6144 B over three layers). The Mamba-2 layers' state is a slot's, not
    a token's: `state_bytes_per_slot`."""
    return n_layers(m, '*') * kv_row_bytes(m, dtype_bytes)


def state_row_bytes(m, dtype_bytes=4):
    """ONE Mamba-2 layer's state and convolution tail of one slot: ``H x P
    x N`` numbers and ``K - 1`` rows of the convolution's channels."""
    return (d_inner(m) * m['ssm_state_size']
            + (m['conv_kernel'] - 1) * conv_width(m)) * dtype_bytes


def state_bytes_per_slot(m, dtype_bytes=4):
    """What one slot keeps in the Mamba-2 layers' pools, whatever its
    context: 9 x 2 170 880 = 19 537 920 B in the cut that is served."""
    return n_layers(m, 'M') * state_row_bytes(m, dtype_bytes)


def ssd_decode_state_bytes(m, state_rows_updated, dtype_bytes=4):
    """Bytes the decode update has to move for `state_rows_updated` (slot,
    Mamba-2 layer) rows (serving/generate.py ssd_state_rows_updated_total):
    each row's state and tail read once and written once."""
    return 2 * state_rows_updated * state_row_bytes(m, dtype_bytes)


def grouped_matmul_bytes(m, experts_touched, assignments, dtype_bytes=4):
    """Bytes the expert layers' grouped matmuls have to move: each touched
    (layer, held expert) pair's TWO matrices once, and per computed
    assignment the gathered row in, up out, its square in, the result
    out."""
    d, w = m['hidden_size'], m['moe_intermediate_size']
    return (experts_touched * expert_param_count(m)
            + assignments * (d + 2 * w + d)) * dtype_bytes


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight once (at the cell's
    128 rows every held expert is touched: 6 rows an expert a layer); the
    attention layers' K/V rows of the live context; and each active slot's
    state and tails, read and written."""
    return param_count(m) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + 2 * active_slots * state_bytes_per_slot(m, dtype_bytes)
