"""Operations and bytes of an Olmo-Hybrid-shaped model (`model_type:
olmo_hybrid`: Gated DeltaNet layers to every full-attention layer as
`layer_types` says, a dense SiLU-gated FFN in every layer, an untied head),
from shapes alone. `m` is a configuration file with the keys of the source's
config.json (benchmark/configs/olmo-hybrid-*.json). The delta rule's bytes
and operations are benchmark/flops_qwen3next.py's functions, which read only
the `linear_*` keys and `full_attention_interval`; benchmark/flops.py keeps
the dense LM's formulae and the table of peaks."""
from benchmark import flops_qwen3next

value_width = flops_qwen3next.value_width
conv_width = flops_qwen3next.conv_width
state_row_bytes = flops_qwen3next.state_row_bytes


def is_full(m, i):
    return m['layer_types'][i] == 'full_attention'


def n_full_layers(m):
    return sum(is_full(m, i) for i in range(m['num_hidden_layers']))


def n_gdn_layers(m):
    return m['num_hidden_layers'] - n_full_layers(m)


def head_dim(m):
    return m['hidden_size'] // m['num_attention_heads']


def mixer_param_count(m, full):
    """A layer's mixer. Full attention: q, k, v, o and the two whole-width
    norm weights. DeltaNet: W_in (q, k, v, z), W_ba, the taps, A_log and
    dt_bias a value head, the output norm's one weight, W_out."""
    d = m['hidden_size']
    if full:
        q = m['num_attention_heads'] * head_dim(m)
        kv = m['num_key_value_heads'] * head_dim(m)
        return d * (q + 2 * kv) + q * d + q + kv
    hv = m['linear_num_value_heads']
    return d * (conv_width(m) + value_width(m)) + d * 2 * hv \
        + conv_width(m) * m['linear_conv_kernel_dim'] + 2 * hv \
        + m['linear_value_head_dim'] + value_width(m) * d


def ffn_param_count(m):
    """The dense SiLU-gated FFN: gate, up and down."""
    return 3 * m['hidden_size'] * m['intermediate_size']


def layer_param_count(m, i):
    """Layer `i` with its two RMSNorms: 215.6 M a DeltaNet layer, 185.8 M
    a full-attention one at the published widths."""
    return 2 * m['hidden_size'] + mixer_param_count(m, is_full(m, i)) \
        + ffn_param_count(m)


def param_count(m):
    """Embedding + layers + final RMSNorm + untied head."""
    d, v = m['hidden_size'], m['vocab_size']
    return 2 * v * d + d + sum(layer_param_count(m, i)
                               for i in range(m['num_hidden_layers']))


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE full-attention layer."""
    return 2 * m['num_key_value_heads'] * head_dim(m) * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds: the full-attention layers'
    alone (61 440 B over two layers of 30 heads of 128). The DeltaNet
    layers' state is a slot's, not a token's: `state_bytes_per_slot`."""
    return n_full_layers(m) * kv_row_bytes(m, dtype_bytes)


def state_bytes_per_slot(m, dtype_bytes=4):
    """What one slot keeps in the DeltaNet layers' pools, whatever its
    context: 6 x (30 x 96 x 192 + 3 x 11 520) x 4 B = 14 100 480 B in the
    cut that is served (the pools keep the 3 tail rows in a sublane tile of
    8: 15.48 MB a row)."""
    return n_gdn_layers(m) * state_row_bytes(m, dtype_bytes)


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight but the embedding
    table once (the table gives up one row per active slot); the
    full-attention layers' K/V rows of the live context; and each active
    slot's state and tails, read and written. A prefix's blocks that several
    slots share are counted once a slot that reads them, as the step reads
    them."""
    weights = param_count(m) - m['vocab_size'] * m['hidden_size']
    return (weights + active_slots * m['hidden_size']) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + 2 * active_slots * state_bytes_per_slot(m, dtype_bytes)
