"""Operations and bytes of an Ouro-shaped model (`model_type: ouro`: a stack
of full-attention layers with a dense SiLU-gated FFN and FOUR RMSNorms a
layer, run `total_ut_steps` times a token over ONE set of weights, the final
norm and an exit gate after every pass, an untied head), from shapes alone.
`m` is a configuration file with the keys of the source's config.json
(benchmark/configs/ouro-*.json). The cache is indexed by (pass, layer): a
token holds K and V of every layer ONCE A PASS. benchmark/flops.py keeps the
dense LM's formulae and the table of peaks."""


def passes(m):
    return int(m['total_ut_steps'])


def layer_param_count(m):
    """A layer: q, k, v, o (no bias), gate, up and down, four norms:
    4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048 = 51 388 416 at the published
    widths."""
    d = m['hidden_size']
    width = m['num_attention_heads'] * m['head_dim']
    kv = m['num_key_value_heads'] * m['head_dim']
    return d * (width + 2 * kv) + width * d \
        + 3 * d * m['intermediate_size'] + 4 * d


def param_count(m):
    """Table + layers + final RMSNorm + the exit gate (w [d, 1], b [1]) +
    untied head: 612 438 017 in the 8-layer cut, 2 668.0 M whole. The
    passes add none."""
    d, v = m['hidden_size'], m['vocab_size']
    return 2 * v * d + d + d + 1 \
        + m['num_hidden_layers'] * layer_param_count(m)


def cache_layers(m):
    """The K/V pools' layers: one a (pass, layer)."""
    return passes(m) * m['num_hidden_layers']


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE cache layer (one layer, one pass)."""
    return 2 * m['num_key_value_heads'] * m['head_dim'] * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V one cached token holds: every layer's, once a pass: 8 x 4 x
    16 384 = 524 288 B in the cut that is served."""
    return cache_layers(m) * kv_row_bytes(m, dtype_bytes)


def loop_weight_stream_bytes(m, dtype_bytes=4):
    """Bytes of weights one decode step has to stream: the layers' weights
    ONCE A PASS (nothing of them stays on the chip between two passes: a
    pass's 1.64 GB is a hundred times the vector memory), the final norm
    and the gate with them, and the head once: 4 x 1.644 + 0.403 = 6.98 GB
    in the cut that is served. The table gives up a row a slot, which
    `decode_bytes_per_step` counts."""
    d = m['hidden_size']
    return (passes(m) * (m['num_hidden_layers'] * layer_param_count(m)
                         + 2 * d + 1)
            + d * m['vocab_size']) * dtype_bytes


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: `loop_weight_stream_bytes`, a row
    of the table an active slot, and the K and V of the live context in
    every cache layer -- every pass walks its own."""
    return loop_weight_stream_bytes(m, dtype_bytes) \
        + active_slots * m['hidden_size'] * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes)


def paged_decode_attention_bytes(m, kv_tokens_read, dtype_bytes=4):
    """Bytes the paged decode attention has to read for `kv_tokens_read`
    (token, cache layer) rows (serving/generate.py kv_tokens_read_total,
    booked for the pool's ``shape[1]`` = passes x layers cache layers): K
    and V of the K/V heads, once."""
    return kv_tokens_read * kv_row_bytes(m, dtype_bytes)
