"""Operations and bytes of a Jamba-shaped model (Mamba-1 layers with
Jamba's three inner norms, multi-query attention in one layer of
`attn_layer_period`, a dense SiLU-gated FFN in every layer, a tied head),
from shapes alone. `m` is a configuration file with the keys of the
source's config.json (benchmark/configs/ai21-jamba2-*.json). Only
`num_experts` 1 is counted: every layer's FFN is the dense one.
benchmark/flops.py keeps the dense LM's formulae and the table of peaks."""


def head_dim(m):
    return m.get('head_dim') or m['hidden_size'] // m['num_attention_heads']


def layer_types(m):
    """'attention' | 'mamba' a layer: HF `JambaConfig.layers_block_type`."""
    return ['attention' if i % m['attn_layer_period']
            == m['attn_layer_offset'] else 'mamba'
            for i in range(m['num_hidden_layers'])]


def n_attn_layers(m):
    return layer_types(m).count('attention')


def n_ssm_layers(m):
    return layer_types(m).count('mamba')


def d_inner(m):
    return m['mamba_expand'] * m['hidden_size']


def mixer_param_count(m, kind):
    """A Mamba mixer: in (D x 2 d_inner), the taps and their bias, x
    (d_inner x (R + 2N)), the three inner norms, dt (R x d_inner) and its
    bias, A_log (d_inner x N), D, out (d_inner x D). An attention mixer: q,
    k, v, o."""
    d = m['hidden_size']
    if kind == 'mamba':
        di, n, r = d_inner(m), m['mamba_d_state'], m['mamba_dt_rank']
        return d * 2 * di + di * m['mamba_d_conv'] + di \
            + di * (r + 2 * n) + r + 2 * n + r * di + di \
            + di * n + di + di * d
    dh = head_dim(m)
    q, kv = m['num_attention_heads'] * dh, m['num_key_value_heads'] * dh
    return d * (q + 2 * kv) + q * d


def layer_param_count(m, layer):
    """One layer: its mixer, two RMSNorms, the dense FFN."""
    d = m['hidden_size']
    return mixer_param_count(m, layer_types(m)[layer]) + 2 * d \
        + 3 * d * m['intermediate_size']


def param_count(m):
    """The embedding table (it is the head as well) + layers + the final
    RMSNorm."""
    d = m['hidden_size']
    return m['vocab_size'] * d + d + sum(
        layer_param_count(m, i) for i in range(m['num_hidden_layers']))


def kv_row_bytes(m, dtype_bytes=4):
    """K and V of one token in ONE attention layer: the K/V heads'."""
    return 2 * m['num_key_value_heads'] * head_dim(m) * dtype_bytes


def kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds: the attention layers' alone.
    (The Mamba layers' state is a slot's, not a token's:
    `state_bytes_per_slot`.)"""
    return n_attn_layers(m) * kv_row_bytes(m, dtype_bytes)


def state_row_bytes(m, dtype_bytes=4):
    """ONE Mamba layer's state and convolution tail of one slot: ``(N + K
    - 1) x d_inner`` numbers."""
    return (m['mamba_d_state'] + m['mamba_d_conv'] - 1) * d_inner(m) \
        * dtype_bytes


def state_bytes_per_slot(m, dtype_bytes=4):
    """What one slot keeps in the Mamba layers' pools, whatever its
    context: 10 117 120 B in Jamba2-3B."""
    return n_ssm_layers(m) * state_row_bytes(m, dtype_bytes)


def ssm_decode_state_bytes(m, state_rows_updated, dtype_bytes=4):
    """Bytes the decode update has to move for `state_rows_updated`
    (slot, Mamba layer) rows (serving/generate.py
    ssm_state_rows_updated_total): each row's state and tail read once and
    written once."""
    return 2 * state_rows_updated * state_row_bytes(m, dtype_bytes)


def ssm_prefill_scan_bytes(m, rows, dispatches, dtype_bytes=4):
    """Bytes the prefill scans have to move for `rows` (real row, Mamba
    layer) pairs (ssm_prefill_rows_total) in `dispatches` (dispatch, Mamba
    layer) scans -- what the scan's OPERATION moves (ops/ssm_ops.py
    `prefill_scan`) and nothing around it: a row's ``delta`` and ``delta *
    u`` in and ``y`` out (``d_inner`` each), its ``B`` and ``C`` (``N``
    each); a scan's state once in and once out. The gate ``silu(z)`` and
    the skip ``D * u`` are applied outside the operation, so ``z`` and
    ``u`` are not the scan's to move."""
    di, n = d_inner(m), m['mamba_d_state']
    return (rows * (3 * di + 2 * n) + dispatches * 2 * n * di) * dtype_bytes


def ssm_scan_flops(m, rows):
    """The recurrence's operations for `rows` (row, Mamba layer) pairs:
    per state entry a product for the decay's exponent, the decay times
    the state, the input's product and sum, the read-out's product and sum
    (the ``exp`` itself not counted: it is the EUP's)."""
    return 6.0 * rows * m['mamba_d_state'] * d_inner(m)


def decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight once (the table
    too: it is the head); the attention layers' K/V rows of the live
    context; and each active slot's state and tails, read and written."""
    return param_count(m) * dtype_bytes \
        + live_tokens * kv_bytes_per_token(m, dtype_bytes) \
        + 2 * active_slots * state_bytes_per_slot(m, dtype_bytes)
