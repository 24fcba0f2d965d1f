"""Operations and bytes the algorithm needs, from shapes alone, and the
table of peaks. Kept with the benchmark so that no PR that claims a gain
can change the yardstick.

`m` below is a configuration file (benchmark/configs/*.json) as a dict.
"""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind):
    """The published peaks of `device_kind` (exact match on the string JAX
    reports). A device that is not in peaks.json is an error, never a
    default."""
    with open(os.path.join(_HERE, 'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError('no peaks for device_kind %r in benchmark/peaks.json '
                       '(known: %s)' % (device_kind, sorted(table)))
    return table[device_kind]


def lm_param_count(m):
    """Parameters of the LM as the repo builds it (untied head, biases,
    two LayerNorms a layer and a final one)."""
    d, f, v, n = m['d_model'], m['ffn_dim'], m['vocab_size'], m['num_layers']
    layer = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) + (f * d + d) \
        + 4 * d
    return v * d + n * layer + 2 * d + d * v


def lm_matmul_weight_count(m):
    """Weights every token is multiplied by: the four matrices of each
    layer and the head (the embedding is a gather, not a matmul)."""
    d, f, v, n = m['d_model'], m['ffn_dim'], m['vocab_size'], m['num_layers']
    return n * (4 * d * d + 2 * d * f) + d * v


def lm_forward_flops_per_token(m, seq_len):
    """Model FLOPs of one causal forward pass, per token of a `seq_len`
    sequence:

      2 * (weights every token is multiplied by)        the GEMMs and head
    + n_layers * 2 * 2 * d_model * (seq_len + 1) / 2    Q.K^T and P.V

    The attention term is the CAUSAL HALF: position i attends to i + 1
    keys, (seq_len + 1) / 2 on average, not the full square. Softmax,
    LayerNorm, GELU, bias and residual adds are not counted (the usual
    convention for model FLOPs)."""
    d, n = m['d_model'], m['num_layers']
    attn = n * 2 * 2 * d * (seq_len + 1) / 2.0
    return 2.0 * lm_matmul_weight_count(m) + attn


def lm_train_flops_per_token(m, seq_len):
    """Forward + backward = 3 x forward (the backward pass computes two
    products for each one of the forward pass). Recomputed operations do
    not count: this is what the model requires, not what the step ran."""
    return 3.0 * lm_forward_flops_per_token(m, seq_len)


def lm_kv_bytes_per_token(m, dtype_bytes=4):
    """K and V rows one cached token holds over all layers."""
    return 2 * m['num_layers'] * m['d_model'] * dtype_bytes


def lm_decode_bytes_per_step(m, live_tokens, active_slots, dtype_bytes=4):
    """Bytes one decode step has to move: every weight but the embedding
    table once (the table gives up one row per active slot), plus the K/V
    rows of the live context of the active slots. What the program reads
    beyond that (whole block tables, cache copies) is the program's
    overhead and is what `decode_hbm_share` exposes."""
    weights = lm_param_count(m) - m['vocab_size'] * m['d_model']
    emb_rows = active_slots * m['d_model']
    return (weights + emb_rows) * dtype_bytes \
        + live_tokens * lm_kv_bytes_per_token(m, dtype_bytes)
