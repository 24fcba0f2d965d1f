"""flops.py against a hand count for a 2-layer toy, and the table of
peaks."""
import pytest

from benchmark import flops

TOY = {'num_layers': 2, 'd_model': 8, 'ffn_dim': 16, 'vocab_size': 10,
       'attention_heads': 2, 'head_dim': 4}


def test_param_count_by_hand():
    # layer: qkv 8*24+24, proj 8*8+8, ffn1 8*16+16, ffn2 16*8+8, 2 LN 4*8
    layer = 216 + 72 + 144 + 136 + 32
    assert flops.lm_param_count(TOY) == 10 * 8 + 2 * layer + 16 + 8 * 10


def test_forward_flops_by_hand():
    # per token: matmul weights 2*(4*64 + 2*128) + head 80 = 1104, x2;
    # attention 2 layers * 2 products * 2 * d 8 * (seq 4 + 1)/2 keys
    assert flops.lm_matmul_weight_count(TOY) == 1104
    assert flops.lm_forward_flops_per_token(TOY, 4) == 2 * 1104 + 2 * 2 * 2 * 8 * 2.5
    assert flops.lm_train_flops_per_token(TOY, 4) == 3 * (2208 + 160)


def test_causal_half_not_the_full_square():
    full_square = 2 * 2 * 2 * 8 * 4          # what bench.py charges
    causal = flops.lm_forward_flops_per_token(TOY, 4) - 2 * 1104
    assert causal == full_square * (4 + 1) / (2 * 4)


def test_355m_is_2_4_gflop_a_token():
    m = {'num_layers': 24, 'd_model': 1024, 'ffn_dim': 4096,
         'vocab_size': 50264}
    assert flops.lm_train_flops_per_token(m, 2048) == pytest.approx(2.42e9,
                                                                     rel=0.01)
    assert flops.lm_param_count(m) == pytest.approx(405e6, rel=0.01)
    assert flops.lm_kv_bytes_per_token(m) == 196608


def test_decode_bytes_by_hand():
    weights = flops.lm_param_count(TOY) - 80
    kv = 2 * 2 * 8 * 4
    assert flops.lm_kv_bytes_per_token(TOY) == kv
    assert flops.lm_decode_bytes_per_step(TOY, 100, 3) == \
        (weights + 3 * 8) * 4 + 100 * kv


def test_peaks_exact_kind_or_error():
    p = flops.peaks_for('TPU v5 lite')
    assert p['bf16_flops_per_s'] == 197e12 and p['hbm_bytes_per_s'] == 819e9
    for kind in ('TPU v5', 'TPU v5p', 'cpu', ''):
        with pytest.raises(KeyError):
            flops.peaks_for(kind)
