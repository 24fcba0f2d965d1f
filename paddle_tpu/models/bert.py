"""BERT-base pretraining model (SURVEY §7 stage 8 / BASELINE.json north-star
"ERNIE / BERT-base pretraining"): bidirectional encoder with token +
position + segment embeddings, masked-LM head (tied decoder over the
token embedding) and next-sentence head — the reference exercises BERT
through its inference analyzers (inference/tests/api/analyzer_bert_tester
.cc); here it is a first-class trainable model.

TPU notes: attention uses the additive padding-mask path (bidirectional —
the fused causal kernel does not apply); MLM loss gathers only the masked
positions, so the [B*L, V] logits never materialize for unmasked tokens
(the memory-efficient-CE trick applied to BERT).
"""
import numpy as np

from .. import layers
from ..param_attr import ParamAttr
from .transformer import transformer_block, LMConfig

__all__ = ['BertConfig', 'build_bert_pretrain']


class BertConfig(LMConfig):
    def __init__(self, vocab_size=30522, seq_len=128, d_model=768,
                 n_head=12, n_layer=12, d_ff=3072, dropout=0.1,
                 type_vocab_size=2, max_predictions=20, **kw):
        kw.setdefault('use_flash_attention', True)
        super(BertConfig, self).__init__(
            vocab_size=vocab_size, seq_len=seq_len, d_model=d_model,
            n_head=n_head, n_layer=n_layer, d_ff=d_ff, dropout=dropout,
            **kw)
        self.type_vocab_size = type_vocab_size
        self.max_predictions = max_predictions


def build_bert_pretrain(cfg=None, is_test=False):
    """Feeds: tokens/segments [B, L] int64, input_mask [B, L] float32
    (1 = real token), mlm_positions [B, P] int64 (flat positions into the
    [B*L] token stream), mlm_labels [B, P] int64, nsp_labels [B, 1] int64.
    Returns (total_loss, mlm_loss, nsp_loss)."""
    cfg = cfg or BertConfig()
    tokens = layers.data(name='tokens', shape=[cfg.seq_len], dtype='int64')
    segments = layers.data(name='segments', shape=[cfg.seq_len],
                           dtype='int64')
    input_mask = layers.data(name='input_mask', shape=[cfg.seq_len],
                             dtype='float32')
    mlm_pos = layers.data(name='mlm_positions',
                          shape=[cfg.max_predictions], dtype='int64')
    mlm_labels = layers.data(name='mlm_labels',
                             shape=[cfg.max_predictions], dtype='int64')
    nsp_labels = layers.data(name='nsp_labels', shape=[1], dtype='int64')

    tok_emb = layers.embedding(
        tokens, size=[cfg.vocab_size, cfg.d_model],
        param_attr=ParamAttr(name='bert.tok_emb.w'))
    seg_emb = layers.embedding(
        segments, size=[cfg.type_vocab_size, cfg.d_model],
        param_attr=ParamAttr(name='bert.seg_emb.w'))
    x = layers.elementwise_add(tok_emb, seg_emb)
    x = layers.add_position_encoding(x, alpha=1.0, beta=1.0)
    x = layers.layer_norm(x, begin_norm_axis=2,
                          param_attr=ParamAttr(name='bert.emb_ln.w'),
                          bias_attr=ParamAttr(name='bert.emb_ln.b'))
    if cfg.dropout and not is_test:
        x = layers.dropout(x, dropout_prob=cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')

    # per-key additive padding bias [B, L]: 0 real, -1e9 pad — fused into
    # the flash kernel when enabled; otherwise broadcast to [B,1,1,L]
    neg = layers.scale(input_mask, scale=1e9, bias=-1e9)
    attn_drop = getattr(cfg, 'attn_dropout', 0.0)
    flash_ok = getattr(cfg, 'use_flash_attention', False) and \
        (is_test or not attn_drop)
    if flash_ok:
        bias_var = neg
        mask_var = None
    else:
        bias_var = None
        mask_var = layers.reshape(neg, shape=[-1, 1, 1, cfg.seq_len])

    ckpts = []
    # zero pending delta: every block (block 0 included) lowers the same
    # op sequence — see build_lm; x + x*0 is bitwise x
    delta = layers.scale(x, scale=0.0)
    for i in range(cfg.n_layer):
        x, delta = transformer_block(x, cfg, 'bert.layer_%d' % i,
                                     mask_var=mask_var, is_test=is_test,
                                     causal=False,
                                     key_padding_bias=bias_var,
                                     residual=delta, defer_residual=True)
        ckpts.append(x)
    tokens.block.program._lm_checkpoint_vars = ckpts
    # resolve the last block's deferred FFN delta inside the final LN
    # (fused residual-add + LN; tier 'off' is bitwise add + layer_norm)
    x, _ = layers.fused_layer_norm_residual(
        x, delta, begin_norm_axis=2,
        param_attr=ParamAttr(name='bert.final_ln.w'),
        bias_attr=ParamAttr(name='bert.final_ln.b'))

    # --- MLM head: gather only the masked positions
    flat = layers.reshape(x, shape=[-1, cfg.d_model])      # [B*L, D]
    pos_flat = layers.reshape(mlm_pos, shape=[-1])          # [B*P]
    picked = layers.gather(flat, pos_flat)                  # [B*P, D]
    picked = layers.fc(picked, size=cfg.d_model, act='gelu',
                       param_attr=ParamAttr(name='bert.mlm.trans.w'),
                       bias_attr=ParamAttr(name='bert.mlm.trans.b'))
    picked = layers.layer_norm(
        picked, begin_norm_axis=1,
        param_attr=ParamAttr(name='bert.mlm.ln.w'),
        bias_attr=ParamAttr(name='bert.mlm.ln.b'))
    mlm_logits = layers.fc(picked, size=cfg.vocab_size,
                           param_attr=ParamAttr(name='bert.mlm.out.w'),
                           bias_attr=ParamAttr(name='bert.mlm.out.b'))
    mlm_lbl = layers.reshape(mlm_labels, shape=[-1, 1])
    mlm_loss = layers.mean(layers.softmax_with_cross_entropy(
        mlm_logits, mlm_lbl))

    # --- NSP head over the [CLS] (first) position
    first = layers.slice(x, axes=[1], starts=[0], ends=[1])
    pooled = layers.fc(layers.reshape(first, shape=[-1, cfg.d_model]),
                       size=cfg.d_model, act='tanh',
                       param_attr=ParamAttr(name='bert.pooler.w'),
                       bias_attr=ParamAttr(name='bert.pooler.b'))
    nsp_logits = layers.fc(pooled, size=2,
                           param_attr=ParamAttr(name='bert.nsp.w'),
                           bias_attr=ParamAttr(name='bert.nsp.b'))
    nsp_loss = layers.mean(layers.softmax_with_cross_entropy(
        nsp_logits, nsp_labels))

    total = layers.elementwise_add(mlm_loss, nsp_loss)
    return total, mlm_loss, nsp_loss


def make_pretrain_batch(cfg, batch, rng, toks=None):
    """Synthetic pretraining batch with the BERT feed contract. `toks`
    overrides the uniform-random token stream (shape [batch, L]) so
    structured corpora (e.g. tools/convergence.py's Markov teacher) share
    this masking/flat-position/[MASK]-id contract instead of copying
    it."""
    L, P = cfg.seq_len, cfg.max_predictions
    if toks is None:
        toks = rng.randint(4, cfg.vocab_size, (batch, L)).astype('int64')
    else:
        toks = np.asarray(toks, 'int64')
        assert toks.shape == (batch, L), (toks.shape, batch, L)
    segs = np.zeros((batch, L), 'int64')
    segs[:, L // 2:] = 1
    mask = np.ones((batch, L), 'float32')
    # vectorized uniform P-subset without replacement (same distribution
    # as a per-row rng.choice loop, one draw for the whole batch)
    pos = np.argsort(rng.rand(batch, L), axis=1)[:, :P]
    flat_pos = (pos + np.arange(batch)[:, None] * L).astype('int64')
    labels = np.take_along_axis(toks, pos, axis=1).astype('int64')
    toks_masked = toks.copy()
    np.put_along_axis(toks_masked, pos, 3, axis=1)   # [MASK] id = 3
    nsp = rng.randint(0, 2, (batch, 1)).astype('int64')
    return {'tokens': toks_masked, 'segments': segs, 'input_mask': mask,
            'mlm_positions': flat_pos, 'mlm_labels': labels,
            'nsp_labels': nsp}
