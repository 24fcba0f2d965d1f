"""Maps a configuration file of the Mellum 2 family (`model_type: mellum`;
keys as in the source's config.json) onto the repo's LMConfig and names
what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only. Every expert is
held; `intermediate_size` (a dense layer's width) is kept and unused: no
layer is `dense`. `rope_parameters` is keyed by layer kind: the sliding
layers' plain RoPE, the full layers' YaRN. The multi-token-prediction head
that the family's description names has no key in the config and is not
built."""
from benchmark import flops_mellum2

KINDS = {'sliding_attention': 'window', 'full_attention': 'attention'}
YARN = {'factor': 'factor',
        'original_max_position_embeddings': 'original_max_position',
        'beta_fast': 'beta_fast', 'beta_slow': 'beta_slow',
        'attention_factor': 'attention_factor'}


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/mellum2.py: the block is served only '
                         '(build_lm cannot express it)')
    n = m['num_hidden_layers']
    kinds = flops_mellum2.layer_types(m)
    rope = m['rope_parameters']
    for key, want in (
            ('hidden_act', 'silu'), ('tie_word_embeddings', False),
            ('attention_bias', False), ('norm_topk_prob', True),
            ('use_sliding_window', True),
            ('mlp_layer_types', ['sparse'] * n)):
        if m.get(key) != want:
            raise ValueError('models/mellum2.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if len(kinds) != n or set(kinds) - set(KINDS):
        raise ValueError('models/mellum2.py: layer_types %r for %d layers'
                         % (kinds, n))
    if sorted(rope) != sorted(KINDS) \
            or rope['sliding_attention'].get('rope_type') != 'default' \
            or rope['full_attention'].get('rope_type') != 'yarn':
        raise ValueError('models/mellum2.py builds rope_parameters keyed by '
                         'layer kind, the sliding layers default and the '
                         'full layers yarn; the file says %r' % (rope,))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    full = rope['full_attention']
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'], head_dim=m['head_dim'],
        n_layer=n, layer_types=[KINDS[k] for k in kinds],
        sliding_window=m['sliding_window'],
        d_ff=m['intermediate_size'], dropout=0.0, attn_dropout=0.0,
        use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], position='rope',
        rope_theta=float(rope['sliding_attention']['rope_theta']),
        attention_rope=dict(
            {ours: full[theirs] for theirs, ours in YARN.items()},
            theta=float(full['rope_theta'])),
        qk_norm='head', bias=False, ffn='moe', n_dense_layers=0,
        n_experts=m['num_experts'],
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=True, moe_score='softmax',
        matmul_precision=m.get('matmul_precision'))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. q, k and v lie as the three column ranges of one matrix
    (`attn.qkv.w`)."""
    d, v, dh = m['hidden_size'], m['vocab_size'], m['head_dim']
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    e, w = m['num_experts'], m['moe_intermediate_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({p + 'ln1.w': (d,), p + 'ln2.w': (d,),
                       p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                       p + 'attn.q_norm.w': (dh,),
                       p + 'attn.k_norm.w': (dh,),
                       p + 'attn.proj.w': (h * dh, d),
                       p + 'moe.router.w': (d, e),
                       p + 'moe.gate.w': (e, d, w), p + 'moe.up.w': (e, d, w),
                       p + 'moe.down.w': (e, w, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32: matrices (and the stacked expert matrices) N(0, 0.02);
    norm weights N(1, 0.1), so that a forward that leaves them out is
    another forward. The seed goes in as a key array, so another seed
    reuses the compiled program (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            mean, std = (1.0, 0.1) if len(shape) == 1 else (0.0, 0.02)
            out[name] = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


class _Noted(object):
    """The reference module as the serve driver's check uses it, which
    says beside each comparison what the request compared had resumed
    from: the check's second request shares the first's prefix, and the
    notes are where a run shows that it was served from the shared window
    blocks and not recomputed."""

    def __init__(self, module):
        self._module = module
        self.LOGIT_MARGIN = module.LOGIT_MARGIN

    def greedy_margins(self, scope, m, prompt, generated):
        from paddle_tpu import monitor
        c = monitor.counters()
        print('[mellum2] check, a prompt of %d tokens: so far '
              'kv_window_prefix_resumes_total %d, '
              'kv_window_blocks_shared_total %d, kv_prefix_hit_total %r'
              % (len(prompt), c.get('kv_window_prefix_resumes_total', 0),
                 c.get('kv_window_blocks_shared_total', 0),
                 {k: v for k, v in c.items()
                  if k.startswith('kv_prefix_hit_total')}), flush=True)
        return self._module.greedy_margins(scope, m, prompt, generated)


def reference():
    from benchmark.reference import mellum2_reference
    return _Noted(mellum2_reference)


decode_bytes_per_step = flops_mellum2.decode_bytes_per_step
kv_bytes_per_token = flops_mellum2.kv_bytes_per_token
