"""Maps a configuration file of the LFM2-MoE family (keys as in the
source's config.json, `model_type: lfm2_moe`) onto the repo's LMConfig and
names what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only. Every expert is
held. `tie_word_embeddings` and `head_dim` are the file's `assumed`
values where the source's row gives none."""
from benchmark import flops_lfm2

KINDS = {'conv': 'conv', 'full_attention': 'attention'}


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/lfm2.py: the block is served only '
                         '(build_lm cannot express it)')
    for key, want in (('conv_bias', False), ('use_expert_bias', True),
                      ('tie_word_embeddings', True)):
        if m.get(key) != want:
            raise ValueError('models/lfm2.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    kinds = flops_lfm2.layer_types(m)
    if len(kinds) != m['num_hidden_layers'] or set(kinds) - set(KINDS):
        raise ValueError('models/lfm2.py: layer_types %r for %d layers'
                         % (kinds, m['num_hidden_layers']))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'],
        head_dim=flops_lfm2.head_dim(m), n_layer=m['num_hidden_layers'],
        layer_types=[KINDS[k] for k in kinds],
        conv_kernel=m['conv_L_cache'], d_ff=m['intermediate_size'],
        dropout=0.0, attn_dropout=0.0, use_flash_attention=True,
        norm='rms_norm', rms_eps=m['norm_eps'], position='rope',
        rope_theta=float(m['rope_theta']), qk_norm='head', bias=False,
        tie_embeddings=True,
        ffn='moe', n_dense_layers=m['num_dense_layers'],
        n_experts=m['num_experts'],
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=bool(m['norm_topk_prob']), moe_score='sigmoid',
        routed_scale=float(m['routed_scaling_factor']), router_eps=1e-6)


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. q, k and v lie as the three column ranges of one matrix
    (`attn.qkv.w`); there is no `lm_head.w`: the head is the table."""
    d, v, dh = m['hidden_size'], m['vocab_size'], flops_lfm2.head_dim(m)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    e, w = m['num_experts'], m['moe_intermediate_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,)}
    for i, kind in enumerate(flops_lfm2.layer_types(m)):
        p = 'layer_%d.' % i
        shapes.update({p + 'ln1.w': (d,), p + 'ln2.w': (d,)})
        if kind == 'conv':
            shapes.update({p + 'conv.in.w': (d, 3 * d),
                           p + 'conv.w': (d, m['conv_L_cache']),
                           p + 'conv.out.w': (d, d)})
        else:
            shapes.update({p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                           p + 'attn.q_norm.w': (dh,),
                           p + 'attn.k_norm.w': (dh,),
                           p + 'attn.proj.w': (h * dh, d)})
        if i < m['num_dense_layers']:
            wide = m['intermediate_size']
            shapes.update({p + 'ffn.gate.w': (d, wide),
                           p + 'ffn.up.w': (d, wide),
                           p + 'ffn.down.w': (wide, d)})
        else:
            shapes.update({p + 'moe.router.w': (d, e),
                           p + 'moe.router.bias': (e,),
                           p + 'moe.gate.w': (e, d, w),
                           p + 'moe.up.w': (e, d, w),
                           p + 'moe.down.w': (e, w, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32: matrices (and the stacked expert matrices) N(0, 0.02),
    the convolution's taps N(0, 0.3) so that all three count, norm weights
    1, the router's selection bias (`expert_bias`) N(0, 0.01) — wide
    enough against the sigmoid scores' spread to decide some of the
    choices. The seed goes in as a key array, so another seed reuses the
    compiled program (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith('.bias'):
                std = 0.01
            elif name.endswith('.conv.w'):
                std = 0.3
            elif len(shape) > 1:
                std = 0.02
            else:
                out[name] = jnp.ones(shape, jnp.float32)
                continue
            out[name] = std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import lfm2_reference
    return lfm2_reference


decode_bytes_per_step = flops_lfm2.decode_bytes_per_step
kv_bytes_per_token = flops_lfm2.kv_bytes_per_token
