"""Maps a configuration file of the K-EXAONE family (`model_type:
exaone_moe`; keys as in the source's config.json) onto the repo's LMConfig
and names what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only. `num_experts`
is the chip's SHARE of `reduced_from.num_experts` (experts
`first_expert_held` ..): the router keeps the published width.
`vocab_size` is its slice of the vocabulary: table and head hold those
rows alone. The multi-token-prediction layer (`num_nextn_predict_layers`,
`mtp_*`) is not built: the main model is served without it."""
from benchmark import flops_kexaone

KINDS = {'sliding_attention': 'window', 'full_attention': 'attention'}


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/kexaone.py: the block is served only '
                         '(build_lm cannot express it)')
    n, dense = m['num_hidden_layers'], m['first_k_dense_replace']
    kinds = flops_kexaone.layer_types(m)
    window = m['sliding_window']
    for key, want in (
            ('hidden_act', 'silu'), ('tie_word_embeddings', False),
            ('scoring_func', 'sigmoid'), ('norm_topk_prob', True),
            ('n_group', 1), ('topk_group', 1),
            ('rope_parameters', {'rope_theta': m['rope_parameters'].get(
                'rope_theta'), 'rope_type': 'default'}),
            ('mlp_layer_types', ['dense'] * dense + ['sparse'] * (n - dense)),
            ('sliding_windows', [window if k == 'sliding_attention' else 0
                                 for k in kinds])):
        if m.get(key) != want:
            raise ValueError('models/kexaone.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if len(kinds) != n or set(kinds) - set(KINDS):
        raise ValueError('models/kexaone.py: layer_types %r for %d layers'
                         % (kinds, n))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'], head_dim=m['head_dim'],
        n_layer=n, layer_types=[KINDS[k] for k in kinds],
        sliding_window=window, global_rope=False,
        d_ff=m['intermediate_size'], dropout=0.0, attn_dropout=0.0,
        use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], position='rope',
        rope_theta=float(m['rope_parameters']['rope_theta']),
        qk_norm='head', bias=False,
        ffn='moe', n_dense_layers=dense,
        n_experts=flops_kexaone.router_width(m),
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=True, moe_score='sigmoid',
        routed_scale=float(m['routed_scaling_factor']),
        n_shared_experts=m['num_shared_experts'],
        experts_held=(int(m.get('first_expert_held', 0)), m['num_experts']))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. q, k and v lie as the three column ranges of one matrix
    (`attn.qkv.w`); table and head over the vocabulary's slice."""
    d, v, dh = m['hidden_size'], m['vocab_size'], m['head_dim']
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    held, w = m['num_experts'], m['moe_intermediate_size']
    routed = flops_kexaone.router_width(m)
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({p + 'ln1.w': (d,), p + 'ln2.w': (d,),
                       p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                       p + 'attn.q_norm.w': (dh,),
                       p + 'attn.k_norm.w': (dh,),
                       p + 'attn.proj.w': (h * dh, d)})
        if i < m['first_k_dense_replace']:
            wide = m['intermediate_size']
            shapes.update({p + 'ffn.gate.w': (d, wide),
                           p + 'ffn.up.w': (d, wide),
                           p + 'ffn.down.w': (wide, d)})
            continue
        shapes.update({
            p + 'moe.router.w': (d, routed), p + 'moe.router.bias': (routed,),
            p + 'moe.gate.w': (held, d, w), p + 'moe.up.w': (held, d, w),
            p + 'moe.down.w': (held, w, d)})
        if m['num_shared_experts']:
            sw = m['num_shared_experts'] * w
            shapes.update({p + 'moe.shared.gate.w': (d, sw),
                           p + 'moe.shared.up.w': (d, sw),
                           p + 'moe.shared.down.w': (sw, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32: matrices (and the stacked expert matrices) N(0, 0.02);
    norm weights N(1, 0.1), so that a forward that leaves them out is
    another forward; the router's selection bias
    (`e_score_correction_bias`) N(0, 0.01) — wide enough against the
    sigmoid scores' spread to decide some of the choices. The seed goes
    in as a key array, so another seed reuses the compiled program
    (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            mean, std = 0.0, 0.02
            if name.endswith('.bias'):
                std = 0.01
            elif len(shape) == 1:
                mean, std = 1.0, 0.1
            out[name] = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import kexaone_reference
    return kexaone_reference


decode_bytes_per_step = flops_kexaone.decode_bytes_per_step
kv_bytes_per_token = flops_kexaone.kv_bytes_per_token
