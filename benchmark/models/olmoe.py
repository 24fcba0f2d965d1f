"""Maps a configuration file of the OLMoE family (keys as in the source's
config.json) onto the repo's LMConfig and names what the serve driver
needs from it: `lm_config`, `init_params`, `reference`,
`decode_bytes_per_step` (and `param_shapes` for the manifest test,
`kv_bytes_per_token` for the readers). Serving only: the train path
(`build_lm`) refuses this block (ROADMAP R1, second half)."""
from benchmark import flops_moe


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/olmoe.py: the OLMoE block is served only '
                         '(build_lm cannot express it yet)')
    for key, want in (('hidden_act', 'silu'), ('attention_bias', False),
                      ('clip_qkv', None), ('tie_word_embeddings', False),
                      ('rope_scaling', None)):
        if m.get(key) != want:
            raise ValueError('models/olmoe.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if m['num_key_value_heads'] != m['num_attention_heads']:
        raise ValueError('models/olmoe.py: grouped K/V heads are not built')
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_layer=m['num_hidden_layers'], d_ff=m['intermediate_size'],
        dropout=0.0, attn_dropout=0.0, use_flash_attention=True,
        norm='rms_norm', rms_eps=m['rms_norm_eps'], position='rope',
        rope_theta=float(m['rope_theta']),
        head_dim=m['hidden_size'] // m['num_attention_heads'],
        qk_norm=True, bias=False, ffn='moe', n_experts=m['num_experts'],
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['intermediate_size'],
        norm_topk_prob=bool(m['norm_topk_prob']))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them."""
    d, v = m['hidden_size'], m['vocab_size']
    e, w = m['num_experts'], m['intermediate_size']
    width = m['num_attention_heads'] * (d // m['num_attention_heads'])
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'attn.qkv.w': (d, 3 * width),
            p + 'attn.q_norm.w': (width,), p + 'attn.k_norm.w': (width,),
            p + 'attn.proj.w': (width, d), p + 'ln2.w': (d,),
            p + 'moe.router.w': (d, e), p + 'moe.gate.w': (e, d, w),
            p + 'moe.up.w': (e, d, w), p + 'moe.down.w': (e, w, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32: matrices (and the stacked expert matrices) N(0, 0.02),
    norm weights 1. The seed goes in as a key array, so another seed
    reuses the compiled program (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        return {name: (0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
                       if len(shape) > 1 else jnp.ones(shape, jnp.float32))
                for i, (name, shape) in enumerate(sorted(shapes.items()))}
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import olmoe_reference
    return olmoe_reference


decode_bytes_per_step = flops_moe.decode_bytes_per_step
kv_bytes_per_token = flops_moe.kv_bytes_per_token
