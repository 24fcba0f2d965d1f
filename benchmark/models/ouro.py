"""Maps a configuration file of the Ouro family (`model_type: ouro`; keys as
in the source's config.json) onto the repo's LMConfig and names what the
serve driver needs from it: `lm_config`, `init_params`, `reference`,
`decode_bytes_per_step` (and `param_shapes` for the manifest test,
`kv_bytes_per_token` for the readers). Serving only: the train path
(`build_lm`) builds the classic block and one pass. The layer stack runs
`total_ut_steps` times a token over one set of weights
(`LMConfig.passes`), a norm before and after each sublayer
(`norm_placement='sandwich'`); what the config does not itself say is listed
under `assumed` in the configuration's file."""
from benchmark import flops_ouro


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/ouro.py: the looped block is served only '
                         '(build_lm cannot express it)')
    for key, want in (('hidden_act', 'silu'), ('tie_word_embeddings', False),
                      ('rope_scaling', None), ('sliding_window', None),
                      ('use_sliding_window', False),
                      ('early_exit_threshold', 1)):
        if m.get(key) != want:
            raise ValueError('models/ouro.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if m['layer_types'] != ['full_attention'] * m['num_hidden_layers'] \
            or m['max_window_layers'] != m['num_hidden_layers']:
        raise ValueError('models/ouro.py builds num_hidden_layers=%r '
                         'full_attention layers, none of them windowed; the '
                         'file says layer_types=%r, max_window_layers=%r'
                         % (m['num_hidden_layers'], m['layer_types'],
                            m['max_window_layers']))
    if m['num_key_value_heads'] != m['num_attention_heads']:
        raise ValueError('models/ouro.py: grouped K/V heads are not built')
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        head_dim=m['head_dim'], n_layer=m['num_hidden_layers'],
        passes=m['total_ut_steps'], norm_placement='sandwich',
        matmul_precision=m.get('matmul_precision'), dropout=0.0,
        attn_dropout=0.0, use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], position='rope',
        rope_theta=float(m['rope_theta']), qk_norm=False, bias=False,
        ffn='gated', d_ff=m['intermediate_size'])


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name them:
    ONE set of layers whatever `total_ut_steps`. q, k and v lie as the
    three column ranges of one matrix (`attn.qkv.w`); `ln1` / `ln1_out`
    norm the attention's input and output, `ln2` / `ln2_out` the FFN's; a
    model of one pass has no exit gate."""
    d, v, w = m['hidden_size'], m['vocab_size'], m['intermediate_size']
    width = m['num_attention_heads'] * m['head_dim']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    if m['total_ut_steps'] > 1:
        shapes.update({'exit_gate.w': (d, 1), 'exit_gate.b': (1,)})
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'ln1_out.w': (d,), p + 'ln2.w': (d,),
            p + 'ln2_out.w': (d,), p + 'attn.qkv.w': (d, 3 * width),
            p + 'attn.proj.w': (width, d), p + 'ffn.gate.w': (d, w),
            p + 'ffn.up.w': (d, w), p + 'ffn.down.w': (w, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32. Matrices N(0, 0.02); every norm's weight N(1, 0.1), so
    that a forward that leaves a norm out, or puts it elsewhere, is another
    forward; the exit gate's bias N(0, 1), so that a forward that drops it
    is another forward (its weight [d, 1] as the matrices). The seed goes in
    as a key array, so another seed reuses the compiled program
    (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    # a program that cannot build the block says so here, before the
    # weights are made for it
    lm_config(m, 1, False)
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            mean, std = 0.0, 0.02
            if name == 'exit_gate.b':
                std = 1.0
            elif len(shape) == 1:
                mean, std = 1.0, 0.1
            out[name] = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import ouro_reference
    return ouro_reference


decode_bytes_per_step = flops_ouro.decode_bytes_per_step
kv_bytes_per_token = flops_ouro.kv_bytes_per_token
