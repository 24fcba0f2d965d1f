"""Maps a configuration file of the Olmo-Hybrid family (`model_type:
olmo_hybrid`; keys as in the source's config.json) onto the repo's LMConfig
and names what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only. Layer ``i`` is
what `layer_types[i]` says: ``full_attention`` or ``linear_attention``, a
Gated DeltaNet layer whose write strength is ``2 sigmoid(b)``
(`linear_allow_neg_eigval`). What the config does not say is the family's
convention, listed under `assumed` in the configuration's file: the norm on
each sublayer's OUTPUT (`LMConfig.norm_placement='post'`), the whole-width
q/k-norm, nothing rotated (`rope_parameters.rope_theta` null), the DeltaNet's
output gate."""
from benchmark import flops_olmohybrid

_KINDS = {'full_attention': 'attention', 'linear_attention': 'gdn'}


def layer_types(m):
    return [_KINDS[kind] for kind in m['layer_types']]


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/olmohybrid.py: the block is served only '
                         '(build_lm cannot express it)')
    for key, want in (
            ('hidden_act', 'silu'), ('tie_word_embeddings', False),
            ('attention_bias', False),
            ('rope_parameters', {'rope_theta': None})):
        if m.get(key) != want:
            raise ValueError('models/olmohybrid.py builds %s=%r only, the '
                             'file says %r' % (key, want, m.get(key)))
    if len(m['layer_types']) != m['num_hidden_layers'] \
            or set(m['layer_types']) - set(_KINDS):
        raise ValueError('models/olmohybrid.py builds num_hidden_layers=%r '
                         'layers of %r, the file says %r'
                         % (m['num_hidden_layers'], sorted(_KINDS),
                            m['layer_types']))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'],
        n_layer=m['num_hidden_layers'], layer_types=layer_types(m),
        matmul_precision=m.get('matmul_precision'), dropout=0.0,
        attn_dropout=0.0, use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], norm_placement='post', position='none',
        qk_norm=True, bias=False, ffn='gated', d_ff=m['intermediate_size'],
        gdn_key_heads=m['linear_num_key_heads'],
        gdn_value_heads=m['linear_num_value_heads'],
        gdn_key_dim=m['linear_key_head_dim'],
        gdn_value_dim=m['linear_value_head_dim'],
        gdn_allow_neg_eigval=bool(m['linear_allow_neg_eigval']),
        ssm_conv=m['linear_conv_kernel_dim'],
        gdn_chunk=int(m.get('gdn_chunk', 64)))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. A full-attention layer's q, k and v lie as the three column
    ranges of one matrix (`attn.qkv.w`), its two norms over the whole
    projected width; `gdn.in.w`'s columns are the blocks [q | k | v | z]
    and `gdn.ba.w`'s [b | a]; `ln1` / `ln2` norm the mixer's and the FFN's
    OUTPUT."""
    d, v, w = m['hidden_size'], m['vocab_size'], m['intermediate_size']
    dh = flops_olmohybrid.head_dim(m)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    cw, vw = flops_olmohybrid.conv_width(m), flops_olmohybrid.value_width(m)
    hv, k = m['linear_num_value_heads'], m['linear_conv_kernel_dim']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'ln2.w': (d,),
            p + 'ffn.gate.w': (d, w), p + 'ffn.up.w': (d, w),
            p + 'ffn.down.w': (w, d)})
        if flops_olmohybrid.is_full(m, i):
            shapes.update({p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                           p + 'attn.q_norm.w': (h * dh,),
                           p + 'attn.k_norm.w': (hkv * dh,),
                           p + 'attn.proj.w': (h * dh, d)})
        else:
            s = p + 'gdn.'
            shapes.update({
                s + 'in.w': (d, cw + vw), s + 'ba.w': (d, 2 * hv),
                s + 'conv.w': (cw, k), s + 'A_log': (hv,), s + 'dt.b': (hv,),
                s + 'norm.w': (m['linear_value_head_dim'],),
                s + 'out.w': (vw, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32. Matrices N(0, 0.02); every norm's weight N(1, 0.1), so
    that a forward that leaves a norm out, or puts it elsewhere, is another
    forward; the convolution's taps N(0, 0.3) so that all four count. The
    recurrence takes the family's kernels' initialisation (FLA's
    `GatedDeltaNet`): `A_log` = log of a uniform draw in (0, 16) a value
    head -- floored at 1e-4 before the log --, `dt.b` its ones SPREAD by
    N(0, 0.5) a head. With these the decay a position e^g lies between ~0
    and ~1 over the heads: some heads forget within a few positions, the
    slow ones keep 0.99 and more, so a lost, stale or foreign state shows
    hundreds of positions later. The seed goes in as a key array, so
    another seed reuses the compiled program (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    # a program that cannot build the block says so here, before 10 GB of
    # weights are made for it
    lm_config(m, 1, False)
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith('.A_log'):
                out[name] = jnp.log(jnp.maximum(jax.random.uniform(
                    k, shape, jnp.float32, 0.0, 16.0), 1e-4))
                continue
            mean, std = 0.0, 0.02
            if name.endswith('.conv.w'):
                std = 0.3
            elif name.endswith('.dt.b'):
                mean, std = 1.0, 0.5
            elif len(shape) == 1:
                mean, std = 1.0, 0.1
            out[name] = mean + std * jax.random.normal(k, shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import olmohybrid_reference
    return olmohybrid_reference


decode_bytes_per_step = flops_olmohybrid.decode_bytes_per_step
kv_bytes_per_token = flops_olmohybrid.kv_bytes_per_token
