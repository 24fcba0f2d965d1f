"""Maps a configuration file of the Jamba family (`model_type: jamba`; keys
as in the source's config.json) onto the repo's LMConfig and names what
the serve driver needs from it: `lm_config`, `init_params`, `reference`,
`decode_bytes_per_step` (and `param_shapes` for the manifest test,
`kv_bytes_per_token` for the readers). Serving only. Every layer, head and
row of the vocabulary is held. `num_experts` 1 only: every layer's FFN is
the dense one, and `expert_layer_period` / `expert_layer_offset` select
nothing."""
from benchmark import flops_jamba

KINDS = {'mamba': 'ssm', 'attention': 'attention'}


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/jamba.py: the block is served only '
                         '(build_lm cannot express it)')
    for key, want in (('hidden_act', 'silu'), ('num_experts', 1),
                      ('num_experts_per_tok', 1),
                      ('mamba_conv_bias', True), ('mamba_proj_bias', False),
                      ('tie_word_embeddings', True),
                      ('sliding_window', None)):
        if m.get(key) != want:
            raise ValueError('models/jamba.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'],
        head_dim=flops_jamba.head_dim(m), n_layer=m['num_hidden_layers'],
        layer_types=[KINDS[k] for k in flops_jamba.layer_types(m)],
        d_ff=m['intermediate_size'], dropout=0.0, attn_dropout=0.0,
        use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], position='none', bias=False,
        ffn='gated', tie_embeddings=True,
        ssm_expand=m['mamba_expand'], ssm_state=m['mamba_d_state'],
        ssm_conv=m['mamba_d_conv'], ssm_dt_rank=m['mamba_dt_rank'])


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. q, k and v lie as the three column ranges of one matrix
    (`attn.qkv.w`); `ssm.A_log` lies [N, d_inner], as the state does;
    there is no `lm_head.w`: the head is the table."""
    d, v, dh = m['hidden_size'], m['vocab_size'], flops_jamba.head_dim(m)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    di, n = flops_jamba.d_inner(m), m['mamba_d_state']
    r, k, wide = m['mamba_dt_rank'], m['mamba_d_conv'], m['intermediate_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,)}
    for i, kind in enumerate(flops_jamba.layer_types(m)):
        p = 'layer_%d.' % i
        shapes.update({p + 'ln1.w': (d,), p + 'ln2.w': (d,),
                       p + 'ffn.gate.w': (d, wide), p + 'ffn.up.w': (d, wide),
                       p + 'ffn.down.w': (wide, d)})
        if kind == 'mamba':
            s = p + 'ssm.'
            shapes.update({
                s + 'in.w': (d, 2 * di), s + 'conv.w': (di, k),
                s + 'conv.b': (di,), s + 'x.w': (di, r + 2 * n),
                s + 'dt_norm.w': (r,), s + 'b_norm.w': (n,),
                s + 'c_norm.w': (n,), s + 'dt.w': (r, di),
                s + 'dt.b': (di,), s + 'A_log': (n, di), s + 'D': (di,),
                s + 'out.w': (di, d)})
        else:
            shapes.update({p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                           p + 'attn.proj.w': (h * dh, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32. Matrices N(0, 0.02); norm weights N(1, 0.1), so that a
    forward that leaves them out is another forward; the convolution's
    taps N(0, 0.3) so that all four count, its bias N(0, 0.1). The
    recurrence takes MAMBA'S OWN initialisation (state-spaces/mamba
    `Mamba.__init__`), not N(0, 0.02): `A_log` = log(1 .. N) a channel,
    `D` = 1, `dt.b` = softplus^-1(dt) with dt log-uniform in [1e-3, 1e-1]
    -- with a zero bias delta is ~0.69, exp(delta A) <= 0.5 a position,
    and the state forgets within a few positions: a forward that loses or
    keeps a stale state would read like the sound one. The seed goes in as
    a key array, so another seed reuses the compiled program
    (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    import math
    # a program that cannot build the block says so here, before 12 GB of
    # weights are made for it
    lm_config(m, 1, False)
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith('.A_log'):
                out[name] = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, shape[0] + 1,
                                       dtype=jnp.float32))[:, None], shape)
            elif name.endswith('.D'):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith('.dt.b'):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                mean, std = 0.0, 0.02
                if name.endswith('.conv.w'):
                    std = 0.3
                elif name.endswith('.conv.b'):
                    std = 0.1
                elif len(shape) == 1:
                    mean, std = 1.0, 0.1
                out[name] = mean + std * jax.random.normal(k, shape,
                                                           jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import jamba_reference
    return jamba_reference


decode_bytes_per_step = flops_jamba.decode_bytes_per_step
kv_bytes_per_token = flops_jamba.kv_bytes_per_token
