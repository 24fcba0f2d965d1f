"""Maps a configuration file of the Nemotron-H family (`model_type:
nemotron_h`; keys as in the source's config.json) onto the repo's LMConfig
and names what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only.
`n_routed_experts` is the chip's SHARE of `reduced_from.n_routed_experts`
(experts `first_expert_held` ..): the router keeps the published width.
`vocab_size` is its slice of the vocabulary: table and head hold those
rows alone. `hybrid_override_pattern` gives a layer's one sublayer by its
letter."""
from benchmark import flops_nemotron

KINDS = {'M': 'ssd', '*': 'attention', 'E': 'ffn'}


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/nemotron.py: the block is served only '
                         '(build_lm cannot express it)')
    letters = flops_nemotron.pattern(m)
    for key, want in (
            ('mamba_hidden_act', 'silu'), ('mlp_hidden_act', 'relu2'),
            ('tie_word_embeddings', False), ('norm_topk_prob', True),
            ('n_group', 1), ('topk_group', 1), ('n_shared_experts', 1),
            ('use_conv_bias', True), ('use_bias', False),
            ('mamba_proj_bias', False), ('attention_bias', False),
            ('mlp_bias', False), ('sliding_window', None),
            ('norm_eps', m.get('layer_norm_epsilon'))):
        if m.get(key) != want:
            raise ValueError('models/nemotron.py builds %s=%r only, the '
                             'file says %r' % (key, want, m.get(key)))
    if len(letters) != m['num_hidden_layers'] or set(letters) - set(KINDS):
        raise ValueError('models/nemotron.py: hybrid_override_pattern %r '
                         'for %d layers of M | * | E'
                         % (letters, m['num_hidden_layers']))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'], head_dim=m['head_dim'],
        n_layer=len(letters), layer_types=[KINDS[c] for c in letters],
        matmul_precision='highest', dropout=0.0, attn_dropout=0.0,
        use_flash_attention=True, norm='rms_norm',
        rms_eps=m['layer_norm_epsilon'], position='none', bias=False,
        ssm_heads=m['mamba_num_heads'], ssm_head_dim=m['mamba_head_dim'],
        ssm_groups=m['n_groups'], ssm_state=m['ssm_state_size'],
        ssm_conv=m['conv_kernel'], ssm_chunk=m['chunk_size'],
        ffn='moe', expert_form='relu2',
        n_experts=flops_nemotron.router_width(m),
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=True, moe_score='sigmoid',
        routed_scale=float(m['routed_scaling_factor']),
        n_shared_experts=m['n_shared_experts'],
        shared_expert_width=m['moe_shared_expert_intermediate_size'],
        experts_held=(int(m.get('first_expert_held', 0)),
                      m['n_routed_experts']))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. A mixer layer's one norm is `ln1`, an expert layer's `ln2`. q, k
    and v lie as the three column ranges of one matrix (`attn.qkv.w`);
    `ssd.in.w`'s columns are [z | xBC | dt] as published; table and head
    over the vocabulary's slice."""
    d, v, dh = m['hidden_size'], m['vocab_size'], m['head_dim']
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    di, cw = flops_nemotron.d_inner(m), flops_nemotron.conv_width(m)
    mh, k = m['mamba_num_heads'], m['conv_kernel']
    held, w = m['n_routed_experts'], m['moe_intermediate_size']
    routed = flops_nemotron.router_width(m)
    sw = m['moe_shared_expert_intermediate_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i, letter in enumerate(flops_nemotron.pattern(m)):
        p = 'layer_%d.' % i
        if letter == 'M':
            s = p + 'ssd.'
            shapes.update({
                p + 'ln1.w': (d,), s + 'in.w': (d, di + cw + mh),
                s + 'conv.w': (cw, k), s + 'conv.b': (cw,),
                s + 'dt.b': (mh,), s + 'A_log': (mh,), s + 'D': (mh,),
                s + 'norm.w': (di,), s + 'out.w': (di, d)})
        elif letter == '*':
            shapes.update({p + 'ln1.w': (d,),
                           p + 'attn.qkv.w': (d, (h + 2 * hkv) * dh),
                           p + 'attn.proj.w': (h * dh, d)})
        else:
            shapes.update({
                p + 'ln2.w': (d,), p + 'moe.router.w': (d, routed),
                p + 'moe.router.bias': (routed,),
                p + 'moe.up.w': (held, d, w), p + 'moe.down.w': (held, w, d),
                p + 'moe.shared.up.w': (d, sw),
                p + 'moe.shared.down.w': (sw, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32. Matrices (and the stacked expert matrices) N(0, 0.02);
    norm weights N(1, 0.1), so that a forward that leaves them out is
    another forward; the convolution's taps N(0, 0.3) so that all four
    count, its bias N(0, 0.1); the router's selection bias
    (`e_score_correction_bias`) N(0, 0.01). The recurrence takes MAMBA-2'S
    OWN initialisation (state-spaces/mamba `Mamba2.__init__`), not N(0,
    0.02): `A_log` = log of a uniform draw in [1, 16] a head, `D` = 1,
    `dt.b` = softplus^-1(dt) with dt log-uniform in [`time_step_min`,
    `time_step_max`], floored at `time_step_floor` -- with a zero bias dt
    is ~0.69, the decay <= 0.5 a position, and the state forgets within a
    few positions: a forward that loses or keeps a stale state would read
    like the sound one. The seed goes in as a key array, so another seed
    reuses the compiled program (models/lm.py)."""
    import math

    import jax
    import jax.numpy as jnp
    # a program that cannot build the block says so here, before 8 GB of
    # weights are made for it
    lm_config(m, 1, False)
    shapes = param_shapes(m)
    lo, hi = math.log(m['time_step_min']), math.log(m['time_step_max'])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith('.A_log'):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith('.D'):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith('.dt.b'):
                dt = jnp.maximum(
                    jnp.exp(jax.random.uniform(k, shape, jnp.float32, lo,
                                               hi)), m['time_step_floor'])
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                mean, std = 0.0, 0.02
                if name.endswith('.conv.w'):
                    std = 0.3
                elif name.endswith('.conv.b'):
                    std = 0.1
                elif name.endswith('.bias'):
                    std = 0.01
                elif len(shape) == 1:
                    mean, std = 1.0, 0.1
                out[name] = mean + std * jax.random.normal(k, shape,
                                                           jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import nemotron_reference
    return nemotron_reference


decode_bytes_per_step = flops_nemotron.decode_bytes_per_step
kv_bytes_per_token = flops_nemotron.kv_bytes_per_token
