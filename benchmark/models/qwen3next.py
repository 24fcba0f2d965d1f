"""Maps a configuration file of the Qwen3-Next family (`model_type:
qwen3_next`; keys as in the source's config.json) onto the repo's LMConfig
and names what the serve driver needs from it: `lm_config`, `init_params`,
`reference`, `decode_bytes_per_step` (and `param_shapes` for the manifest
test, `kv_bytes_per_token` for the readers). Serving only. `num_experts` is
the chip's SHARE of `reduced_from.num_experts` (experts `first_expert_held`
..): the router keeps the published width. `vocab_size` is its slice of the
vocabulary: table and head hold those rows alone. Layer ``i`` is full
attention iff ``(i + 1) % full_attention_interval == 0``, else a Gated
DeltaNet layer. `intermediate_size` (a dense layer's width) is kept and
unused: `mlp_only_layers` is empty and `decoder_sparse_step` 1. The
multi-token-prediction module that the family's description names has no
key in the config and is not built."""
from benchmark import flops_qwen3next


def layer_types(m):
    return ['attention' if flops_qwen3next.is_full(m, i) else 'gdn'
            for i in range(m['num_hidden_layers'])]


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/qwen3next.py: the block is served only '
                         '(build_lm cannot express it)')
    for key, want in (
            ('hidden_act', 'silu'), ('tie_word_embeddings', False),
            ('norm_topk_prob', True), ('decoder_sparse_step', 1),
            ('mlp_only_layers', []), ('use_sliding_window', False),
            ('rope_scaling', None)):
        if m.get(key) != want:
            raise ValueError('models/qwen3next.py builds %s=%r only, the '
                             'file says %r' % (key, want, m.get(key)))
    if m['num_hidden_layers'] % m['full_attention_interval']:
        raise ValueError('models/qwen3next.py builds whole periods of '
                         'full_attention_interval=%r layers, the file says '
                         '%r layers' % (m['full_attention_interval'],
                                        m['num_hidden_layers']))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_kv_head=m['num_key_value_heads'], head_dim=m['head_dim'],
        n_layer=m['num_hidden_layers'], layer_types=layer_types(m),
        matmul_precision=m.get('matmul_precision'), dropout=0.0,
        attn_dropout=0.0, use_flash_attention=True, norm='rms_norm',
        rms_eps=m['rms_norm_eps'], norm_zero_centred=True, position='rope',
        rope_theta=float(m['rope_theta']),
        rotary_dim=int(round(m['head_dim'] * m['partial_rotary_factor'])),
        qk_norm='head', attention_gate=True, bias=False,
        gdn_key_heads=m['linear_num_key_heads'],
        gdn_value_heads=m['linear_num_value_heads'],
        gdn_key_dim=m['linear_key_head_dim'],
        gdn_value_dim=m['linear_value_head_dim'],
        ssm_conv=m['linear_conv_kernel_dim'],
        gdn_chunk=int(m.get('gdn_chunk', 64)),
        d_ff=m['intermediate_size'], ffn='moe', n_dense_layers=0,
        n_experts=flops_qwen3next.router_width(m),
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=True, moe_score='softmax', n_shared_experts=1,
        shared_expert_width=m['shared_expert_intermediate_size'],
        shared_expert_gate=True,
        experts_held=(int(m.get('first_expert_held', 0)), m['num_experts']))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. A full-attention layer's q (with its gate, a head: q then gate),
    k and v lie as the three column ranges of one matrix (`attn.qkv.w`);
    `gdn.in.w`'s columns are the blocks [q | k | v | z] and `gdn.ba.w`'s
    [b | a]; table and head over the vocabulary's slice."""
    d, v, dh = m['hidden_size'], m['vocab_size'], m['head_dim']
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    cw, vw = flops_qwen3next.conv_width(m), flops_qwen3next.value_width(m)
    hv, k = m['linear_num_value_heads'], m['linear_conv_kernel_dim']
    held, w = m['num_experts'], m['moe_intermediate_size']
    sw = m['shared_expert_intermediate_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'ln2.w': (d,),
            p + 'moe.router.w': (d, flops_qwen3next.router_width(m)),
            p + 'moe.gate.w': (held, d, w), p + 'moe.up.w': (held, d, w),
            p + 'moe.down.w': (held, w, d),
            p + 'moe.shared.gate.w': (d, sw), p + 'moe.shared.up.w': (d, sw),
            p + 'moe.shared.down.w': (sw, d),
            p + 'moe.shared_gate.w': (d, 1)})
        if flops_qwen3next.is_full(m, i):
            shapes.update({p + 'attn.qkv.w': (d, (2 * h + 2 * hkv) * dh),
                           p + 'attn.q_norm.w': (dh,),
                           p + 'attn.k_norm.w': (dh,),
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
    seed, float32. Matrices (and the stacked expert matrices) N(0, 0.02);
    the ZERO-CENTRED norms' weights (`ln1`, `ln2`, `final_ln`, `q_norm`,
    `k_norm`: the programs multiply by 1 + w) N(0, 0.1) and the DeltaNet's
    plain output norm `gdn.norm.w` N(1, 0.1), so that a forward that takes
    the one for the other, or leaves a weight out, is another forward; the
    convolution's taps N(0, 0.3) so that all four count. The recurrence
    takes THE FAMILY'S OWN initialisation (HF `Qwen3NextGatedDeltaNet`):
    `A_log` = log of a uniform draw in (0, 16) a value head -- floored at
    1e-4 before the log --, `dt.b` its ones SPREAD by N(0, 0.5) a head
    (the published initialisation is exactly 1; a spread makes a forward
    that permutes or drops the bias another forward). With these the decay
    a position e^g lies between ~0 and ~1 over the heads: some heads
    forget within a few positions, the slow ones keep 0.99 and more, so a
    lost or stale state shows hundreds of positions later. The seed goes
    in as a key array, so another seed reuses the compiled program
    (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    # a program that cannot build the block says so here, before 8 GB of
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
            elif name.endswith('.gdn.norm.w'):
                mean, std = 1.0, 0.1
            elif len(shape) == 1:
                std = 0.1                       # zero-centred: 1 + w
            out[name] = mean + std * jax.random.normal(k, shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import qwen3next_reference
    return qwen3next_reference


decode_bytes_per_step = flops_qwen3next.decode_bytes_per_step
kv_bytes_per_token = flops_qwen3next.kv_bytes_per_token
