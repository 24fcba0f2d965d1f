"""Maps a configuration file of the JoyAI-LLM-Flash family (the
DeepSeek-V3 layer; keys as in the source's config.json) onto the repo's
LMConfig and names what the serve driver needs from it: `lm_config`,
`init_params`, `reference`, `decode_bytes_per_step` (and `param_shapes`
for the manifest test, `kv_bytes_per_token` for the readers). Serving
only. `n_routed_experts` is the chip's SHARE of
`reduced_from.n_routed_experts` (experts `first_expert_held` ..): the
router keeps the published width. The multi-token-prediction module
(`num_nextn_predict_layers`) is not built: the main model is served
without it (DeepSeek-V3, section 2.2)."""
from benchmark import flops_joyai


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if training:
        raise ValueError('models/joyai.py: the block is served only '
                         '(build_lm cannot express it)')
    for key, want in (('hidden_act', 'silu'), ('attention_bias', False),
                      ('tie_word_embeddings', False), ('rope_scaling', None),
                      ('scoring_func', 'sigmoid'), ('topk_method', 'noaux_tc'),
                      ('n_group', 1), ('topk_group', 1),
                      ('moe_layer_freq', 1)):
        if m.get(key) != want:
            raise ValueError('models/joyai.py builds %s=%r only, the file '
                             'says %r' % (key, want, m.get(key)))
    if m['num_key_value_heads'] != m['num_attention_heads']:
        raise ValueError('models/joyai.py: grouped K/V heads are not built')
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len,
        d_model=m['hidden_size'], n_head=m['num_attention_heads'],
        n_layer=m['num_hidden_layers'], d_ff=m['intermediate_size'],
        dropout=0.0, attn_dropout=0.0, use_flash_attention=True,
        norm='rms_norm', rms_eps=m['rms_norm_eps'], position='rope',
        rope_theta=float(m['rope_theta']), bias=False,
        attention='mla', q_lora_rank=m['q_lora_rank'],
        kv_lora_rank=m['kv_lora_rank'], qk_nope_dim=m['qk_nope_head_dim'],
        qk_rope_dim=m['qk_rope_head_dim'], v_head_dim=m['v_head_dim'],
        head_dim=m['qk_nope_head_dim'] + m['qk_rope_head_dim'],
        rope_interleave=bool(m['rope_interleave']),
        ffn='moe', n_dense_layers=m['first_k_dense_replace'],
        n_experts=flops_joyai.router_width(m),
        experts_per_token=m['num_experts_per_tok'],
        expert_width=m['moe_intermediate_size'],
        norm_topk_prob=bool(m['norm_topk_prob']), moe_score='sigmoid',
        routed_scale=float(m['routed_scaling_factor']),
        n_shared_experts=m['n_shared_experts'],
        experts_held=(int(m.get('first_expert_held', 0)),
                      m['n_routed_experts']))


def param_shapes(m):
    """Name -> shape of every parameter, as the decode programs name
    them. The published `kv_b_proj` [rank, H (nope + v)] lies as its two
    halves by head: `kv_b_k` [H, nope, rank] and `kv_b_v` [H, rank, v]."""
    d, v, h = m['hidden_size'], m['vocab_size'], m['num_attention_heads']
    nope, rope = m['qk_nope_head_dim'], m['qk_rope_head_dim']
    rank, q_rank, vd = m['kv_lora_rank'], m['q_lora_rank'], m['v_head_dim']
    held, w = m['n_routed_experts'], m['moe_intermediate_size']
    routed = flops_joyai.router_width(m)
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'lm_head.w': (d, v)}
    for i in range(m['num_hidden_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'ln2.w': (d,),
            p + 'attn.q_a.w': (d, q_rank), p + 'attn.q_a_norm.w': (q_rank,),
            p + 'attn.q_b.w': (q_rank, h * (nope + rope)),
            p + 'attn.kv_a.w': (d, rank + rope),
            p + 'attn.kv_a_norm.w': (rank,),
            p + 'attn.kv_b_k.w': (h, nope, rank),
            p + 'attn.kv_b_v.w': (h, rank, vd),
            p + 'attn.proj.w': (h * vd, d)})
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
        if m['n_shared_experts']:
            sw = m['n_shared_experts'] * w
            shapes.update({p + 'moe.shared.gate.w': (d, sw),
                           p + 'moe.shared.up.w': (d, sw),
                           p + 'moe.shared.down.w': (sw, d)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32: matrices (and the stacked expert matrices) N(0, 0.02),
    norm weights 1, the router's selection bias N(0, 0.01) — wide enough
    against the sigmoid scores' spread to decide some of the choices. The
    seed goes in as a key array, so another seed reuses the compiled
    program (models/lm.py)."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith('.bias'):
                std = 0.01
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
    from benchmark.reference import joyai_reference
    return joyai_reference


decode_bytes_per_step = flops_joyai.decode_bytes_per_step
kv_bytes_per_token = flops_joyai.kv_bytes_per_token
