"""Maps a configuration file of the LM family onto the repo's LMConfig and
names what the drivers need from it. A new architecture is a new file here
plus its reference under benchmark/reference/."""
from benchmark import flops


def lm_config(m, seq_len, training):
    from paddle_tpu.models.transformer import LMConfig
    if m['head_dim'] * m['attention_heads'] != m['d_model']:
        raise ValueError('heads x head_dim != d_model in %r' % (m,))
    if seq_len > m['max_position_embeddings']:
        raise ValueError('seq_len %d beyond the published context %d'
                         % (seq_len, m['max_position_embeddings']))
    return LMConfig(
        vocab_size=m['vocab_size'], seq_len=seq_len, d_model=m['d_model'],
        n_head=m['attention_heads'], n_layer=m['num_layers'],
        d_ff=m['ffn_dim'], dropout=m['dropout'] if training else 0.0,
        attn_dropout=m['attention_dropout'], use_flash_attention=True)


def build_train(m, seq_len):
    """(LMConfig, build function) for drivers/train.py: the function
    builds the LM into the current program and returns
    (tokens, labels, logits, avg_loss)."""
    from paddle_tpu.models.transformer import build_lm
    return lm_config(m, seq_len, True), build_lm


def param_shapes(m):
    """Name -> shape of every parameter, as build_lm and the decode
    programs name them."""
    d, f, v = m['d_model'], m['ffn_dim'], m['vocab_size']
    shapes = {'tok_emb.w': (v, d), 'final_ln.w': (d,), 'final_ln.b': (d,),
              'lm_head.w': (d, v)}
    for i in range(m['num_layers']):
        p = 'layer_%d.' % i
        shapes.update({
            p + 'ln1.w': (d,), p + 'ln1.b': (d,),
            p + 'attn.qkv.w': (d, 3 * d), p + 'attn.qkv.b': (3 * d,),
            p + 'attn.proj.w': (d, d), p + 'attn.proj.b': (d,),
            p + 'ln2.w': (d,), p + 'ln2.b': (d,),
            p + 'ffn1.w': (d, f), p + 'ffn1.b': (f,),
            p + 'ffn2.w': (f, d), p + 'ffn2.b': (d,)})
    return shapes


def init_params(m, seed):
    """Every parameter, made on the device in ONE jitted call from the
    seed, float32 (the type they are trained and served in): matrices
    N(0, 0.02), biases 0, LayerNorm weights 1. The seed goes in as a key
    array, so another seed reuses the compiled program. (The repo's startup
    programs fold `random_seed` into the compiled code as a constant: a
    new seed there is a new compilation. PERF.md, for the tracing issue.)"""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(m)

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if len(shape) == 2:
                out[name] = 0.02 * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            elif name.endswith('.w'):
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                out[name] = jnp.zeros(shape, jnp.float32)
        return out
    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 32)))


def reference():
    from benchmark.reference import lm_reference
    return lm_reference


train_flops_per_token = flops.lm_train_flops_per_token
decode_bytes_per_step = flops.lm_decode_bytes_per_step
kv_bytes_per_token = flops.lm_kv_bytes_per_token
