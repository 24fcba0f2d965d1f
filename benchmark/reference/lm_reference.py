"""The plain reference of the LM family: the forward pass in jax.numpy,
float32, matmuls at precision "highest", no kernels, no cache, no
batching. It follows the block the repo's LMConfig describes (and
fairseq-dense / XGLM publishes): token embedding + sinusoid positions,
pre-LayerNorm blocks with fused-QKV multi-head causal attention and an
exact-GELU feed-forward, a final LayerNorm, an untied linear head.

Departures from the published model are the configuration file's
`changed` list (untied head, no sqrt(d_model) embedding scale).

Parameters are read out of a scope by the names build_lm gives them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5

# Tolerances, each with its reason.
#
# LOSS_RTOL: the system's is_test forward runs float32 programs whose
# matmuls the TPU executes at default precision (bf16 operands, float32
# accumulation), the reference at "highest". Over 24 layers that moved the
# mean loss of 2048 tokens by 1-3e-4 relative on the v5e (PERF.md §6); 2e-3
# leaves room for another seed and is far below what computing the whole
# forward in bf16 storage would move it (~1e-2).
LOSS_RTOL = 2e-3
# FIRST_STEP_ATOL: the first train step differs from the is_test loss by
# dropout 0.1, bf16 AMP and a different batch of uniform random tokens; at
# random init all of these are near ln(V) + a constant.
FIRST_STEP_ATOL = 0.5
# LOGIT_MARGIN: a greedy token must be (near) the reference's argmax. With
# random weights the top logits are close together and default-precision
# float32 matmuls flip the argmax, so the test is on the reference's own
# logits: ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean).
# A wrong token (a uniform draw) sits ~1.0 of that spread below the max.
LOGIT_MARGIN = 0.15


def sinusoid_table(length, d_model):
    pos = np.arange(length)[:, None]
    half = d_model // 2
    freq = np.power(10000.0, -np.arange(half) / float(half))
    enc = np.zeros((length, d_model), dtype=np.float32)
    enc[:, :half] = np.sin(pos * freq)
    enc[:, half:2 * half] = np.cos(pos * freq)
    return enc


def _ln(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * w + b


@functools.partial(jax.jit, static_argnames=('n_head',))
def _block(x, p, n_head):
    """One pre-LN block on x [T, D]; p is the layer's parameter dict."""
    with jax.default_matmul_precision('highest'):
        t, d = x.shape
        dh = d // n_head
        h = _ln(x, p['ln1.w'], p['ln1.b'])
        qkv = (h @ p['attn.qkv.w'] + p['attn.qkv.b']).reshape(t, 3, n_head,
                                                               dh)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [T, H, dh]
        s = jnp.einsum('qhd,khd->hqk', q, k) * (dh ** -0.5)
        causal = jnp.tril(jnp.ones((t, t), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum('hqk,khd->qhd', a, v).reshape(t, d)
        x = x + ctx @ p['attn.proj.w'] + p['attn.proj.b']
        h = _ln(x, p['ln2.w'], p['ln2.b'])
        f = jax.nn.gelu(h @ p['ffn1.w'] + p['ffn1.b'], approximate=False)
        return x + f @ p['ffn2.w'] + p['ffn2.b']


@jax.jit
def _head(x, ln_w, ln_b, head_w):
    with jax.default_matmul_precision('highest'):
        return _ln(x, ln_w, ln_b) @ head_w


_LAYER_KEYS = ('ln1.w', 'ln1.b', 'attn.qkv.w', 'attn.qkv.b', 'attn.proj.w',
               'attn.proj.b', 'ln2.w', 'ln2.b', 'ffn1.w', 'ffn1.b',
               'ffn2.w', 'ffn2.b')


def _param(scope, name):
    v = scope.get(name)
    if v is None:
        raise KeyError('reference: scope has no parameter %r' % name)
    return jnp.asarray(v, jnp.float32)


def hidden(scope, m, tokens):
    """Residual stream after the last block for one sequence of token ids
    (1-D), [T, D] float32."""
    tokens = np.asarray(tokens).reshape(-1)
    d = m['d_model']
    x = jnp.take(_param(scope, 'tok_emb.w'), jnp.asarray(tokens), axis=0) \
        + jnp.asarray(sinusoid_table(len(tokens), d))
    for i in range(m['num_layers']):
        p = {k: _param(scope, 'layer_%d.%s' % (i, k)) for k in _LAYER_KEYS}
        x = _block(x, p, n_head=m['attention_heads'])
    return x


def logits(scope, m, tokens, positions=None):
    """Reference logits [len(positions), V] of one sequence; `positions`
    (default: all) selects the rows the head is applied to."""
    x = hidden(scope, m, tokens)
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, _param(scope, 'final_ln.w'), _param(scope, 'final_ln.b'),
                 _param(scope, 'lm_head.w'))


def loss(scope, m, tokens, labels):
    """Mean softmax cross-entropy of one sequence against `labels`."""
    lg = logits(scope, m, tokens)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, jnp.asarray(np.asarray(labels).reshape(-1, 1)), axis=1)[:, 0]
    return float(jnp.mean(lse - picked))


def greedy_margins(scope, m, prompt, generated):
    """For each generated token, how far its reference logit lies below the
    reference's maximum at that position, as a share of (max - mean) there.
    One teacher-forced forward over prompt + generated."""
    prompt = np.asarray(prompt).reshape(-1)
    generated = np.asarray(generated).reshape(-1)
    seq = np.concatenate([prompt, generated[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    lg = np.asarray(logits(scope, m, seq, positions=pos))
    top = lg.max(axis=1)
    got = lg[np.arange(len(generated)), generated]
    return (top - got) / (top - lg.mean(axis=1))
