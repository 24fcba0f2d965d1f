"""The plain reference of OLMoE (HF `modeling_olmoe.py`, model_type
`olmoe`): the forward pass in jax.numpy, float32, matmuls at precision
"highest", a Python loop over the experts with a mask — no sort, no
grouped matmul, no kernels, no cache, no batching.

For hidden x [T, D], per layer:

    h = RMSNorm(x; ln1)
    q = RMSNorm(h Wq; q_norm), k = RMSNorm(h Wk; k_norm), v = h Wv
        (each norm over the WHOLE projected width, before the heads)
    heads of head_dim; rotary on q and k, rotate_half convention,
        inv_freq = theta^(-2i/head_dim), angle pos * inv_freq
    causal softmax attention, scale head_dim^-1/2;  x = x + ctx Wo
    g = RMSNorm(x; ln2);  p = softmax(g Wr) over ALL experts
    the num_experts_per_tok largest p, NOT renormalised (norm_topk_prob
        false);  y = sum_e p_e (silu(g Wgate_e) * (g Wup_e)) Wdown_e
    x = x + y
then RMSNorm(x; final_ln) and the untied head. No bias anywhere.
RMSNorm: x * rsqrt(mean(x^2) + rms_norm_eps) * w.

Departures from the published model are the configuration file's
`changed` list. Parameters are read out of a scope by the names the
decode programs give them (paddle_tpu/models/transformer.py: q, k and v
are the three column blocks of `attn.qkv.w`), as they lie on the device:
no second copy of the expert weights is made.

`routing` (per layer a [T, k] array of expert ids) puts the SYSTEM's
choice of experts in the place of the reference's own top-k, at the
reference's own probabilities: default-precision matmuls flip the k-th
and (k+1)-th expert where they are nearly tied, and the comparison of
logits should then say how exact the arithmetic is, not how often that
happens (`router_probs` says that).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as lm_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean). The
# limit is set between what the sound system and what the controls
# (olmoe_control.py: bfloat16 forward, top-7, renormalised weights) read
# on the chip at the published widths; the readings are in PERF.md (PR 28).
LOGIT_MARGIN = 0.15


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rotate(x, pos, theta):
    """x [T, H, dh] rotated by pos [T]: rotate_half convention."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T,1,dh]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return x * jnp.cos(emb) + half * jnp.sin(emb)


@functools.partial(jax.jit, static_argnames=('n_head', 'eps', 'theta'))
def _attention(x, p, n_head, eps, theta):
    """x + attention(x), and the FFN's normed input g."""
    with jax.default_matmul_precision('highest'):
        t, d = x.shape
        h = _rms(x, p['ln1.w'], eps)
        width = p['attn.qkv.w'].shape[1] // 3
        dh = width // n_head
        qkv = h @ p['attn.qkv.w']
        q = _rms(qkv[:, :width], p['attn.q_norm.w'], eps)
        k = _rms(qkv[:, width:2 * width], p['attn.k_norm.w'], eps)
        v = qkv[:, 2 * width:]
        pos = jnp.arange(t)
        q = _rotate(q.reshape(t, n_head, dh), pos, theta)
        k = _rotate(k.reshape(t, n_head, dh), pos, theta)
        v = v.reshape(t, n_head, dh)
        s = jnp.einsum('qhd,khd->hqk', q, k) * (dh ** -0.5)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum('hqk,khd->qhd', a, v).reshape(t, width)
        x = x + ctx @ p['attn.proj.w']
        return x, _rms(x, p['ln2.w'], eps)


@jax.jit
def _router(g, router_w):
    with jax.default_matmul_precision('highest'):
        return jax.nn.softmax(g @ router_w, axis=-1)


def chosen_mask(probs, top_k, routing=None):
    """[T, E] bool: the top_k largest of each row, or `routing`'s ids."""
    if routing is not None:
        ids = jnp.asarray(np.asarray(routing))
        return jnp.any(ids[:, :, None] == jnp.arange(probs.shape[1]),
                       axis=1)
    kth = jnp.sort(probs, axis=-1)[:, -top_k]
    return probs >= kth[:, None]


def expert_weights(probs, chosen, norm_topk_prob):
    """[T, E]: a chosen expert's probability, 0 elsewhere; renormalised
    over the chosen only where the configuration says so."""
    w = jnp.where(chosen, probs, 0.0)
    return w / jnp.sum(w, axis=-1, keepdims=True) if norm_topk_prob else w


@jax.jit
def _experts(x, g, w, gate_w, up_w, down_w):
    """x + sum_e w[:, e] * FFN_e(g): every expert in turn, over every
    row, masked by its weight."""
    with jax.default_matmul_precision('highest'):
        y = jnp.zeros_like(x)
        for e in range(gate_w.shape[0]):
            f = (jax.nn.silu(g @ gate_w[e]) * (g @ up_w[e])) @ down_w[e]
            y = y + w[:, e:e + 1] * f
        return x + y


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, ln_w, head_w, eps):
    with jax.default_matmul_precision('highest'):
        return _rms(x, ln_w, eps) @ head_w


_ATTN_KEYS = ('ln1.w', 'attn.qkv.w', 'attn.q_norm.w', 'attn.k_norm.w',
              'attn.proj.w', 'ln2.w')


def forward(scope, m, tokens, routing=None, top_k=None, weights=None,
            dtype=jnp.float32):
    """(hidden [T, D] after the last block, [per layer the router's
    probabilities [T, E]]). The controls (olmoe_control.py): `top_k` and
    `weights` (in `expert_weights`' place) other than the
    configuration's; parameters and activations in a `dtype` below
    float32."""
    def _param(scope, name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    tokens = np.asarray(tokens).reshape(-1)
    top_k = m['num_experts_per_tok'] if top_k is None else top_k
    weights = weights or functools.partial(
        expert_weights, norm_topk_prob=bool(m['norm_topk_prob']))
    x = jnp.take(_param(scope, 'tok_emb.w'), jnp.asarray(tokens), axis=0)
    probs = []
    for i in range(m['num_hidden_layers']):
        name = 'layer_%d.' % i
        p = {k: _param(scope, name + k) for k in _ATTN_KEYS}
        x, g = _attention(x, p, n_head=m['num_attention_heads'],
                          eps=float(m['rms_norm_eps']),
                          theta=float(m['rope_theta']))
        pr = _router(g, _param(scope, name + 'moe.router.w'))
        probs.append(pr)
        chosen = chosen_mask(pr, top_k,
                             None if routing is None else routing[i])
        x = _experts(x, g, weights(pr, chosen),
                     _param(scope, name + 'moe.gate.w'),
                     _param(scope, name + 'moe.up.w'),
                     _param(scope, name + 'moe.down.w'))
    return x, probs


def router_probs(scope, m, tokens, routing=None):
    """Per layer the reference router's probabilities [T, E] (numpy), on
    the hidden states of the reference's forward (under `routing`, if
    given)."""
    return [np.asarray(p) for p in forward(scope, m, tokens, routing)[1]]


def logits(scope, m, tokens, routing=None, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    x = forward(scope, m, tokens, routing, **control)[0]
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['rms_norm_eps'])).astype(jnp.float32)


def margins(lg, generated):
    """How far each generated token's logit lies below the row's maximum,
    as a share of (max - mean)."""
    lg = np.asarray(lg)
    generated = np.asarray(generated).reshape(-1)
    top = lg.max(axis=1)
    got = lg[np.arange(len(generated)), generated]
    return (top - got) / (top - lg.mean(axis=1))


def greedy_margins(scope, m, prompt, generated):
    """For each generated token, how far its reference logit lies below the
    reference's maximum at that position, as a share of (max - mean) there.
    One teacher-forced forward over prompt + generated, the reference's own
    routing."""
    prompt = np.asarray(prompt).reshape(-1)
    generated = np.asarray(generated).reshape(-1)
    seq = np.concatenate([prompt, generated[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    return margins(logits(scope, m, seq, positions=pos), generated)
