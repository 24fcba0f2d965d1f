"""The plain reference of LFM2-8B-A1B (`model_type: lfm2_moe`, HF
`modeling_lfm2_moe.py`): the forward pass in jax.numpy, float32, matmuls at
precision "highest", the whole sequence at once — the convolution as
shifted adds, full causal attention with the K/V heads repeated, a loop
over the experts with a mask. No cache, no block pool, no tails, no
kernels, no sort, no grouped matmul, no batching, nothing of paddle_tpu/.
Queries are taken in blocks of `QUERY_BLOCK` rows so that the scores of
4 616 positions never stand whole.

For hidden x [T, D] (every norm RMSNorm with a weight, eps norm_eps; no
bias anywhere; `layer_types` says which mixer a layer has):

    h = x + Mixer(norm(x; ln1));   out = h + FFN(norm(h; ln2))

    conv mixer:  [B | C | u] = z W_in  (D each, in that order)
        g = B * u
        c_t = sum_j w[:, j] * g_{t - (K - 1) + j}, K = conv_L_cache = 3,
              g before position 0 is zero (causal, depthwise, no
              activation, conv_bias false)
        y = (C * c) W_out
    attention mixer:  q = z W_q -> num_attention_heads heads of head_dim,
        k = z W_k, v = z W_v -> num_key_value_heads heads;
        q and k each through an RMSNorm over the head's head_dim numbers
        (one weight for q, one for k, shared by the heads), THEN RoPE
        (theta rope_theta, rotate_half: the pairs (i, i + head_dim/2), all
        of the head); query head h reads K/V head h // (heads / kv heads);
        scores / sqrt(head_dim), causal softmax; y = ctx W_o
    FFN, the num_dense_layers leading layers:
        (silu(g W_1) * (g W_3)) W_2, width intermediate_size
    FFN, the others: s = sigmoid(g W_r) over num_experts (float32); the
        num_experts_per_tok largest of s + b (b: expert_bias, used to
        choose only); w_e = s_e / (sum_chosen s + 1e-6) *
        routed_scaling_factor (norm_topk_prob); sum_chosen w_e FFN_e(g),
        experts SiLU-gated of width moe_intermediate_size; no shared
        expert
then norm(x; final_ln) (the source's `embedding_norm`) and the TIED head:
logits = x E^T with E the embedding table.

Departures from the published model are the configuration file's
`changed` list. Parameters are read out of a scope by the names the
decode programs give them (`benchmark/models/lfm2.py param_shapes`), as
they lie on the device: q, k and v are the three column ranges of ONE
matrix `attn.qkv.w`. `routing` (per expert layer a [T, k] array of expert
ids) puts the SYSTEM's choice in the place of the reference's own top-k,
at the reference's own scores (olmoe_reference.py says why).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS. Set between two
# readings on the v5e at the published widths (PERF.md, PR 35). The sound
# system: 0.0 to 0.143 over 17 seeds (14 x 2 prompts x 8 rows in the
# cell's own check, 3 x 2 x 25 in lfm2_control.py) — the float32 programs'
# matmuls run at the TPU's default precision, which flips the 4th and 5th
# expert of 32 where they are nearly tied, and a row with a flip serves
# the reference's second or third token now and then. The control it
# refuses: the head untied, 1.27 to 1.50 (a token taken at random reads
# ~1). The limit is a factor 2.8 above the one and 3.2 under the other.
# The bfloat16 forward (0 to 0.080), K/V head h % 8 (0.029 to 0.119), one
# expert fewer (0.114 to 0.206), the per-head norms left out, the bias in
# the weights and a zero tail read at late rows (0 to 0.079) serve nearly
# the sound system's tokens and are NOT refused by any limit on tokens:
# what tells them apart, where anything does, is on LOGITS
# (lfm2_control.py's three limits).
LOGIT_MARGIN = 0.4
QUERY_BLOCK = 256


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope(x, pos, theta):
    """x [T, H, dh] rotated by pos [T]: the pairs (i, i + dh/2)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype)


def head_of_group(n_head, n_kv_head):
    """The model's rule: query head h reads K/V head h // (H / Hkv)."""
    return np.arange(n_head) // (n_head // n_kv_head)


@functools.partial(jax.jit, static_argnames=('eps', 'zero_before'))
def _conv_mixer(x, ln_w, in_w, conv_w, out_w, eps, zero_before):
    """x + the convolution mixer of norm(x). `zero_before` (a control):
    positions from it on see g of the positions before it as zero — a
    suffix resumed from a zero tail."""
    with jax.default_matmul_precision('highest'):
        t, d = x.shape
        taps = conv_w.shape[1]
        bcu = _rms(x, ln_w, eps) @ in_w
        b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
        g = b * u
        rows = jnp.arange(t)
        mixed = jnp.zeros_like(g)
        for j in range(taps):
            back = taps - 1 - j                 # tap j reads g_{t - back}
            src = jnp.pad(g, ((back, 0), (0, 0)))[:t]
            if zero_before is not None:
                src = jnp.where(((rows >= zero_before)
                                 & (rows - back < zero_before))[:, None],
                                0.0, src)
            mixed = mixed + src * conv_w[:, j]
        return x + (c * mixed) @ out_w


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'eps',
                                             'theta', 'head_norm'))
def _project(x, ln_w, qkv_w, q_norm, k_norm, n_head, n_kv_head, eps, theta,
             head_norm):
    """(q [T, H, dh], k [T, Hkv, dh], v [T, Hkv, dh]): everything ahead of
    the scores. `head_norm` False (a control) leaves the two per-head
    norms out."""
    with jax.default_matmul_precision('highest'):
        t = x.shape[0]
        dh = qkv_w.shape[1] // (n_head + 2 * n_kv_head)
        qkv = _rms(x, ln_w, eps) @ qkv_w
        q = qkv[:, :n_head * dh].reshape(t, n_head, dh)
        k = qkv[:, n_head * dh:(n_head + n_kv_head) * dh].reshape(
            t, n_kv_head, dh)
        v = qkv[:, (n_head + n_kv_head) * dh:].reshape(t, n_kv_head, dh)
        if head_norm:
            q, k = _rms(q, q_norm, eps), _rms(k, k_norm, eps)
        pos = jnp.arange(t)
        return rope(q, pos, theta), rope(k, pos, theta), v


@jax.jit
def _attend(q, start, k, v):
    """One block of queries (rows start ..) against every key, causal; k
    and v already repeated to the query heads."""
    with jax.default_matmul_precision('highest'):
        s = jnp.einsum('qhd,khd->hqk', q, k) * (q.shape[-1] ** -0.5)
        rows = start + jnp.arange(q.shape[0])
        s = jnp.where((jnp.arange(k.shape[0])[None, :]
                       <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)


@jax.jit
def _residual_proj(x, ctx, proj_w):
    with jax.default_matmul_precision('highest'):
        return x + ctx.reshape(x.shape[0], -1) @ proj_w


@jax.jit
def _gated(g, gate_w, up_w, down_w):
    with jax.default_matmul_precision('highest'):
        return (jax.nn.silu(g @ gate_w) * (g @ up_w)) @ down_w


@jax.jit
def _scores(g, router_w):
    with jax.default_matmul_precision('highest'):
        return jax.nn.sigmoid(g.astype(jnp.float32)
                              @ router_w.astype(jnp.float32))


def chosen_mask(scores, bias, top_k, routing=None):
    """[T, E] bool: the top_k largest of scores + bias in each row, or
    `routing`'s ids."""
    if routing is not None:
        ids = jnp.asarray(np.asarray(routing))
        return jnp.any(ids[:, :, None] == jnp.arange(scores.shape[1]),
                       axis=1)
    choose = scores + bias[None, :]
    kth = jnp.sort(choose, axis=-1)[:, -top_k]
    return choose >= kth[:, None]


def expert_weights(scores, chosen, bias, norm_topk_prob, scale):
    """[T, E]: a chosen expert's score (WITHOUT the bias), 0 elsewhere,
    over the sum of the chosen + 1e-6 where the configuration says so,
    times the scaling factor."""
    del bias
    w = jnp.where(chosen, scores, 0.0)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * scale


@jax.jit
def _experts(g, w, gate_w, up_w, down_w):
    """sum_e w[:, e] * FFN_e(g): every expert in turn, over every row,
    masked by its weight (a `scan`: one expert's code compiled)."""
    def one(y, expert):
        we, gate, up, down = expert
        with jax.default_matmul_precision('highest'):
            f = (jax.nn.silu(g @ gate) * (g @ up)) @ down
        return y + we[:, None].astype(g.dtype) * f, None
    return jax.lax.scan(one, jnp.zeros_like(g),
                        (w.T, gate_w, up_w, down_w))[0]


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, ln_w, table, eps):
    with jax.default_matmul_precision('highest'):
        return _rms(x, ln_w, eps) @ table.T


def head_dim(m):
    return m.get('head_dim') or m['hidden_size'] // m['num_attention_heads']


def forward(scope, m, tokens, routing=None, top_k=None, weights=None,
            dtype=jnp.float32, kv_head_of=None, head_norm=True,
            zero_tail_at=None):
    """(hidden [T, D] after the last block, [per expert layer the router's
    scores [T, E]]). The controls (lfm2_control.py): `top_k`, `weights`
    (in `expert_weights`' place), a `kv_head_of` other than the model's,
    `head_norm` False, `zero_tail_at` (`_conv_mixer`); parameters and
    activations in a `dtype` below float32."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    tokens = np.asarray(tokens).reshape(-1)
    t = len(tokens)
    top_k = m['num_experts_per_tok'] if top_k is None else top_k
    weights = weights or functools.partial(
        expert_weights, norm_topk_prob=bool(m['norm_topk_prob']),
        scale=float(m['routed_scaling_factor']))
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    kv_head_of = head_of_group(h, hkv) if kv_head_of is None \
        else np.asarray(kv_head_of)
    eps = float(m['norm_eps'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    scores = []
    for i, kind in enumerate(m['layer_types'][:m['num_hidden_layers']]):
        name = 'layer_%d.' % i
        if kind == 'conv':
            x = _conv_mixer(x, param(name + 'ln1.w'),
                            param(name + 'conv.in.w'),
                            param(name + 'conv.w'),
                            param(name + 'conv.out.w'), eps=eps,
                            zero_before=zero_tail_at)
        else:
            q, k, v = _project(
                x, param(name + 'ln1.w'), param(name + 'attn.qkv.w'),
                param(name + 'attn.q_norm.w'), param(name + 'attn.k_norm.w'),
                n_head=h, n_kv_head=hkv, eps=eps,
                theta=float(m['rope_theta']), head_norm=head_norm)
            k, v = k[:, kv_head_of], v[:, kv_head_of]       # repeated
            ctx = jnp.concatenate(
                [_attend(q[s:s + QUERY_BLOCK], s, k, v)
                 for s in range(0, t, QUERY_BLOCK)], axis=0)
            x = _residual_proj(x, ctx, param(name + 'attn.proj.w'))
        g = _rms(x, param(name + 'ln2.w'), eps)
        if i < m['num_dense_layers']:
            x = x + _gated(g, *(param(name + 'ffn.%s.w' % k)
                                for k in ('gate', 'up', 'down')))
            continue
        sc = _scores(g, param(name + 'moe.router.w'))
        scores.append(sc)
        bias = jnp.asarray(scope.get(name + 'moe.router.bias'), jnp.float32)
        j = len(scores) - 1
        chosen = chosen_mask(sc, bias, top_k,
                             None if routing is None else routing[j])
        x = x + _experts(g, weights(sc, chosen, bias),
                         *(param(name + 'moe.%s.w' % k)
                           for k in ('gate', 'up', 'down')))
    return x, scores


def router_scores(scope, m, tokens, routing=None):
    """Per expert layer the reference router's scores [T, E] (numpy), on
    the hidden states of the reference's forward (under `routing`, if
    given)."""
    return [np.asarray(s) for s in forward(scope, m, tokens, routing)[1]]


def head(scope, m, x, positions=None, table=None):
    """The final norm and the tied head on `forward`'s hidden states (the
    rows `positions` select; default: all), float32. `table` (a control):
    another matrix [V, D] in the embedding table's place."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    table = scope.get('tok_emb.w') if table is None else table
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(table, x.dtype),
                 eps=float(m['norm_eps'])).astype(jnp.float32)


def logits(scope, m, tokens, routing=None, positions=None, head_table=None,
           **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, routing, **control)[0],
                positions, head_table)


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
