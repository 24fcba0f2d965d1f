"""The plain reference of K-EXAONE-236B-A23B (`model_type: exaone_moe`): the
forward pass in jax.numpy, float32, matmuls at precision "highest", the
whole sequence at once — attention with the K/V heads repeated under a
causal mask that is BANDED on the window layers, a loop over the held
experts with a mask. No cache, no block pool, no ring, no kernels, no
sort, no grouped matmul, no batching, nothing of paddle_tpu/. Queries are
taken in blocks of `QUERY_BLOCK` rows and the feed-forward layers in
blocks of `ROW_BLOCK` rows, so that neither the scores of 4 104 positions
nor their 18 432-wide activations ever stand whole.

For hidden x [T, D] (every norm RMSNorm with a weight, eps rms_norm_eps;
no bias anywhere; `layer_types` says which attention a layer has):

    h = x + Attention(norm(x; ln1));   out = h + FFN(norm(h; ln2))

    attention (HF modeling_exaone4.py): q = z W_q -> num_attention_heads
        heads of head_dim, k = z W_k, v = z W_v -> num_key_value_heads
        heads; q and k each through an RMSNorm over the head's head_dim
        numbers (one weight for q, one for k, shared by the heads);
        `sliding_attention` layers ONLY: then RoPE (theta
        rope_parameters.rope_theta, rotate_half: the pairs (i, i +
        head_dim/2), all of the head) — a `full_attention` layer rotates
        nothing (the model card's "Global attention: NoPE");
        query head h reads K/V head h // (heads / kv heads);
        scores / sqrt(head_dim); the query at position i sees key j iff
        0 <= i - j (every layer) and i - j < sliding_window (a
        `sliding_attention` layer); softmax; y = ctx W_o
    FFN, the first_k_dense_replace leading layers:
        (silu(g W_1) * (g W_3)) W_2, width intermediate_size
    FFN, the others (DeepSeek-V3's, key for key): s = sigmoid(g W_r) over
        ALL the router's experts (float32); the num_experts_per_tok
        largest of s + b (b: e_score_correction_bias, used to choose
        only; n_group = topk_group = 1: no group limit); w_e = s_e /
        (sum_chosen s + 1e-20) * routed_scaling_factor (norm_topk_prob);
        x = x + sum_{chosen e HELD HERE} w_e FFN_e(g) + FFN_shared(g),
        experts SiLU-gated of width moe_intermediate_size
then norm(x; final_ln) and the untied head over the vocabulary slice.

THE SHARE. The configuration file says how many experts this chip holds
(`num_experts`, of `reduced_from.num_experts`, from `first_expert_held`
on): the router keeps its published width and its experts per token, a
chosen expert that is held elsewhere adds nothing, here as in the
program, and that partial sum goes on to the next layer. `held=(0, all)`
is the uncut layer. The vocabulary slice is a smaller vocabulary: table
and head hold its rows alone.

THE NORM PLACEMENT (pre-norm, as above) and the per-head q/k norm have no
key in the config: the configuration file's `assumed` says why these.
Other departures from the published model are its `changed` list.
Parameters are read out of a scope by the names the decode programs give
them (`benchmark/models/kexaone.py param_shapes`), as they lie on the
device: q, k and v are the three column ranges of ONE matrix
`attn.qkv.w`. `routing` (per expert layer a [T, k] array of expert ids)
puts the SYSTEM's choice in the place of the reference's own top-k, at
the reference's own scores (olmoe_reference.py says why).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS. Set between two
# readings on the v5e at the published widths (PERF.md, PR 41). The sound
# system: 0.0 to 0.0622 over 40 seeds (34 x 2 prompts x 8 rows in the
# cell's own check, 2 x 3 x 2 x 25 in kexaone_control.py) — the float32
# programs' matmuls run at the TPU's default precision, which flips the
# 8th and 9th expert of 128 where they are nearly tied, and a row with a
# flip serves the reference's second token now and then. The control it
# refuses in every one of twelve readings: the window layers without their
# bound, 0.328 to 1.66 (a token taken at random reads ~1); the limit is a
# factor 3.2 above the one and 1.6 under the other. Every norm's weight
# taken as 1 reads 0.157 to 0.356 and is refused in ten of the twelve (a
# 128-token prompt's two were not). A window one key off (0.049 to 0.189),
# one held expert fewer (0 to 0.103), RoPE on the global layer (0 to
# 0.079) and the bfloat16 forward (0 to 0.017) serve nearly the sound
# system's tokens and are NOT refused by any limit on tokens: what tells
# them apart is on LOGITS (kexaone_control.py's two limits, which refuse
# every control in every reading).
LOGIT_MARGIN = 0.2
QUERY_BLOCK = 256
ROW_BLOCK = 1024


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


def router_width(m):
    """The experts the router scores: the published count."""
    return m.get('reduced_from', {}).get('num_experts', m['num_experts'])


def experts_held(m):
    """(first, count) of the experts this chip holds."""
    return int(m.get('first_expert_held', 0)), int(m['num_experts'])


def rope_theta(m):
    return float(m['rope_parameters']['rope_theta'])


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'eps',
                                             'theta', 'rotate'))
def _project(x, ln_w, qkv_w, q_norm, k_norm, n_head, n_kv_head, eps, theta,
             rotate):
    """(q [T, H, dh], k [T, Hkv, dh], v [T, Hkv, dh]): everything ahead of
    the scores."""
    with jax.default_matmul_precision('highest'):
        t = x.shape[0]
        dh = qkv_w.shape[1] // (n_head + 2 * n_kv_head)
        qkv = _rms(x, ln_w, eps) @ qkv_w
        q = qkv[:, :n_head * dh].reshape(t, n_head, dh)
        k = qkv[:, n_head * dh:(n_head + n_kv_head) * dh].reshape(
            t, n_kv_head, dh)
        v = qkv[:, (n_head + n_kv_head) * dh:].reshape(t, n_kv_head, dh)
        q, k = _rms(q, q_norm, eps), _rms(k, k_norm, eps)
        if rotate:
            pos = jnp.arange(t)
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        return q, k, v


@functools.partial(jax.jit, static_argnames=('window',))
def _attend(q, start, k, v, window):
    """One block of queries (rows start ..) against every key, causal and,
    with `window`, banded; k and v already repeated to the query heads."""
    with jax.default_matmul_precision('highest'):
        s = jnp.einsum('qhd,khd->hqk', q, k) * (q.shape[-1] ** -0.5)
        back = (start + jnp.arange(q.shape[0]))[:, None] \
            - jnp.arange(k.shape[0])[None, :]
        seen = back >= 0
        if window:
            seen &= back < window
        s = jnp.where(seen[None], s, -jnp.inf)
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


def expert_weights(scores, chosen, norm_topk_prob, scale):
    """[T, E]: a chosen expert's score (WITHOUT the bias), 0 elsewhere,
    over the sum of the chosen (+ 1e-20) where the configuration says so,
    times the scaling factor."""
    w = jnp.where(chosen, scores, 0.0)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@jax.jit
def _experts(g, w, gate_w, up_w, down_w):
    """sum_e w[:, e] * FFN_e(g) over the experts whose weights are given:
    every one in turn, over every row, masked by its weight (a `scan`:
    one expert's code compiled)."""
    def one(y, expert):
        we, gate, up, down = expert
        with jax.default_matmul_precision('highest'):
            f = (jax.nn.silu(g @ gate) * (g @ up)) @ down
        return y + we[:, None].astype(g.dtype) * f, None
    return jax.lax.scan(one, jnp.zeros_like(g),
                        (w.T, gate_w, up_w, down_w))[0]


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, ln_w, head_w, eps):
    with jax.default_matmul_precision('highest'):
        return _rms(x, ln_w, eps) @ head_w


def _in_row_blocks(fn, *rows):
    """`fn` over blocks of `ROW_BLOCK` rows of its arguments."""
    n = rows[0].shape[0]
    return jnp.concatenate([fn(*(r[s:s + ROW_BLOCK] for r in rows))
                            for s in range(0, n, ROW_BLOCK)], axis=0)


def forward(scope, m, tokens, routing=None, dtype=jnp.float32, held=None,
            window='published', rope_on_global=False, norm_weights=True):
    """(hidden [T, D] after the last block, [per expert layer the router's
    scores [T, E]]). `held`: the share of the experts computed (default:
    the configuration's; the scope has to hold at least their weights,
    from its first on). The controls (kexaone_control.py): a `window`
    other than the published one (None: the window layers see every key),
    `rope_on_global`, `norm_weights` False (every norm's weight taken as
    1), fewer experts `held` than the scope has, parameters and
    activations in a `dtype` below float32."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        v = jnp.asarray(v, dtype)
        is_norm = v.ndim == 1 and not name.endswith('.bias')
        return jnp.ones_like(v) if is_norm and not norm_weights else v

    tokens = np.asarray(tokens).reshape(-1)
    t = len(tokens)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    kv_head_of = np.arange(h) // (h // hkv)
    first, count = experts_held(m) if held is None else held
    window = m['sliding_window'] if window == 'published' else window
    eps = float(m['rms_norm_eps'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    scores = []
    for i, kind in enumerate(m['layer_types'][:m['num_hidden_layers']]):
        name = 'layer_%d.' % i
        local = kind == 'sliding_attention'
        q, k, v = _project(
            x, param(name + 'ln1.w'), param(name + 'attn.qkv.w'),
            param(name + 'attn.q_norm.w'), param(name + 'attn.k_norm.w'),
            n_head=h, n_kv_head=hkv, eps=eps, theta=rope_theta(m),
            rotate=local or rope_on_global)
        k, v = k[:, kv_head_of], v[:, kv_head_of]           # repeated
        ctx = jnp.concatenate(
            [_attend(q[s:s + QUERY_BLOCK], s, k, v,
                     window=window if local else None)
             for s in range(0, t, QUERY_BLOCK)], axis=0)
        x = _residual_proj(x, ctx, param(name + 'attn.proj.w'))
        g = _rms(x, param(name + 'ln2.w'), eps)
        if i < m['first_k_dense_replace']:
            x = x + _in_row_blocks(
                functools.partial(_gated, **{
                    k + '_w': param(name + 'ffn.%s.w' % k)
                    for k in ('gate', 'up', 'down')}), g)
            continue
        sc = _scores(g, param(name + 'moe.router.w'))
        scores.append(sc)
        bias = jnp.asarray(scope.get(name + 'moe.router.bias'), jnp.float32)
        chosen = chosen_mask(
            sc, bias, m['num_experts_per_tok'],
            None if routing is None else routing[len(scores) - 1])
        w = expert_weights(sc, chosen, bool(m['norm_topk_prob']),
                           float(m['routed_scaling_factor']))
        gate, up, down = (param(name + 'moe.%s.w' % k)[:count]
                          for k in ('gate', 'up', 'down'))
        x = x + _in_row_blocks(
            functools.partial(_experts, gate_w=gate, up_w=up, down_w=down),
            g, w[:, first:first + count])
        if m['num_shared_experts']:
            x = x + _in_row_blocks(
                functools.partial(_gated, **{
                    k + '_w': param(name + 'moe.shared.%s.w' % k)
                    for k in ('gate', 'up', 'down')}), g)
    return x, scores


def router_scores(scope, m, tokens, routing=None):
    """Per expert layer the reference router's scores [T, E] (numpy), on
    the hidden states of the reference's forward (under `routing`, if
    given)."""
    return [np.asarray(s) for s in forward(scope, m, tokens, routing)[1]]


def head(scope, m, x, positions=None, norm_weights=True):
    """The final norm and the head on `forward`'s hidden states (the rows
    `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    ln = jnp.asarray(scope.get('final_ln.w'), x.dtype)
    return _head(x, ln if norm_weights else jnp.ones_like(ln),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['rms_norm_eps'])).astype(jnp.float32)


def logits(scope, m, tokens, routing=None, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, routing, **control)[0],
                positions, control.get('norm_weights', True))


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
