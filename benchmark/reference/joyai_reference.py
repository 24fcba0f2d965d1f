"""The plain reference of JoyAI-LLM-Flash (`model_type: joyai_llm_flash`;
every key of its config.json is one of the DeepSeek-V3 layer's, HF
`modeling_deepseek_v3.py`): the forward pass in jax.numpy, float32,
matmuls at precision "highest", attention in the EXPANDED form only (keys
and values rebuilt from the latents for every head), a loop over the
experts with a mask — no cache, no absorbed form, no sort, no grouped
matmul, no kernels, no batching. Queries are taken in blocks of
`QUERY_BLOCK` rows so that the scores of 2 816 positions never stand
whole.

For hidden x [T, D] (all norms RMSNorm, eps rms_norm_eps; no bias):

    h    = norm(x; ln1)
    c_q  = norm(h W_dq; q_a_norm)                      [q_lora_rank]
    q    = c_q W_uq -> heads of (qk_nope | qk_rope);   q_r = RoPE(q_r)
    [c_kv | k_r] = h W_dkv;  c_kv = norm(c_kv; kv_a_norm)
    k_r  = RoPE(k_r), ONE rotary key for all heads
    k_nope_h = c_kv W_uk_h^T,  v_h = c_kv W_uv_h       (kv_b_proj's halves)
    score = (q_nope . k_nope + q_r . k_r) / sqrt(qk_nope + qk_rope)
    causal softmax;  x = x + [sum p v]_heads W_o
    RoPE: theta rope_theta, no scaling, INTERLEAVED (rope_interleave): the
        pair (2i, 2i + 1) of a head by position * theta^(-2i/rope dim).
        (HF moves the pairs apart and rotates halves: q and k permuted
        alike, the same scores.)
    g    = norm(x; ln2)
    the first_k_dense_replace leading layers:
        x = x + (silu(g W_g) * (g W_u)) W_d
    the others: s = sigmoid(g W_r) over ALL the router's experts; the
        num_experts_per_tok largest of s + b (b: e_score_correction_bias,
        used to choose only; n_group = topk_group = 1: no group limit);
        w_e = s_e / (sum_chosen s + 1e-20) * routed_scaling_factor
        x = x + sum_{chosen e HELD HERE} w_e FFN_e(g) + FFN_shared(g)
then norm(x; final_ln) and the untied head.

THE SHARE. The configuration file says how many experts this chip holds
(`n_routed_experts`, of `reduced_from.n_routed_experts`, from
`first_expert_held` on): the router keeps its published width and its
experts per token, a chosen expert that is held elsewhere adds nothing,
here as in the program, and that partial sum goes on to the next layer.
`held=(0, all)` is the uncut model.

Departures from the published model are the configuration file's
`changed` list. Parameters are read out of a scope by the names the
decode programs give them (`benchmark/models/joyai.py param_shapes`), as
they lie on the device. `routing` (per expert layer a [T, k] array of
expert ids) puts the SYSTEM's choice in the place of the reference's own
top-k, at the reference's own scores (olmoe_reference.py says why).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS. Set between two
# readings on the v5e at the published widths (PERF.md, PR 32). The sound
# system: 0.0 to 0.090 over 21 seeds (5 x 2 prompts x 41 rows, 16 x 2 x 8)
# — the float32 programs' matmuls run at the TPU's default precision,
# which flips the 8th and 9th expert of 256 where they are nearly tied in
# ~8 % of the (row, layer) choices, and a row with a flip serves the
# reference's second or third token now and then. The controls it refuses:
# `rotate-half` 0.29 to 0.46, `not-renormalised` 0.84 to 1.10 (a token
# taken at random reads ~1). The bfloat16 forward (0.006 to 0.094), one
# expert fewer (0.037 to 0.075), the scaling left out (0.051 to 0.142) and
# the bias in the weights (0 to 0.029) serve nearly the sound system's
# tokens and are NOT refused by any limit on tokens: what tells them apart
# is on LOGITS (joyai_control.py's two limits).
LOGIT_MARGIN = 0.25
QUERY_BLOCK = 256


def router_width(m):
    """The experts the router scores: the published count."""
    return m.get('reduced_from', {}).get('n_routed_experts',
                                         m['n_routed_experts'])


def experts_held(m):
    """(first, count) of the experts whose weights are here."""
    return int(m.get('first_expert_held', 0)), int(m['n_routed_experts'])


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rope_interleaved(x, pos, theta):
    """x [T, H, dh] rotated by pos [T]: the pairs (2i, 2i + 1)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None, None] * inv_freq     # [T,1,dh/2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def rope_rotate_half(x, pos, theta):
    """The WRONG convention for this model (a control): the pairs
    (i, i + dh/2)."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=('n_head', 'nope', 'eps',
                                             'theta', 'rope'))
def _project(x, p, n_head, nope, eps, theta, rope):
    """(q [T, H, nope + rope], k_nope [T, H, nope], k_r [T, rope],
    v [T, H, v]) of one layer: everything ahead of the scores."""
    with jax.default_matmul_precision('highest'):
        t = x.shape[0]
        rank = p['attn.kv_a_norm.w'].shape[0]
        pos = jnp.arange(t)
        h = _rms(x, p['ln1.w'], eps)
        q = (_rms(h @ p['attn.q_a.w'], p['attn.q_a_norm.w'], eps)
             @ p['attn.q_b.w']).reshape(t, n_head, -1)
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)],
                            axis=-1)
        kv = h @ p['attn.kv_a.w']
        c_kv = _rms(kv[:, :rank], p['attn.kv_a_norm.w'], eps)
        k_r = rope(kv[:, None, rank:], pos, theta)[:, 0]
        k_nope = jnp.einsum('tr,hnr->thn', c_kv, p['attn.kv_b_k.w'])
        v = jnp.einsum('tr,hrv->thv', c_kv, p['attn.kv_b_v.w'])
        return q, k_nope, k_r, v


@functools.partial(jax.jit, static_argnames=('nope',))
def _attend(q, start, k_nope, k_r, v, nope):
    """One block of queries (rows start ..) against every key, causal."""
    with jax.default_matmul_precision('highest'):
        s = (jnp.einsum('qhn,khn->hqk', q[..., :nope], k_nope)
             + jnp.einsum('qhr,kr->hqk', q[..., nope:], k_r)) \
            * (q.shape[-1] ** -0.5)
        rows = start + jnp.arange(q.shape[0])
        s = jnp.where((jnp.arange(k_r.shape[0])[None, :]
                       <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum('hqk,khv->qhv', jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=('eps',))
def _after_attention(x, ctx, proj_w, ln2_w, eps):
    with jax.default_matmul_precision('highest'):
        x = x + ctx.reshape(x.shape[0], -1) @ proj_w
        return x, _rms(x, ln2_w, eps)


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
    over the sum of the chosen (+ 1e-20) where the configuration says so,
    times the scaling factor."""
    del bias
    w = jnp.where(chosen, scores, 0.0)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * scale


@jax.jit
def _experts(g, w, gate_w, up_w, down_w):
    """sum_e w[:, e] * FFN_e(g) over the experts whose weights are given:
    every one in turn, over every row, masked by its weight (a `scan`
    over the experts: one expert's code compiled, not 64 copies)."""
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


_ATTN_KEYS = ('ln1.w', 'attn.q_a.w', 'attn.q_a_norm.w', 'attn.q_b.w',
              'attn.kv_a.w', 'attn.kv_a_norm.w', 'attn.kv_b_k.w',
              'attn.kv_b_v.w')


def forward(scope, m, tokens, routing=None, top_k=None, weights=None,
            rope=rope_interleaved, dtype=jnp.float32, held=None):
    """(hidden [T, D] after the last block, [per expert layer the router's
    scores [T, E]]). `held`: the share of the experts computed (default:
    the configuration's; the scope has to hold exactly their weights).
    The controls (joyai_control.py): `top_k`, `weights` (in
    `expert_weights`' place) and `rope` other than the model's;
    parameters and activations in a `dtype` below float32."""
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
    first, count = experts_held(m) if held is None else held
    eps, nope = float(m['rms_norm_eps']), int(m['qk_nope_head_dim'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    scores = []
    for i in range(m['num_hidden_layers']):
        name = 'layer_%d.' % i
        p = {k: param(name + k) for k in _ATTN_KEYS}
        q, k_nope, k_r, v = _project(
            x, p, n_head=m['num_attention_heads'], nope=nope, eps=eps,
            theta=float(m['rope_theta']), rope=rope)
        ctx = jnp.concatenate(
            [_attend(q[s:s + QUERY_BLOCK], s, k_nope, k_r, v, nope=nope)
             for s in range(0, t, QUERY_BLOCK)], axis=0)
        x, g = _after_attention(x, ctx, param(name + 'attn.proj.w'),
                                param(name + 'ln2.w'), eps=eps)
        if i < m['first_k_dense_replace']:
            x = x + _gated(g, *(param(name + 'ffn.%s.w' % k)
                                for k in ('gate', 'up', 'down')))
            continue
        sc = _scores(g, param(name + 'moe.router.w'))
        scores.append(sc)
        bias = jnp.asarray(scope.get(name + 'moe.router.bias'), jnp.float32)
        j = len(scores) - 1
        chosen = chosen_mask(sc, bias, top_k,
                             None if routing is None else routing[j])
        w = weights(sc, chosen, bias)[:, first:first + count]
        x = x + _experts(g, w, *(param(name + 'moe.%s.w' % k)
                                 for k in ('gate', 'up', 'down')))
        if m['n_shared_experts']:
            x = x + _gated(g, *(param(name + 'moe.shared.%s.w' % k)
                                for k in ('gate', 'up', 'down')))
    return x, scores


def router_scores(scope, m, tokens, routing=None):
    """Per expert layer the reference router's scores [T, E] (numpy), on
    the hidden states of the reference's forward (under `routing`, if
    given)."""
    return [np.asarray(s) for s in forward(scope, m, tokens, routing)[1]]


def head(scope, m, x, positions=None):
    """The final norm and the head on `forward`'s hidden states (the rows
    `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['rms_norm_eps'])).astype(jnp.float32)


def logits(scope, m, tokens, routing=None, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, routing, **control)[0],
                positions)


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
