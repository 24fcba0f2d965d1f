"""The plain reference of Mellum2-12B-A2.5B-Instruct (`model_type: mellum`):
the forward pass in jax.numpy, float32, matmuls at precision "highest", the
whole sequence at once — attention with the K/V heads repeated under a
causal mask that is BANDED on the sliding layers, a loop over the experts
with a mask. No cache, no block pool, no ring, no kernels, no sort, no
grouped matmul, no batching, nothing of paddle_tpu/. Queries are taken in
blocks of `QUERY_BLOCK` rows and the expert layers in blocks of `ROW_BLOCK`
rows, so that the scores of 10 752 positions never stand whole.

For hidden x [T, D] (every norm RMSNorm with a weight, eps rms_norm_eps; no
bias anywhere; `layer_types` says which attention a layer has):

    h = x + Attention(norm(x; ln1));   out = h + MoE(norm(h; ln2))

    attention: q = z W_q -> num_attention_heads heads of head_dim, k = z
        W_k, v = z W_v -> num_key_value_heads heads; q and k each through
        an RMSNorm over the head's head_dim numbers (one weight for q, one
        for k, shared by the heads); then rotated (rotate_half: the pairs
        (i, i + head_dim/2)) with the table of the layer's KIND
        (`rope_parameters[kind]`):
          `sliding_attention`, rope_type default:
              inv_freq_i = theta^(-2i/head_dim), cos and sin as they are;
          `full_attention`, rope_type yarn (HF _compute_yarn_parameters):
              extra_i = theta^(-2i/dh), inter_i = extra_i / factor;
              dim(r) = dh ln(original_max / (2 pi r)) / (2 ln theta);
              low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)),
              clamped to [0, dh - 1]   (18 and 35 at the published numbers);
              ramp_i = clip((i - low) / (high - low), 0, 1), i < dh/2;
              inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i);
              cos and sin TIMES attention_factor (so the scores carry its
              square);
        query head h reads K/V head h // (heads / kv heads); scores /
        sqrt(head_dim); the query at position i sees key j iff 0 <= i - j
        (every layer) and i - j < sliding_window (a sliding layer);
        softmax; y = ctx W_o
    MoE (every layer; Qwen3-MoE's, key for key): p = softmax(g W_r) over
        ALL num_experts (float32); the num_experts_per_tok largest; w_e =
        p_e / sum_chosen p (norm_topk_prob); sum_e w_e (silu(g W_g,e) *
        (g W_u,e)) W_d,e, experts of width moe_intermediate_size; no shared
        expert
then norm(x; final_ln) and the untied head.

THE NORM PLACEMENT (pre-norm, as above) and the per-head q/k norm have no
key in the config: the configuration file's `assumed` says why these.
Other departures from the published model are its `changed` list.
Parameters are read out of a scope by the names the decode programs give
them (`benchmark/models/mellum2.py param_shapes`), as they lie on the
device: q, k and v are the three column ranges of ONE matrix `attn.qkv.w`.
`routing` (per layer a [T, k] array of expert ids) puts the SYSTEM's choice
in the place of the reference's own top-k, at the reference's own scores
(olmoe_reference.py says why).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS. Readings on the v5e
# at the published widths (PERF.md, PR 51): the served system 0 to 0.0169
# over 17 runs' two checked requests and 0 to 0.0147 over the control's
# eight (a near-tie flipped by the default precision); of the controls no
# window reads 0.46 to 1.46 and a resumed ring given zeros or the first
# tenant's later rows 0.83 to 1.37 -- refused in every reading -- YaRN on
# the sliding layers too 0.26 to 0.50 (refused in eight of eight); the
# others serve the sound system's greedy tokens too often for any margin
# (0 to 0.27): mellum2_control.py's two limits on LOGITS refuse them all.
# K-EXAONE's value: a factor 12 above the largest sound reading, 1.3 under
# the smallest reading of those three controls.
LOGIT_MARGIN = 0.2
QUERY_BLOCK = 256
ROW_BLOCK = 1024


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def inv_freq(dh, rope):
    """(the dh/2 frequencies, the factor on cos and sin) of one kind's
    `rope_parameters` entry, in float64 numpy."""
    i = np.arange(dh // 2, dtype=np.float64)
    extra = float(rope['rope_theta']) ** (-2.0 * i / dh)
    if rope['rope_type'] == 'default':
        return extra, 1.0
    if rope['rope_type'] != 'yarn':
        raise ValueError('reference: rope_type %r' % (rope['rope_type'],))
    low, high = yarn_range(dh, rope)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    factor = float(rope['factor'])
    return extra / factor * ramp + extra * (1.0 - ramp), \
        float(rope.get('attention_factor', 0.1 * math.log(factor) + 1.0))


def yarn_range(dh, rope):
    """(low, high): the pair below which a frequency is kept and the pair
    from which it is divided by the factor."""
    def dim(turns):
        return dh * math.log(rope['original_max_position_embeddings']
                             / (turns * 2 * math.pi)) \
            / (2 * math.log(rope['rope_theta']))
    return max(math.floor(dim(rope['beta_fast'])), 0), \
        min(math.ceil(dim(rope['beta_slow'])), dh - 1)


def rope(x, pos, freq, factor):
    """x [T, H, dh] rotated by pos [T]: the pairs (i, i + dh/2)."""
    dh = x.shape[-1]
    angle = pos.astype(jnp.float32)[:, None] * freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * (jnp.cos(emb) * factor)
            + half * (jnp.sin(emb) * factor)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'eps',
                                             'factor'))
def _project(x, ln_w, qkv_w, q_norm, k_norm, freq, n_head, n_kv_head, eps,
             factor):
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
        pos = jnp.arange(t)
        return rope(q, pos, freq, factor), rope(k, pos, freq, factor), v


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
def _scores(g, router_w):
    with jax.default_matmul_precision('highest'):
        return jax.nn.softmax(g.astype(jnp.float32)
                              @ router_w.astype(jnp.float32), axis=-1)


def chosen_mask(scores, top_k, routing=None):
    """[T, E] bool: the top_k largest scores of each row, or `routing`'s
    ids."""
    if routing is not None:
        ids = jnp.asarray(np.asarray(routing))
        return jnp.any(ids[:, :, None] == jnp.arange(scores.shape[1]),
                       axis=1)
    kth = jnp.sort(scores, axis=-1)[:, -top_k]
    return scores >= kth[:, None]


def expert_weights(scores, chosen, norm_topk_prob):
    """[T, E]: a chosen expert's probability, 0 elsewhere, over the sum of
    the chosen where the configuration says so."""
    w = jnp.where(chosen, scores, 0.0)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w


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


def forward(scope, m, tokens, routing=None, dtype=jnp.float32,
            window='published', rope_parameters=None, norm_weights=True,
            norm_topk_prob=None, top_k=None, resumed=None,
            keep_window_kv=False):
    """(hidden [T, D] after the last block, [per layer the router's
    probabilities [T, E]]). The controls (mellum2_control.py): a `window`
    other than the published one (None: the sliding layers see every key),
    other `rope_parameters` (kind -> entry), `norm_weights` False (every
    norm's weight taken as 1), `norm_topk_prob` off, fewer experts a token
    (`top_k`), parameters and activations in a `dtype` below float32, and
    `resumed` = (R, rows): the queries from position R on see, in the
    SLIDING layers, the keys and values of positions before R as `rows`
    has them -- per sliding layer (k, v) [R, Hkv, dh], what a request that
    resumed at R found in its ring -- and not the sequence's own.
    `keep_window_kv`: also return the sliding layers' (k, v), for such
    rows."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        v = jnp.asarray(v, dtype)
        return jnp.ones_like(v) if v.ndim == 1 and not norm_weights else v

    tokens = np.asarray(tokens).reshape(-1)
    t = len(tokens)
    h, hkv, dh = m['num_attention_heads'], m['num_key_value_heads'], \
        m['head_dim']
    kv_head_of = np.arange(h) // (h // hkv)
    window = m['sliding_window'] if window == 'published' else window
    ropes = rope_parameters or m['rope_parameters']
    eps = float(m['rms_norm_eps'])
    topk = m['num_experts_per_tok'] if top_k is None else top_k
    renorm = bool(m['norm_topk_prob']) if norm_topk_prob is None \
        else norm_topk_prob
    # query blocks end at R, so that one block has one set of keys
    cuts = sorted(set(range(0, t, QUERY_BLOCK)) | {t}
                  | ({resumed[0]} if resumed else set()))
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    scores, kept = [], []
    for i, kind in enumerate(m['layer_types'][:m['num_hidden_layers']]):
        name = 'layer_%d.' % i
        local = kind == 'sliding_attention'
        freq, factor = inv_freq(dh, ropes[kind])
        q, k, v = _project(
            x, param(name + 'ln1.w'), param(name + 'attn.qkv.w'),
            param(name + 'attn.q_norm.w'), param(name + 'attn.k_norm.w'),
            jnp.asarray(freq, jnp.float32), n_head=h, n_kv_head=hkv,
            eps=eps, factor=float(factor))
        if local and keep_window_kv:
            kept.append((k, v))
        found = None
        if local and resumed:
            at, rows = resumed
            fk, fv = rows[list(m['layer_types'][:i]).count(
                'sliding_attention')]
            found = (jnp.concatenate([jnp.asarray(fk, k.dtype), k[at:]]),
                     jnp.concatenate([jnp.asarray(fv, v.dtype), v[at:]]))
        keys = {False: (k[:, kv_head_of], v[:, kv_head_of])}
        if found is not None:
            keys[True] = (found[0][:, kv_head_of], found[1][:, kv_head_of])
        ctx = jnp.concatenate(
            [_attend(q[s:e], s, *keys[found is not None and s >= resumed[0]],
                     window=window if local else None)
             for s, e in zip(cuts[:-1], cuts[1:])], axis=0)
        x = _residual_proj(x, ctx, param(name + 'attn.proj.w'))
        g = _rms(x, param(name + 'ln2.w'), eps)
        sc = _scores(g, param(name + 'moe.router.w'))
        scores.append(sc)
        chosen = chosen_mask(sc, topk,
                             None if routing is None else routing[i])
        w = expert_weights(sc, chosen, renorm)
        gate, up, down = (param(name + 'moe.%s.w' % key)
                          for key in ('gate', 'up', 'down'))
        x = x + _in_row_blocks(
            functools.partial(_experts, gate_w=gate, up_w=up, down_w=down),
            g, w)
    return (x, scores, kept) if keep_window_kv else (x, scores)


def router_scores(scope, m, tokens, routing=None):
    """Per layer the reference router's probabilities [T, E] (numpy), on
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
