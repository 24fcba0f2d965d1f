"""The plain reference of Qwen3-Next-80B-A3B-Instruct (`model_type:
qwen3_next`; `Qwen3NextDecoderLayer`, `Qwen3NextGatedDeltaNet`,
`Qwen3NextAttention`, `Qwen3NextSparseMoeBlock` of HF
`modeling_qwen3_next.py`; the recurrence is Gated Delta Networks,
arXiv:2412.06464): the forward pass in jax.numpy, float32, matmuls at
precision "highest", the whole sequence at once -- the convolution as
shifted adds, THE DELTA RULE AS A PLAIN `lax.scan` OVER THE POSITIONS with
the ``[Hv, dk, dv]`` state as its carry (the definition, NOT the chunked
form: the system's chunked prefill is held to it), full causal attention
with the two K/V heads repeated, the expert layer over THIS CHIP'S SHARE.
No cache, no block pool, no state rows, no kernels, no chunks, no batching,
nothing of paddle_tpu/. Queries are taken in blocks of `QUERY_BLOCK` rows
and the held experts one at a time, so that ~9 000 positions fit beside the
weights.

For hidden x [T, D]. Every RMSNorm but one is ZERO-CENTRED, ``n(x) = x /
rms(x) (1 + w)`` (eps rms_norm_eps): the block's two, the final one, q-norm
and k-norm; the DeltaNet's output norm is ``x / rms(x) w``. No bias
anywhere. Layer i is full attention iff (i + 1) % full_attention_interval
== 0, else a Gated DeltaNet layer:

    h = x + Mixer(n1(x));   y = h + MoE(n2(h))

    Full attention (H = num_attention_heads on Hkv = num_key_value_heads of
    dh = head_dim; r = partial_rotary_factor dh):
        [q | gate] = g Wq, a HEAD: head h owns columns h 2dh ..: dh of q,
                     then dh of gate;  k = g Wk;  v = g Wv
        q, k = n_head(q), n_head(k)    zero-centred, over each head's dh
        the FIRST r numbers of each head rotated (rotate-half inside them,
        inv_freq_i = theta^(-2i/r)), the other dh - r passed through
        causal softmax(q k / sqrt(dh)) v, query head h on K/V head h // (H /
        Hkv);  out = (attn * sigmoid(gate)) Wo

    Gated DeltaNet (Hk = linear_num_key_heads of dk, Hv =
    linear_num_value_heads of dv; K = linear_conv_kernel_dim):
        [q | k | v | z] = g W_in;   [b | a] = g W_ba
        [q | k | v] = silu(conv_K([q | k | v]))   causal, depthwise, no bias
        value head h reads key head h // (Hv / Hk)   (repeat_interleave)
        q = q / |q|_2 / sqrt(dk);   k = k / |k|_2    (eps 1e-6 in the root)
        beta = sigmoid(b);  gdec = -exp(A_log) softplus(a + dt_bias)
        S = e^gdec S;  u = beta (v - S^T k);  S = S + k u^T;  o = S^T q
        o = rms_w(o) * silu(z)   over each head's dv, ONE weight [dv]
        out = o W_out

    MoE: p = softmax(g Wr) over ALL num_experts (reduced_from) in float32,
        the num_experts_per_tok largest, divided by their sum; of those the
        experts held here (first_expert_held ..): sum_e p_e (silu(g Wg_e) *
        (g Wu_e)) Wd_e;  + sigmoid(g w_sg) SharedExpert(g)

Departures from the family's code, none of which changes a value: q, k, v
(and the gate) lie as column ranges of ONE matrix ``attn.qkv.w``; W_in's
columns are the blocks [q | k | v | z] and W_ba's [b | a], not grouped a key
head; the depthwise kernel lies ``[channels, K]``. The multi-token-prediction
module has no key in the config and is not built.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as nemotron_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS, 8 a prompt. The
# programs multiply as float32 (LMConfig.matmul_precision 'highest'), so a
# served token is the reference's own unless two logits tie to the order of
# the sums, and the limit is as tight as that. Set between two readings on
# the v5e at the published widths (PERF.md section 6, PR 55). The sound
# system: 0.0 in every one of 26 readings (10 runs of the cell x its 2
# prompts of 256 and 8 192 tokens x 8 rows, 3 seeds x 2 prompts x 25 rows in
# qwen3next_control.py); its largest single logit stood 2.2e-5 of (max -
# mean) from the reference's, so a tie it could turn reads at most 4.5e-5.
# The controls, over the check's 8 rows, where their tokens differ at all:
# the bfloat16 forward 0.012 to 0.056 (4 readings of 6), the programs at the
# TPU's default precision 0.005 to 0.019 (3 of 6), all 256 numbers rotated
# 0.006 to 0.048 (4 of 6), nine experts 0.008 to 0.028 (3 of 6), the
# attention's gate left out 0.058 to 0.10 and every other control 0.55 to
# 1.6 (6 of 6); a stale state (at most 0.0007) and the state dropped at a
# chunk's edge (0.0) turn no token of the eight. qwen3next_control.py's
# limit on the served LOGITS refuses all thirteen in every reading, and the
# driver cannot apply it (PERF.md section 7).
LOGIT_MARGIN = 1e-3
QUERY_BLOCK = 256
PRECISION = 'highest'
L2_EPS = 1e-6


def _rms(x, w, eps, plain=False):
    """The zero-centred RMSNorm ``x / rms(x) (1 + w)``; ``plain``: ``w`` in
    ``1 + w``'s place (the DeltaNet's output norm, and a control)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (w if plain else 1.0 + w)


def is_full(m, i):
    return (i + 1) % m['full_attention_interval'] == 0


def router_width(m):
    return m.get('reduced_from', {}).get('num_experts', m['num_experts'])


def rope(x, pos, theta, rotary_dim):
    """x [T, H, dh]: the first `rotary_dim` numbers of each head rotated by
    pos [T] (the pairs (i, i + rotary_dim / 2)), the others as they are."""
    r = rotary_dim
    inv_freq = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    part = x[..., :r]
    half = jnp.concatenate([-part[..., r // 2:], part[..., :r // 2]], axis=-1)
    return jnp.concatenate(
        [(part * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype),
         x[..., r:]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    'key_heads', 'value_heads', 'eps', 'decay', 'unit_beta', 'l2norm',
    'tile_keys', 'plain_norm', 'zero_state_at'))
def _gdn_mixer(x, s0, w, key_heads, value_heads, eps, decay=True,
               unit_beta=False, l2norm=True, tile_keys=False,
               plain_norm=False, zero_state_at=None):
    """(x + the Gated DeltaNet mixer of norm(x), the state after the last
    row). ``w``: the layer's parameters by their short names; ``s0 [Hv, dk,
    dv]`` the state before row 0 (zeros in the model). The controls:
    ``decay`` False leaves the decay out (g = 0); ``unit_beta`` writes with
    beta = 1; ``l2norm`` False leaves q and k unnormed; ``tile_keys`` reads
    key head h % Hk for value head h; ``plain_norm`` norms the block's input
    by w, not 1 + w; ``zero_state_at`` sets the state to zero before that
    row (a chunk resumed from zeros)."""
    with jax.default_matmul_precision(PRECISION):
        t, dt_ = x.shape[0], x.dtype
        hk, hv = key_heads, value_heads
        taps = w['conv.w'].shape[1]
        dv = w['norm.w'].shape[0]
        vd = hv * dv
        g = _rms(x, w['ln1.w'], eps, plain_norm)
        qkvz, ba = g @ w['in.w'], g @ w['ba.w']
        qkv, z = qkvz[:, :-vd], qkvz[:, -vd:]
        conv = jnp.zeros_like(qkv)
        for j in range(taps):
            back = taps - 1 - j                 # tap j reads row t - back
            conv = conv + jnp.pad(qkv, ((back, 0), (0, 0)))[:t] \
                * w['conv.w'][:, j]
        qkv = jax.nn.silu(conv)
        kd = (qkv.shape[1] - vd) // 2
        dk = kd // hk
        q = qkv[:, :kd].reshape(t, hk, dk)
        k = qkv[:, kd:2 * kd].reshape(t, hk, dk)
        v = qkv[:, 2 * kd:].reshape(t, hv, dv)
        if l2norm:
            q, k = [y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                      + L2_EPS) for y in (q, k)]
        q = q * dk ** -0.5
        # the key head each value head reads
        of = np.arange(hv) % hk if tile_keys else np.arange(hv) // (hv // hk)
        q, k = q[:, of], k[:, of]                           # [T, Hv, dk]
        beta = jnp.ones((t, hv), dt_) if unit_beta \
            else jax.nn.sigmoid(ba[:, :hv])
        gdec = (-jnp.exp(w['A_log'].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, hv:].astype(jnp.float32)
            + w['dt.b'].astype(jnp.float32))).astype(dt_)
        if not decay:
            gdec = jnp.zeros_like(gdec)

        def step(s, row):
            i, g_t, b_t, q_t, k_t, v_t = row
            if zero_state_at is not None:
                s = jnp.where(i == zero_state_at, jnp.zeros_like(s), s)
            s = jnp.exp(g_t)[:, None, None] * s
            u = b_t[:, None] * (v_t - jnp.einsum('hkv,hk->hv', s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s, jnp.einsum('hkv,hk->hv', s, q_t)

        last, o = jax.lax.scan(step, s0.astype(dt_),
                               (jnp.arange(t), gdec, beta, q, k, v))
        o = _rms(o, w['norm.w'], eps, plain=True) \
            * jax.nn.silu(z.reshape(t, hv, dv))
        return x + o.reshape(t, vd) @ w['out.w'], last


@functools.partial(jax.jit, static_argnames=(
    'n_head', 'n_kv_head', 'eps', 'theta', 'rotary_dim', 'plain_norm'))
def _project(x, w, n_head, n_kv_head, eps, theta, rotary_dim,
             plain_norm=False):
    """(q [T, H, dh], k, v [T, Hkv, dh], gate [T, H dh]): q and k normed a
    head and rotated over their first `rotary_dim` numbers (a control: all
    dh)."""
    with jax.default_matmul_precision(PRECISION):
        t = x.shape[0]
        dh = w['attn.q_norm.w'].shape[0]
        qkv = _rms(x, w['ln1.w'], eps, plain_norm) @ w['attn.qkv.w']
        qg = qkv[:, :2 * n_head * dh].reshape(t, n_head, 2 * dh)
        k = qkv[:, 2 * n_head * dh:(2 * n_head + n_kv_head) * dh].reshape(
            t, n_kv_head, dh)
        v = qkv[:, (2 * n_head + n_kv_head) * dh:].reshape(t, n_kv_head, dh)
        q = _rms(qg[..., :dh], w['attn.q_norm.w'], eps, plain_norm)
        k = _rms(k, w['attn.k_norm.w'], eps, plain_norm)
        pos = jnp.arange(t)
        return rope(q, pos, theta, rotary_dim), \
            rope(k, pos, theta, rotary_dim), v, \
            qg[..., dh:].reshape(t, n_head * dh)


@jax.jit
def _attend(q, start, k, v):
    """One block of queries (rows start ..) against every key, causal; k
    and v already repeated to the query heads."""
    with jax.default_matmul_precision(PRECISION):
        s = jnp.einsum('qhd,khd->hqk', q, k) * (q.shape[-1] ** -0.5)
        rows = start + jnp.arange(q.shape[0])
        s = jnp.where((jnp.arange(k.shape[0])[None, :]
                       <= rows[:, None])[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=('gated',))
def _residual_proj(x, ctx, gate, proj_w, gated=True):
    with jax.default_matmul_precision(PRECISION):
        ctx = ctx.reshape(x.shape[0], -1)
        if gated:
            ctx = ctx * jax.nn.sigmoid(gate)
        return x + ctx @ proj_w


@functools.partial(jax.jit, static_argnames=(
    'top_k', 'first', 'eps', 'shared_gate', 'plain_norm'))
def _experts(x, w, top_k, first, eps, shared_gate=True, plain_norm=False):
    """x + this chip's share of the expert layer of norm(x). The router
    runs in float32 at "highest" over ALL its experts, as the programs'
    does (ops/moe_ops.py `route`). The controls: ``top_k`` an expert fewer;
    ``shared_gate`` False adds the shared expert ungated."""
    with jax.default_matmul_precision(PRECISION):
        g = _rms(x, w['ln2.w'], eps, plain_norm)
        p = jax.nn.softmax(jnp.dot(
            g.astype(jnp.float32), w['moe.router.w'].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        chosen, idx = jax.lax.top_k(p, top_k)
        weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        # [T, E]: a row's weight on each expert, 0 where it is not chosen
        dense = jnp.zeros_like(p).at[
            jnp.arange(p.shape[0])[:, None], idx].set(weight)
        held = w['moe.up.w'].shape[0]

        def ffn(gate, up, down):
            return (jax.nn.silu(g @ gate) * (g @ up)) @ down

        def one(out, e):
            gate, up, down, we = e
            return out + we[:, None].astype(x.dtype) * ffn(gate, up, down), \
                None

        out, _ = jax.lax.scan(
            one, jnp.zeros_like(x),
            (w['moe.gate.w'], w['moe.up.w'], w['moe.down.w'],
             dense[:, first:first + held].T))
        shared = ffn(w['moe.shared.gate.w'], w['moe.shared.up.w'],
                     w['moe.shared.down.w'])
        if shared_gate:
            shared = shared * jax.nn.sigmoid(g @ w['moe.shared_gate.w'])
        return x + out + shared


@functools.partial(jax.jit, static_argnames=('eps', 'plain_norm'))
def _head(x, ln_w, head_w, eps, plain_norm=False):
    with jax.default_matmul_precision(PRECISION):
        return _rms(x, ln_w, eps, plain_norm) @ head_w


_GDN = ('in.w', 'ba.w', 'conv.w', 'A_log', 'dt.b', 'norm.w', 'out.w')
_ATTN = ('attn.qkv.w', 'attn.q_norm.w', 'attn.k_norm.w')
_EXPERTS = ('moe.router.w', 'moe.gate.w', 'moe.up.w', 'moe.down.w',
            'moe.shared.gate.w', 'moe.shared.up.w', 'moe.shared.down.w',
            'moe.shared_gate.w')
# which sublayer takes which control of `forward`'s ``**control``
_GDN_CONTROLS = ('decay', 'unit_beta', 'l2norm', 'tile_keys',
                 'zero_state_at')


def forward(scope, m, tokens, dtype=jnp.float32, init_states=None,
            rotate_all=False, attention_gate=True, shared_gate=True,
            experts_fewer=0, plain_norm=False, **control):
    """(hidden [T, D] after the last layer, [per DeltaNet layer the state
    after the last row, [Hv, dk, dv]]). The controls
    (qwen3next_control.py): parameters and activations in a ``dtype`` below
    float32; ``init_states`` in the zeros' place before row 0 (a row's last
    tenant's state); ``rotate_all``: all of a head rotated; ``attention_gate``
    False: the attention's gate left out; ``shared_gate`` False: the shared
    expert ungated; ``experts_fewer``: that many experts a token fewer;
    ``plain_norm``: every zero-centred norm multiplies by w, not 1 + w;
    ``**control``: `_gdn_mixer`'s."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    unknown = set(control) - set(_GDN_CONTROLS)
    if unknown:
        raise TypeError('forward: unknown controls %r' % sorted(unknown))
    tokens = np.asarray(tokens).reshape(-1)
    t = len(tokens)
    h, hkv, dh = m['num_attention_heads'], m['num_key_value_heads'], \
        m['head_dim']
    hk, hv = m['linear_num_key_heads'], m['linear_num_value_heads']
    eps = float(m['rms_norm_eps'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    states = []
    for i in range(m['num_hidden_layers']):
        name = 'layer_%d.' % i
        ln1 = param(name + 'ln1.w')
        if is_full(m, i):
            w = dict({k: param(name + k) for k in _ATTN}, **{'ln1.w': ln1})
            q, k, v, gate = _project(
                x, w, n_head=h, n_kv_head=hkv, eps=eps,
                theta=float(m['rope_theta']),
                rotary_dim=dh if rotate_all
                else int(round(dh * m['partial_rotary_factor'])),
                plain_norm=plain_norm)
            of = np.arange(h) // (h // hkv)
            k, v = k[:, of], v[:, of]
            ctx = jnp.concatenate(
                [_attend(q[s:s + QUERY_BLOCK], s, k, v)
                 for s in range(0, t, QUERY_BLOCK)], axis=0)
            x = _residual_proj(x, ctx, gate, param(name + 'attn.proj.w'),
                               gated=attention_gate)
        else:
            w = dict({k: param(name + 'gdn.' + k) for k in _GDN},
                     **{'ln1.w': ln1})
            s0 = jnp.zeros((hv, m['linear_key_head_dim'],
                            m['linear_value_head_dim']), dtype) \
                if init_states is None \
                else jnp.asarray(init_states[len(states)], dtype)
            x, last = _gdn_mixer(x, s0, w, key_heads=hk, value_heads=hv,
                                 eps=eps, plain_norm=plain_norm, **control)
            states.append(last)
        w = dict({k: param(name + k) for k in _EXPERTS},
                 **{'ln2.w': param(name + 'ln2.w')})
        x = _experts(x, w, top_k=m['num_experts_per_tok'] - experts_fewer,
                     first=int(m.get('first_expert_held', 0)), eps=eps,
                     shared_gate=shared_gate, plain_norm=plain_norm)
    return x, states


def head(scope, m, x, positions=None, plain_norm=False):
    """The final norm and the head on `forward`'s hidden states (the rows
    `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['rms_norm_eps']),
                 plain_norm=plain_norm).astype(jnp.float32)


def logits(scope, m, tokens, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, **control)[0], positions,
                plain_norm=control.get('plain_norm', False))


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
    One teacher-forced forward over prompt + generated."""
    prompt = np.asarray(prompt).reshape(-1)
    generated = np.asarray(generated).reshape(-1)
    seq = np.concatenate([prompt, generated[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    return margins(logits(scope, m, seq, positions=pos), generated)
