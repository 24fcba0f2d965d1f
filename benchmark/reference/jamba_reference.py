"""The plain reference of AI21-Jamba2-3B (`model_type: jamba`, HF
`modeling_jamba.py`, the Mamba mixer's published "slow path"): the forward
pass in jax.numpy, float32, matmuls at precision "highest", the whole
sequence at once -- the convolution as shifted adds, the recurrence as a
plain `lax.scan` over the positions with the ``[d_state, d_inner]`` state
as its carry, full causal attention with the one K/V head repeated. No
cache, no block pool, no state rows, no kernels, no chunked or associative
scan, no batching, nothing of paddle_tpu/. Queries are taken in blocks of
`QUERY_BLOCK` rows and the scan emits one row of ``y`` a position, so that
~3 000 positions at ``d_inner`` 5 120 never stand as ``[T, 16, 5120]``.

For hidden x [T, D] (every norm RMSNorm with a weight, eps rms_norm_eps;
no bias on any projection; NO positional encoding anywhere: the recurrence
carries the order). Layer ``i`` is an attention layer iff ``i %
attn_layer_period == attn_layer_offset``, else a Mamba layer (HF
`JambaConfig.layers_block_type`); ``num_experts`` is 1, so every layer's
FFN is the dense one:

    h = x + Mixer(norm(x; ln1));   out = h + FFN(norm(h; ln2))

    Mamba mixer (d_inner = mamba_expand * D, N = mamba_d_state, K =
    mamba_d_conv, R = mamba_dt_rank):
        [u | z] = g W_in                                   (D -> 2 d_inner)
        u = silu(conv_K(u) + b_conv)     causal, depthwise: c_t = sum_j
                                         w[:, j] u_{t - (K - 1) + j}
        [dt | B | C] = u W_x                               (d_inner -> R + 2N)
        dt, B, C = norm(dt; dt_layernorm), norm(B; b_layernorm),
                   norm(C; c_layernorm)                    (Jamba's addition)
        delta = softplus(dt W_dt + b_dt)                   (R -> d_inner)
        A = -exp(A_log)                                    [d_inner, N]
        s_t = exp(delta_t x A) * s_{t-1} + (delta_t * u_t) x B_t
        y_t = s_t C_t + D * u_t
        out = (y * silu(z)) W_out                          (d_inner -> D)
    attention mixer: q = g W_q -> num_attention_heads heads of head_dim,
        k = g W_k, v = g W_v -> num_key_value_heads heads; no rotation, no
        q/k norm; query head h reads K/V head h // (heads / kv heads);
        scores / sqrt(head_dim), causal softmax; y = ctx W_o
    FFN: (silu(g W_1) * (g W_3)) W_2, width intermediate_size

then norm(x; final_ln) and the TIED head: logits = x E^T with E the
embedding table.

Departures from the published model are the configuration file's
`changed` list. Parameters are read out of a scope by the names the decode
programs give them (`benchmark/models/jamba.py param_shapes`), as they lie
on the device: q, k and v are the three column ranges of ONE matrix
`attn.qkv.w`; ``A_log`` lies ``[N, d_inner]``, the state's own layout.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS. Set between two
# readings on the v5e at the published widths (PERF.md, PR 43). The sound
# system: 0.0 to 0.0162 over 8 runs x 2 prompts x 8 rows of the cell's own
# check and 0.0 to 0.0152 over 3 seeds x 2 prompts x 25 rows in
# jamba_control.py (a near-tie of the two largest logits, flipped by the
# float32 matmuls' bfloat16 operands). The controls it refuses: a stale
# state under a short prompt 0.437 to 0.478, the pad rows walked 1.29 to
# 1.38, the inner norms, D or the convolution's bias left out 0.72 to 1.65
# -- a factor 6.2 above the one and 4.4 under the smallest of the others.
# The bfloat16 forward (0.0147 to 0.0369), the state kept in bfloat16 (0 to
# 0.0004), the second chunk from zeros (0.0147 to 0.0875), a stale state
# under a long prompt (0 to 0.0114) and RoPE on the attention layers (0 to
# 0.0747) serve nearly the sound system's tokens and are NOT refused by any
# limit on tokens: what tells them apart is on LOGITS (jamba_control.py's
# two limits).
LOGIT_MARGIN = 0.1
QUERY_BLOCK = 256


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def head_dim(m):
    return m.get('head_dim') or m['hidden_size'] // m['num_attention_heads']


def layer_kinds(m):
    """'attention' | 'mamba' a layer: HF `layers_block_type`."""
    return ['attention' if i % m['attn_layer_period']
            == m['attn_layer_offset'] else 'mamba'
            for i in range(m['num_hidden_layers'])]


def rope(x, pos, theta):
    """x [T, H, dh] rotated by pos [T]: the pairs (i, i + dh/2). The
    model has none; a control applies it."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    'eps', 'inner_norms', 'skip_d', 'conv_bias', 'zero_state_at',
    'state_dtype', 'precision'))
def _mamba_mixer(x, s0, w, eps, inner_norms=True, skip_d=False,
                 conv_bias=True, zero_state_at=None, state_dtype=None,
                 precision='highest'):
    """(x + the Mamba mixer of norm(x), the state after the last row).
    ``w``: the layer's parameters by their short names; ``s0 [N,
    d_inner]`` the state before row 0 (zeros in the model). The controls:
    ``inner_norms`` False leaves the three norms out, ``skip_d`` the ``D *
    u`` term, ``conv_bias`` False the convolution's bias; ``zero_state_at``
    sets the state to zero before that row (a chunk resumed from zeros);
    ``state_dtype`` rounds the carried state to it after every step.
    ``precision`` (`forward`'s ``matmul_precision``) is the two large
    projections'; the two inner ones stay at "highest", as the programs
    have them (ops/ssm_ops.py says why)."""
    inner = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    with jax.default_matmul_precision(precision):
        t = x.shape[0]
        dt_ = x.dtype
        n = w['A_log'].shape[0]
        r = w['dt.w'].shape[0]
        taps = w['conv.w'].shape[1]
        uz = _rms(x, w['ln1.w'], eps) @ w['in.w']
        di = uz.shape[1] // 2
        u, z = uz[:, :di], uz[:, di:]
        conv = jnp.zeros_like(u)
        for j in range(taps):
            back = taps - 1 - j                 # tap j reads u_{t - back}
            conv = conv + jnp.pad(u, ((back, 0), (0, 0)))[:t] \
                * w['conv.w'][:, j]
        if conv_bias:
            conv = conv + w['conv.b']
        u = jax.nn.silu(conv)
        dbc = inner(u, w['x.w'])
        dt, b, c = dbc[:, :r], dbc[:, r:r + n], dbc[:, r + n:]
        if inner_norms:
            dt = _rms(dt, w['dt_norm.w'], eps)
            b = _rms(b, w['b_norm.w'], eps)
            c = _rms(c, w['c_norm.w'], eps)
        delta = jax.nn.softplus(inner(dt, w['dt.w']) + w['dt.b'])  # [T, di]
        a = -jnp.exp(w['A_log'].astype(jnp.float32)).astype(dt_)  # [N, di]
        carry_dtype = state_dtype or dt_

        def step(s, row):
            i, d_t, u_t, b_t, c_t = row
            if zero_state_at is not None:
                s = jnp.where(i == zero_state_at, jnp.zeros_like(s), s)
            s = jnp.exp(d_t[None, :] * a) * s.astype(dt_) \
                + (d_t * u_t)[None, :] * b_t[:, None]
            return s.astype(carry_dtype), jnp.sum(s * c_t[:, None], axis=0)

        last, y = jax.lax.scan(step, s0.astype(carry_dtype),
                               (jnp.arange(t), delta, u, b, c))
        if not skip_d:
            y = y + w['D'] * u
        return x + (y * jax.nn.silu(z)) @ w['out.w'], last


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'eps',
                                             'theta', 'precision'))
def _project(x, ln_w, qkv_w, n_head, n_kv_head, eps, theta,
             precision='highest'):
    """(q [T, H, dh], k [T, Hkv, dh], v [T, Hkv, dh]). ``theta`` (a
    control): rotate q and k as a RoPE model would."""
    with jax.default_matmul_precision(precision):
        t = x.shape[0]
        dh = qkv_w.shape[1] // (n_head + 2 * n_kv_head)
        qkv = _rms(x, ln_w, eps) @ qkv_w
        q = qkv[:, :n_head * dh].reshape(t, n_head, dh)
        k = qkv[:, n_head * dh:(n_head + n_kv_head) * dh].reshape(
            t, n_kv_head, dh)
        v = qkv[:, (n_head + n_kv_head) * dh:].reshape(t, n_kv_head, dh)
        if theta is not None:
            pos = jnp.arange(t)
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        return q, k, v


@functools.partial(jax.jit, static_argnames=('precision',))
def _attend(q, start, k, v, hidden, precision='highest'):
    """One block of queries (rows start ..) against every key, causal; k
    and v already repeated to the query heads. ``hidden [T]`` bool: keys
    no query sees (a control's pad rows)."""
    with jax.default_matmul_precision(precision):
        s = jnp.einsum('qhd,khd->hqk', q, k) * (q.shape[-1] ** -0.5)
        rows = start + jnp.arange(q.shape[0])
        keys = jnp.arange(k.shape[0])
        seen = (keys[None, :] <= rows[:, None]) & ~hidden[None, :]
        # a hidden row sees itself, so that its softmax is finite: its
        # output is dropped
        seen = seen | (keys[None, :] == rows[:, None])
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=('precision',))
def _residual_proj(x, ctx, proj_w, precision='highest'):
    with jax.default_matmul_precision(precision):
        return x + ctx.reshape(x.shape[0], -1) @ proj_w


@functools.partial(jax.jit, static_argnames=('eps', 'precision'))
def _ffn(x, ln_w, gate_w, up_w, down_w, eps, precision='highest'):
    with jax.default_matmul_precision(precision):
        g = _rms(x, ln_w, eps)
        return x + (jax.nn.silu(g @ gate_w) * (g @ up_w)) @ down_w


@functools.partial(jax.jit, static_argnames=('eps', 'precision'))
def _head(x, ln_w, table, eps, precision='highest'):
    with jax.default_matmul_precision(precision):
        return _rms(x, ln_w, eps) @ table.T


_MAMBA = ('in.w', 'conv.w', 'conv.b', 'x.w', 'dt_norm.w', 'b_norm.w',
          'c_norm.w', 'dt.w', 'dt.b', 'A_log', 'D', 'out.w')


def forward(scope, m, tokens, dtype=jnp.float32, init_states=None,
            pad_rows=None, rope_theta=None, matmul_precision='highest',
            **mamba):
    """(hidden [T, D] after the last block, [per Mamba layer the state
    after the last row, [N, d_inner]]). The controls (jamba_control.py):
    parameters and activations in a ``dtype`` below float32;
    ``init_states`` in the zeros' place before row 0 (a row's last tenant's
    state); ``pad_rows = (at, count)``: ``count`` rows of token 0 put in
    at row ``at`` that every Mamba layer walks and no attention query sees
    (a bucket's pad rows advancing the state), taken out of what is
    returned; ``rope_theta``: RoPE on the attention layers; ``**mamba``:
    `_mamba_mixer`'s. ``matmul_precision`` "default" is no control: the
    same forward with its matmuls as the TPU runs the programs' float32
    ones (bfloat16 operands, float32 sums), for the comparison that takes
    the matmuls' rounding out of both sides (jamba_control.py)."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    tokens = np.asarray(tokens).reshape(-1)
    hidden = np.zeros(len(tokens), bool)
    if pad_rows is not None:
        at, count = pad_rows
        tokens = np.concatenate([tokens[:at], np.zeros(count, tokens.dtype),
                                 tokens[at:]])
        hidden = np.zeros(len(tokens), bool)
        hidden[at:at + count] = True
    t = len(tokens)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    eps = float(m['rms_norm_eps'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    states = []
    for i, kind in enumerate(layer_kinds(m)):
        name = 'layer_%d.' % i
        if kind == 'mamba':
            w = {k: param(name + 'ssm.' + k) for k in _MAMBA}
            w['ln1.w'] = param(name + 'ln1.w')
            s0 = jnp.zeros(w['A_log'].shape, dtype) if init_states is None \
                else jnp.asarray(init_states[len(states)], dtype)
            x, last = _mamba_mixer(x, s0, w, eps=eps,
                                   precision=matmul_precision, **mamba)
            states.append(last)
        else:
            q, k, v = _project(x, param(name + 'ln1.w'),
                               param(name + 'attn.qkv.w'), n_head=h,
                               n_kv_head=hkv, eps=eps, theta=rope_theta,
                               precision=matmul_precision)
            rep = np.arange(h) // (h // hkv)
            k, v = k[:, rep], v[:, rep]
            hid = jnp.asarray(hidden)
            ctx = jnp.concatenate(
                [_attend(q[s:s + QUERY_BLOCK], s, k, v, hid,
                         precision=matmul_precision)
                 for s in range(0, t, QUERY_BLOCK)], axis=0)
            x = _residual_proj(x, ctx, param(name + 'attn.proj.w'),
                               precision=matmul_precision)
        x = _ffn(x, param(name + 'ln2.w'),
                 *(param(name + 'ffn.%s.w' % k)
                   for k in ('gate', 'up', 'down')), eps=eps,
                 precision=matmul_precision)
    if pad_rows is not None:
        x = x[jnp.asarray(np.flatnonzero(~hidden))]
    return x, states


def head(scope, m, x, positions=None, matmul_precision='highest'):
    """The final norm and the tied head on `forward`'s hidden states (the
    rows `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('tok_emb.w'), x.dtype),
                 eps=float(m['rms_norm_eps']),
                 precision=matmul_precision).astype(jnp.float32)


def logits(scope, m, tokens, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, **control)[0], positions,
                control.get('matmul_precision', 'highest'))


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
