"""The plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B (`model_type:
nemotron_h`; `NemotronHBlock`, `NemotronHMamba2Mixer`, `NemotronHAttention`,
`NemotronHMOE` of the family's modelling code, Mamba-2 as state-spaces/mamba
`Mamba2`, arXiv:2405.21060): the forward pass in jax.numpy, float32, matmuls
at precision "highest", the whole sequence at once -- the convolution as
shifted adds, THE RECURRENCE AS A PLAIN `lax.scan` OVER THE POSITIONS with
the ``[H, P, N]`` state as its carry (the definition, not the chunked form:
the system's SSD prefill is held to it), full causal attention with the two
K/V heads repeated, the expert layer over THIS CHIP'S SHARE. No cache, no
block pool, no state rows, no kernels, no chunks, no batching, nothing of
paddle_tpu/. Queries are taken in blocks of `QUERY_BLOCK` rows and the held
experts one at a time, so that ~3 000 positions fit beside the weights.

For hidden x [T, D] (every norm RMSNorm with a weight, eps
layer_norm_epsilon; no bias on any projection; NO positional encoding
anywhere). EVERY LAYER IS ONE SUBLAYER, by its letter in
hybrid_override_pattern:

    x = x + mixer_i(norm_i(x))

    M, Mamba-2 (H = mamba_num_heads heads of P = mamba_head_dim, d_inner =
    H P; G = n_groups; N = ssm_state_size; K = conv_kernel):
        [z | xBC | dt] = g W_in            (D -> d_inner + (d_inner + 2GN) + H)
        xBC = silu(conv_K(xBC) + b_conv)   causal, depthwise, ALL channels
        [x | B | C] = xBC                  x [H, P], B [G, N], C [G, N]
        dt = softplus(dt + dt_bias) [H];   A = -exp(A_log) [H]
        head h reads group h // (H / G)
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
        y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]
        y = norm_groups(y * silu(z))       the gate FIRST, then an RMSNorm
                                           over each of G groups of
                                           d_inner / G channels, one weight
        out = y W_out
    *, attention: q = g W_q -> num_attention_heads heads of head_dim, k, v
        -> num_key_value_heads heads; no rotation, no q/k norm; query head
        h reads K/V head h // (heads / kv heads); scores / sqrt(head_dim),
        causal softmax; out = ctx W_o
    E, experts: s = sigmoid(g W_r) over all reduced_from.n_routed_experts;
        the num_experts_per_tok largest of s + e_score_correction_bias are
        chosen; weights s[chosen] / (sum + 1e-20) * routed_scaling_factor;
        an expert is relu(g W_up)^2 W_down; the experts first_expert_held
        .. + n_routed_experts - 1 are computed, the rest left out; plus the
        shared expert relu(g W_su)^2 W_sd for every row

then norm(x; final_ln) and logits = x W_head.

Departures from the published model are the configuration file's `changed`
list. Parameters are read out of a scope by the names the decode programs
give them (`benchmark/models/nemotron.py param_shapes`), as they lie on the
device.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as olmoe_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS, 8 a prompt. The
# programs multiply as float32 (LMConfig.matmul_precision 'highest'), so a
# served token is the reference's own unless two logits tie to the order of
# the sums, and the limit is as tight as that. Set between two readings on
# the v5e at the published widths (PERF.md, PR 48, second session). The
# sound system: 0.0 in every one of 20 readings (7 runs of the cell x its 2
# prompts x 8 rows, 3 seeds x 2 prompts x 25 rows in nemotron_control.py);
# its largest single logit stood 2.6e-4 of (max - mean) from the
# reference's, so a tie it could turn reads at most 5.3e-4. The controls,
# over the check's 8 rows, where their tokens differ at all: the programs
# at the TPU's default precision (bfloat16 operands) 0.052 to 0.096, the
# bfloat16 forward 0.020 to 0.082, the state kept in bfloat16 0.0011 and
# 0.0034, a chunk from zeros 0.0024 and 0.012, a stale state 0.047 to 0.11,
# the bias in the weights 0.0034, every other control 0.0067 to 1.7. REFUSED IN EVERY READING: the pad rows
# walked, group 0's B and C, the norm over all channels, the norm before
# the gate, D left out, the convolution's bias left out, relu, silu, a gate
# in the experts, the shared expert left out, the routed scale left out,
# RoPE, an SSD block from zeros. NOT in every reading, because 8 greedy
# tokens are often the reference's own under a small fault: the default
# precision (3 prompts of 6: each of the 3 seeds by one of its two), the
# bfloat16 forward (3 of 6: 2 seeds of 3), a stale state (3 of 6), the
# state in bfloat16 (2 of 6), a chunk from zeros (2 of 3), the bias in the
# weights (1 of 6) --
# nemotron_control.py's limit on the served LOGITS refuses all nineteen
# in every reading, and the driver cannot apply it (PERF.md section 7).
LOGIT_MARGIN = 1e-3
QUERY_BLOCK = 256
PRECISION = 'highest'


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def pattern(m):
    return m['hybrid_override_pattern'][:m['num_hidden_layers']]


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
    'heads', 'groups', 'eps', 'one_group', 'norm_groups', 'norm_first',
    'skip_d', 'conv_bias', 'zero_state_at', 'zero_state_every',
    'state_dtype'))
def _mamba2_mixer(x, s0, w, heads, groups, eps, one_group=False,
                  norm_groups=None, norm_first=False, skip_d=False,
                  conv_bias=True, zero_state_at=None, zero_state_every=None,
                  state_dtype=None):
    """(x + the Mamba-2 mixer of norm(x), the state after the last row).
    ``w``: the layer's parameters by their short names; ``s0 [H, P, N]``
    the state before row 0 (zeros in the model). The controls:
    ``one_group`` reads group 0's B and C for every head; ``norm_groups``
    norms over that many groups (1: all channels at once); ``norm_first``
    norms, then gates; ``skip_d`` leaves ``D x`` out, ``conv_bias`` False
    the convolution's bias; ``zero_state_at`` sets the state to zero
    before that row (a chunk resumed from zeros), ``zero_state_every``
    before every row that is a multiple of it (every block from zeros);
    ``state_dtype`` rounds the carried state to it after every step."""
    with jax.default_matmul_precision(PRECISION):
        t = x.shape[0]
        dt_ = x.dtype
        taps = w['conv.w'].shape[1]
        di = w['norm.w'].shape[0]
        size = di // heads
        zxd = _rms(x, w['ln1.w'], eps) @ w['in.w']
        z, xbc, dt = zxd[:, :di], zxd[:, di:-heads], zxd[:, -heads:]
        conv = jnp.zeros_like(xbc)
        for j in range(taps):
            back = taps - 1 - j                 # tap j reads row t - back
            conv = conv + jnp.pad(xbc, ((back, 0), (0, 0)))[:t] \
                * w['conv.w'][:, j]
        if conv_bias:
            conv = conv + w['conv.b']
        xbc = jax.nn.silu(conv)
        n = (xbc.shape[1] - di) // (2 * groups)
        u = xbc[:, :di].reshape(t, heads, size)
        b = xbc[:, di:di + groups * n].reshape(t, groups, n)
        c = xbc[:, di + groups * n:].reshape(t, groups, n)
        # the group each head reads
        of = np.zeros(heads, int) if one_group \
            else np.arange(heads) // (heads // groups)
        b, c = b[:, of], c[:, of]                           # [T, H, N]
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + w['dt.b'].astype(jnp.float32)).astype(dt_)
        a = -jnp.exp(w['A_log'].astype(jnp.float32)).astype(dt_)   # [H]
        carry_dtype = state_dtype or dt_

        def step(s, row):
            i, d_t, u_t, b_t, c_t = row
            if zero_state_at is not None:
                s = jnp.where(i == zero_state_at, jnp.zeros_like(s), s)
            if zero_state_every is not None:
                s = jnp.where(i % zero_state_every == 0, jnp.zeros_like(s),
                              s)
            s = jnp.exp(d_t * a)[:, None, None] * s.astype(dt_) \
                + (d_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
            return s.astype(carry_dtype), jnp.sum(s * c_t[:, None, :],
                                                  axis=-1)

        last, y = jax.lax.scan(step, s0.astype(carry_dtype),
                               (jnp.arange(t), dt, u, b, c))  # y [T, H, P]
        if not skip_d:
            y = y + w['D'][:, None] * u
        y = y.reshape(t, di)

        def norm(v):
            g = norm_groups or groups
            v = v.reshape(t, g, di // g)
            v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                                  + eps)
            return v.reshape(t, di) * w['norm.w']
        y = norm(y) * jax.nn.silu(z) if norm_first \
            else norm(y * jax.nn.silu(z))
        return x + y @ w['out.w'], last


@functools.partial(jax.jit, static_argnames=('n_head', 'n_kv_head', 'eps',
                                             'theta'))
def _project(x, ln_w, qkv_w, n_head, n_kv_head, eps, theta):
    """(q [T, H, dh], k [T, Hkv, dh], v [T, Hkv, dh]). ``theta`` (a
    control): rotate q and k as a RoPE model would."""
    with jax.default_matmul_precision(PRECISION):
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


@jax.jit
def _attend(q, start, k, v, hidden):
    """One block of queries (rows start ..) against every key, causal; k
    and v already repeated to the query heads. ``hidden [T]`` bool: keys
    no query sees (a control's pad rows)."""
    with jax.default_matmul_precision(PRECISION):
        s = jnp.einsum('qhd,khd->hqk', q, k) * (q.shape[-1] ** -0.5)
        rows = start + jnp.arange(q.shape[0])
        keys = jnp.arange(k.shape[0])
        seen = (keys[None, :] <= rows[:, None]) & ~hidden[None, :]
        # a hidden row sees itself, so that its softmax is finite: its
        # output is dropped
        seen = seen | (keys[None, :] == rows[:, None])
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum('hqk,khd->qhd', jax.nn.softmax(s, axis=-1), v)


@jax.jit
def _residual_proj(x, ctx, proj_w):
    with jax.default_matmul_precision(PRECISION):
        return x + ctx.reshape(x.shape[0], -1) @ proj_w


def _act(h, name):
    """An expert's activation on ``h = g W_up``. The model's is "relu2";
    the others are controls ("gated": the SiLU-gated form the repo's other
    expert models have, the up matrix standing in for the gate it would
    need)."""
    return {'relu2': lambda: jnp.square(jax.nn.relu(h)),
            'relu': lambda: jax.nn.relu(h),
            'silu': lambda: jax.nn.silu(h),
            'gated': lambda: jax.nn.silu(h) * h}[name]()


@functools.partial(jax.jit, static_argnames=(
    'top_k', 'first', 'scale', 'eps', 'act', 'shared', 'bias_in_weights'))
def _experts(x, w, top_k, first, scale, eps, act='relu2', shared=True,
             bias_in_weights=False):
    """x + this chip's share of the expert layer of norm(x). The router
    runs in float32 at "highest" over ALL its experts, as the programs'
    does (ops/moe_ops.py `route`). The controls: ``act`` (`_act`);
    ``shared`` False leaves the shared expert out; ``scale`` 1.0 the
    routed scaling factor; ``bias_in_weights`` weighs by score +
    correction bias, not by the score alone."""
    with jax.default_matmul_precision(PRECISION):
        g = _rms(x, w['ln2.w'], eps)
        s = jax.nn.sigmoid(jnp.dot(
            g.astype(jnp.float32), w['moe.router.w'].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        biased = s + w['moe.router.bias'].astype(jnp.float32)[None, :]
        idx = jax.lax.top_k(biased, top_k)[1]
        chosen = jnp.take_along_axis(biased if bias_in_weights else s, idx,
                                     axis=1)
        weight = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) \
            * scale
        # [T, E]: a row's weight on each expert, 0 where it is not chosen
        dense = jnp.zeros_like(s).at[
            jnp.arange(s.shape[0])[:, None], idx].set(weight)
        held = w['moe.up.w'].shape[0]

        def one(out, e):
            up, down, we = e
            return out + we[:, None].astype(x.dtype) \
                * (_act(g @ up, act) @ down), None

        out, _ = jax.lax.scan(
            one, jnp.zeros_like(x),
            (w['moe.up.w'], w['moe.down.w'],
             dense[:, first:first + held].T))
        if shared:
            out = out + _act(g @ w['moe.shared.up.w'], act) \
                @ w['moe.shared.down.w']
        return x + out


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, ln_w, head_w, eps):
    with jax.default_matmul_precision(PRECISION):
        return _rms(x, ln_w, eps) @ head_w


_MAMBA = ('in.w', 'conv.w', 'conv.b', 'dt.b', 'A_log', 'D', 'norm.w',
          'out.w')
_EXPERTS = ('moe.router.w', 'moe.router.bias', 'moe.up.w', 'moe.down.w',
            'moe.shared.up.w', 'moe.shared.down.w')
# which sublayer takes which control of `forward`'s ``**control``
_MAMBA_CONTROLS = ('one_group', 'norm_groups', 'norm_first', 'skip_d',
                   'conv_bias', 'zero_state_at', 'zero_state_every',
                   'state_dtype')
_EXPERT_CONTROLS = ('act', 'shared', 'bias_in_weights')


def forward(scope, m, tokens, dtype=jnp.float32, init_states=None,
            pad_rows=None, rope_theta=None, routed_scale=True, **control):
    """(hidden [T, D] after the last layer, [per Mamba-2 layer the state
    after the last row, [H, P, N]]). The controls (nemotron_control.py):
    parameters and activations in a ``dtype`` below float32;
    ``init_states`` in the zeros' place before row 0 (a row's last
    tenant's state); ``pad_rows = (at, count)``: ``count`` rows of token 0
    put in at row ``at`` that every Mamba-2 layer walks and no attention
    query sees (a bucket's pad rows advancing the state), taken out of
    what is returned; ``rope_theta``: RoPE on the attention layers;
    ``routed_scale`` False: the routed scaling factor left out;
    ``**control``: `_mamba2_mixer`'s and `_experts`'."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    unknown = set(control) - set(_MAMBA_CONTROLS + _EXPERT_CONTROLS)
    if unknown:
        raise TypeError('forward: unknown controls %r' % sorted(unknown))
    mamba = {k: v for k, v in control.items() if k in _MAMBA_CONTROLS}
    experts = {k: v for k, v in control.items() if k in _EXPERT_CONTROLS}
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
    mh, size, n = m['mamba_num_heads'], m['mamba_head_dim'], \
        m['ssm_state_size']
    eps = float(m['layer_norm_epsilon'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    states = []
    for i, letter in enumerate(pattern(m)):
        name = 'layer_%d.' % i
        if letter == 'M':
            w = {k: param(name + 'ssd.' + k) for k in _MAMBA}
            w['ln1.w'] = param(name + 'ln1.w')
            s0 = jnp.zeros((mh, size, n), dtype) if init_states is None \
                else jnp.asarray(init_states[len(states)], dtype)
            x, last = _mamba2_mixer(x, s0, w, heads=mh,
                                    groups=m['n_groups'], eps=eps, **mamba)
            states.append(last)
        elif letter == '*':
            q, k, v = _project(x, param(name + 'ln1.w'),
                               param(name + 'attn.qkv.w'), n_head=h,
                               n_kv_head=hkv, eps=eps, theta=rope_theta)
            rep = np.arange(h) // (h // hkv)
            k, v = k[:, rep], v[:, rep]
            hid = jnp.asarray(hidden)
            ctx = jnp.concatenate(
                [_attend(q[s:s + QUERY_BLOCK], s, k, v, hid)
                 for s in range(0, t, QUERY_BLOCK)], axis=0)
            x = _residual_proj(x, ctx, param(name + 'attn.proj.w'))
        else:
            w = {k: param(name + k) for k in _EXPERTS}
            w['ln2.w'] = param(name + 'ln2.w')
            x = _experts(
                x, w, top_k=m['num_experts_per_tok'],
                first=int(m.get('first_expert_held', 0)),
                scale=float(m['routed_scaling_factor']) if routed_scale
                else 1.0, eps=eps, **experts)
    if pad_rows is not None:
        x = x[jnp.asarray(np.flatnonzero(~hidden))]
    return x, states


def head(scope, m, x, positions=None):
    """The final norm and the head on `forward`'s hidden states (the rows
    `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['layer_norm_epsilon'])).astype(jnp.float32)


def logits(scope, m, tokens, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    return head(scope, m, forward(scope, m, tokens, **control)[0], positions)


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
