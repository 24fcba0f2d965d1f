"""The plain reference of Olmo-Hybrid-7B (`model_type: olmo_hybrid`; the
linear-attention layer is FLA's Gated DeltaNet, arXiv:2412.06464, with
`linear_allow_neg_eigval`; the block is the Olmo 2 / Olmo 3 line's,
arXiv:2501.00656): the forward pass in jax.numpy, float32, matmuls at
precision "highest", the whole sequence at once -- the convolution as
shifted adds, THE DELTA RULE AS A PLAIN `lax.scan` OVER THE POSITIONS with
the ``[Hv, dk, dv]`` state as its carry (the definition, NOT the chunked
form: the system's chunked prefill, its decode update and its snapshot rows
are held to it), full causal attention over 30 heads. No cache, no block
pool, no state rows, no snapshots, no kernels, no chunks, no batching,
nothing of paddle_tpu/. Queries are taken in blocks of `QUERY_BLOCK` rows
and the head is applied to the rows asked for alone, so that a 3.3 k-token
prompt over 100 352 logits fits beside the engine.

For hidden x [T, D]; ``n_w(y) = y / rms(y) w`` (eps rms_norm_eps); no bias
anywhere. Layer i is what layer_types[i] says. THE NORM IS ON THE SUBLAYER'S
OUTPUT and the sublayer reads the stream un-normed (the Olmo line's
reordered norm):

    h = x + n_1(Mixer(x));   y = h + n_2(FFN(h))
    FFN(h) = (silu(h W_g) * (h W_u)) W_d
    logits = n_f(x_last) W_head

    full_attention (H = num_attention_heads on Hkv = num_key_value_heads
    of dh = hidden_size / H):
        q = n_wq(x W_q);  k = n_wk(x W_k)     over the WHOLE projected
                                              width, before the heads
        v = x W_v;   NOTHING IS ROTATED (rope_theta null)
        causal softmax(q k / sqrt(dh)) v, query head h on K/V head
        h // (H / Hkv);  out = attn W_o

    linear_attention, Gated DeltaNet (Hk = linear_num_key_heads of dk, Hv =
    linear_num_value_heads of dv; K = linear_conv_kernel_dim):
        [q | k | v | z] = x W_in;   [b | a] = x W_ba
        [q | k | v] = silu(conv_K([q | k | v]))   causal, depthwise, no bias
        value head h reads key head h // (Hv / Hk)
        q = q / |q|_2 / sqrt(dk);   k = k / |k|_2    (eps 1e-6 in the root)
        beta = 2 sigmoid(b)   (linear_allow_neg_eigval; else sigmoid(b))
        gdec = -exp(A_log) softplus(a + dt_bias)
        S = e^gdec S;  u = beta (v - S^T k);  S = S + k u^T;  o = S^T q
        o = n_w(o) * silu(z)   over each head's dv, ONE weight [dv]
        out = o W_out

Departures from the family's layout, none of which changes a value: q, k, v
lie as column ranges of ONE matrix ``attn.qkv.w``; W_in's columns are the
blocks [q | k | v | z] and W_ba's [b | a]; the depthwise kernel lies
``[channels, K]`` over [q | k | v]. What the config does not say is listed
under `assumed` in benchmark/configs/olmo-hybrid-7b-l8.json.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as qwen3next_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean); the
# comparison `drivers/serve.py _check` makes, on TOKENS, 8 a prompt. The
# programs multiply as float32 (LMConfig.matmul_precision 'highest'), so a
# served token is the reference's own unless two logits tie to the order of
# the sums, and the limit is as tight as that. Set between two readings on
# the v5e at the published widths (PERF.md section 6, PR 58). The sound
# system: 0.0 in every reading (22 runs of the cell x its 2 prompts x 8
# rows; 4 seeds x a miss and a hit x 25 rows in olmohybrid_control.py); its
# largest single logit stood 1.9e-5 of (max - mean) from the reference's.
# The bfloat16 forward, over the check's 8 rows: 0.0056 to 0.0154 in six of
# its eight readings, 0.0004 and 0.0 in two (no token turned); the programs
# at the default precision 0.0033 to 0.0154 in six of eight. The state kept
# in bfloat16 and the state dropped at a chunk's edge turn no token of the
# eight in any reading: olmohybrid_control.py's limit on the served LOGITS
# refuses all ten controls in every reading, and the driver cannot apply it
# (PERF.md section 7).
LOGIT_MARGIN = 1e-3
QUERY_BLOCK = 256
PRECISION = 'highest'
L2_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def is_full(m, i):
    return m['layer_types'][i] == 'full_attention'


def rope(x, pos, theta):
    """A CONTROL's (the model rotates nothing): x [T, H, dh] rotated by pos
    [T], rotate-half over the whole head."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=(
    'key_heads', 'value_heads', 'eps', 'neg_eigval', 'pre_norm',
    'state_dtype', 'resume_at', 'resume_state', 'resume_tail'))
def _gdn_mixer(x, s0, t0, w, key_heads, value_heads, eps, neg_eigval=True,
               pre_norm=False, state_dtype=None, resume_at=None,
               resume_state=False, resume_tail=False):
    """(x + n_1(the Gated DeltaNet mixer of x), the state after the last
    row, the convolution's last K - 1 inputs). ``w``: the layer's
    parameters by their short names. ``s0 [Hv, dk, dv]`` and ``t0 [K - 1,
    channels]``: zeros in the model. The controls: ``neg_eigval`` False
    writes with beta = sigmoid(b); ``pre_norm`` norms the mixer's INPUT by
    ln1 and adds its output as it is; ``state_dtype`` keeps the state in a
    lower precision between positions; ``resume_at`` is a row at which
    ``s0`` stands in the state's place (``resume_state``) and ``t0`` in the
    place of the convolution's inputs of the K - 1 rows before it
    (``resume_tail``): a hit resumed from another row than its own."""
    with jax.default_matmul_precision(PRECISION):
        t, dt_ = x.shape[0], x.dtype
        hk, hv = key_heads, value_heads
        taps = w['conv.w'].shape[1]
        dv = w['norm.w'].shape[0]
        vd = hv * dv
        g = _rms(x, w['ln1.w'], eps) if pre_norm else x
        qkvz, ba = g @ w['in.w'], g @ w['ba.w']
        qkv, z = qkvz[:, :-vd], qkvz[:, -vd:]
        at = 0 if resume_at is None else resume_at
        conv = jnp.zeros_like(qkv)
        for j in range(taps):
            back = taps - 1 - j                 # tap j reads row t - back
            src = jnp.pad(qkv, ((back, 0), (0, 0)))[:t]
            if resume_tail and back:
                # rows at .. at + back - 1 read row `at - back + i`: t0's
                rows = jnp.arange(t)[:, None]
                held = jnp.pad(t0.astype(dt_)[taps - 1 - back:],
                               ((at, t), (0, 0)))[:t]
                src = jnp.where((rows >= at) & (rows < at + back), held, src)
            conv = conv + src * w['conv.w'][:, j]
        tail = qkv[t - (taps - 1):]
        qkv = jax.nn.silu(conv)
        kd = (qkv.shape[1] - vd) // 2
        dk = kd // hk
        q = qkv[:, :kd].reshape(t, hk, dk)
        k = qkv[:, kd:2 * kd].reshape(t, hk, dk)
        v = qkv[:, 2 * kd:].reshape(t, hv, dv)
        q, k = [y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True)
                                  + L2_EPS) for y in (q, k)]
        q = q * dk ** -0.5
        of = np.arange(hv) // (hv // hk)    # the key head a value head reads
        q, k = q[:, of], k[:, of]                           # [T, Hv, dk]
        beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(ba[:, :hv])
        gdec = (-jnp.exp(w['A_log'].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, hv:].astype(jnp.float32)
            + w['dt.b'].astype(jnp.float32))).astype(dt_)
        held = state_dtype or dt_
        start = jnp.zeros_like(s0) if resume_at is not None else s0

        def step(s, row):
            i, g_t, b_t, q_t, k_t, v_t = row
            if resume_state:
                s = jnp.where(i == at, s0.astype(held), s)
            s = jnp.exp(g_t)[:, None, None] * s.astype(dt_)
            u = b_t[:, None] * (v_t - jnp.einsum('hkv,hk->hv', s, k_t))
            s = s + k_t[:, :, None] * u[:, None, :]
            return s.astype(held), jnp.einsum('hkv,hk->hv', s, q_t)

        last, o = jax.lax.scan(step, start.astype(held),
                               (jnp.arange(t), gdec, beta, q, k, v))
        o = _rms(o, w['norm.w'], eps) * jax.nn.silu(z.reshape(t, hv, dv))
        out = o.reshape(t, vd) @ w['out.w']
        return x + (out if pre_norm else _rms(out, w['ln1.w'], eps)), \
            last, tail


@functools.partial(jax.jit, static_argnames=(
    'n_head', 'n_kv_head', 'eps', 'theta', 'pre_norm'))
def _project(x, w, n_head, n_kv_head, eps, theta=None, pre_norm=False):
    """(q [T, H, dh], k, v [T, Hkv, dh]): q and k normed over their whole
    width, nothing rotated (a control: rotated by `theta`)."""
    with jax.default_matmul_precision(PRECISION):
        t = x.shape[0]
        dh = w['attn.q_norm.w'].shape[0] // n_head
        g = _rms(x, w['ln1.w'], eps) if pre_norm else x
        qkv = g @ w['attn.qkv.w']
        qw, kw = n_head * dh, n_kv_head * dh
        q = _rms(qkv[:, :qw], w['attn.q_norm.w'], eps).reshape(t, n_head, dh)
        k = _rms(qkv[:, qw:qw + kw], w['attn.k_norm.w'], eps).reshape(
            t, n_kv_head, dh)
        v = qkv[:, qw + kw:].reshape(t, n_kv_head, dh)
        if theta is not None:
            q, k = rope(q, jnp.arange(t), theta), rope(k, jnp.arange(t), theta)
        return q, k, v


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


@functools.partial(jax.jit, static_argnames=('eps', 'pre_norm'))
def _residual_proj(x, ctx, proj_w, ln_w, eps, pre_norm=False):
    with jax.default_matmul_precision(PRECISION):
        out = ctx.reshape(x.shape[0], -1) @ proj_w
        return x + (out if pre_norm else _rms(out, ln_w, eps))


@functools.partial(jax.jit, static_argnames=('eps', 'pre_norm'))
def _ffn(x, w, eps, pre_norm=False):
    """x + n_2(the gated FFN of x) (a control: the FFN of n_2(x))."""
    with jax.default_matmul_precision(PRECISION):
        g = _rms(x, w['ln2.w'], eps) if pre_norm else x
        out = (jax.nn.silu(g @ w['ffn.gate.w']) * (g @ w['ffn.up.w'])) \
            @ w['ffn.down.w']
        return x + (out if pre_norm else _rms(out, w['ln2.w'], eps))


@functools.partial(jax.jit, static_argnames=('eps',))
def _head(x, ln_w, head_w, eps):
    with jax.default_matmul_precision(PRECISION):
        return _rms(x, ln_w, eps) @ head_w


_GDN = ('in.w', 'ba.w', 'conv.w', 'A_log', 'dt.b', 'norm.w', 'out.w')
_ATTN = ('attn.qkv.w', 'attn.q_norm.w', 'attn.k_norm.w')
_FFN = ('ffn.gate.w', 'ffn.up.w', 'ffn.down.w')


def forward(scope, m, tokens, dtype=jnp.float32, neg_eigval=None,
            pre_norm=False, rope_theta=None, state_dtype=None, resume=None):
    """(hidden [T, D] after the last layer, [per DeltaNet layer (the state
    after the last row [Hv, dk, dv], the convolution's last K - 1 inputs
    [K - 1, channels])]). The controls (olmohybrid_control.py): parameters
    and activations in a ``dtype`` below float32; ``neg_eigval`` False:
    beta without the factor 2; ``pre_norm``: the norms on the sublayers'
    inputs; ``rope_theta``: the full layers' q and k rotated;
    ``state_dtype``: the recurrent state kept in a lower precision;
    ``resume = (row, rows, state, tail)``: at position `row` every DeltaNet
    layer's state (if `state`) and convolution inputs of the rows before it
    (if `tail`) are replaced by those of `rows`, a list as this function
    returns (None: zeros) -- a hit that resumes from a row that is not its
    prefix's."""
    def param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    tokens = np.asarray(tokens).reshape(-1)
    t = len(tokens)
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    hk, hv = m['linear_num_key_heads'], m['linear_num_value_heads']
    dk, dv = m['linear_key_head_dim'], m['linear_value_head_dim']
    taps = m['linear_conv_kernel_dim']
    eps = float(m['rms_norm_eps'])
    if neg_eigval is None:
        neg_eigval = bool(m['linear_allow_neg_eigval'])
    x = jnp.take(param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    rows = []
    for i in range(m['num_hidden_layers']):
        name = 'layer_%d.' % i
        ln1 = param(name + 'ln1.w')
        if is_full(m, i):
            w = dict({k: param(name + k) for k in _ATTN}, **{'ln1.w': ln1})
            q, k, v = _project(x, w, n_head=h, n_kv_head=hkv, eps=eps,
                               theta=rope_theta, pre_norm=pre_norm)
            of = np.arange(h) // (h // hkv)
            k, v = k[:, of], v[:, of]
            ctx = jnp.concatenate(
                [_attend(q[s:s + QUERY_BLOCK], s, k, v)
                 for s in range(0, t, QUERY_BLOCK)], axis=0)
            x = _residual_proj(x, ctx, param(name + 'attn.proj.w'), ln1,
                               eps=eps, pre_norm=pre_norm)
        else:
            w = dict({k: param(name + 'gdn.' + k) for k in _GDN},
                     **{'ln1.w': ln1})
            s0 = jnp.zeros((hv, dk, dv), dtype)
            t0 = jnp.zeros((taps - 1, 2 * hk * dk + hv * dv), dtype)
            kw = {}
            if resume is not None:
                at, given, state, tail = resume
                if given is not None:
                    s0, t0 = [jnp.asarray(y, dtype)
                              for y in given[len(rows)]]
                kw = dict(resume_at=int(at), resume_state=bool(state),
                          resume_tail=bool(tail))
            x, last, tail = _gdn_mixer(
                x, s0, t0, w, key_heads=hk, value_heads=hv, eps=eps,
                neg_eigval=neg_eigval, pre_norm=pre_norm,
                state_dtype=state_dtype, **kw)
            rows.append((last, tail))
        w = dict({k: param(name + k) for k in _FFN},
                 **{'ln2.w': param(name + 'ln2.w')})
        x = _ffn(x, w, eps=eps, pre_norm=pre_norm)
    return x, rows


def head(scope, m, x, positions=None):
    """The final norm and the head on `forward`'s hidden states (the rows
    `positions` select; default: all), float32."""
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('final_ln.w'), x.dtype),
                 jnp.asarray(scope.get('lm_head.w'), x.dtype),
                 eps=float(m['rms_norm_eps'])).astype(jnp.float32)


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
