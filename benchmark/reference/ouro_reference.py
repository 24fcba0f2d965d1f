"""The plain reference of Ouro (ByteDance `modeling_ouro.py`, model_type
`ouro`; the looped language model of arXiv:2510.25741, "Scaling Latent
Reasoning via Looped Language Models"): the forward pass in jax.numpy,
float32, matmuls at precision "highest" -- no cache, no kernels, no
batching: a FULL forward of ALL passes over the whole sequence.

With x^0 [T, D] the token embeddings (nothing added), for pass t = 1 .. R
(R = `total_ut_steps`), over THE SAME parameters every pass:

    h <- x^(t-1)
    for each layer l = 1 .. L:                      (the sandwich norm)
        n = RMSNorm(h; ln1)
        q, k, v = n Wq, n Wk, n Wv                  heads of head_dim, no
            bias, no q/k-norm; rotary on q and k over the WHOLE head,
            rotate_half convention, inv_freq = theta^(-2i/head_dim), angle
            pos * inv_freq
        a = causal softmax(q k^T head_dim^-1/2) v   K_l^t, V_l^t: the keys
            and values of THIS pass at THIS layer, every earlier position
            and the row's own
        h <- h + RMSNorm(a Wo; ln1_out)
        n = RMSNorm(h; ln2)
        h <- h + RMSNorm((silu(n Wg) * (n Wu)) Wd; ln2_out)
    x^t <- RMSNorm(h; final_ln)                     the SAME final norm
                                                    after EVERY pass
    lambda_t <- sigmoid(x^t w_gate + b_gate)        the exit gate

and logits = x^R W_head (untied). RMSNorm: x * rsqrt(mean(x^2) +
rms_norm_eps) * w. The exit distribution is p_t = lambda_t prod_{s<t} (1 -
lambda_s) for t < R and p_R the rest; a token leaves at the first t whose
cumulative p reaches `early_exit_threshold`. The published threshold is 1,
which only t = R reaches: every token runs all R passes, and that is what
is computed here. `exit_masses` gives p for the counters' check; it changes
no logit.

The four norms a layer are the model's `input_layernorm`,
`input_layernorm_2`, `post_attention_layernorm` and
`post_attention_layernorm_2` (here `ln1`, `ln1_out`, `ln2`, `ln2_out`).
Departures from the published model are the configuration file's `changed`
list; what the config does not itself state is under its `assumed`.
Parameters are read out of a scope by the names the decode programs give
them (paddle_tpu/models/transformer.py: q, k and v are the three column
blocks of `attn.qkv.w`), as they lie on the device.

The controls (ouro_control.py) are this forward computed WRONG in one way:
`dtype` below float32; `cross` -- pass t >= 2 attends the keys and values
that pass t - 1 left at the layer (a cache indexed by the layer alone, read
before it is written); `passes` other than the configuration's.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

# LOGIT_MARGIN: as lm_reference's, on the reference's own logits:
# ref_max - ref_logit[token] <= LOGIT_MARGIN * (ref_max - ref_mean), over a
# request's greedy tokens. Set between two readings on the v5e at the
# published widths and the cell's sizes (PERF.md section 6, PR 63; `python3
# benchmark/reference/ouro_control.py`, seeds 3000000201-204, and the
# driver's own check on fourteen more seeds): the programs as served read
# 0.0 on every row of every seed (their greedy token IS the reference's
# argmax: float32 at `highest` on both sides); over the driver's 8 rows a
# forward of THREE passes reads 0.319 to 0.609 and one with the passes'
# caches crossed 0.667 to 1.25 -- each above the limit in all eight of its
# readings, by a factor 2 at the least, the sound system a whole limit
# below. A bfloat16 forward's argmax agrees with the reference's on most
# rows (0.0 to 0.0425): what refuses IT in every reading is the limit on
# logits beside this one, `ouro_control.LOGITS_RMS_LIMIT`.
LOGIT_MARGIN = 0.15


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                                + eps)).astype(x.dtype) * w


def _rotate(x, pos, theta):
    """x [T, H, dh] rotated by pos [T]: rotate_half convention."""
    dh = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([angle, angle], axis=-1)[:, None, :]  # [T,1,dh]
    half = jnp.concatenate([-x[..., dh // 2:], x[..., :dh // 2]], axis=-1)
    return (x * jnp.cos(emb) + half * jnp.sin(emb)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=('n_head', 'eps', 'theta'))
def _layer(x, p, kv, n_head, eps, theta):
    """One layer of one pass over x [T, D]: (the stream after it, this
    pass's (K, V) at the layer). `kv`: keys and values attended IN THE
    PLACE of the pass's own (the crossed-cache control; None: its own)."""
    with jax.default_matmul_precision('highest'):
        t = x.shape[0]
        n = _rms(x, p['ln1.w'], eps)
        width = p['attn.qkv.w'].shape[1] // 3
        dh = width // n_head
        qkv = n @ p['attn.qkv.w']
        pos = jnp.arange(t)
        q = _rotate(qkv[:, :width].reshape(t, n_head, dh), pos, theta)
        k = _rotate(qkv[:, width:2 * width].reshape(t, n_head, dh), pos,
                    theta)
        v = qkv[:, 2 * width:].reshape(t, n_head, dh)
        own = (k, v)
        if kv is not None:
            k, v = kv
        s = jnp.einsum('qhd,khd->hqk', q, k) * (dh ** -0.5)
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None], s, -jnp.inf)
        a = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        ctx = jnp.einsum('hqk,khd->qhd', a, v).reshape(t, width)
        x = x + _rms(ctx @ p['attn.proj.w'], p['ln1_out.w'], eps)
        n = _rms(x, p['ln2.w'], eps)
        f = (jax.nn.silu(n @ p['ffn.gate.w']) * (n @ p['ffn.up.w'])) \
            @ p['ffn.down.w']
        return x + _rms(f, p['ln2_out.w'], eps), own


@functools.partial(jax.jit, static_argnames=('eps',))
def _close(x, ln_w, gate_w, gate_b, eps):
    """The end of a pass: (the final norm's output, the exit gate [T])."""
    with jax.default_matmul_precision('highest'):
        x = _rms(x, ln_w, eps)
        lam = jax.nn.sigmoid((x @ gate_w).astype(jnp.float32)[:, 0]
                             + gate_b.astype(jnp.float32)[0])
        return x, lam


@jax.jit
def _head(x, head_w):
    with jax.default_matmul_precision('highest'):
        return x @ head_w


_LAYER_KEYS = ('ln1.w', 'attn.qkv.w', 'attn.proj.w', 'ln1_out.w', 'ln2.w',
               'ffn.gate.w', 'ffn.up.w', 'ffn.down.w', 'ln2_out.w')


def forward(scope, m, tokens, passes=None, cross=False, dtype=jnp.float32):
    """(x^R [T, D], the last pass's output after the final norm; [per pass
    the exit gate lambda_t [T]]). The controls (ouro_control.py): `passes`
    other than `total_ut_steps`; `cross`, pass t >= 2 attending pass t -
    1's keys and values; parameters and activations in a `dtype` below
    float32."""
    def _param(name):
        v = scope.get(name)
        if v is None:
            raise KeyError('reference: scope has no parameter %r' % name)
        return jnp.asarray(v, dtype)

    tokens = np.asarray(tokens).reshape(-1)
    passes = int(m['total_ut_steps'] if passes is None else passes)
    x = jnp.take(_param('tok_emb.w'), jnp.asarray(tokens), axis=0)
    layers = [{k: _param('layer_%d.%s' % (i, k)) for k in _LAYER_KEYS}
              for i in range(m['num_hidden_layers'])]
    # a one-pass model has no gate (its one pass has all the mass)
    gate = (_param('exit_gate.w'), _param('exit_gate.b')) \
        if scope.get('exit_gate.w') is not None \
        else (jnp.zeros((x.shape[1], 1), dtype), jnp.zeros((1,), dtype))
    gates, before = [], [None] * len(layers)
    for _t in range(passes):
        for i, p in enumerate(layers):
            x, own = _layer(x, p, before[i] if cross else None,
                            n_head=m['num_attention_heads'],
                            eps=float(m['rms_norm_eps']),
                            theta=float(m['rope_theta']))
            before[i] = own
        x, lam = _close(x, _param('final_ln.w'), *gate,
                        eps=float(m['rms_norm_eps']))
        gates.append(lam)
    return x, gates


def exit_masses(gates):
    """[T, R]: the exit distribution a row, p_t = lambda_t prod_{s<t} (1 -
    lambda_s) for t < R, p_R the rest (a row sums to 1)."""
    rest = jnp.ones_like(gates[0])
    out = []
    for lam in gates[:-1]:
        out.append(lam * rest)
        rest = rest * (1.0 - lam)
    return np.asarray(jnp.stack(out + [rest], axis=1))


def logits(scope, m, tokens, positions=None, **control):
    """Reference logits [len(positions), V] (float32) of one sequence;
    `positions` (default: all) selects the rows the head is applied to."""
    x = forward(scope, m, tokens, **control)[0]
    if positions is not None:
        x = x[jnp.asarray(np.asarray(positions))]
    return _head(x, jnp.asarray(scope.get('lm_head.w'),
                                x.dtype)).astype(jnp.float32)


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
