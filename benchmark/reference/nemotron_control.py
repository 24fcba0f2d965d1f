"""The controls of the NVIDIA-Nemotron-3-Nano-30B-A3B comparison, and the
comparison itself at a cell's own size on the chip (after jamba_control.py,
whose `Session` it runs the programs with; `logit_gap` is olmoe_control's).

`controls(...)`: the plain reference put in the program's place and
computed WRONG in one way --

- `chunk-edge`: the recurrent state not carried across the edge of the
  prompt's first chunk: the second chunk starts from zeros (a prompt of
  one chunk has no such edge: the control does not apply);
- `block-edge`: not carried across an SSD block's edge either: every block
  of `chunk_size` rows starts from zeros;
- `stale-state`: the state another sequence left in the slot's row stands
  in the zeros' place before position 0 (no reset for a new tenant);
- `pad-rows`: the pad rows of the prompt's last bucket walked by the
  recurrence and the convolution like real rows, before the first decode
  step (a prompt that fills its bucket has none: does not apply);
- `group-0`: every head reads B and C of group 0;
- `norm-all-channels`: the gated norm over all of d_inner at once, not a
  group at a time;
- `norm-before-gate`: the norm first, then the gate (`norm_before_gate`
  true);
- `no-D`: the `D x` skip term left out;
- `no-conv-bias`: the convolution's bias left out;
- `relu`, `silu`: an expert's activation without the square, or SiLU;
- `gated-experts`: an expert as the SiLU-gated form of the repo's other
  expert models (the up matrix standing in for the gate it would need);
- `no-shared-expert`: the shared expert left out;
- `no-routed-scale`: `routed_scaling_factor` left out;
- `bias-in-weights`: the correction bias in the weights, not in the
  choice alone;
- `rope-on-attention`: the attention layers rotated as a RoPE model's
  (`rope_theta`); the model has no positional encoding;
- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `bfloat16-state`: the forward in float32, the recurrent state alone
  rounded to bfloat16 after every position;
- `default-matmul-precision`: not the reference but THE PROGRAMS, built
  without the configuration's `matmul_precision` ('highest'): float32
  matmuls with bfloat16 operands, the TPU's default and what the repo's
  other cells serve at.

The configuration states float32 and its programs multiply as float32
(`LMConfig.matmul_precision`, `changed.matmul_precision` in the
configuration file), so what is left between the served logits and the
reference is the order of the sums, and ONE limit on logits holds the
served programs -- the rms over a prompt's rows of (logits - the
reference's), each row relative to its (max - mean): `LOGITS_RMS_LIMIT`.
`drivers/serve.py _check` compares TOKENS (`nemotron_reference.
LOGIT_MARGIN`), 8 a prompt; each control's own greedy tokens are held to
that limit here as the driver holds the system's, over the check's rows
(`greedy_margin_check_rows`) and over all of them. A control is the
reference computed wrong at full precision (or the programs at a lower one),
and is refused where it exceeds a limit. The readings are beside the limits
and in PERF.md (PR 48).

    python3 benchmark/reference/nemotron_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters and
outside any timed window, the shortest and the longest prompt of the
seed's pool through `Executor.run` on the programs the engine builds -- the
SAME row of the state pools for every prompt, so each starts on the last
one's state; chunks of the widest bucket, each resuming from the row --
then `DECODE_STEPS` decode steps, and prints one JSON line a prompt: the
served logits against the reference's full forward, `greedy_margins'
reading, and the same for each control in the system's place.
"""
import copy
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.reference import nemotron_reference as ref  # noqa: E402
from benchmark.reference.jamba_control import Session      # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# The limit beside nemotron_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (the served logits - the reference's), each row relative
# to its (max - mean). A computation that exceeds it is refused. Readings on
# the v5e at the published widths (PERF.md, PR 48, second session; 3 seeds x
# 2 prompts -- 32 and 1 024 tokens, the second two prompt chunks and eight
# SSD blocks -- x 25 rows, six readings each). The programs as served:
# 4.4e-7 to 4.8e-7 under the short prompt, 6.7e-6, 1.3e-5 and 4.8e-5 under
# the long one (the order of the sums through 1 048 positions; the first
# session's same programs re-traced at "highest" read 3.9e-7 to 1.34e-5).
# The smallest control, the state kept in bfloat16 under a 32-token prompt,
# 2.5e-4 to 3.3e-4; the bias in the weights 1.0e-3 or more, a stale state
# 1.9e-3, THE PROGRAMS AT THE DEFAULT PRECISION 7.1e-3 to 2.0e-2, a chunk
# from zeros 9.4e-3, the bfloat16 forward 9.8e-3 to 2.8e-2, every other
# control 1.6e-2 or more. The limit is a factor 2.3 above the largest sound
# reading and 2.3 under the smallest control's: all nineteen are refused by
# it in every one of their readings, the sound system in none. (Thin: the
# next readings may move it; the driver applies `LOGIT_MARGIN` alone.)
LOGITS_RMS_LIMIT = 1.1e-4


def controls(m, prompt_len, buckets, stale):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong, for a prompt of `prompt_len` rows prefilled through
    `buckets`; `stale` the per-layer states another sequence left. A
    control that does not apply to the prompt is left out."""
    wide = max(buckets)
    out = {}
    if prompt_len > wide:
        out['chunk-edge'] = {'zero_state_at': wide}
    if prompt_len > m['chunk_size']:
        out['block-edge'] = {'zero_state_every': m['chunk_size']}
    out['stale-state'] = {'init_states': stale}
    last = prompt_len - (prompt_len - 1) // wide * wide
    pads = min(b for b in buckets if b >= last) - last
    if pads:
        out['pad-rows'] = {'pad_rows': (prompt_len, pads)}
    out.update({
        'group-0': {'one_group': True},
        'norm-all-channels': {'norm_groups': 1},
        'norm-before-gate': {'norm_first': True},
        'no-D': {'skip_d': True},
        'no-conv-bias': {'conv_bias': False},
        'relu': {'act': 'relu'},
        'silu': {'act': 'silu'},
        'gated-experts': {'act': 'gated'},
        'no-shared-expert': {'shared': False},
        'no-routed-scale': {'routed_scale': False},
        'bias-in-weights': {'bias_in_weights': True},
        'rope-on-attention': {'rope_theta': float(m['rope_theta'])},
        'bfloat16': {'dtype': jnp.bfloat16},
        'bfloat16-state': {'state_dtype': jnp.bfloat16}})
    return out


def _reference(scope, m, prompt, tokens, **kw):
    """The reference's logits at the rows `tokens` were read from."""
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens[:-1]])
    return np.asarray(ref.logits(
        scope, m, seq, positions=np.arange(len(prompt) - 1, len(seq)), **kw))


def _held(gap, want, tokens, check_rows):
    """A computation's reading against both limits: its logits' `gap` and
    its greedy `tokens`' margins in the reference's logits `want`, over the
    driver's `check_rows` first rows and over all of them."""
    margins = ref.margins(want, tokens)
    return {'logits_vs_ref': gap,
            'greedy_margin_check_rows': float(margins[:check_rows].max()),
            'greedy_margin_worst': float(margins.max()),
            'refused_by': [name for name, over in (
                ('logits', gap[0] > LOGITS_RMS_LIMIT),
                ('tokens', margins[:check_rows].max() > ref.LOGIT_MARGIN))
                if over]}


def readings(scope, m, buckets, prompt, served, lower, stale, check_rows):
    """One prompt's readings. `served` and `lower`: (tokens, logits) of the
    programs as served and of the `default-matmul-precision` control,
    `tokens[i]` the argmax of `logits[i]`, the logits at position
    len(prompt) - 1 + i; `stale` the states the `stale-state` control
    starts from. The other controls are computed along the served
    tokens."""
    own = _reference(scope, m, prompt, served[0])
    out = dict(_held(logit_gap(served[1], own), own, served[0], check_rows),
               prompt_len=int(len(prompt)), rows=int(len(served[0])),
               controls={})
    along = _reference(scope, m, prompt, lower[0])
    out['controls']['default-matmul-precision'] = _held(
        logit_gap(lower[1], along), along, lower[0], check_rows)
    for name, kw in controls(m, len(prompt), buckets, stale).items():
        wrong = _reference(scope, m, prompt, served[0], **kw)
        # the control's own greedy tokens, held to the reference as the
        # driver holds the system's
        out['controls'][name] = _held(logit_gap(wrong, own), own,
                                      wrong.argmax(axis=1), check_rows)
    return out


def sessions(cfg, engine, scope):
    """The programs as served, and the same built with their matmuls left
    at the backend's default precision (both on the scope's one set of
    pools)."""
    lower = copy.copy(cfg)
    lower.matmul_precision = None
    return Session(cfg, engine, scope), Session(lower, engine, scope)


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import nemotron
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = nemotron.lm_config(m, int(tr['engine']['max_len']), False)
    scope, both = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in nemotron.param_shapes(m):
            scope.drop(name)
        for name, value in nemotron.init_params(m, seed).items():
            scope.set(name, value)
        both = both or sessions(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        # the state the `stale-state` control starts from: what a tenant
        # of median length leaves behind
        stale = ref.forward(scope, m,
                            requests[len(requests) // 2]['prompt'])[1]
        for r in (requests[0], requests[-1]):
            served, lower = [s.generate(r['prompt'], DECODE_STEPS)
                             for s in both]
            print(json.dumps(dict(readings(
                scope, m, tr['engine']['prompt_buckets'], r['prompt'],
                served, lower, stale, int(tr['check_new_tokens'])),
                seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
