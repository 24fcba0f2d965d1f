"""The controls of the Qwen3-Next-80B-A3B-Instruct comparison, and the
comparison itself at a cell's own size on the chip (after
nemotron_control.py; `Session` is jamba_control's, `logit_gap`
olmoe_control's).

`controls(...)`: the plain reference put in the program's place and
computed WRONG in one way --

- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `no-decay`: the delta rule without its decay (``g = 0``): plain DeltaNet;
- `beta-1`: the write strength left out (``beta = 1``);
- `no-l2norm`: q and k of the DeltaNet layers not normed;
- `tiled-key-heads`: value head ``h`` reads key head ``h % 16`` (a tile)
  where the model repeats (``h // 2``);
- `rotate-all`: all 256 numbers of a head rotated, not the first 64;
- `no-attention-gate`: the attention's sigmoid gate left out;
- `ungated-shared-expert`: the shared expert added without its gate;
- `9-experts`: nine experts a token where the model takes ten;
- `plain-norm`: every zero-centred norm multiplies by ``w``, not ``1 + w``;
- `stale-state`: the state another sequence left in the slot's row stands
  in the zeros' place before position 0 (no reset for a new tenant), under
  the short and under the long prompt;
- `chunk-edge`: the recurrent state not carried across the edge of the
  prompt's first chunk: the second chunk starts from zeros (a prompt of
  one chunk has no such edge: the control does not apply);
- `default-matmul-precision`: not the reference but THE PROGRAMS, built
  without the configuration's `matmul_precision` ('highest'): float32
  matmuls with bfloat16 operands, the TPU's default.

The configuration states float32 and its programs multiply as float32
(`LMConfig.matmul_precision`, `changed.matmul_precision` in the
configuration file), so what is left between the served logits and the
reference is the order of the sums, and ONE limit on logits holds the
served programs -- the rms over a prompt's rows of (logits - the
reference's), each row relative to its (max - mean): `LOGITS_RMS_LIMIT`.
`drivers/serve.py _check` compares TOKENS (`qwen3next_reference.
LOGIT_MARGIN`), 8 a prompt; each control's own greedy tokens are held to
that limit here as the driver holds the system's, over the check's rows
(`greedy_margin_check_rows`) and over all of them. A control is refused
where it exceeds a limit. The readings are beside the limits and in PERF.md
(PR 55).

    python3 benchmark/reference/qwen3next_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters (the block
pool cut to one slot's blocks: the comparison drives slot 0 alone, and two
sets of programs and the reference have to fit beside the weights) and
outside any timed window, the shortest and the longest prompt of the seed's
pool through `Executor.run` on the programs the engine builds -- the SAME
row of the state pools for every prompt, so each starts on the last one's
state; chunks of the widest bucket, each resuming from the row -- then
`DECODE_STEPS` decode steps, and prints one JSON line a prompt: the served
logits against the reference's full forward, `greedy_margins`' reading,
and the same for each control in the system's place.
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

from benchmark.reference import qwen3next_reference as ref  # noqa: E402
from benchmark.reference.jamba_control import Session      # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# The limit beside qwen3next_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (the served logits - the reference's), each row relative
# to its (max - mean). A computation that exceeds it (or is not finite) is
# refused. Readings on the v5e at the published widths (PERF.md section 6,
# PR 55; 3 seeds x 2 prompts -- 256 and 8 192 tokens, the second sixteen
# prompt chunks and 128 blocks of the delta rule -- x 25 rows, six readings
# each). The programs as served: 1.18e-6 to 1.58e-6 under the short prompt,
# 3.99e-6 to 4.39e-6 under the long one (the order of the sums through 8 216
# positions). The smallest control, the state dropped at the first chunk's
# edge under the long prompt (7 680 positions of decay later), 1.73e-4 to
# 2.86e-4; a stale state 2.98e-4 to 2.6e-3 under the long prompt and 8.1e-3
# to 1.06e-2 under the short one; THE PROGRAMS AT THE DEFAULT PRECISION
# 9.2e-3 to 1.7e-2, all 256 numbers rotated 1.1e-2 or more, nine experts
# 1.4e-2 or more, THE BFLOAT16 FORWARD 2.0e-2 to 2.8e-2, the attention's
# gate left out 2.8e-2 or more, every other control 0.2 or more (q and k
# unnormed overflow under the long prompt: not finite, refused as such).
# The limit is a factor 6.8 above the largest sound reading and 5.8 under
# the smallest control's: all thirteen are refused by it in every one of
# their readings, the sound system in none.
LOGITS_RMS_LIMIT = 3e-5


def controls(prompt_len, buckets, stale):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong, for a prompt of `prompt_len` rows prefilled through
    `buckets`; `stale` the per-layer states another sequence left. A
    control that does not apply to the prompt is left out."""
    out = {'bfloat16': {'dtype': jnp.bfloat16},
           'no-decay': {'decay': False},
           'beta-1': {'unit_beta': True},
           'no-l2norm': {'l2norm': False},
           'tiled-key-heads': {'tile_keys': True},
           'rotate-all': {'rotate_all': True},
           'no-attention-gate': {'attention_gate': False},
           'ungated-shared-expert': {'shared_gate': False},
           '9-experts': {'experts_fewer': 1},
           'plain-norm': {'plain_norm': True},
           'stale-state': {'init_states': stale}}
    if prompt_len > max(buckets):
        out['chunk-edge'] = {'zero_state_at': max(buckets)}
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
                # not finite is over any limit (the unnormed keys overflow)
                ('logits', not gap[0] <= LOGITS_RMS_LIMIT),
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
    for name, kw in controls(len(prompt), buckets, stale).items():
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
    from benchmark.models import qwen3next
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    engine = dict(tr['engine'])
    # slot 0's blocks and the trash block: the other slots' sit idle here
    engine['num_blocks'] = engine['max_len'] // engine['block_size'] + 1
    cfg = qwen3next.lm_config(m, int(engine['max_len']), False)
    scope, both = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in qwen3next.param_shapes(m):
            scope.drop(name)
        for name, value in qwen3next.init_params(m, seed).items():
            scope.set(name, value)
        both = both or sessions(cfg, engine, scope)
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
                scope, m, engine['prompt_buckets'], r['prompt'], served,
                lower, stale, int(tr['check_new_tokens'])), seed=seed)),
                flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
