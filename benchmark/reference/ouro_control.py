"""The controls of the Ouro-2.6B comparison, and the comparison itself at a
cell's own size on the chip (after olmohybrid_control.py, whose `tap` and
`serve` put the engine's own programs on the system's side with their logits
fetched beside the tokens; `logit_gap` is olmoe_control's).

`controls()`: the plain reference put in the program's place and computed
WRONG in one way --

- `bfloat16`: parameters and activations in bfloat16, the nearest precision
  below the float32 the configuration states;
- `crossed-cache`: pass t >= 2 attends the keys and values that pass t - 1
  left at the layer -- what a cache indexed by the layer alone gives a
  looped model: every pass reads the entry before it writes its own;
- `three-passes`: one pass fewer than `total_ut_steps`;
- `default-matmul-precision`: not the reference but THE PROGRAMS, built
  without the configuration's `matmul_precision` ('highest'): float32
  matmuls with bfloat16 operands, the TPU's default.

The configuration states float32 and its programs multiply as float32, so
what is left between the served logits and the reference is the order of
the sums, and ONE limit on logits holds the served programs -- the rms over
a request's rows of (logits - the reference's), each row relative to its
(max - mean): `LOGITS_RMS_LIMIT`. `drivers/serve.py _check` compares TOKENS
(`ouro_reference.LOGIT_MARGIN`), 8 a prompt; each control's own greedy
tokens are held to that limit here as the driver holds the system's. A
control is refused where it exceeds a limit. The readings are beside the
limits and in PERF.md (PR 63).

    python3 benchmark/reference/ouro_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters (two slots
and the blocks two requests need: the comparison admits one request at a
time, and two sets of programs and the reference have to fit beside the
weights) and outside any timed window, the seed's shortest and longest
prompt (the driver's two) through the engine, one after the other, the
longest in chunks of the widest bucket where it is wider, then
`DECODE_STEPS` decode steps each, and prints one JSON line a request: the
served logits against the reference's full forward of ALL passes,
`greedy_margins`' reading, and the same for each control in the system's
place.
"""
import copy
import gc
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.reference import ouro_reference as ref        # noqa: E402
from benchmark.reference.olmoe_control import logit_gap      # noqa: E402
from benchmark.reference.olmohybrid_control import (         # noqa: E402,F401
    serve, tap)

DECODE_STEPS = 24
# The limit beside ouro_reference.LOGIT_MARGIN, on the rms over a request's
# rows of (the served logits - the reference's), each row relative to its
# (max - mean). A computation that exceeds it (or is not finite) is refused.
# Readings on the v5e at the published widths and the cell's engine (PERF.md
# section 6, PR 63: seeds 3000000201-204 x the shortest prompt, 65 tokens,
# and the longest, 319, x 25 rows: eight readings). The programs as served:
# 3.8e-7 to 6.2e-7 at 65 tokens, 1.7e-6 to 2.0e-6 at 319. THE PROGRAMS AT
# THE DEFAULT PRECISION 4.6e-3 to 7.3e-3; THE BFLOAT16 FORWARD 7.3e-3 to
# 1.23e-2 -- the two overlap, which is why the configuration states
# `matmul_precision: highest`: at the default precision no limit on logits
# tells the served programs from a bfloat16 forward; three passes 0.156 to
# 0.207 and the passes' caches crossed 0.259 to 0.334 (another model's
# logits). The limit is a factor 15 above the largest sound reading and a
# factor 154 under the smallest control's: all four are refused by it in
# every one of their readings, the sound system in none.
LOGITS_RMS_LIMIT = 3e-5


def controls(m):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong."""
    return {'bfloat16': {'dtype': jnp.bfloat16},
            'crossed-cache': {'cross': True},
            'three-passes': {'passes': int(m['total_ut_steps']) - 1}}


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
                ('logits', not gap[0] <= LOGITS_RMS_LIMIT),
                ('tokens', margins[:check_rows].max() > ref.LOGIT_MARGIN))
                if over]}


def readings(scope, m, prompt, served, lower, check_rows):
    """One request's readings. `served` and `lower`: (tokens, logits, ..)
    of the programs as served and of the `default-matmul-precision` control
    (None: not built); the other controls are computed along the served
    tokens."""
    own = _reference(scope, m, prompt, served[0])
    out = dict(_held(logit_gap(served[1], own), own, served[0], check_rows),
               prompt_len=int(len(prompt)), rows=int(len(served[0])),
               controls={})
    if lower is not None:
        along = _reference(scope, m, prompt, lower[0])
        out['controls']['default-matmul-precision'] = _held(
            logit_gap(lower[1], along), along, lower[0], check_rows)
    for name, kw in controls(m).items():
        wrong = _reference(scope, m, prompt, served[0], **kw)
        out['controls'][name] = _held(logit_gap(wrong, own), own,
                                      wrong.argmax(axis=1), check_rows)
    return out


def engines(cfg, engine, scope):
    """The engine as the cell builds it, its programs' logits tapped, and
    the same built with its matmuls left at the backend's default precision
    (both on the scope's one set of weights and pools): [(engine, its
    log)]."""
    from paddle_tpu.serving import GenerateConfig, GenerateEngine
    lower = copy.copy(cfg)
    lower.matmul_precision = None
    out = []
    for model in (cfg, lower):
        eng = GenerateEngine(GenerateConfig(
            model=model, slots=engine['slots'], max_len=engine['max_len'],
            prompt_buckets=list(engine['prompt_buckets']),
            block_size=engine['block_size'],
            num_blocks=engine['num_blocks'], eos_id=None, seed=0),
            scope=scope)
        eng.warmup()
        out.append((eng, tap(eng)))
    return out


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import ouro
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    engine = dict(tr['engine'], slots=2)
    engine['num_blocks'] = 2 * engine['max_len'] // engine['block_size'] + 1
    cfg = ouro.lm_config(m, int(engine['max_len']), False)
    scope, both = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights, and an engine's bound programs
        # keep theirs staged: the last seed's engines go first
        both = None
        gc.collect()
        for name in ouro.param_shapes(m):
            scope.drop(name)
        for name, value in ouro.init_params(m, seed).items():
            scope.set(name, value)
        both = engines(cfg, engine, scope)
        by_len = sorted(traffic_gen.make_requests(tr, m['vocab_size'], seed),
                        key=lambda r: len(r['prompt']))
        picked = [by_len[0], by_len[-1]]        # the driver's two
        runs = [[serve(eng, log, r['prompt'], DECODE_STEPS) for r in picked]
                for eng, log in both]
        for r, served, lower in zip(picked, *runs):
            print(json.dumps(dict(readings(
                scope, m, r['prompt'], served, lower,
                int(tr['check_new_tokens'])), seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
