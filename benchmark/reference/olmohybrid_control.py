"""The controls of the Olmo-Hybrid-7B comparison, and the comparison itself
at a cell's own size on the chip (after qwen3next_control.py; `logit_gap` is
olmoe_control's). The system's side is `GenerateEngine` ITSELF with
`prefix_sharing` on -- admissions, chunks, the prefix cache and its snapshot
rows as the cell runs them -- its programs rebound with their logits fetched
beside the tokens (`tap`).

`controls(...)`: the plain reference put in the program's place and computed
WRONG in one way --

- `bfloat16`: parameters and activations in bfloat16, the nearest precision
  below the float32 the configuration states;
- `bfloat16-state`: the recurrent state alone kept in bfloat16 between
  positions;
- `beta-in-0-1`: the write strength without its factor 2 (``beta =
  sigmoid(b)``: `linear_allow_neg_eigval` ignored);
- `pre-norm`: the block's norms on the sublayers' INPUTS (``x +
  f(norm(x))``) where the model norms their outputs;
- `rope`: the full-attention layers' q and k rotated (theta 500 000) where
  the model rotates nothing;
- `chunk-edge`: the recurrent state not carried across the edge of the
  prompt's first chunk: the second chunk starts from zeros (a prompt of one
  chunk has no such edge: the control does not apply);
- on a HIT, a prompt that resumes at a shared prefix's edge (the controls do
  not apply to a miss): `another-prefix-snapshot`, the state and tail that
  ANOTHER document of the same length leaves at that edge in the row's
  place (K/V shared by content, the row taken from the wrong entry);
  `kv-shared-state-zero`, the K/V shared and the recurrence started from
  zeros at the edge (what sharing without snapshot rows would compute);
  `tail-not-restored`, the state restored and the convolution's last three
  inputs not;
- `default-matmul-precision`: not the reference but THE PROGRAMS, built
  without the configuration's `matmul_precision` ('highest'): float32
  matmuls with bfloat16 operands, the TPU's default.

The configuration states float32 and its programs multiply as float32, so
what is left between the served logits and the reference is the order of
the sums, and ONE limit on logits holds the served programs -- the rms over
a prompt's rows of (logits - the reference's), each row relative to its (max
- mean): `LOGITS_RMS_LIMIT`. `drivers/serve.py _check` compares TOKENS
(`olmohybrid_reference.LOGIT_MARGIN`), 8 a prompt; each control's own greedy
tokens are held to that limit here as the driver holds the system's. A
control is refused where it exceeds a limit. The readings are beside the
limits and in PERF.md (PR 58).

    python3 benchmark/reference/olmohybrid_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters (two slots
and the blocks two prompts need: the comparison admits one request at a
time, and two sets of programs and the reference have to fit beside the
weights) and outside any timed window, two requests of ONE document of the
seed's pool through the engine, one after the other -- the first a MISS, in
chunks of the widest bucket, each chunk's edge leaving a snapshot row; the
second a HIT that resumes at the document's end from the row -- then
`DECODE_STEPS` decode steps each, and prints one JSON line a request: the
served logits against the reference's full forward, `greedy_margins`'
reading, and the same for each control in the system's place.
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

from benchmark.reference import olmohybrid_reference as ref  # noqa: E402
from benchmark.reference.olmoe_control import logit_gap      # noqa: E402

DECODE_STEPS = 24
# The limit beside olmohybrid_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (the served logits - the reference's), each row relative
# to its (max - mean). A computation that exceeds it (or is not finite) is
# refused. Readings on the v5e at the published widths (PERF.md section 6,
# PR 58: seeds 3000000101-104 x a miss of seven chunks and a hit resumed at
# 3 072 from a snapshot row, 3.1-3.3 k tokens each, x 25 rows: eight
# readings). The programs as served: 7.5e-7 to 1.54e-6, the hit's as the
# miss's. The smallest controls: the recurrent state kept in bfloat16
# 2.5e-4 to 2.6e-3 and the state dropped at the first chunk's edge 3.8e-4
# to 2.9e-3 (2.7 k positions of decay later); on a hit the tail not
# restored, the state started from zeros and a snapshot of another document
# 1.9e-3 to 6.8e-3 each; THE PROGRAMS AT THE DEFAULT PRECISION 6.6e-3 to
# 1.06e-2, THE BFLOAT16 FORWARD 1.06e-2 to 1.42e-2, RoPE on the full
# layers, beta without its factor 2 and the pre-norm block 3.8e-2 or more.
# The limit is a factor 19 above the largest sound reading and a factor 8
# under the smallest control's: all ten are refused by it in every one of
# their readings, the sound system in none.
LOGITS_RMS_LIMIT = 3e-5
ROPE_THETA = 500000.0


def controls(prompt_len, buckets, edge=0, other=None):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong, for a prompt of `prompt_len` rows prefilled through
    `buckets` that resumed at row `edge` (0: a miss); `other` the rows
    another document leaves at that edge (`ref.forward`'s second result). A
    control that does not apply to the prompt is left out."""
    out = {'bfloat16': {'dtype': jnp.bfloat16},
           'bfloat16-state': {'state_dtype': jnp.bfloat16},
           'beta-in-0-1': {'neg_eigval': False},
           'pre-norm': {'pre_norm': True},
           'rope': {'rope_theta': ROPE_THETA}}
    if edge:
        out.update({
            'another-prefix-snapshot': {'resume': (edge, other, True, True)},
            'kv-shared-state-zero': {'resume': (edge, None, True, True)},
            'tail-not-restored': {'resume': (edge, None, False, True)}})
    elif prompt_len > max(buckets):
        out['chunk-edge'] = {'resume': (max(buckets), None, True, False)}
    return out


def tap(eng):
    """Rebind a warmed engine's programs with their logits fetched beside
    the tokens; every dispatch's (kind, logits) goes to the list
    returned."""
    log = []

    def tapped(bound, kind):
        def call(feed, return_numpy=True):
            out = bound(feed, return_numpy=return_numpy)
            log.append((kind, np.asarray(out[1])))
            return out
        return call
    S, mb = eng.config.slots, eng._max_blocks
    for b, (prog, v) in eng._prefill.items():
        feed = {'gen_prompt': np.zeros((1, b), 'int64'),
                'gen_pos': np.zeros((1, b), 'int64'),
                'gen_len': np.ones((1, 1), 'int64')}
        feed.update(eng._tables_feed(np.zeros((1, mb), 'int64')))
        feed.update(eng._sample_feed(1))
        eng._prefill_bound[b] = tapped(eng.executor.bind(
            prog, feed, scope=eng.scope,
            fetch_list=[v['first_token'], v['logits']]), 'prefill')
    feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
            'gen_pos': np.zeros((S, 1), 'int64')}
    feed.update(eng._tables_feed(np.zeros((S, mb), 'int64')))
    feed.update(eng._sample_feed(S))
    eng._step_bound = tapped(eng.executor.bind(
        eng._step_prog, feed, scope=eng.scope,
        fetch_list=[eng._step_vars['next_tokens'],
                    eng._step_vars['logits']]), 'step')
    return log


def serve(eng, log, prompt, steps):
    """One request admitted and stepped by hand, alone (the loop's own
    path, its counters moving): (its greedy tokens, the logits of each --
    the last prefill dispatch's row, then its slot's of each step --, the
    row it resumed at: 0, a miss)."""
    from paddle_tpu import monitor
    del log[:]
    before = monitor.counters().get('kv_prefix_tokens_saved_total', 0)
    req = eng.submit(np.asarray(prompt, 'int64'), max_new_tokens=steps + 1)
    eng._admit()
    slot = next(i for i, s in enumerate(eng._slots)
                if s is not None and s.req is req)
    while req.finish_reason is None and req._error is None:
        eng._step()
    tokens = np.asarray(req.result(timeout=5))
    last = max(i for i, e in enumerate(log) if e[0] == 'prefill')
    logits = np.stack([log[last][1][0]]
                      + [e[1][slot] for e in log[last + 1:]])
    edge = monitor.counters().get('kv_prefix_tokens_saved_total', 0) - before
    return tokens, logits[:len(tokens)], int(edge)


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


def readings(scope, m, buckets, prompt, served, lower, other, check_rows):
    """One prompt's readings. `served` and `lower`: (tokens, logits, the
    row resumed at) of the programs as served and of the
    `default-matmul-precision` control; `other` the rows another document
    leaves at the edge. The other controls are computed along the served
    tokens."""
    own = _reference(scope, m, prompt, served[0])
    out = dict(_held(logit_gap(served[1], own), own, served[0], check_rows),
               prompt_len=int(len(prompt)), rows=int(len(served[0])),
               resumed_at=served[2], controls={})
    along = _reference(scope, m, prompt, lower[0])
    out['controls']['default-matmul-precision'] = _held(
        logit_gap(lower[1], along), along, lower[0], check_rows)
    for name, kw in controls(len(prompt), buckets, served[2],
                             other).items():
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
            num_blocks=engine['num_blocks'], prefix_sharing=True,
            eos_id=None, seed=0), scope=scope)
        eng.warmup()
        out.append((eng, tap(eng)))
    return out


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import olmohybrid
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    engine = dict(tr['engine'])
    # a slot a chunk of the document, so a snapshot row a chunk's edge (the
    # pools have one a slot), and two prompts' blocks
    shared, group = int(tr['shared_prefix_len']), int(tr['group_size'])
    engine['slots'] = max(2, -(-shared // max(engine['prompt_buckets'])))
    engine['num_blocks'] = 2 * engine['max_len'] // engine['block_size'] + 1
    cfg = olmohybrid.lm_config(m, int(engine['max_len']), False)
    scope, both = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights, and an engine's bound programs
        # keep theirs staged: the last seed's engines go first
        both = None
        gc.collect()
        for name in olmohybrid.param_shapes(m):
            scope.drop(name)
        for name, value in olmohybrid.init_params(m, seed).items():
            scope.set(name, value)
        both = engines(cfg, engine, scope)
        requests = traffic_gen.make_requests(tr, m['vocab_size'], seed)
        first, second = requests[0], requests[1]        # one document's
        # what ANOTHER document leaves at the shared prefix's edge
        other = ref.forward(scope, m, requests[group]['prompt'][:shared])[1]
        # an engine's two requests behind one another: the two engines
        # share the pools, and the second's blocks and rows are the first's
        runs = [[serve(eng, log, r['prompt'], DECODE_STEPS)
                 for r in (first, second)] for eng, log in both]
        for r, served, lower in zip((first, second), *runs):
            print(json.dumps(dict(readings(
                scope, m, engine['prompt_buckets'], r['prompt'], served,
                lower, other, int(tr['check_new_tokens'])), seed=seed)),
                flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
