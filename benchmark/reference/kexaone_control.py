"""The controls of the K-EXAONE-236B-A23B comparison, and the comparison
itself at a cell's own size on the chip (after lfm2_control.py;
`logit_gap` is olmoe_control's).

`controls(m)`: the plain reference put in the program's place and computed
WRONG in one way —

- `no-window`: the window layers see every key, as the global layer does
  (a program that reads the whole cache through the global table);
- `window-127`, `window-129`: the window one key short and one key long
  (an off-by-one in the first key seen, the ring's arithmetic or the rows
  a prefill leaves behind);
- `rope-on-global`: the global layer rotated like the window layers (the
  model card: "Global attention: NoPE");
- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `held-7`: one of the chip's held experts left out;
- `no-norm-weights`: every norm's weight taken as 1.

`drivers/serve.py _check` compares TOKENS
(`kexaone_reference.LOGIT_MARGIN`); what tells a control that serves
nearly the sound system's tokens from the sound system is the rms over a
prompt's rows of (logits - the reference's), each row relative to its
(max - mean): against the reference's own routing (`LOGITS_RMS_LIMIT`) and
given the computation's own (`LOGITS_RMS_GIVEN_ROUTING_LIMIT`), with the
readings beside the limits and in PERF.md (PR 41).

    python3 benchmark/reference/kexaone_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters and
outside any timed window, the shortest and the longest prompt of the
seed's pool through `Executor.run` on the programs the engine builds
(chunks of the widest bucket, each attending the rows the ring holds from
the chunk before), then `DECODE_STEPS` decode steps — and prints one JSON
line a prompt: the logits against the reference's full forward,
`greedy_margins`' reading, and the same for each control in the system's
place.
"""
import functools
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.reference import kexaone_reference as ref   # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# Two limits beside kexaone_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (logits - the reference's), each row relative to its
# (max - mean); readings on the v5e at the published widths (PERF.md, PR
# 41: two runs of 3 seeds x 2 prompts — 128 and 4 096 tokens — x 25 rows,
# twelve readings). A computation that exceeds one is refused. The
# benchmark's driver applies neither (it compares tokens only: PERF.md
# section 7).
#
# Against the reference's OWN routing — what a wrong rule moves. The sound
# system 0.0025 to 0.0082 (default-precision matmuls flip the 8th and 9th
# expert of 128 where they are nearly tied); a window of 127 keys 0.0365
# to 0.0445, of 129 0.0366 to 0.0439, no window 0.107 to 0.353, the norm
# weights left out 0.098 to 0.106. The limit is a factor 2.2 above the
# largest sound reading and 2.0 under the smallest of those.
LOGITS_RMS_LIMIT = 1.8e-2
# GIVEN the computation's own choice of experts — what is left is
# arithmetic. The sound system 0.0024 to 0.0025 (twelve readings); one held
# expert of 8 left out 0.0036 to 0.0157 (the smallest a 128-token prompt's:
# few of its rows choose that expert), the bfloat16
# forward 0.0048 to 0.0050, RoPE on the global layer 0.0058 to 0.0143: a
# factor 1.2 above the one and 1.2 under the smallest of the others (1.6
# under the bfloat16 forward). Every control is refused by one limit or
# the other in every reading.
LOGITS_RMS_GIVEN_ROUTING_LIMIT = 3.0e-3


def controls(m):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong."""
    w = m['sliding_window']
    first, count = ref.experts_held(m)
    return {
        'no-window': {'window': None},
        'window-%d' % (w - 1): {'window': w - 1},
        'window-%d' % (w + 1): {'window': w + 1},
        'rope-on-global': {'rope_on_global': True},
        'bfloat16': {'dtype': jnp.bfloat16},
        'held-%d' % (count - 1): {'held': (first, count - 1)},
        'no-norm-weights': {'norm_weights': False},
    }


class Session(object):
    """Prompts through the paged prefill (in chunks of the widest bucket)
    and the decode step, run by `Executor.run` on the programs
    `GenerateEngine` builds, as slot 0: global blocks 1.., and slot 0's
    ring in the window layers' pools (block 0 of each is the trash
    block)."""

    def __init__(self, cfg, engine, scope):
        from paddle_tpu import unique_name
        from paddle_tpu.executor import Executor
        from paddle_tpu.framework import Program, TPUPlace, program_guard
        from paddle_tpu.models import transformer as T
        self.cfg, self.e, self.scope = cfg, engine, scope
        self.exe = Executor(TPUPlace(0))
        self.max_blocks = engine['max_len'] // engine['block_size']
        self.ring = T.window_ring(cfg, engine['block_size'])
        for name, shape in T.kv_cache_shapes(
                cfg, engine['num_blocks'], engine['block_size'],
                engine['slots']).items():
            scope.set(name, jnp.zeros(shape, jnp.float32))
        self.progs = {}

        def build(key, fn):
            main = Program()
            with program_guard(main, Program()):
                with unique_name.guard():
                    self.progs[key] = (main, fn())
        build('step', lambda: T.build_lm_decode_step(
            cfg, engine['slots'], engine['max_len'],
            block_size=engine['block_size'],
            num_blocks=engine['num_blocks']))
        for b in engine['prompt_buckets']:
            build(b, functools.partial(
                T.build_lm_prefill_paged, cfg, b, engine['num_blocks'],
                engine['block_size'], self.max_blocks,
                slots=engine['slots']))

    def _run(self, key, feed, n):
        main, v = self.progs[key]
        feed.update({'gen_temp': np.zeros((n, 1), 'float32'),
                     'gen_topk': np.zeros((n, 1), 'int64'),
                     'gen_topp': np.zeros((n, 1), 'float32'),
                     'gen_u': np.zeros((n, 1), 'float32')})
        out = self.exe.run(main, feed=feed, scope=self.scope,
                           fetch_list=[v['logits']] + v['topk_idx'])
        return np.asarray(out[0]), [np.asarray(o) for o in out[1:]]

    def _tables(self, rows, blocks):
        """'gen_btab' and 'gen_wtab' of `rows` rows, row 0 slot 0's."""
        btab = np.zeros((rows, self.max_blocks), 'int64')
        btab[0, :len(blocks)] = blocks
        wtab = np.zeros((rows, self.ring), 'int64')
        wtab[0] = 1 + np.arange(self.ring)
        return {'gen_btab': btab, 'gen_wtab': wtab}

    def generate(self, prompt, steps):
        """(greedy tokens, logits [1 + steps, V], per expert layer the
        experts chosen for the rows computed [T + steps, k]) of the prompt
        prefilled and `steps` decode steps."""
        e = self.e
        prompt = np.asarray(prompt, 'int64').reshape(-1)
        steps = min(steps, e['max_len'] - len(prompt))
        blocks = 1 + np.arange(-(-(len(prompt) + steps) // e['block_size']))
        wide = max(e['prompt_buckets'])
        off, chosen = 0, None
        while off < len(prompt):
            n = min(wide, len(prompt) - off)
            b = min(x for x in e['prompt_buckets'] if x >= n)
            padded = np.zeros((1, b), 'int64')
            padded[0, :n] = prompt[off:off + n]
            pos = np.clip(off + np.arange(b), 0, e['max_len'] - 1)[None]
            lg, idx = self._run(b, dict(
                self._tables(1, blocks), gen_prompt=padded,
                gen_pos=pos.astype('int64'),
                gen_len=np.array([[n]], 'int64')), 1)
            chosen = [i[:n] for i in idx] if chosen is None else \
                [np.concatenate([c, i[:n]]) for c, i in zip(chosen, idx)]
            off += n
        logits, tokens = [lg[0]], [int(np.argmax(lg[0]))]
        S = e['slots']
        for step in range(steps):
            toks, posf = np.zeros((S, 1), 'int64'), np.zeros((S, 1), 'int64')
            toks[0], posf[0] = tokens[-1], len(prompt) + step
            lg, idx = self._run('step', dict(
                self._tables(S, blocks), gen_tokens=toks, gen_pos=posf), S)
            logits.append(lg[0])
            chosen = [np.concatenate([c, i[:1]]) for c, i in zip(chosen,
                                                                 idx)]
            tokens.append(int(np.argmax(lg[0])))
        return tokens, np.stack(logits), chosen


def _refused(own_gap, given_gap):
    return bool(own_gap[0] > LOGITS_RMS_LIMIT
                or given_gap[0] > LOGITS_RMS_GIVEN_ROUTING_LIMIT)


def readings(scope, m, prompt, tokens, logits, chosen):
    """One prompt's readings: `tokens[i]` is the argmax of `logits[i]`,
    the system's logits at position len(prompt) - 1 + i; `chosen` the
    experts it chose, a layer. Against the reference's own routing, and
    GIVEN the computation's own (what is left is arithmetic)."""
    k = m['num_experts_per_tok']
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    own = np.asarray(ref.logits(scope, m, seq, positions=pos))
    given = np.asarray(ref.logits(scope, m, seq, routing=chosen,
                                  positions=pos))
    biases = [np.asarray(scope.get('layer_%d.moe.router.bias' % i))
              for i in range(m['first_k_dense_replace'],
                             m['num_hidden_layers'])]
    out = {'prompt_len': int(len(prompt)), 'rows': int(len(tokens)),
           'logits_vs_ref': logit_gap(logits, own),
           'logits_vs_ref_given_routing': logit_gap(logits, given),
           'greedy_margin_worst': float(ref.margins(own, tokens).max()),
           'controls': {}}
    out['refused_by_logits_rms'] = _refused(
        out['logits_vs_ref'], out['logits_vs_ref_given_routing'])
    for name, kw in controls(m).items():
        hidden, its_scores = ref.forward(scope, m, seq, **kw)
        wrong = np.asarray(ref.head(scope, m, hidden, pos,
                                    kw.get('norm_weights', True)))
        gap = logit_gap(wrong, own)
        # the control held to the reference GIVEN the control's own choice
        # of experts, as the system is above
        its_routing = [np.argsort(-(np.asarray(sc, np.float32) + b[None, :]),
                                  axis=1, kind='stable')[:, :k]
                       for sc, b in zip(its_scores, biases)]
        given_gap = logit_gap(wrong, np.asarray(ref.logits(
            scope, m, seq, routing=its_routing, positions=pos)))
        out['controls'][name] = {
            'logits_vs_ref': gap,
            'logits_vs_ref_given_routing': given_gap,
            'refused_by_logits_rms': _refused(gap, given_gap),
            # the control's own greedy tokens, held to the reference as
            # the driver holds the system's
            'greedy_margin_worst': float(ref.margins(
                own, wrong.argmax(axis=1)).max())}
    return out


def compare(cfg, engine, scope, m, prompt, new_tokens, session=None):
    """`prompt` through the pools and `new_tokens` decode steps: its
    `readings`."""
    session = session or Session(cfg, engine, scope)
    tokens, logits, chosen = session.generate(prompt, new_tokens)
    return readings(scope, m, prompt, tokens, logits, chosen)


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import kexaone
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = kexaone.lm_config(m, int(tr['engine']['max_len']), False)
    scope, session = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in kexaone.param_shapes(m):
            scope.drop(name)
        for name, value in kexaone.init_params(m, seed).items():
            scope.set(name, value)
        session = session or Session(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        for r in (requests[0], requests[-1]):
            print(json.dumps(dict(compare(
                cfg, tr['engine'], scope, m, r['prompt'], DECODE_STEPS,
                session), seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
