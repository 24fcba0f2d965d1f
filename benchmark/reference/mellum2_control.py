"""The controls of the Mellum2-12B-A2.5B-Instruct comparison, and the
comparison itself at a cell's own size on the chip (after
kexaone_control.py; `logit_gap` is olmoe_control's).

`controls(m)`: the plain reference put in the program's place and computed
WRONG in one way —

- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `window-1023`, `window-1025`, `no-window`: the sliding layers' window one
  key short, one key long, and gone (an off-by-one in the first key seen,
  the ring's arithmetic or the rows a prefill leaves behind; a program that
  reads the whole cache through the full layer's table);
- `no-yarn`: the full layer rotated with the sliding layers' plain table;
  `yarn-no-attention-factor`: YaRN's frequencies without the factor on cos
  and sin; `yarn-on-sliding`: the sliding layers rotated with the full
  layer's table too;
- `no-norm-topk`: the chosen experts' probabilities not divided by their
  sum; `top-7`: one expert a token fewer;
- `no-norm-weights`: every norm's weight taken as 1.

`ring_controls(...)`: a request that RESUMED at a shared prefix's edge,
whose sliding layers found in their ring, in the place of the prefix's
last `sliding_window - 1` rows,

- `ring-zeros`: nothing (a hit that gave the new tenant fresh blocks);
- `ring-later`: the first tenant's LATER rows — the ring as it stood once
  that tenant had moved on, each column holding its newest block (a cache
  that kept no reference, or a tenant that wrote over a shared block).

`drivers/serve.py _check` compares TOKENS
(`mellum2_reference.LOGIT_MARGIN`); what tells a control from the sound
system is the rms over a request's rows of (logits - the reference's), each
row relative to its (max - mean): against the reference's own routing
(`LOGITS_RMS_LIMIT`) and given the computation's own
(`LOGITS_RMS_GIVEN_ROUTING_LIMIT`), with the readings beside the limits and
in PERF.md (PR 51).

    python3 benchmark/reference/mellum2_control.py <config> <traffic> <seed>...

builds a `GenerateEngine` a seed under the traffic file's engine parameters
(prefix sharing on), outside any timed window, and serves two
of the seed's requests one after the other, `DECODE_STEPS` tokens each: the
first cold (every chunk of its ~10 k tokens, past the ring's wrap), the
second RESUMING at the shared prefix's edge from the window blocks the
first left in the prefix cache, after the first has moved on by more than a
ring. It prints one JSON line a request: the logits against the
reference's full forward, `greedy_margins`' reading, and the same for each
control in the system's place.
"""
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

from benchmark.reference import mellum2_reference as ref   # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# Two limits beside mellum2_reference.LOGIT_MARGIN, on the rms over a
# request's rows of (logits - the reference's), each row relative to its
# (max - mean); readings on the v5e at the published widths and the cell's
# own lengths, the programs at the default precision (bfloat16 operands
# under float32 sums; PERF.md, PR 51: four seeds x 2 requests -- one of
# 10 239 tokens served cold, one of 8 449 resumed at the shared prefix's
# edge behind it -- x 24 rows, eight readings). A computation that exceeds
# one is refused. The benchmark's driver applies neither (it compares
# tokens only: PERF.md section 7).
#
# Against the reference's OWN routing -- what a wrong rule moves. The sound
# system 0.0033 to 0.0064 (default-precision matmuls flip the 8th and 9th
# expert of 64 where they are nearly tied); 7 of 8 experts 0.0150 to
# 0.0198, YaRN without its attention factor 0.0275 to 0.0362, no YaRN on
# the full layer 0.0575 to 0.0833, the norm weights left out 0.0595 to
# 0.0741, norm_topk_prob off 0.0794 to 0.0956, YaRN on the sliding layers
# too 0.111 to 0.133, a resumed ring given zeros 0.244 to 0.276 or the
# first tenant's later rows 0.268 to 0.296, no window 0.26 to 0.317. The
# limit is a factor 1.6 above the largest sound reading and 1.5 under the
# smallest of those.
LOGITS_RMS_LIMIT = 1.0e-2
# GIVEN the computation's own choice of experts -- what is left is
# arithmetic, and the one key more or less of a window that is off by one.
# The sound system 0.00153 to 0.00167; the bfloat16 forward 0.00240 to
# 0.00271, a window of 1 023 keys 0.00229 to 0.00264, of 1 025 0.00232 to
# 0.00275: a factor 1.17 above the one and 1.17 under the smallest of the
# others (K-EXAONE's limits stand 1.2 and 1.2 apart, at the same
# precision). With the programs at `matmul_precision='highest'` the sound
# system reads 1.5e-5 given the routing and 0.0005 to 0.0015 against the
# reference's own (one seed): the configuration states the default
# because these two limits tell every control from it as it is. Every
# control is refused by one limit or the other in every reading.
LOGITS_RMS_GIVEN_ROUTING_LIMIT = 1.95e-3


def controls(m):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong."""
    w, k = m['sliding_window'], m['num_experts_per_tok']
    ropes = m['rope_parameters']
    full, sliding = ropes['full_attention'], ropes['sliding_attention']
    return {
        'bfloat16': {'dtype': jnp.bfloat16},
        'window-%d' % (w - 1): {'window': w - 1},
        'window-%d' % (w + 1): {'window': w + 1},
        'no-window': {'window': None},
        'no-yarn': {'rope_parameters': dict(ropes, full_attention=dict(
            sliding, rope_theta=full['rope_theta']))},
        'yarn-no-attention-factor': {'rope_parameters': dict(
            ropes, full_attention=dict(full, attention_factor=1.0))},
        'yarn-on-sliding': {'rope_parameters': dict(
            ropes, sliding_attention=full)},
        'no-norm-topk': {'norm_topk_prob': False},
        'top-%d' % (k - 1): {'top_k': k - 1},
        'no-norm-weights': {'norm_weights': False},
    }


def ring_controls(scope, m, resumed_at, first_seq, block_size, ring):
    """name -> `ref.forward` arguments for a request that resumed at
    position `resumed_at` behind a first tenant whose whole sequence was
    `first_seq` (both start with the same `resumed_at` tokens): what its
    sliding layers found in the place of the prefix's rows."""
    kept = ref.forward(scope, m, first_seq, keep_window_kv=True)[2]
    at = np.arange(resumed_at)
    # the newest block of the first tenant in the column of each row's
    last = (len(first_seq) - 1) // block_size
    block = at // block_size
    later = (block + ring * ((last - block) // ring)) * block_size \
        + at % block_size
    written = later < len(first_seq)
    rows = {'ring-zeros': [], 'ring-later': []}
    for k, v in kept:
        k, v = np.asarray(k), np.asarray(v)
        rows['ring-zeros'].append((np.zeros_like(k[:resumed_at]),
                                   np.zeros_like(v[:resumed_at])))
        rows['ring-later'].append(tuple(
            np.where(written[:, None, None],
                     x[np.minimum(later, len(first_seq) - 1)], 0.0)
            for x in (k, v)))
    return {name: {'resumed': (resumed_at, r)} for name, r in rows.items()}


class Served(object):
    """One `GenerateEngine` with prefix sharing, driven pass by pass from
    here (never started), every dispatch's logits and choice of experts
    fetched beside its tokens."""

    def __init__(self, cfg, engine, scope):
        from paddle_tpu.serving.generate import (GenerateConfig,
                                                 GenerateEngine)
        self.eng = eng = GenerateEngine(GenerateConfig(
            model=cfg, slots=int(engine['slots']),
            max_len=int(engine['max_len']),
            block_size=int(engine['block_size']),
            num_blocks=int(engine['num_blocks']),
            prompt_buckets=list(engine['prompt_buckets']),
            prefix_sharing=True, eos_id=None, seed=0), scope=scope)
        eng.warmup()
        self.log = log = []

        def tapped(bound, kind):
            def call(feed, return_numpy=True):
                out = bound(feed, return_numpy=return_numpy)
                log.append((kind, int(np.asarray(feed['gen_len'])[0, 0])
                            if kind == 'prefill' else None,
                            np.asarray(out[1]),
                            [np.asarray(o) for o in out[2:]]))
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
                fetch_list=[v['tokens_and_load'], v['logits']]
                + v['topk_idx']), 'prefill')
        feed = {'gen_tokens': np.zeros((S, 1), 'int64'),
                'gen_pos': np.zeros((S, 1), 'int64')}
        feed.update(eng._tables_feed(np.zeros((S, mb), 'int64')))
        feed.update(eng._sample_feed(S))
        v = eng._step_vars
        eng._step_bound = tapped(eng.executor.bind(
            eng._step_prog, feed, scope=eng.scope,
            fetch_list=[v['tokens_and_load'], v['logits']]
            + v['topk_idx']), 'step')

    def serve(self, prompt, new_tokens, keep=False):
        """(greedy tokens, logits [new_tokens, V], per layer the experts
        chosen for the rows computed, the position the prefill resumed at)
        of one request alone in the engine. `keep`: stop before its last
        token, so that it stays resident (`finish` ends it)."""
        eng, log = self.eng, self.log
        del log[:]
        req = eng.submit(np.asarray(prompt, 'int64'),
                         max_new_tokens=new_tokens + (1 if keep else 0))
        eng._admit()
        slot, = [i for i, st in enumerate(eng._slots)
                 if st is not None and st.req is req]
        logits, chosen = [], None
        while len(req.tokens) < new_tokens:
            eng._step()
        rows = 0
        for kind, n, lg, idx in log:
            if kind == 'prefill':
                rows += n
                logits = [lg[0]]
                idx = [i[:n] for i in idx]
            else:
                logits.append(lg[slot])
                idx = [i[slot:slot + 1] for i in idx]
            chosen = idx if chosen is None else \
                [np.concatenate([c, i]) for c, i in zip(chosen, idx)]
        return (list(req.tokens)[:new_tokens], np.stack(logits)[:new_tokens],
                chosen, len(prompt) - rows, req)

    def finish(self, req):
        while req.finish_reason is None and req._error is None:
            self.eng._step()


def _refused(own_gap, given_gap):
    return bool(own_gap[0] > LOGITS_RMS_LIMIT
                or (given_gap is not None
                    and given_gap[0] > LOGITS_RMS_GIVEN_ROUTING_LIMIT))


def readings(scope, m, prompt, tokens, logits, routing, wrong):
    """One request's readings: `tokens[i]` is the argmax of `logits[i]`,
    the system's logits at position len(prompt) - 1 + i; `routing` the
    experts chosen for every row of prompt + tokens[:-1], a layer (the
    system's own: for a resumed request's shared rows, what the tenant that
    computed them chose). Against the reference's own routing, and GIVEN
    the computation's own (what is left is arithmetic). `wrong`: name ->
    `ref.forward` arguments, each control in the system's place."""
    k = m['num_experts_per_tok']
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    own = np.asarray(ref.logits(scope, m, seq, positions=pos))
    given = np.asarray(ref.logits(scope, m, seq, routing=routing,
                                  positions=pos))
    out = {'prompt_len': int(len(prompt)), 'rows': int(len(tokens)),
           'logits_vs_ref': logit_gap(logits, own),
           'logits_vs_ref_given_routing': logit_gap(logits, given),
           'greedy_margin_worst': float(ref.margins(own, tokens).max()),
           'controls': {}}
    out['refused_by_logits_rms'] = _refused(
        out['logits_vs_ref'], out['logits_vs_ref_given_routing'])
    for name, kw in wrong.items():
        hidden, its_scores = ref.forward(scope, m, seq, **kw)[:2]
        lg = np.asarray(ref.head(scope, m, hidden, pos,
                                 kw.get('norm_weights', True)))
        gap, given_gap = logit_gap(lg, own), None
        if not _refused(gap, None):
            # the control held to the reference GIVEN the control's own
            # choice of experts, as the system is above
            its = [np.argsort(-np.asarray(sc, np.float32), axis=1,
                              kind='stable')[:, :kw.get('top_k', k)]
                   for sc in its_scores]
            given_gap = logit_gap(lg, np.asarray(ref.logits(
                scope, m, seq, routing=its, positions=pos)))
        out['controls'][name] = {
            'logits_vs_ref': gap,
            'logits_vs_ref_given_routing': given_gap,
            'refused_by_logits_rms': _refused(gap, given_gap),
            # the control's own greedy tokens, held to the reference as
            # the driver holds the system's
            'greedy_margin_worst': float(ref.margins(
                own, lg.argmax(axis=1)).max())}
    return out


def compare(served, scope, m, first, second, new_tokens):
    """`first` served cold and `second` resumed behind it (two prompts
    that share a prefix of whole blocks): their `readings`, the second's
    with the ring controls among its controls."""
    e = served.eng.config
    toks1, lg1, ch1, at1, _ = served.serve(first, new_tokens)
    toks2, lg2, ch2, at2, _ = served.serve(second, new_tokens)
    seq1 = np.concatenate([np.asarray(first).reshape(-1), toks1[:-1]])
    one = dict(readings(scope, m, first, toks1, lg1, ch1, controls(m)),
               resumed_at=int(at1))
    ring = served.eng._books[0].ring
    two = readings(
        scope, m, second, toks2, lg2,
        # the shared rows' experts are the first tenant's choice
        [np.concatenate([a[:at2], b]) for a, b in zip(ch1, ch2)],
        dict(controls(m), **(ring_controls(
            scope, m, at2, seq1, e.block_size, ring) if at2 else {})))
    return one, dict(two, resumed_at=int(at2),
                     first_moved_on_blocks=int(
                         (len(seq1) - 1) // e.block_size
                         - at2 // e.block_size))


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import mellum2
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = mellum2.lm_config(m, int(tr['engine']['max_len']), False)
    scope, served = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights, and an engine's bound programs
        # keep theirs staged: the last seed's engine goes first
        served = None
        gc.collect()
        for name in mellum2.param_shapes(m):
            scope.drop(name)
        for name, value in mellum2.init_params(m, seed).items():
            scope.set(name, value)
        served = Served(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        # the longest first: it moves on past the prefix by the most
        for out in compare(served, scope, m, requests[-1]['prompt'],
                           requests[0]['prompt'], DECODE_STEPS):
            print(json.dumps(dict(out, seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
