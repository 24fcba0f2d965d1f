"""The controls of the LFM2-8B-A1B comparison, and the comparison itself at
a cell's own size on the chip (after joyai_control.py; `logit_gap` is
olmoe_control's).

`controls(m, shared_len)`: the plain reference put in the program's place
and computed WRONG in one way —

- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `zero-tail-resume`: the positions from `shared_len` on see the
  convolution layers' `g` of the positions before it as zero — a suffix
  behind a prefix hit resumed from a zero tail instead of the shared
  block's entry (`compare` reads it at the first row behind the prefix
  as well, where it is largest);
- `kv-head-modulo`: query head h reads K/V head h % 8 where the model
  reads h // 4;
- `no-head-norm`: the per-head q and k norms left out;
- `top-3`: one expert fewer a token;
- `bias-in-weights`: `expert_bias` added to the WEIGHTS too, where it
  chooses only;
- `untied-head`: fresh N(0, 0.02) weights in the head's place, where the
  model's head is its embedding table.

`drivers/serve.py _check` compares TOKENS (`lfm2_reference.LOGIT_MARGIN`);
what tells a control that serves nearly the sound system's tokens from the
sound system is the rms over a prompt's rows of (logits - the
reference's), each row relative to its (max - mean): against the
reference's own routing (`LOGITS_RMS_LIMIT`), given the computation's own
(`LOGITS_RMS_GIVEN_ROUTING_LIMIT`), and at the first row behind the shared
prefix (`ROW_BEHIND_PREFIX_LIMIT`), with the readings beside the limits
and in PERF.md (PR 35).

    python3 benchmark/reference/lfm2_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters and
outside any timed window, the shortest and the longest prompt of the
seed's pool through `Executor.run` on the programs the engine builds:
WHOLE (the miss path: chunks of the widest bucket, each resuming from the
tail the last one left in the pool), then the same prompt RESUMED at
`shared_prefix_len` behind the whole run's blocks (a prefix hit's path),
each followed by `DECODE_STEPS` decode steps — and prints one JSON line a
prompt: the logits against the reference's full forward, `greedy_margins`'
reading, and the same for each control in the system's place.
"""
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from benchmark.reference import lfm2_reference as ref      # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# Three limits beside lfm2_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (logits - the reference's), each row relative to its
# (max - mean); readings on the v5e at the published widths (PERF.md, PR
# 35: 3 seeds x 2 prompts x 25 rows). A computation that exceeds one is
# refused. The benchmark's driver applies none of them (it compares tokens
# only: PERF.md section 7).
#
# Against the reference's OWN routing — what a wrong rule moves. The sound
# system 0.0160 to 0.0216 (default-precision matmuls flip the 4th and 5th
# expert of 32 where they are nearly tied); K/V head h % 8 0.0283 to
# 0.0328, one expert fewer 0.0562 to 0.0608, the head untied 0.321 to
# 0.334. The limit is a factor 1.16 above the largest sound and 1.13 under
# the smallest of those.
LOGITS_RMS_LIMIT = 2.5e-2
# GIVEN the computation's own choice of experts — what is left is
# arithmetic. The sound system 0.0034 to 0.0035, the bfloat16 forward
# 0.0054 to 0.0057 (K/V head h % 8: 0.0123 to 0.0131): a factor 1.26 above
# the one and 1.23 under the other.
LOGITS_RMS_GIVEN_ROUTING_LIMIT = 4.4e-3
# The FIRST ROW BEHIND the shared prefix (`compare`: a prompt of
# shared_len + 1 tokens resumed behind the shared blocks), against the
# reference's own routing: the sound system 0.0030 to 0.0464, the
# reference resumed from a zero tail 0.274 to 0.318 — at a late row the
# same control reads 0.0002 to 0.0079, under the sound system, because a
# tail moves two positions a layer and reaches a late row through two keys
# of thousands. A factor 2.6 above the one and 2.3 under the other.
ROW_BEHIND_PREFIX_LIMIT = 0.12
# NOT told from the sound system by any of the three: the per-head q/k
# norms left out (own 0.0046 to 0.0176, given 0.0016 to 0.0018: with norm
# weights 1 and random projections a head's norm is nearly the same number
# in every row, the norm a rescaling of the scores, and attention over 4 k
# random keys is near uniform either way; on the CPU in float32 the same
# control is 40 times outside the tier-1 tolerance), and `expert_bias`
# added to the weights (own 0.0011 to 0.0143, given 0.0010 to 0.0012: a
# bias of N(0, 0.01) moves a weight of ~0.25 by a few per cent, less than
# default precision moves the sound system — JoyAI's finding again).


def _weights(scores, chosen, bias, m, biased=False):
    w = jnp.where(chosen, scores + bias[None, :] if biased else scores, 0.0)
    if m['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * float(m['routed_scaling_factor'])


def controls(m, shared_len):
    """name -> the keyword arguments of `control_logits` that make the
    reference wrong."""
    h, hkv = m['num_attention_heads'], m['num_key_value_heads']
    return {
        'bfloat16': {'dtype': jnp.bfloat16},
        'zero-tail-resume': {'zero_tail_at': int(shared_len)},
        'kv-head-modulo': {'kv_head_of': np.arange(h) % hkv},
        'no-head-norm': {'head_norm': False},
        'top-%d' % (m['num_experts_per_tok'] - 1):
            {'top_k': m['num_experts_per_tok'] - 1},
        'bias-in-weights': {'weights': functools.partial(_weights, m=m,
                                                         biased=True)},
        'untied-head': {'head_table': 'fresh'},
    }


def _fresh_table(m):
    return 0.02 * jax.random.normal(
        jax.random.PRNGKey(7), (m['vocab_size'], m['hidden_size']),
        jnp.float32)


def control_logits(scope, m, seq, kw, positions=None, with_scores=False):
    """`ref.logits` under one of `controls`' entries; `with_scores`: and
    the control's own router scores, an expert layer."""
    kw = dict(kw)
    table = kw.pop('head_table', None)
    hidden, scores = ref.forward(scope, m, seq, **kw)
    lg = ref.head(scope, m, hidden, positions,
                  _fresh_table(m) if table == 'fresh' else table)
    return (lg, scores) if with_scores else lg


class Session(object):
    """Prompts through the paged prefill (in chunks of the widest bucket,
    from any block edge on) and the decode step, run by `Executor.run` on
    the programs `GenerateEngine` builds, on block tables the caller
    gives (block 0 is the trash block)."""

    def __init__(self, cfg, engine, scope):
        from paddle_tpu import unique_name
        from paddle_tpu.executor import Executor
        from paddle_tpu.framework import Program, TPUPlace, program_guard
        from paddle_tpu.models import transformer as T
        self.cfg, self.e, self.scope = cfg, engine, scope
        self.exe = Executor(TPUPlace(0))
        self.max_blocks = engine['max_len'] // engine['block_size']
        for name, shape in T.kv_cache_shapes(
                cfg, engine['num_blocks'], engine['block_size']).items():
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
                engine['block_size'], self.max_blocks))

    def _run(self, key, feed, n):
        main, v = self.progs[key]
        feed.update({'gen_temp': np.zeros((n, 1), 'float32'),
                     'gen_topk': np.zeros((n, 1), 'int64'),
                     'gen_topp': np.zeros((n, 1), 'float32'),
                     'gen_u': np.zeros((n, 1), 'float32')})
        out = self.exe.run(main, feed=feed, scope=self.scope,
                           fetch_list=[v['logits']] + v['topk_idx'])
        return np.asarray(out[0]), [np.asarray(o) for o in out[1:]]

    def table(self, blocks):
        t = np.zeros((1, self.max_blocks), 'int64')
        t[0, :len(blocks)] = blocks
        return t

    def generate(self, prompt, steps, table, off=0):
        """(greedy tokens, logits [1 + steps, V], per expert layer the
        experts chosen for the rows computed [T - off + steps, k]) of the
        prompt prefilled from position `off` on (what lies before it is
        in `table`'s blocks already) and `steps` decode steps."""
        e = self.e
        prompt = np.asarray(prompt, 'int64').reshape(-1)
        wide = max(e['prompt_buckets'])
        chosen = None
        while off < len(prompt):
            n = min(wide, len(prompt) - off)
            b = min(x for x in e['prompt_buckets'] if x >= n)
            padded = np.zeros((1, b), 'int64')
            padded[0, :n] = prompt[off:off + n]
            pos = np.clip(off + np.arange(b), 0, e['max_len'] - 1)[None]
            lg, idx = self._run(b, {
                'gen_prompt': padded, 'gen_pos': pos.astype('int64'),
                'gen_btab': table, 'gen_len': np.array([[n]], 'int64')}, 1)
            chosen = [i[:n] for i in idx] if chosen is None else \
                [np.concatenate([c, i[:n]]) for c, i in zip(chosen, idx)]
            off += n
        logits, tokens = [lg[0]], [int(np.argmax(lg[0]))]
        S = e['slots']
        for step in range(min(steps, e['max_len'] - len(prompt))):
            toks, posf = np.zeros((S, 1), 'int64'), np.zeros((S, 1), 'int64')
            btab = np.zeros((S, self.max_blocks), 'int64')
            toks[0], posf[0], btab[0] = tokens[-1], len(prompt) + step, \
                table[0]
            lg, idx = self._run('step', {'gen_tokens': toks, 'gen_pos': posf,
                                         'gen_btab': btab}, S)
            logits.append(lg[0])
            chosen = [np.concatenate([c, i[:1]]) for c, i in zip(chosen,
                                                                 idx)]
            tokens.append(int(np.argmax(lg[0])))
        return tokens, np.stack(logits), chosen


def _refused(own_gap, given_gap):
    return bool(own_gap[0] > LOGITS_RMS_LIMIT
                or given_gap[0] > LOGITS_RMS_GIVEN_ROUTING_LIMIT)


def readings(scope, m, prompt, tokens, logits, chosen, shared_len):
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
              for i in range(m['num_dense_layers'], m['num_hidden_layers'])]
    out = {'prompt_len': int(len(prompt)), 'rows': int(len(tokens)),
           'logits_vs_ref': logit_gap(logits, own),
           'logits_vs_ref_given_routing': logit_gap(logits, given),
           'greedy_margin_worst': float(ref.margins(own, tokens).max()),
           'controls': {}}
    out['refused_by_logits_rms'] = _refused(
        out['logits_vs_ref'], out['logits_vs_ref_given_routing'])
    for name, kw in controls(m, shared_len).items():
        wrong, its_scores = control_logits(scope, m, seq, kw, pos,
                                           with_scores=True)
        wrong = np.asarray(wrong)
        gap = logit_gap(wrong, own)
        # the control held to the reference GIVEN the control's own choice
        # of experts, as the system is above
        its_routing = [np.argsort(-(np.asarray(sc, np.float32) + b[None, :]),
                                  axis=1, kind='stable')[
                                      :, :kw.get('top_k', k)]
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


def compare(cfg, engine, scope, m, prompt, new_tokens, shared_len,
            session=None):
    """`prompt` whole through blocks 1.., then RESUMED at `shared_len`
    behind those blocks (the rest in fresh ones): the whole run's
    `readings`, and the resumed run's logits against the reference and
    against the whole run's."""
    session = session or Session(cfg, engine, scope)
    bs = engine['block_size']
    n_blocks = -(-min(len(prompt) + new_tokens, engine['max_len']) // bs)
    first = 1 + np.arange(n_blocks)
    tokens, logits, chosen = session.generate(prompt, new_tokens,
                                              session.table(first))
    out = readings(scope, m, prompt, tokens, logits, chosen, shared_len)
    kept = shared_len // bs
    second = np.concatenate([first[:kept],
                             1 + n_blocks + np.arange(n_blocks - kept)])
    tokens2, logits2, _ = session.generate(prompt, new_tokens,
                                           session.table(second),
                                           off=kept * bs)
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens2[:-1]])
    own = np.asarray(ref.logits(
        scope, m, seq, positions=np.arange(len(prompt) - 1, len(seq))))
    out['resumed_logits_vs_ref'] = logit_gap(logits2, own)
    out['resumed_tokens_equal_whole'] = tokens2 == tokens
    # A zero tail moves the rows RIGHT behind the prefix (two positions a
    # convolution layer) and reaches a late row only through attention's
    # share of two keys among thousands: the reading that tells it is the
    # first row behind the prefix, a prompt of shared_len + 1 tokens
    # resumed the same way.
    short = np.asarray(prompt).reshape(-1)[:kept * bs + 1]
    third = np.concatenate([first[:kept], [2 * n_blocks + 1]])
    _, logits3, _ = session.generate(short, 0, session.table(third),
                                     off=kept * bs)
    at = [kept * bs]
    own3 = np.asarray(ref.logits(scope, m, short, positions=at))
    zero3 = np.asarray(ref.logits(scope, m, short, positions=at,
                                  zero_tail_at=kept * bs))
    out['row_behind_prefix_vs_ref'] = logit_gap(logits3, own3)
    out['row_behind_prefix_zero_tail_vs_ref'] = logit_gap(zero3, own3)
    out['refused_by_row_behind_prefix'] = bool(
        out['row_behind_prefix_vs_ref'][0] > ROW_BEHIND_PREFIX_LIMIT)
    return out


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import lfm2
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = lfm2.lm_config(m, int(tr['engine']['max_len']), False)
    scope, session = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in lfm2.param_shapes(m):
            scope.drop(name)
        for name, value in lfm2.init_params(m, seed).items():
            scope.set(name, value)
        session = session or Session(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        for r in (requests[0], requests[-1]):
            print(json.dumps(dict(compare(
                cfg, tr['engine'], scope, m, r['prompt'], DECODE_STEPS,
                int(tr['shared_prefix_len']), session), seed=seed)),
                flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
