"""The controls of the AI21-Jamba2-3B comparison, and the comparison itself
at a cell's own size on the chip (after kexaone_control.py; `logit_gap` is
olmoe_control's).

`controls(...)`: the plain reference put in the program's place and
computed WRONG in one way —

- `chunk-edge`: the recurrent state not carried across the edge of the
  prompt's first chunk: the second chunk starts from zeros (a prompt of
  one chunk has no such edge: the control does not apply);
- `stale-state`: the state another sequence left in the slot's row stands
  in the zeros' place before position 0 (no reset for a new tenant);
- `pad-rows`: the pad rows of the prompt's last bucket walked by the
  recurrence and the convolution like real rows, before the first decode
  step (a prompt that fills its bucket has none: does not apply);
- `no-inner-norms`: Jamba's `dt` / `B` / `C` norms left out (plain
  Mamba-1);
- `no-D`: the `D * u` skip term left out;
- `no-conv-bias`: the convolution's bias left out;
- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `bfloat16-state`: the forward in float32, the recurrent state alone
  rounded to bfloat16 after every position;
- `rope-on-attention`: the two attention layers rotated as a RoPE model's
  (theta 10 000); the model has no positional encoding.

`drivers/serve.py _check` compares TOKENS (`jamba_reference.LOGIT_MARGIN`);
what tells a control that serves nearly the sound system's tokens from the
sound system is on LOGITS: the rms over a prompt's rows of (logits - the
reference's), each row relative to its (max - mean). Two limits: against
the reference as it is, matmuls at "highest" (`LOGITS_RMS_LIMIT`), and
GIVEN THE MATMULS' PRECISION (`LOGITS_RMS_GIVEN_PRECISION_LIMIT`): the
programs' float32 matmuls run with bfloat16 operands on the TPU, which
through 28 layers moves the logits by more than a stale state under a long
prompt or a state kept in bfloat16 does — so the system, and each control,
is also held to the reference computed with ITS matmuls that way
(`matmul_precision='default'`), which takes that rounding out of both
sides and leaves the fault. The readings are beside the limits and in
PERF.md (PR 43).

    python3 benchmark/reference/jamba_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters and
outside any timed window, the shortest and the longest prompt of the
seed's pool through `Executor.run` on the programs the engine builds — the
SAME row of the state pools for every prompt, so each starts on the last
one's state; chunks of the widest bucket, each resuming from the row —
then `DECODE_STEPS` decode steps, and prints one JSON line a prompt: the
logits against the reference's full forward, `greedy_margins`' reading,
and the same for each control in the system's place.
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

from benchmark.reference import jamba_reference as ref     # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 24
# Two limits beside jamba_reference.LOGIT_MARGIN, on the rms over a
# prompt's rows of (logits - the reference's), each row relative to its
# (max - mean). A computation that exceeds one is refused. The benchmark's
# driver applies neither (it compares tokens only: PERF.md section 7).
# Readings on the v5e at the published widths (PERF.md, PR 43; 3 seeds x 2
# prompts -- 32 and 1 024 tokens, the second two chunks -- x 25 rows, six
# readings each).
#
# Against the reference as it is (matmuls at "highest"). The sound system
# 0.00808 to 0.00845: its float32 matmuls run with bfloat16 operands on the
# TPU, through 28 layers. The bfloat16 forward 0.01442 to 0.01524; the
# second chunk from zeros 0.0185 to 0.0237; a stale state under a 32-token
# prompt 0.099 to 0.102, RoPE on the attention layers there 0.029 to 0.030;
# the pad rows walked 0.258 to 0.304; the inner norms, D or the
# convolution's bias left out 0.19 to 0.33. The limit is a factor 1.30
# above the largest sound reading and 1.31 under the bfloat16 forward's
# smallest. NOT told by this limit, because the sound system's own rounding
# is larger: the state kept in bfloat16 (0.0013 to 0.0045), a stale state
# under a 1 024-token prompt (0.0049 to 0.0061: 1 000 positions on, only
# the slowest channels remember) and RoPE there (0.0092 to 0.0095).
LOGITS_RMS_LIMIT = 1.1e-2
# GIVEN the matmuls' precision -- both sides' large matmuls with bfloat16
# operands. The sound system 0.00323 to 0.00387 (what is left: the order of
# the sums, and the kernels' own products at "highest"). The state kept in
# bfloat16 0.00575 to 0.00745, a stale state under the long prompt 0.00752
# to 0.00829, RoPE there 0.00927 to 0.00964, the bfloat16 forward 0.0143 to
# 0.0151. Most of a small fault's reading here is not the fault's own size
# (the bfloat16 state's is 0.0013 against the exact reference) but that a
# perturbed computation rounds its operands differently from then on: the
# two sides' rounding stops cancelling. The limit is a factor 1.22 above
# the largest sound reading and 1.22 under the smallest control's. Every
# control is refused by one limit or the other in every one of its
# readings, and the sound system by neither.
LOGITS_RMS_GIVEN_PRECISION_LIMIT = 4.7e-3
ROPE_THETA = 10000.0


def controls(prompt_len, buckets, stale):
    """name -> the keyword arguments of `ref.forward` that make the
    reference wrong, for a prompt of `prompt_len` rows prefilled through
    `buckets`; `stale` the per-layer states another sequence left. A
    control that does not apply to the prompt is left out."""
    wide = max(buckets)
    out = {}
    if prompt_len > wide:
        out['chunk-edge'] = {'zero_state_at': wide}
    out['stale-state'] = {'init_states': stale}
    last = prompt_len - (prompt_len - 1) // wide * wide
    pads = min(b for b in buckets if b >= last) - last
    if pads:
        out['pad-rows'] = {'pad_rows': (prompt_len, pads)}
    out.update({
        'no-inner-norms': {'inner_norms': False},
        'no-D': {'skip_d': True},
        'no-conv-bias': {'conv_bias': False},
        'bfloat16': {'dtype': jnp.bfloat16},
        'bfloat16-state': {'state_dtype': jnp.bfloat16},
        'rope-on-attention': {'rope_theta': ROPE_THETA}})
    return out


class Session(object):
    """Prompts through the paged prefill (in chunks of the widest bucket)
    and the decode step, run by `Executor.run` on the programs
    `GenerateEngine` builds, as slot 0: blocks 1.. of the K/V pools and
    row 1 of the state pools (block 0 and row 0 are the trash)."""

    def __init__(self, cfg, engine, scope):
        from paddle_tpu import unique_name
        from paddle_tpu.executor import Executor
        from paddle_tpu.framework import Program, TPUPlace, program_guard
        from paddle_tpu.models import transformer as T
        self.cfg, self.e, self.scope = cfg, engine, scope
        self.exe = Executor(TPUPlace(0))
        self.max_blocks = engine['max_len'] // engine['block_size']
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

    def _run(self, key, feed, n, blocks):
        main, v = self.progs[key]
        btab = np.zeros((n, self.max_blocks), 'int64')
        btab[0, :len(blocks)] = blocks
        srow = np.zeros((n, 1), 'int64')
        srow[0] = 1
        feed.update({'gen_btab': btab, 'gen_srow': srow,
                     'gen_temp': np.zeros((n, 1), 'float32'),
                     'gen_topk': np.zeros((n, 1), 'int64'),
                     'gen_topp': np.zeros((n, 1), 'float32'),
                     'gen_u': np.zeros((n, 1), 'float32')})
        return np.asarray(self.exe.run(main, feed=feed, scope=self.scope,
                                       fetch_list=[v['logits']])[0])

    def generate(self, prompt, steps):
        """(greedy tokens, logits [1 + steps, V]) of the prompt prefilled
        and `steps` decode steps."""
        e = self.e
        prompt = np.asarray(prompt, 'int64').reshape(-1)
        steps = min(steps, e['max_len'] - len(prompt))
        blocks = 1 + np.arange(-(-(len(prompt) + steps) // e['block_size']))
        wide = max(e['prompt_buckets'])
        off = 0
        while off < len(prompt):
            n = min(wide, len(prompt) - off)
            b = min(x for x in e['prompt_buckets'] if x >= n)
            padded = np.zeros((1, b), 'int64')
            padded[0, :n] = prompt[off:off + n]
            pos = np.clip(off + np.arange(b), 0, e['max_len'] - 1)[None]
            lg = self._run(b, dict(gen_prompt=padded,
                                   gen_pos=pos.astype('int64'),
                                   gen_len=np.array([[n]], 'int64')), 1,
                           blocks)
            off += n
        logits, tokens = [lg[0]], [int(np.argmax(lg[0]))]
        S = e['slots']
        for step in range(steps):
            toks, posf = np.zeros((S, 1), 'int64'), np.zeros((S, 1), 'int64')
            toks[0], posf[0] = tokens[-1], len(prompt) + step
            lg = self._run('step', dict(gen_tokens=toks, gen_pos=posf), S,
                           blocks)
            logits.append(lg[0])
            tokens.append(int(np.argmax(lg[0])))
        return tokens, np.stack(logits)


def _refused(gap, given_gap):
    return bool(gap[0] > LOGITS_RMS_LIMIT
                or given_gap[0] > LOGITS_RMS_GIVEN_PRECISION_LIMIT)


def readings(scope, m, buckets, prompt, tokens, logits, stale):
    """One prompt's readings: `tokens[i]` is the argmax of `logits[i]`,
    the system's logits at position len(prompt) - 1 + i; `stale` the
    states the `stale-state` control starts from. Against the reference
    as it is, and GIVEN the matmuls' precision (both sides' matmuls with
    bfloat16 operands: what is left is the fault)."""
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    own = np.asarray(ref.logits(scope, m, seq, positions=pos))
    given = np.asarray(ref.logits(scope, m, seq, positions=pos,
                                  matmul_precision='default'))
    out = {'prompt_len': int(len(prompt)), 'rows': int(len(tokens)),
           'logits_vs_ref': logit_gap(logits, own),
           'logits_vs_ref_given_precision': logit_gap(logits, given),
           'greedy_margin_worst': float(ref.margins(own, tokens).max()),
           'controls': {}}
    out['refused_by_logits_rms'] = _refused(
        out['logits_vs_ref'], out['logits_vs_ref_given_precision'])
    for name, kw in controls(len(prompt), buckets, stale).items():
        wrong = np.asarray(ref.logits(scope, m, seq, positions=pos, **kw))
        gap = logit_gap(wrong, own)
        # the control with its matmuls as the programs run theirs, held to
        # the reference computed the same way, as the system is above
        given_gap = logit_gap(np.asarray(ref.logits(
            scope, m, seq, positions=pos, matmul_precision='default',
            **kw)), given)
        out['controls'][name] = {
            'logits_vs_ref': gap,
            'logits_vs_ref_given_precision': given_gap,
            'refused_by_logits_rms': _refused(gap, given_gap),
            # the control's own greedy tokens, held to the reference as
            # the driver holds the system's
            'greedy_margin_worst': float(ref.margins(
                own, wrong.argmax(axis=1)).max())}
    return out


def compare(cfg, engine, scope, m, prompt, new_tokens, stale, session=None):
    """`prompt` through the pools and `new_tokens` decode steps: its
    `readings`."""
    session = session or Session(cfg, engine, scope)
    tokens, logits = session.generate(prompt, new_tokens)
    return readings(scope, m, engine['prompt_buckets'], prompt, tokens,
                    logits, stale)


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import jamba
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = jamba.lm_config(m, int(tr['engine']['max_len']), False)
    scope, session = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in jamba.param_shapes(m):
            scope.drop(name)
        for name, value in jamba.init_params(m, seed).items():
            scope.set(name, value)
        session = session or Session(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        # the state the `stale-state` control starts from: what a tenant
        # of median length leaves behind
        stale = ref.forward(scope, m,
                            requests[len(requests) // 2]['prompt'])[1]
        for r in (requests[0], requests[-1]):
            print(json.dumps(dict(compare(
                cfg, tr['engine'], scope, m, r['prompt'], DECODE_STEPS,
                stale, session), seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
