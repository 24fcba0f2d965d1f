"""The controls of the JoyAI-LLM-Flash comparison, and the comparison itself
at a cell's own size on the chip (after olmoe_control.py, whose `Session`
drives the programs and whose `logit_gap` / `routing_gap` measure).

`controls(m)`: the plain reference put in the program's place and computed
WRONG in one way —

- `bfloat16`: parameters and activations in bfloat16, the nearest
  precision below the float32 the configuration states;
- `top-7`: one expert fewer a token;
- `not-renormalised`: the chosen experts' scores not divided by their sum;
- `unscaled`: `routed_scaling_factor` left out;
- `rotate-half`: RoPE over the pairs (i, i + dh/2) where the model
  rotates (2i, 2i + 1);
- `bias-in-weights`: the selection bias added to the WEIGHTS too, where it
  chooses only.

`drivers/serve.py _check` compares TOKENS (`LOGIT_MARGIN`), which most of
these pass: what tells them from the sound system is the rms over a
prompt's rows of (logits - the reference's), each row relative to its
(max - mean), against the reference's own routing (`LOGITS_RMS_LIMIT`) and
given the computation's own (`LOGITS_RMS_GIVEN_ROUTING_LIMIT`); the
readings are beside the limits and in PERF.md (PR 32).

    python3 benchmark/reference/joyai_control.py <config> <traffic> <seed>...

runs, for each seed, under the traffic file's engine parameters and
outside any timed window, the paged prefill and then `DECODE_STEPS` decode
steps through the latent cache of two seeded prompts (the shortest and the
longest of the seed's pool) through `Executor.run`, and prints one JSON
line a prompt: the routing's gap, the logits against the reference's full
forward (given the system's routing, and its own), `greedy_margins`'
reading, and the same for each control in the system's place.
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

from benchmark.reference import joyai_reference as ref     # noqa: E402
from benchmark.reference import olmoe_control              # noqa: E402
from benchmark.reference.olmoe_control import logit_gap    # noqa: E402

DECODE_STEPS = 40
# Two limits beside joyai_reference.LOGIT_MARGIN, both on the rms over a
# prompt's 41 rows of (logits - the reference's), each row relative to its
# (max - mean); readings on the v5e at the published widths (PERF.md, PR 32:
# 5 seeds x 2 prompts for the sound system, 3 x 2 for the controls' second
# reading). A computation that exceeds either is refused.
#
# Against the reference's OWN routing — what a wrong routing rule moves. The
# sound system 7.0e-3 to 1.34e-2 (the float32 programs' matmuls run at the
# TPU's default precision, which flips the 8th and 9th expert of 256 where
# they are nearly tied, in 7-9 % of the (row, layer) choices); one expert
# fewer 0.022 to 0.029, the scaling left out 0.035 to 0.043, rotate-half
# 0.104 to 0.114, not renormalised 0.229 to 0.239. The limit is a factor
# 1.35 above the largest sound and 1.23 under the smallest of those.
LOGITS_RMS_LIMIT = 1.8e-2
# Against the reference GIVEN the computation's own choice of experts —
# what is left is arithmetic. The sound system 2.01e-3 to 2.31e-3 (10
# prompts), the bfloat16 forward 3.23e-3 to 3.45e-3 (6 prompts): the limit
# is a factor 1.21 above the one and 1.15 under the other. (One expert
# fewer reads 0 here, as it must: the reference takes the 7.)
LOGITS_RMS_GIVEN_ROUTING_LIMIT = 2.8e-3
# NOT told from the sound system by either: the selection bias added to the
# weights (own routing 5.8e-4 to 6.7e-3, given 5.3e-4 to 7.2e-4 — a bias of
# N(0, 0.01) moves a weight of ~0.3 by 1-3 %, less than default precision
# moves the sound system). The benchmark's driver applies neither limit (it
# compares tokens only: PERF.md section 7).


def _weights(scores, chosen, bias, m, norm=True, scale=True,
             biased=False):
    w = jnp.where(chosen, scores + bias[None, :] if biased else scores, 0.0)
    if norm and m['norm_topk_prob']:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * (float(m['routed_scaling_factor']) if scale else 1.0)


def controls(m):
    """name -> the keyword arguments of `ref.logits` that make it wrong."""
    return {
        'bfloat16': {'dtype': jnp.bfloat16},
        'top-%d' % (m['num_experts_per_tok'] - 1):
            {'top_k': m['num_experts_per_tok'] - 1},
        'not-renormalised': {'weights': functools.partial(
            _weights, m=m, norm=False)},
        'unscaled': {'weights': functools.partial(_weights, m=m,
                                                  scale=False)},
        'rotate-half': {'rope': ref.rope_rotate_half},
        'bias-in-weights': {'weights': functools.partial(_weights, m=m,
                                                         biased=True)},
    }


class Session(olmoe_control.Session):
    """olmoe_control's Session on the pools THIS model declares (one pool
    of latent rows, no V)."""

    def __init__(self, cfg, engine, scope):
        from paddle_tpu import unique_name
        from paddle_tpu.executor import Executor
        from paddle_tpu.framework import Program, TPUPlace, program_guard
        from paddle_tpu.models import transformer as T
        self.cfg, self.e, self.scope = cfg, engine, scope
        self.exe = Executor(TPUPlace(0))
        self.max_blocks = engine['max_len'] // engine['block_size']
        pool = (engine['num_blocks'], cfg.n_layer, engine['block_size'],
                cfg.kv_width)
        for name in T.kv_cache_names(cfg):
            scope.set(name, jnp.zeros(pool, jnp.float32))
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


def _refused(own_gap, given_gap):
    return bool(own_gap[0] > LOGITS_RMS_LIMIT
                or given_gap[0] > LOGITS_RMS_GIVEN_ROUTING_LIMIT)


def routing_gap(chosen, scores, bias, top_k):
    """olmoe_control.routing_gap on what this router chooses by: the
    scores plus the selection bias."""
    return olmoe_control.routing_gap(chosen, scores + bias[None, :], top_k)


def compare(scope, m, prompt, tokens, logits, chosen):
    """The readings of one prompt: `tokens[i]` is the argmax of
    `logits[i]`, the system's logits at position len(prompt) - 1 + i."""
    k = m['num_experts_per_tok']
    seq = np.concatenate([np.asarray(prompt).reshape(-1), tokens[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq))
    scores = ref.router_scores(scope, m, seq, routing=chosen)
    biases = [np.asarray(scope.get('layer_%d.moe.router.bias' % i))
              for i in range(m['first_k_dense_replace'],
                             m['num_hidden_layers'])]
    gaps = [routing_gap(c, s, b, k)
            for c, s, b in zip(chosen, scores, biases)]
    given = np.asarray(ref.logits(scope, m, seq, routing=chosen,
                                  positions=pos))
    own = np.asarray(ref.logits(scope, m, seq, positions=pos))
    out = {
        'prompt_len': int(len(prompt)), 'rows': int(len(tokens)),
        'routing_rows_not_ref_top_k': float(np.mean([g[0] for g in gaps])),
        'routing_worst_score_shortfall': max(g[1] for g in gaps),
        'logits_vs_ref_given_routing': logit_gap(logits, given),
        'logits_vs_ref_own_routing': logit_gap(logits, own),
        'greedy_margin_worst': float(ref.margins(own, tokens).max()),
        'controls': {}}
    out['refused_by_logits_rms'] = _refused(
        out['logits_vs_ref_own_routing'], out['logits_vs_ref_given_routing'])
    for name, kw in controls(m).items():
        hidden, its_scores = ref.forward(scope, m, seq, **kw)
        wrong = np.asarray(ref.head(scope, m, hidden, pos))
        gap = logit_gap(wrong, own)
        # the control held to the reference GIVEN the control's own choice
        # of experts, as the system is above: what is left is arithmetic
        its_routing = [np.argsort(-(np.asarray(s, np.float32) + b[None, :]),
                                  axis=1, kind='stable')[
                                      :, :kw.get('top_k', k)]
                       for s, b in zip(its_scores, biases)]
        given_gap = logit_gap(wrong, np.asarray(ref.logits(
            scope, m, seq, routing=its_routing, positions=pos)))
        out['controls'][name] = {
            'logits_vs_ref_own_routing': gap,
            'logits_vs_ref_given_routing': given_gap,
            'refused_by_logits_rms': _refused(gap, given_gap),
            # the control's own greedy tokens, held to the reference as
            # the driver holds the system's
            'greedy_margin_worst': float(ref.margins(
                own, wrong.argmax(axis=1)).max())}
    return out


def main(argv):
    from benchmark import traffic_gen
    from benchmark.models import joyai
    from paddle_tpu import Scope
    with open(argv[0]) as f:
        m = json.load(f)
    with open(argv[1]) as f:
        tr = json.load(f)
    cfg = joyai.lm_config(m, int(tr['engine']['max_len']), False)
    scope, session = Scope(), None
    for seed in [int(s) for s in argv[2:]]:
        # the chip holds one set of weights: the last seed's go first
        for name in joyai.param_shapes(m):
            scope.drop(name)
        for name, value in joyai.init_params(m, seed).items():
            scope.set(name, value)
        session = session or Session(cfg, tr['engine'], scope)
        requests = sorted(traffic_gen.make_requests(tr, m['vocab_size'],
                                                    seed),
                          key=lambda r: len(r['prompt']))
        for r in (requests[0], requests[-1]):
            got = session.generate(r['prompt'], DECODE_STEPS)
            print(json.dumps(dict(compare(scope, m, r['prompt'], *got),
                                  seed=seed)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
