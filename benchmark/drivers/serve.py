"""Driver of `kind: serve` traffic: the paged GenerateEngine behind
submit() / stream(), loaded by benchmark/traffic_gen.py. Every timing is
the client's, on the benchmark's own clock."""
import threading
import time

import numpy as np

from benchmark import traffic_gen
from benchmark.drivers.common import PROGRAM_SEED, compile_misses

REQUEST_DEADLINE_S = 300.0


def _hist(monitor, name):
    h = monitor.snapshot()['histograms'].get(name, {})
    return h.get('count', 0), h.get('sum', 0.0)


def _check(ctx, eng, m, ref, requests, new_tokens):
    """Two seeded requests, one from each end of the prompt range: greedy
    tokens under concurrency equal generate_once (the same compiled
    programs), and every token's reference logit is within LOGIT_MARGIN of
    the reference's maximum there."""
    by_len = sorted(requests, key=lambda r: len(r['prompt']))
    picked = [by_len[0], by_len[-1]]
    fillers = by_len[len(by_len) // 2:len(by_len) // 2 + 2]
    solo = [list(eng.generate_once(r['prompt'], max_new_tokens=new_tokens))
            for r in picked]
    eng.start()
    handles = [eng.submit(r['prompt'], max_new_tokens=new_tokens,
                          deadline_s=REQUEST_DEADLINE_S)
               for r in picked + fillers]
    got = [list(h.result(timeout=REQUEST_DEADLINE_S)) for h in handles]
    ok = True
    for i, r in enumerate(picked):
        same = got[i] == solo[i]
        margins = ref.greedy_margins(eng.scope, m, r['prompt'], got[i])
        worst = float(np.max(margins))
        ctx.note('check: prompt of %d tokens, %d greedy tokens; equal to '
                 'generate_once: %s; worst reference-logit gap %.4f of '
                 '(max - mean), allowed %.2f'
                 % (len(r['prompt']), len(got[i]), same, worst,
                    ref.LOGIT_MARGIN))
        ok = ok and same and len(got[i]) == new_tokens \
            and worst <= ref.LOGIT_MARGIN
    return ok


def run(ctx):
    from paddle_tpu import monitor
    from paddle_tpu.serving.generate import GenerateEngine, GenerateConfig

    m, tr, model = ctx.config, ctx.traffic, ctx.model
    e = tr['engine']
    if tr.get('sampling', 'greedy') != 'greedy':
        raise ValueError('serve driver samples greedily only')
    requests = traffic_gen.make_requests(tr, m['vocab_size'], ctx.seed)
    longest = max(len(r['prompt']) + r['max_new_tokens'] for r in requests)
    if longest > e['max_len']:
        raise ValueError('traffic asks for %d positions, max_len is %d'
                         % (longest, e['max_len']))

    from paddle_tpu import Scope
    scope = Scope()
    for name, value in model.init_params(m, ctx.seed).items():
        scope.set(name, value)
    eng = GenerateEngine(GenerateConfig(
        model=model.lm_config(m, int(e['max_len']), False),
        slots=int(e['slots']), max_len=int(e['max_len']),
        paged=bool(e['paged']), block_size=int(e['block_size']),
        num_blocks=int(e['num_blocks']),
        prompt_buckets=list(e['prompt_buckets']),
        prefix_sharing=bool(tr.get('shared_prefix_len', 0)),
        queue_cap=4096, default_deadline_s=REQUEST_DEADLINE_S,
        seed=PROGRAM_SEED), scope=scope)
    ctx.note('weights from the seed + engine built: %.1f s'
             % ctx.since_start())
    warm = eng.warmup()
    ctx.note('warmup: %r at %.1f s' % (warm, ctx.since_start()))

    correct = _check(ctx, eng, m, model.reference(), requests,
                     int(tr['check_new_tokens']))
    ctx.note('correctness check done at %.1f s' % ctx.since_start())

    def submit(prompt, max_new_tokens):
        with ctx.span('client'):
            return eng.submit(prompt, max_new_tokens=max_new_tokens,
                              deadline_s=REQUEST_DEADLINE_S)

    load = traffic_gen.Load(tr['arrival'], requests, submit, ctx.seed)
    load.start()
    load.wait_ramped()

    live = []                 # blocks in use, sampled in a traced run only

    def sample_live(stop):
        while not stop.wait(0.5):
            live.append(eng.stats()['blocks']['in_use'])

    if ctx.trace:
        ctx.start_trace()
        time.sleep(float(tr['trace_seconds']))
        ctx.stop_trace()
        stop_sampler = threading.Event()
        sampler = threading.Thread(target=sample_live, args=(stop_sampler,),
                                   name='bench-sampler', daemon=True)
        sampler.start()

    # The loop books a step's histogram observation, then its counters:
    # with the histograms read first here and last at the close, every
    # step the counters' interval holds is in the histograms' too, so a
    # count of steps over their number cannot pass 1 by the snapshots'
    # order (one booking split by the opening snapshots still can).
    h0 = {n: _hist(monitor, n)
          for n in ('decode_step_seconds', 'prefill_seconds')}
    before = monitor.counters()
    s0 = eng.stats()
    t0 = ctx.open_window()
    time.sleep(ctx.seconds)
    t1 = t0 + ctx.seconds
    s1 = eng.stats()
    delta = monitor.counter_delta(before)
    h1 = {n: _hist(monitor, n) for n in h0}
    load.stop()
    if ctx.trace:
        stop_sampler.set()
        sampler.join()
    eng.stop()                # ends the requests in flight
    load.join()

    w = traffic_gen.window_stats(load.records, t0, t1)
    misses = compile_misses(delta)
    ttft, itl = w['ttft_s'], w['itl_s']
    ctx.note('window: %d requests sent and ended (%d failed %r), %d tokens; ttft '
             'median %.1f ms over %d samples; itl median %.1f ms over %d '
             'samples; compile_cache_miss in the window: %d; generator '
             'lateness max %.1f ms'
             % (w['attempted'], w['failed'], w['errors'], w['tokens'],
                1e3 * (traffic_gen.percentile(ttft, 50) or 0), len(ttft),
                1e3 * (traffic_gen.percentile(itl, 50) or 0), len(itl),
                misses, 1e3 * max(load.lateness_s or [0.0])))
    correct = correct and misses == 0 and w['attempted'] > 0
    hist = {n: (h1[n][0] - h0[n][0], h1[n][1] - h0[n][1]) for n in h0}
    e2e = {'serve_tokens_per_s': w['tokens'] / w['window_s']}
    if ttft:
        e2e['ttft_p95_ms'] = 1e3 * traffic_gen.percentile(ttft, 95)
        e2e['ttft_mean_ms'] = 1e3 * float(np.mean(ttft))
        ctx.note('ttft ms: mean %.1f, p50 %.1f, p90 %.1f, p95 %.1f, p99 %.1f'
                 % ((e2e['ttft_mean_ms'],) + tuple(
                     1e3 * traffic_gen.percentile(ttft, q)
                     for q in (50, 90, 95, 99))))
    if itl:
        e2e['itl_p95_ms'] = 1e3 * traffic_gen.percentile(itl, 95)
    steps = s1['decode_steps'] - s0['decode_steps']
    active = min((s1['decode_tokens'] - s0['decode_tokens']) / steps,
                 float(e['slots'])) if steps else 0.0
    live_blocks = float(np.mean(live)) if live else None
    return {
        'correct': bool(correct), 'attempted': w['attempted'],
        'failed': w['failed'], 'end_to_end': e2e,
        'facts': {
            'kind': 'serve', 'window_s': w['window_s'],
            'tokens': w['tokens'], 'counters': delta, 'histograms': hist,
            'decode_steps': steps,
            'active_slots_mean': active, 'live_blocks_mean': live_blocks,
            'block_size': int(e['block_size']),
            'engine_stats': s1,
            'decode_bytes_per_step': (
                model.decode_bytes_per_step(
                    m, live_blocks * int(e['block_size']), active)
                if live and steps else None)},
    }
