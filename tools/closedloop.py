"""What a closed-loop serve cell's TRAFFIC does to its window, before any
chip time: the spread of tokens/s and of the 95th-percentile token gap
over seeds.

    python tools/closedloop.py <traffic.json> --step-ms 16.1 \\
        --chunk-ms 512=60.6,256=48,128=42 [--live-ms 0] [--seconds 50] \\
        [--seeds 60] [--all-chunks-at-once]

A model of `GenerateEngine`'s loop on the host, with
`benchmark/traffic_gen.py`'s own order of the requests a seed: the device
runs decode steps (`--step-ms`, plus `--live-ms` a 100 k live tokens) and
prefill dispatches (`--chunk-ms`, a cost a bucket, read off a traced run:
`gapreport`'s ms a run by module); a client sends its next request when
the last token of the one before arrived; the window opens when every
client has had a first token. A prompt wider than the widest bucket takes
one chunk a pass and no other admission starts meanwhile (the engine
since PR 41), or, `--all-chunks-at-once`, the whole prompt in one gap.
Arithmetic about the traffic, no device number: the costs are what you
give it. On K-EXAONE's cell it reproduced 24 chip runs seed by seed
(r = 0.989 on tokens/s, both off-mode seeds of the token gap's tail;
PERF.md 6, PR 41) — use it to see whether a cell can be admitted at a
window's length before spending the chip on twelve runs.
"""
import argparse
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark import traffic_gen                           # noqa: E402


def lengths(traffic, seed):
    """(prompt lengths, output lengths) in the order `make_requests`
    gives the pool for `seed`, without drawing a token (tier-1 holds the
    two to each other)."""
    n = int(traffic['pool_size'])
    lanes = int(traffic['arrival']['clients'])
    pairing = traffic_gen._rng(traffic.get('pairing_seed', 0), 4)
    plen = traffic_gen.length_pool(traffic['prompt_len'], n)
    olen = pairing.permutation(
        traffic_gen.length_pool(traffic['output_len'], n))
    rng = traffic_gen._rng(seed, 1)
    order = np.empty(n, dtype=np.int64)
    for lane, share in zip(rng.permutation(lanes), range(lanes)):
        order[lane::lanes] = rng.permutation(np.arange(share, n, lanes))
    return plen[order], olen[order]


def chunks(length, buckets):
    """The buckets of a prompt's dispatches (`_prefill_dispatch`)."""
    out = []
    while length > buckets[-1]:
        out.append(buckets[-1])
        length -= buckets[-1]
    out.append(next(b for b in buckets if length <= b))
    return out


def run(traffic, seed, step_s, chunk_s, live_s=0.0, seconds=50.0,
        chunk_a_pass=True):
    """{'tokens_per_s', 'itl_p95_ms', 'admissions'} of one window."""
    plen, olen = lengths(traffic, seed)
    n, clients = len(plen), int(traffic['arrival']['clients'])
    stagger = float(traffic['arrival'].get('stagger_s', 0.0))
    buckets = sorted(chunk_s)
    nxt = list(range(clients))
    waiting = [(c * stagger, c) for c in range(clients)]
    slots, last, started = {}, {}, set()
    tokens, gaps, admitted = [], [], []
    under_way = None
    now, t0 = 0.0, None

    def resident(c, i):
        slots[c] = [int(olen[i]) - 1, int(plen[i]) + 1]
        last[c] = now
        tokens.append(now)
        admitted.append(now)
        started.add(c)

    while t0 is None or now <= t0 + seconds:
        waiting.sort()
        if under_way is not None:
            c, rest, i = under_way
            now += chunk_s[rest.pop(0)]
            under_way = (c, rest, i) if rest else resident(c, i)
        else:
            while waiting and waiting[0][0] <= now:
                _, c = waiting.pop(0)
                i = nxt[c] % n
                nxt[c] += clients
                todo = chunks(int(plen[i]), buckets)
                if chunk_a_pass and slots and len(todo) > 1:
                    now += chunk_s[todo.pop(0)]
                    under_way = (c, todo, i)
                    break
                now += sum(chunk_s[b] for b in todo)
                resident(c, i)
        if t0 is None and len(started) == clients:
            t0 = now
        if not slots:
            if under_way is None and waiting:
                now = max(now, waiting[0][0])
            continue
        now += step_s + live_s * sum(s[1] for s in slots.values()) / 1e5
        for c in list(slots):
            s = slots[c]
            s[0] -= 1
            s[1] += 1
            gaps.append((last[c], now))
            last[c] = now
            tokens.append(now)
            if s[0] <= 0:
                del slots[c]
                waiting.append((now, c))
    t1 = t0 + seconds
    inside = sorted(b - a for a, b in gaps if a >= t0 and b <= t1)
    return {'tokens_per_s': sum(t0 <= t <= t1 for t in tokens) / seconds,
            'itl_p95_ms': 1e3 * traffic_gen.percentile(inside, 95),
            'admissions': sum(t0 <= t <= t1 for t in admitted)}


def spread(values):
    """The driver's: the middle half's width over the median, with the
    run farthest from the median left out where that narrows it."""
    def iqr(v):
        q = statistics.quantiles(v, n=4)
        return (q[2] - q[0]) / statistics.median(v)
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return min(iqr(values), iqr(rest))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('traffic')
    ap.add_argument('--step-ms', type=float, required=True)
    ap.add_argument('--chunk-ms', required=True,
                    help='bucket=ms,... for every prompt bucket')
    ap.add_argument('--live-ms', type=float, default=0.0)
    ap.add_argument('--seconds', type=float, default=50.0)
    ap.add_argument('--seeds', type=int, default=60)
    ap.add_argument('--all-chunks-at-once', action='store_true')
    args = ap.parse_args(argv)
    with open(args.traffic) as f:
        traffic = json.load(f)
    chunk_s = {int(b): float(ms) / 1e3 for b, ms in
               (kv.split('=') for kv in args.chunk_ms.split(','))}
    if sorted(chunk_s) != sorted(traffic['engine']['prompt_buckets']):
        raise SystemExit('--chunk-ms wants a cost for each of the buckets %r'
                         % traffic['engine']['prompt_buckets'])
    rows = [run(traffic, 4000000000 + 7919 * k, args.step_ms / 1e3, chunk_s,
                args.live_ms / 1e3, args.seconds,
                not args.all_chunks_at_once) for k in range(args.seeds)]
    out = {}
    for key in ('tokens_per_s', 'itl_p95_ms'):
        vals = [r[key] for r in rows]
        sets = [vals[k:k + 6] for k in range(0, len(vals) - 5, 6)]
        out[key] = {'median': statistics.median(vals),
                    'min': min(vals), 'max': max(vals),
                    'spread_of_sets_of_six': [round(spread(s), 4)
                                              for s in sets]}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == '__main__':
    sys.exit(main())
