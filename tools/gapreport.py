"""Who had the device's idle time, and which program its busy time.

    python tools/gapreport.py <trace_dir> [--min-gap-ms 1.0] [--json]

`<trace_dir>` is what jax.profiler.start_trace() wrote (a kept benchmark
trace: BENCH_KEEP_TRACE=1 leaves it under .bench_trace/<cell>). Two tables
over the traced window (benchmark.reduce_trace.traced_window):

- device IDLE time by the innermost `paddle_tpu:` host span open at the
  middle of each idle gap, and the same time split by the innermost span
  at every instant of it — the decode loop's phases
  (`generate.feed`, `generate.deliver` ...), `Executor.run`'s (`run.prepare`
  ...) and every monitor.span (`run`, `compile` ...); `none` where no span
  of the program was open. Gaps shorter than --min-gap-ms are the device's
  own (between two operations of one program) and are summed apart;
- device BUSY time by XLA module: the `XLA Modules` line of each device
  plane, named after the program (`jit_lm_decode_step`,
  `jit_lm_prefill_paged_b512`, `jit_lm_train` ...);
- what the device did UNDER each span, by the innermost span at every
  instant of the window: the seconds it was open, the device's idle
  seconds inside it (every gap, the short ones too) and its busy seconds
  by module — `generate.prefill.fetch`, the loop's wait where it picks a
  prefill's first token up, should hold the rest of the prefill's bucket
  and no idle tail (two decode steps are queued behind it), and no
  `generate.prefill.drain` opens: an admission waits for no step. One
  line under the table: of the window's admissions (`generate.prefill`
  spans), those whose first token was written into the next step's input
  on the device (runs of `jit_first_token_put`).

docs/observability.md "Reading a device trace".
"""
import argparse
import bisect
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reduce_trace as rt                   # noqa: E402

SPAN_PREFIX = 'paddle_tpu:'
MODULES_LINE = 'XLA Modules'
# an admission's span, and the module that leaves its first token on the
# device (serving/generate.py `_admit_one`, `_put_first`)
ADMISSION_SPAN = 'generate.prefill'
FIRST_TOKEN_MODULE = 'jit_first_token_put'
_RUN_ID = re.compile(r'\(\d+\)$')


def load(path):
    """The reduce_trace trace of an .xplane.pb (device operations, the
    benchmark's own host spans) plus 'spans', the program's `paddle_tpu:`
    host spans, and 'modules', the `XLA Modules` events of each device
    plane with the run id cut off the name; (name, start_ns, dur_ns)."""
    import jax
    trace = rt.load_xplane(path)
    trace['spans'], trace['modules'] = [], {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith('/host:'):
                trace['spans'].extend(
                    (e.name[len(SPAN_PREFIX):], int(e.start_ns),
                     int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
            elif plane.name.startswith(rt.DEVICE_PLANE_PREFIX) \
                    and line.name == MODULES_LINE:
                trace['modules'].setdefault(plane.name, []).extend(
                    (_RUN_ID.sub('', e.name), int(e.start_ns),
                     int(e.duration_ns)) for e in line.events)
    return trace


def timeline(spans):
    """Where a span of the program is open, which is the innermost (the
    open one that started last): sorted disjoint (start, end, name)."""
    edges = sorted({s for _n, s, _d in spans}
                   | {s + d for _n, s, d in spans})
    by_start = sorted(spans, key=lambda e: e[1])
    out, open_, i = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while i < len(by_start) and by_start[i][1] <= a:
            open_.append(by_start[i])
            i += 1
        open_ = [e for e in open_ if e[1] + e[2] > a]
        if not open_:
            continue
        # of two that start together the shorter is the inner one
        name = max(open_, key=lambda e: (e[1], -e[2]))[0]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def split(segments, starts, s, e):
    """{name: ns} of [s, e) by the timeline's segments; 'none' for what no
    segment covers."""
    parts, covered = {}, 0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(segments) and segments[i][0] < e:
        a, b, name = segments[i]
        ns = min(b, e) - max(a, s)
        if ns > 0:
            parts[name] = parts.get(name, 0) + ns
            covered += ns
        i += 1
    if e - s > covered:
        parts['none'] = e - s - covered
    return parts


def report(trace, min_gap_ns=1000000):
    """Over the device's idle gaps of at least min_gap_ns: 'idle' {span:
    [seconds, gaps]} by the innermost span at each gap's middle, 'split'
    {span: seconds} of the same time by the innermost span at every
    instant of it ('none': no span of the program open), and
    'labelled_share' of it under a span by the first reading. Beside
    them 'window_s', 'busy_s', 'idle_s', 'short_gaps_s' (the gaps below
    min_gap_ns), 'busy' {module: seconds} and 'runs' {module: its runs
    that touch the window, over all devices}; 'under' {span: {'open_s',
    'idle_s', 'busy': {module: seconds}}}, the whole window by the
    innermost span at every instant; 'first_tokens' {'admitted': the
    admission spans that start in the window, 'on_device': the runs of
    the module that leaves a first token on the device}. Seconds are per
    device, averaged over the devices in the trace."""
    t0, t1 = rt.traced_window(trace)
    segments = timeline(trace['spans'])
    starts = [a for a, _b, _n in segments]
    n = len(trace['devices'])
    idle, parts, short_ns, busy_ns = {}, {}, 0, 0
    open_ns = split(segments, starts, t0, t1)   # one clock for all devices
    idle_under, busy_under = {}, {}
    for _plane, events in sorted(trace['devices'].items()):
        evs = rt.clip(events, t0, t1)
        busy_ns += rt.busy_ns(evs)
        for s, d in rt.idle_gaps(evs, t0, t1):
            for name, ns in split(segments, starts, s, s + d).items():
                idle_under[name] = idle_under.get(name, 0) + ns
            if d < min_gap_ns:
                short_ns += d
                continue
            mid = s + d // 2
            row = idle.setdefault(
                next(iter(split(segments, starts, mid, mid + 1))), [0, 0])
            row[0] += d
            row[1] += 1
            for name, ns in split(segments, starts, s, s + d).items():
                parts[name] = parts.get(name, 0) + ns
    modules, runs = {}, {}
    for plane, events in sorted(trace['modules'].items()):
        # a module's event covers its operations and the gaps between
        # them; the busy union under it is what it kept the device busy
        ops = rt.merge(rt.clip(trace['devices'][plane], t0, t1))
        ends = [b for _a, b in ops]
        for name, s, d in rt.clip(events, t0, t1):
            i, inside = bisect.bisect_right(ends, s), 0
            while i < len(ops) and ops[i][0] < s + d:
                a, b = max(ops[i][0], s), min(ops[i][1], s + d)
                inside += b - a
                for span, ns in split(segments, starts, a, b).items():
                    busy = busy_under.setdefault(span, {})
                    busy[name] = busy.get(name, 0) + ns
                i += 1
            modules[name] = modules.get(name, 0) + inside
            runs[name] = runs.get(name, 0) + 1
    long_ns = sum(v[0] for v in idle.values())
    return {
        'window_s': (t1 - t0) / 1e9,
        'busy_s': busy_ns / n / 1e9,
        'idle_s': (long_ns + short_ns) / n / 1e9,
        'short_gaps_s': short_ns / n / 1e9,
        'idle': {k: [v[0] / n / 1e9, v[1]] for k, v in idle.items()},
        'split': {k: v / n / 1e9 for k, v in parts.items()},
        'labelled_share': (1.0 - idle.get('none', [0])[0] / long_ns)
        if long_ns else None,
        'busy': {k: v / n / 1e9 for k, v in modules.items()},
        'runs': runs,
        'under': {k: {'open_s': ns / 1e9,
                      'idle_s': idle_under.get(k, 0) / n / 1e9,
                      'busy': {m: b / n / 1e9
                               for m, b in busy_under.get(k, {}).items()}}
                  for k, ns in open_ns.items()},
        'first_tokens': {
            'admitted': sum(1 for name, s, _d in trace['spans']
                            if name == ADMISSION_SPAN and t0 <= s < t1),
            'on_device': runs.get(FIRST_TOKEN_MODULE, 0)},
    }


def render(rep, min_gap_ms):
    out = ['traced window %.3f s, device busy %.3f s, idle %.3f s (%.2f %%)'
           % (rep['window_s'], rep['busy_s'], rep['idle_s'],
              100.0 * rep['idle_s'] / rep['window_s']), '',
           'idle gaps of %.3g ms or more: by the innermost paddle_tpu: span '
           'at their middle,' % min_gap_ms,
           'and the same seconds split by the innermost span at every '
           'instant',
           '%-28s %10s %6s %8s %10s' % ('span', 'by middle', 'gaps',
                                        'mean ms', 'split')]
    names = sorted(set(rep['idle']) | set(rep['split']),
                   key=lambda k: -rep['split'].get(k, 0))
    for name in names:
        sec, gaps = rep['idle'].get(name, (0.0, 0))
        out.append('%-28s %10.4f %6d %8s %10.4f'
                   % (name, sec, gaps,
                      '%.3f' % (1e3 * sec / gaps) if gaps else '-',
                      rep['split'].get(name, 0.0)))
    out.append('%-28s %10.4f' % ('(shorter gaps)', rep['short_gaps_s']))
    if rep['labelled_share'] is not None:
        out.append('under a span of the program at their middle: %.1f %% of '
                   'that idle time' % (100.0 * rep['labelled_share']))
    out += ['', 'device busy time by XLA module',
            '%-40s %10s %8s %6s' % ('module', 'seconds', 'share', 'runs')]
    for name, sec in sorted(rep['busy'].items(), key=lambda kv: -kv[1]):
        out.append('%-40s %10.4f %7.1f%% %6d'
                   % (name, sec, 100.0 * sec / rep['busy_s'],
                      rep['runs'][name]))
    out += ['', 'under each span, innermost at every instant: seconds open, '
            'the device idle, busy by module',
            '%-28s %10s %10s  %s' % ('span', 'open', 'idle', 'busy')]
    for name, row in sorted(rep['under'].items(),
                            key=lambda kv: -kv[1]['open_s']):
        out.append('%-28s %10.4f %10.4f  %s' % (
            name, row['open_s'], row['idle_s'], ', '.join(
                '%s %.4f' % kv for kv in sorted(row['busy'].items(),
                                                key=lambda kv: -kv[1]))))
    first = rep['first_tokens']
    if first['admitted']:
        out.append('first tokens left on the device: %d of %d admissions '
                   '(%s runs / %s spans)'
                   % (first['on_device'], first['admitted'],
                      FIRST_TOKEN_MODULE, ADMISSION_SPAN))
    return '\n'.join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('trace_dir')
    ap.add_argument('--min-gap-ms', type=float, default=1.0)
    ap.add_argument('--json', action='store_true')
    args = ap.parse_args(argv)
    rep = report(load(rt.find_xplane(args.trace_dir)),
                 int(args.min_gap_ms * 1e6))
    print(json.dumps(rep) if args.json else render(rep, args.min_gap_ms))
    return 0


if __name__ == '__main__':
    sys.exit(main())
