"""From a profiler trace to numbers. The JAX profiler writes an
.xplane.pb; `load_xplane` turns it into plain lists and the functions
below reduce those, so the reduction is tested on small recorded lists
(tests/benchmark_tests) and every PR computes the same number the same
way.

A trace is {'devices': {plane name: [(op name, start_ns, dur_ns), ...]},
            'host': [(span name, start_ns, dur_ns), ...]}
The device events are the 'XLA Ops' line of each '/device:TPU:n' plane
(seen on the v5e, jax 0.9.0: that plane also has 'Steps', 'XLA Modules' and
'Async XLA Ops'; an event of 'XLA Ops' is named by its whole HLO
instruction, and host and device planes share one clock). `op_name` cuts
that to the instruction's name without its number, so the 24 layers' copies
of one fusion add up, and marks a Mosaic (Pallas) kernel 'mosaic:<name>'.
The host events are the benchmark's own spans
(jax.profiler.TraceAnnotation names that start with 'bench:').
"""
import glob
import os
import re

DEVICE_PLANE_PREFIX = '/device:TPU:'
OPS_LINE = 'XLA Ops'
HOST_SPAN_PREFIX = 'bench:'
WINDOW_SPAN = 'bench:traced'


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not paths:
        raise FileNotFoundError('no .xplane.pb under %s' % trace_dir)
    return paths[-1]


MOSAIC = 'mosaic:'
_NUMBER = re.compile(r'(\.\d+|\.remat\d*|\.clone)+$')


def op_name(hlo):
    """'%jvp_flash_attention_fwd_.24 = f32[...] custom-call(...,
    custom_call_target="tpu_custom_call", ...)' -> 'mosaic:jvp_flash_
    attention_fwd_'; '%divide_subtract_fusion.28 = ... fusion(...)' ->
    'divide_subtract_fusion'."""
    name = _NUMBER.sub('', hlo.split(' = ', 1)[0].lstrip('%'))
    if 'custom_call_target="tpu_custom_call"' in hlo:
        name = MOSAIC + name
    return name


def is_ops_line(plane_name, line_name):
    return plane_name.startswith(DEVICE_PLANE_PREFIX) \
        and line_name == OPS_LINE


def load_xplane(path):
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    trace = {'devices': {}, 'host': []}
    for plane in data.planes:
        for line in plane.lines:
            if is_ops_line(plane.name, line.name):
                trace['devices'].setdefault(plane.name, []).extend(
                    (op_name(e.name), int(e.start_ns), int(e.duration_ns))
                    for e in line.events)
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        trace['host'].append(
                            (e.name, int(e.start_ns), int(e.duration_ns)))
    trace['host'].sort(key=lambda e: e[1])
    return trace


def clip(events, t0, t1):
    """Events cut to [t0, t1]; those wholly outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def merge(events):
    """Union of the events' intervals as a sorted list of (start, end)."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events):
    return sum(b - a for a, b in merge(events))


def idle_gaps(events, t0, t1):
    """The gaps of [t0, t1] in which no event runs, as (start, dur)."""
    gaps, at = [], t0
    for a, b in merge(clip(events, t0, t1)):
        if a > at:
            gaps.append((at, a - at))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1 - at))
    return gaps


def leaves(events):
    """Events that contain no other event of the list. A trace nests an
    op that runs a body (a while loop, a fusion's steps) around the body's
    events; per-name sums take the innermost so no time counts twice."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] >= s and nxt[1] + nxt[2] <= s + d \
                and nxt[2] < d:
            continue
        out.append((name, s, d))
    return out


def sum_by_name(events):
    sums = {}
    for name, _s, d in events:
        sums[name] = sums.get(name, 0) + d
    return sums


def span_at(host, t):
    """The benchmark's innermost host span open at instant t (the one that
    started last), or 'none'. The window span itself does not count."""
    best = None
    for name, s, d in host:
        if name != WINDOW_SPAN and s <= t < s + d:
            if best is None or s >= best[1]:
                best = (name, s)
    return best[0][len(HOST_SPAN_PREFIX):] if best else 'none'


def traced_window(trace):
    """[t0, t1] of the steady window that was traced: the benchmark's
    'bench:traced' span where the trace carries it, else from the first
    device event's start to the last one's end."""
    for name, s, d in trace['host']:
        if name == WINDOW_SPAN:
            return s, s + d
    evs = [e for d in trace['devices'].values() for e in d]
    if not evs:
        raise ValueError('the trace has no device events')
    return min(s for _n, s, _d in evs), max(s + d for _n, s, d in evs)


def reduce(trace, top=10):
    """busy_s and window_s (busy averaged over the devices in the trace),
    the per-name sums of the device operations, the `top` operations with
    most time and the `top` longest idle gaps, each labelled by the host
    span open at its middle."""
    if not trace['devices']:
        raise ValueError('the trace has no %s line on any %s* plane'
                         % (OPS_LINE, DEVICE_PLANE_PREFIX))
    t0, t1 = traced_window(trace)
    busy, names, gaps = [], {}, []
    for plane, events in sorted(trace['devices'].items()):
        evs = clip(events, t0, t1)
        busy.append(busy_ns(evs))
        for name, ns in sum_by_name(leaves(evs)).items():
            names[name] = names.get(name, 0) + ns
        gaps += idle_gaps(evs, t0, t1)
    n = len(busy)
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(names.items(), key=lambda kv: -kv[1])
    return {
        'devices': n,
        'window_s': (t1 - t0) / 1e9,
        'busy_s': sum(busy) / n / 1e9,
        'op_seconds': {k: v / n / 1e9 for k, v in names.items()},
        'device_ops': [[k, v / n / 1e9] for k, v in ops[:top]],
        'idle_gaps': [[span_at(trace['host'], s + d // 2), d / 1e9]
                      for s, d in gaps[:top]],
    }
