"""The benchmark's command:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, no child process, no platform override. It runs one cell of
BENCHMARK.json on the machine it is started on and prints, as the last
line of its standard output, one JSON object: correct, attempted, failed,
metrics, device (and, traced, breakdown). Without a TPU of a kind that
benchmark/peaks.json knows, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.

Everything that belongs to one cell is data found by name (README.md):
the configuration's file, `<path>/traffic/<traffic>.json`,
`<path>/drivers/<kind>.py`, `<path>/models/<builder>.py` and
`<path>/layer_metrics/<metric>.py`, searched in the manifest's `paths`.
"""
import time
T_START = time.perf_counter()

import argparse                                            # noqa: E402
import contextlib                                          # noqa: E402
import importlib.util                                      # noqa: E402
import json                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import sys                                                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
MANIFEST = os.path.join(ROOT, 'BENCHMARK.json')


def find_file(manifest, *parts):
    """The first `<path>/<parts...>` that exists, over the manifest's
    `paths` in order."""
    for base in manifest['paths']:
        p = os.path.join(ROOT, base, *parts)
        if os.path.isfile(p):
            return p
    raise FileNotFoundError('%s not found under any of %r'
                            % (os.path.join(*parts), manifest['paths']))


def load_module(path):
    name = 'bench_file_' + ''.join(
        c if c.isalnum() else '_' for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(manifest, workload):
    """(cell, configuration, traffic) of the workload named."""
    cells = {w['name']: w for w in manifest['workloads']}
    if workload not in cells:
        raise SystemExit('run.py: no workload %r in the manifest (have %s)'
                         % (workload, sorted(cells)))
    cell = cells[workload]
    conf = {c['name']: c for c in manifest['configs']}[cell['config']]
    config = load_json(os.path.join(ROOT, conf['file']))
    traffic = load_json(find_file(manifest, 'traffic',
                                  cell['traffic'] + '.json'))
    return cell, config, traffic


def metrics_of(manifest, section, workload):
    return [x for x in manifest[section]
            if 'workloads' not in x or workload in x['workloads']]


def require_devices(chips):
    """The devices of the cell, or exit non-zero with no result."""
    import jax
    from benchmark import flops
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        raise SystemExit('run.py: no TPU - jax.devices()[0].platform is %r; '
                         'the benchmark has no CPU mode' % devs[0].platform)
    if len(devs) < chips:
        raise SystemExit('run.py: the cell asks for %d chips, JAX finds %d'
                         % (chips, len(devs)))
    try:
        peaks = flops.peaks_for(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit('run.py: %s' % e)
    return devs, peaks


class Context(object):
    """What a driver gets: the cell's data, the clock, spans, the tracer."""

    def __init__(self, args, cell, config, traffic, model, devices, peaks):
        self.workload, self.seed = args.workload, args.seed
        self.seconds, self.trace = float(args.seconds), bool(args.trace)
        self.cell, self.config, self.traffic = cell, config, traffic
        self.model, self.devices, self.peaks = model, devices, peaks
        self.t_window = None
        self.trace_dir = os.path.join(ROOT, '.bench_trace', args.workload)
        self.reduced = None

    def since_start(self):
        return time.perf_counter() - T_START

    def note(self, text):
        print('[%s] %s' % (self.workload, text), flush=True)

    def span(self, name):
        """A host span of the benchmark's own, written into the profiler's
        trace while one is being taken; free otherwise."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation('bench:' + name)

    def open_window(self):
        self.t_window = time.perf_counter()
        return self.t_window

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._traced = jax.profiler.TraceAnnotation('bench:traced')
        self._traced.__enter__()

    def stop_trace(self):
        import jax
        from benchmark import reduce_trace
        self._traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        path = reduce_trace.find_xplane(self.trace_dir)
        self.reduced = reduce_trace.reduce(reduce_trace.load_xplane(path))
        if os.environ.get('BENCH_KEEP_TRACE') != '1':
            shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.note('traced %.3f s, device busy %.3f s'
                  % (self.reduced['window_s'], self.reduced['busy_s']))


def main(argv=None, manifest_path=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    manifest = load_json(manifest_path or MANIFEST)
    cell, config, traffic = load_cell(manifest, args.workload)
    devices, peaks = require_devices(int(cell['chips']))
    devices = devices[:int(cell['chips'])]

    import jax
    from paddle_tpu.executor import _wire_persistent_cache
    print('device: %s %r x%d; compile cache: %s'
          % (devices[0].platform, devices[0].device_kind, len(jax.devices()),
             _wire_persistent_cache()), flush=True)
    # every program of a run is eligible for the cache, also the small ones
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)
    cache = {'requests': 0, 'hits': 0}

    def on_event(event, **_kw):
        if event == '/jax/compilation_cache/compile_requests_use_cache':
            cache['requests'] += 1
        elif event == '/jax/compilation_cache/cache_hits':
            cache['hits'] += 1
    jax.monitoring.register_event_listener(on_event)

    model = load_module(find_file(manifest, 'models',
                                  config['builder'] + '.py'))
    driver = load_module(find_file(manifest, 'drivers',
                                   traffic['kind'] + '.py'))
    ctx = Context(args, cell, config, traffic, model, devices, peaks)
    out = driver.run(ctx)
    print('persistent compile cache: %(hits)d hits of %(requests)d compile '
          'requests' % cache, flush=True)

    end_to_end = dict(out['end_to_end'])
    end_to_end['setup_s'] = ctx.t_window - T_START
    peak_bytes = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                     for d in devices)
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(jax.devices()),
              'memory_peak_bytes': int(peak_bytes)}
    facts = dict(out['facts'], end_to_end=end_to_end, trace=ctx.reduced,
                 config=config, traffic=traffic, chips=int(cell['chips']),
                 peaks=peaks, memory_peak_bytes=int(peak_bytes))
    print('facts: ' + json.dumps(
        {k: v for k, v in facts.items()
         if k not in ('trace', 'config', 'traffic', 'counters',
                      'engine_stats')}, default=str), flush=True)

    metrics = {}
    if args.trace:
        for spec in metrics_of(manifest, 'per_layer', args.workload):
            reader = load_module(find_file(manifest, 'layer_metrics',
                                           spec['name'] + '.py'))
            value = reader.read(facts)
            if value is not None:
                metrics[spec['name']] = {'value': value,
                                         'unit': spec['unit']}
        device['busy_s'] = ctx.reduced['busy_s']
        device['window_s'] = ctx.reduced['window_s']
    else:
        for spec in metrics_of(manifest, 'end_to_end', args.workload):
            if spec['name'] in end_to_end:
                metrics[spec['name']] = {'value': end_to_end[spec['name']],
                                         'unit': spec['unit']}
    result = {'correct': out['correct'], 'attempted': out['attempted'],
              'failed': out['failed'], 'metrics': metrics, 'device': device}
    if args.trace:
        result['breakdown'] = {'device_ops': ctx.reduced['device_ops'],
                               'idle_gaps': ctx.reduced['idle_gaps']}
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
