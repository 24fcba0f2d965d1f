"""What a SAMPLED decode step costs on the chip, at a benchmark cell's
engine shapes.

    python3 tools/sampled_step_trace.py --workload fd355m-serve-chat \
        --seed 7 [--temperature 0.8] [--sampled-rows N] [--out FILE]

No cell of the benchmark samples (drivers/serve.py refuses anything but
greedy), so the sampled branch of `sample_next_token` has no ledger line.
This builds the cell's engine as the serve driver does (configuration,
traffic file, weights from the seed), fills every slot with one request
of the cell's prompts — `--sampled-rows` of them at `--temperature`
(default: all), the rest greedy — and

1. serves 48 tokens to each with pinned sample seeds and prints a digest
   of the tokens, so two commits can be held against each other;
2. runs the cell's own closed loop (benchmark/traffic_gen.py), the first
   `--sampled-rows` clients sampling, and traces `--trace-seconds` of
   its steady state: device busy time by XLA module and per run
   (tools/gapreport.py), the device operations by name
   (benchmark/reduce_trace.py) and the 12 single HLO instructions with
   most time, by their own names.

One process, TPU only (benchmark/run.py's device check). The last line of
standard output is one JSON object; `--out` writes it to a file too.
"""
import argparse
import hashlib
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DIGEST_TOKENS = 48


def instruction_seconds(path, t0, t1, top=12):
    """[(HLO instruction name with its number, seconds, events)] of the
    device's `XLA Ops` events inside [t0, t1], innermost events only."""
    import jax
    from benchmark import reduce_trace as rt
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if rt.is_ops_line(plane.name, line.name):
                events += [(e.name.split(' = ', 1)[0].lstrip('%'),
                            int(e.start_ns), int(e.duration_ns))
                           for e in line.events]
    sums = {}
    for name, _s, d in rt.leaves(rt.clip(events, t0, t1)):
        row = sums.setdefault(name, [0, 0])
        row[0] += d
        row[1] += 1
    rows = sorted(sums.items(), key=lambda kv: -kv[1][0])[:top]
    return [[name, ns / 1e9, n] for name, (ns, n) in rows]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', default='fd355m-serve-chat')
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--temperature', type=float, default=0.8)
    ap.add_argument('--top-k', type=int, default=0)
    ap.add_argument('--top-p', type=float, default=0.0)
    ap.add_argument('--sampled-rows', type=int, default=None)
    ap.add_argument('--trace-seconds', type=float, default=3.0)
    ap.add_argument('--out', default=None)
    args = ap.parse_args(argv)

    from benchmark import run as bench
    manifest = bench.load_json(bench.MANIFEST)
    cell, m, tr = bench.load_cell(manifest, args.workload)
    bench.require_devices(int(cell['chips']))

    import jax
    from benchmark import reduce_trace, traffic_gen
    from benchmark.drivers.common import PROGRAM_SEED
    from paddle_tpu import Scope, monitor
    from paddle_tpu.executor import _wire_persistent_cache
    from paddle_tpu.serving.generate import GenerateConfig, GenerateEngine
    from tools import gapreport
    print('compile cache: %s' % _wire_persistent_cache(), flush=True)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)

    model = bench.load_module(bench.find_file(manifest, 'models',
                                              m['builder'] + '.py'))
    e = tr['engine']
    slots, max_len = int(e['slots']), int(e['max_len'])
    scope = Scope()
    for name, value in model.init_params(m, args.seed).items():
        scope.set(name, value)
    eng = GenerateEngine(GenerateConfig(
        model=model.lm_config(m, max_len, False), slots=slots,
        max_len=max_len,
        block_size=int(e['block_size']), num_blocks=int(e['num_blocks']),
        prompt_buckets=list(e['prompt_buckets']), prefix_sharing=False,
        queue_cap=4096, default_deadline_s=300.0, seed=PROGRAM_SEED),
        scope=scope)
    print('warmup: %r' % (eng.warmup(),), flush=True)

    requests = traffic_gen.make_requests(tr, m['vocab_size'], args.seed)
    sampled_rows = slots if args.sampled_rows is None else args.sampled_rows
    sampling = dict(temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p)

    eng.start()
    handles = [eng.submit(r['prompt'], max_new_tokens=DIGEST_TOKENS,
                          deadline_s=300.0, sample_seed=1000 + i,
                          **(sampling if i < sampled_rows else {}))
               for i, r in enumerate(requests[:slots])]
    got = [list(h.result(timeout=300.0)) for h in handles]
    digest = hashlib.sha256(json.dumps(got).encode()).hexdigest()

    def submit(prompt, max_new_tokens):
        # the cell's own closed loop (traffic_gen.Load names a client's
        # thread 'bench-client-<c>'): the first clients sample
        client = int(threading.current_thread().name.rsplit('-', 1)[1])
        return eng.submit(prompt, max_new_tokens=max_new_tokens,
                          deadline_s=300.0,
                          **(sampling if client < sampled_rows else {}))

    load = traffic_gen.Load(tr['arrival'], requests, submit, args.seed)
    load.start()
    load.wait_ramped()
    time.sleep(3.0)
    before, s0 = monitor.counters(), eng.stats()
    trace_dir = os.path.join(ROOT, '.bench_trace', 'sampled_step')
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(reduce_trace.WINDOW_SPAN):
        time.sleep(args.trace_seconds)
    jax.profiler.stop_trace()
    s1, delta = eng.stats(), monitor.counter_delta(before)
    sent = list(load.records)
    failed = sum(1 for r in sent if r.error)
    load.stop()
    eng.stop()                # ends the requests in flight
    load.join()

    path = reduce_trace.find_xplane(trace_dir)
    trace = gapreport.load(path)
    t0, t1 = reduce_trace.traced_window(trace)
    rep, red = gapreport.report(trace), reduce_trace.reduce(trace, top=14)
    instructions = instruction_seconds(path, t0, t1)
    shutil.rmtree(trace_dir, ignore_errors=True)
    steps = s1['decode_steps'] - s0['decode_steps']
    dev = jax.devices()[0]
    out = {
        'workload': args.workload, 'seed': args.seed,
        'device': {'platform': dev.platform, 'kind': dev.device_kind},
        'slots': slots, 'sampled_rows': sampled_rows,
        'temperature': args.temperature, 'top_k': args.top_k,
        'top_p': args.top_p,
        'tokens_digest': digest, 'first_tokens': [g[:6] for g in got[:4]],
        'requests_sent': len(sent), 'requests_failed': failed,
        'decode_steps_in_window': steps,
        'sampled_steps_in_window': delta.get('generate_sampled_steps_total'),
        'window_s': rep['window_s'], 'busy_s': rep['busy_s'],
        'module_busy_s': rep['busy'], 'module_runs': rep['runs'],
        'module_ms_per_run': {k: 1e3 * v / rep['runs'][k]
                              for k, v in rep['busy'].items()},
        'device_ops_s': red['device_ops'],
        'instructions': instructions,
    }
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, 'w') as f:
            f.write(text + '\n')
    print(text, flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
