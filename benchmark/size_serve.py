"""Sizing of a `serve` cell on no chip: builds the cell's decode step and
its widest prefill bucket at their real size, lowers each for one chip of
the device-less `v5e:2x2` topology, compiles it with the real XLA:TPU and
Mosaic, and prints what `memory_analysis()` says the chip holds (state =
weights + the paged cache, donated; temporaries) and what
`cost_analysis()` says the program computes.

    JAX_PLATFORMS=cpu python3 benchmark/size_serve.py \
        --config benchmark/configs/olmoe-1b-7b-0125-l6.json \
        --traffic benchmark/traffic/chat16-closed.json [--layers 5]

Nothing runs: no time and no rate comes from here.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
for _k, _v in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
               ('TPU_WORKER_HOSTNAMES', 'localhost'),
               ('TPU_SKIP_MDS_QUERY', '1'),
               ('PADDLE_FUSED_TIER', 'pallas')):
    os.environ.setdefault(_k, _v)


def compiled_program(build, fetch, rows, device):
    """`build()` (-> the program's vars) compiled for `device` at `rows`
    rows a feed: shapes in, nothing executed. Every persistable the program reads is state;
    the K/V pools are read and written, so donated."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import unique_name
    from paddle_tpu.core.lowering import build_fn
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models.transformer import KV_CACHE_K, KV_CACHE_V
    main = Program()
    with program_guard(main, Program()):
        with unique_name.guard():
            v = build()
    block = main.global_block()

    def sds(var, lead=1):
        shape = tuple(lead if s < 0 else s for s in var.shape)
        dt = jnp.dtype(str(var.dtype))
        return jax.ShapeDtypeStruct(
            shape, jnp.int32 if dt == jnp.int64 else dt,
            sharding=SingleDeviceSharding(device))
    state = [x.name for x in block.vars.values() if x.persistable]
    pools = [KV_CACHE_K, KV_CACHE_V]
    fn, ro, rw = build_fn(main, [v[fetch].name], state, pools)
    feeds = {n: x for n, x in block.vars.items()
             if n.startswith('gen_') and not x.persistable
             and any(n in names for op in block.ops
                     for names in op.inputs.values())
             and not any(n in names for op in block.ops
                         for names in op.outputs.values())}
    lowered = jax.jit(fn, donate_argnums=2).lower(
        {n: sds(x, lead=rows) for n, x in feeds.items()},
        {n: sds(block.var(n)) for n in ro},
        {n: sds(block.var(n)) for n in rw},
        jax.ShapeDtypeStruct((2,), jnp.uint32,
                             sharding=SingleDeviceSharding(device)))
    return lowered.compile()


def report(compiled):
    ma = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    return {'argument_gb': round(ma.argument_size_in_bytes / 1e9, 3),
            'temp_gb': round(ma.temp_size_in_bytes / 1e9, 3),
            'held_gb': round((ma.argument_size_in_bytes
                              + ma.output_size_in_bytes
                              - ma.alias_size_in_bytes
                              + ma.temp_size_in_bytes) / 1e9, 3),
            'flops': float(cost.get('flops', 0.0)),
            'bytes_accessed': float(cost.get('bytes accessed', 0.0)),
            'mosaic_calls': text.count('tpu_custom_call'),
            'ragged_dots': text.count(' ragged-dot') + text.count(
                '%ragged-dot-')}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True)
    ap.add_argument('--layers', type=int, help='override the depth')
    args = ap.parse_args(argv)
    with open(args.config) as f:
        m = json.load(f)
    with open(args.traffic) as f:
        e = json.load(f)['engine']
    from jax.experimental import topologies
    from benchmark.run import find_file, load_module
    from paddle_tpu.models import transformer as T
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        manifest = json.load(f)
    model = load_module(find_file(manifest, 'models', m['builder'] + '.py'))
    cfg = model.lm_config(m, int(e['max_len']), False)
    if args.layers:
        cfg.n_layer = args.layers
    device = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0]
    wide = max(e['prompt_buckets'])
    out = {'layers': cfg.n_layer}
    for key, build, fetch, rows in (
            ('decode_step', lambda: T.build_lm_decode_step(
                cfg, e['slots'], e['max_len'], block_size=e['block_size'],
                num_blocks=e['num_blocks']), 'next_tokens', e['slots']),
            ('prefill_b%d' % wide, lambda: T.build_lm_prefill_paged(
                cfg, wide, e['num_blocks'], e['block_size'],
                e['max_len'] // e['block_size']), 'first_token', 1)):
        try:
            out[key] = report(compiled_program(build, fetch, rows, device))
        except Exception as err:  # noqa: BLE001 — a refusal IS the finding
            head = str(err).split('Largest program allocations')[0]
            out[key] = {'refused': ' '.join(head.split())[:600]}
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
