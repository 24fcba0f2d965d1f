"""benchmark/size_serve.py for a model whose programs declare more pools
than K and V (the window layers' pools of models/transformer.py
`kv_cache_names`): the same device-less compile of the cell's decode step
and its widest prefill bucket, with EVERY pool the model names donated —
size_serve.py donates `gen_kv_k` and `gen_kv_v` by name, and a pool that
is read and written but not donated is counted twice, once as the copy
beside itself — and the prefill told the slots, which size those pools.

    JAX_PLATFORMS=cpu python3 benchmark/size_serve_pools.py \
        --config benchmark/configs/k-exaone-236b-a23b-ep16-l5.json \
        --traffic benchmark/traffic/mixed64-closed.json

Nothing runs: no time and no rate comes from here.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import size_serve                           # noqa: E402


def compiled_program(build, fetch, rows, device, pools):
    """size_serve.compiled_program with `pools` donated."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import unique_name
    from paddle_tpu.core.lowering import build_fn
    from paddle_tpu.framework import Program, program_guard
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
    fn, ro, rw = build_fn(main, [v[fetch].name], state, list(pools))
    feeds = {n: x for n, x in block.vars.items()
             if n.startswith('gen_') and not x.persistable
             and any(n in names for op in block.ops
                     for names in op.inputs.values())
             and not any(n in names for op in block.ops
                         for names in op.outputs.values())}
    return jax.jit(fn, donate_argnums=2).lower(
        {n: sds(x, lead=rows) for n, x in feeds.items()},
        {n: sds(block.var(n)) for n in ro},
        {n: sds(block.var(n)) for n in rw},
        jax.ShapeDtypeStruct((2,), jnp.uint32,
                             sharding=SingleDeviceSharding(device))).compile()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True)
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
    device = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0]
    pools = T.kv_cache_names(cfg)
    shapes = T.kv_cache_shapes(cfg, e['num_blocks'], e['block_size'],
                               e['slots'])
    out = {'layers': cfg.n_layer,
           'pools_gb': {n: round(4 * s[0] * s[1] * s[2] * s[3] / 1e9, 3)
                        for n, s in shapes.items()}}
    programs = [('decode_step', lambda: T.build_lm_decode_step(
        cfg, e['slots'], e['max_len'], block_size=e['block_size'],
        num_blocks=e['num_blocks']), 'next_tokens', e['slots'])]
    for b in e['prompt_buckets']:
        programs.append(('prefill_b%d' % b, (
            lambda b=b: T.build_lm_prefill_paged(
                cfg, b, e['num_blocks'], e['block_size'],
                e['max_len'] // e['block_size'], slots=e['slots'])),
            'first_token', 1))
    for key, build, fetch, rows in programs:
        try:
            out[key] = size_serve.report(
                compiled_program(build, fetch, rows, device, pools))
        except Exception as err:  # noqa: BLE001 — a refusal IS the finding
            head = str(err).split('Largest program allocations')[0]
            out[key] = {'refused': ' '.join(head.split())[:600]}
    print(json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main())
