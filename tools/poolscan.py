"""What a serve cell's compiled programs hold that is as long as a pool.

    JAX_PLATFORMS=cpu python3 tools/poolscan.py \
        --config benchmark/configs/fairseq-dense-355m.json \
        --traffic benchmark/traffic/chat-closed.json \
        [--layers n] [--root <another checkout>] [--dump <prefix>]

Compiles the cell's decode step and EVERY prefill bucket at their real size
for one chip of the device-less `v5e:2x2` topology (the method and the
functions of benchmark/size_serve_pools.py; nothing runs, so no time comes
from here) and prints one JSON line a program: XLA's `bytes accessed`, the
temporaries, and the instructions outside fusions' bodies whose output is

- `layer_share_outputs`: ``[num_blocks, (1,) block_size, W]`` — one layer's
  share of a pool, made anew. None is wanted: PR 42 found 48 of them a
  prefill in chat (`slice_bitcast_fusion`, the copy that
  ``cache[:, layer][tables]`` cost; `kv_cache_ops.pool_pages`);
- `pool_shaped_outputs`: the pool's own shape — the in-place page writes
  (`scatter` fusions on the donated pool) and nothing else.

`--root` compiles another checkout's programs (the parent's, unpacked with
`git archive`) with this file's reading; `--dump` keeps each program's HLO
text as `<prefix>.<program>.hlo`.
"""
import argparse
import collections
import json
import os
import re
import sys

_SHAPE = re.compile(r'\b(pred|[su]\d+|bf16|f16|f32|f64)\[([\d,]*)\]')
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(')
_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$')
# instructions that make no buffer of their own
_VIEWS = ('parameter', 'get-tuple-element', 'tuple', 'bitcast', 'constant')


def scan(text, pool_shapes, block_size):
    """(`layer_share_outputs`, `pool_shaped_outputs`) of one compiled
    program's HLO `text`: ``{'<opcode> <name> <dtype><dims>': count}`` over
    the instructions outside fusions' bodies, for pools of `pool_shapes`
    ``(num_blocks, layers, block_size, W)``."""
    fused = set(re.findall(r'calls=%?([\w.\-]+)', text))
    share, whole = collections.Counter(), collections.Counter()
    inside = None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if inside in fused or not m or m.group(3) in _VIEWS:
            continue
        name, out, opcode = m.groups()
        kind = re.sub(r'[.\d]+$', '', name)
        for dtype, dims in _SHAPE.findall(out):
            dims = tuple(int(d) for d in dims.split(',') if d)
            key = '%s %s %s%s' % (opcode, kind, dtype, list(dims))
            for pool in pool_shapes:
                if dims == tuple(pool):
                    whole[key] += 1
                elif len(dims) >= 3 and dims[0] == pool[0] \
                        and dims[-2:] == (block_size, pool[3]) \
                        and all(d == 1 for d in dims[1:-2]):
                    share[key] += 1
    return dict(share), dict(whole)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True)
    ap.add_argument('--layers', type=int, help='override the depth')
    ap.add_argument('--root', default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument('--dump')
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    from benchmark import size_serve, size_serve_pools
    from benchmark.run import find_file, load_json, load_module
    from jax.experimental import topologies
    from paddle_tpu.models import transformer as T
    m, e = load_json(args.config), load_json(args.traffic)['engine']
    manifest = load_json(os.path.join(root, 'BENCHMARK.json'))
    model = load_module(find_file(manifest, 'models', m['builder'] + '.py'))
    cfg = model.lm_config(m, int(e['max_len']), False)
    if args.layers:
        cfg.n_layer = args.layers
    device = topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0]
    shapes = T.kv_cache_shapes(cfg, e['num_blocks'], e['block_size'],
                               e['slots'])
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
        compiled = size_serve_pools.compiled_program(
            build, fetch, rows, device, T.kv_cache_names(cfg))
        text = compiled.as_text()
        if args.dump:
            with open('%s.%s.hlo' % (args.dump, key), 'w') as f:
                f.write(text)
        share, whole = scan(text, sorted(set(map(tuple, shapes.values()))),
                            e['block_size'])
        size = size_serve.report(compiled)
        print(json.dumps({
            'program': key, 'layers': cfg.n_layer,
            'pools': {n: list(s) for n, s in shapes.items()},
            'bytes_accessed_gb': round(size['bytes_accessed'] / 1e9, 3),
            'temp_gb': size['temp_gb'], 'flops': size['flops'],
            'mosaic_calls': size['mosaic_calls'],
            'layer_share_outputs': share, 'pool_shaped_outputs': whole}),
            flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
