"""What a serve cell's compiled programs hold that is as long as a pool.

    JAX_PLATFORMS=cpu python3 tools/poolscan.py \
        --config benchmark/configs/fairseq-dense-355m.json \
        --traffic benchmark/traffic/chat-closed.json \
        [--layers n] [--only prefill_b512,...] [--root <another checkout>]
        [--dump <prefix>]

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
  (`scatter` fusions on the donated pool) and nothing else;
- `largest_outputs`: the five kinds of value with the most bytes an
  instruction, pools apart — where a prefill's attention scores showed
  (``f32[8,2048,5120]``, 336 MB, twice a chunk in K-EXAONE, until PR 44's
  kernel kept them on the chip; `largest`).

`--only` names the programs to compile (`decode_step`, `prefill_b<bucket>`;
all of them without it). `--root` compiles another checkout's programs (the
parent's, unpacked with `git archive`) with this file's reading; `--dump`
keeps each program's HLO text as `<prefix>.<program>.hlo`.
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


def _outputs(text):
    """(name, kind, dtype, dims) of every value an instruction outside
    fusions' bodies makes, in one compiled program's HLO `text`; kind =
    ``'<opcode> <name without its number> <dtype><dims>'``."""
    fused = set(re.findall(r'calls=%?([\w.\-]+)', text))
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
        for dtype, dims in _SHAPE.findall(out):
            dims = tuple(int(d) for d in dims.split(',') if d)
            yield name, '%s %s %s%s' % (
                opcode, re.sub(r'[.\d]+$', '', name), dtype,
                list(dims)), dtype, dims


def largest(text, pool_shapes=(), keep=5):
    """The `keep` kinds of value with the most bytes an instruction that
    one compiled program's HLO `text` makes outside fusions' bodies, the
    pools (`pool_shapes`, in whatever shape they are written) apart:
    ``[[kind, bytes an instruction, instructions]]``, largest first."""
    def squeezed(dims):
        return tuple(d for d in dims if d > 1)
    pools = {squeezed(p) for p in pool_shapes}
    count, size = collections.Counter(), {}
    for _, kind, dtype, dims in _outputs(text):
        if squeezed(dims) in pools:
            continue
        # an element's bytes from the type's bits (pred: one byte)
        size[kind] = max(1, int(re.sub(r'\D', '', dtype) or 8) // 8)
        for d in dims:
            size[kind] *= d
        count[kind] += 1
    top = sorted(size, key=size.get, reverse=True)[:keep]
    return [[k, size[k], count[k]] for k in top]


def scan(text, pool_shapes, block_size):
    """(`layer_share_outputs`, `pool_shaped_outputs`) of one compiled
    program's HLO `text`: ``{'<opcode> <name> <dtype><dims>': count}`` over
    the instructions outside fusions' bodies, for pools of `pool_shapes`
    ``(num_blocks, layers, block_size, W)``."""
    share, whole = collections.Counter(), collections.Counter()
    for _, key, _, dims in _outputs(text):
        for pool in pool_shapes:
            if dims == tuple(pool):
                whole[key] += 1
            elif len(dims) >= 3 and dims[0] == pool[0] \
                    and dims[-2:] == (block_size, pool[3]) \
                    and all(d == 1 for d in dims[1:-2]):
                share[key] += 1
    return dict(share), dict(whole)


def cell_programs(config, traffic, layers=None, only=None, root=None):
    """(program's name, the cell's engine settings, its `LMConfig`, the
    program's builder, its fetch, the rows a feed has) for the decode step
    and every prefill bucket of the cell `config` x `traffic` (two paths),
    those `only` names if given, at `layers` layers if given, from the
    checkout `root` (this one)."""
    root = os.path.abspath(root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.run import find_file, load_json, load_module
    from paddle_tpu.models import transformer as T
    m, e = load_json(config), load_json(traffic)['engine']
    manifest = load_json(os.path.join(root, 'BENCHMARK.json'))
    model = load_module(find_file(manifest, 'models', m['builder'] + '.py'))
    cfg = model.lm_config(m, int(e['max_len']), False)
    if layers:
        cfg.n_layer = layers
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
        if not only or key in only:
            yield key, e, cfg, build, fetch, rows


def one_chip():
    """One chip of the device-less `v5e:2x2` topology (benchmark.size_serve
    sets the environment the description needs as it is imported)."""
    from benchmark import size_serve                        # noqa: F401
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform='tpu', topology_name='v5e:2x2').devices[0]


def compiled_programs(config, traffic, layers=None, only=None, root=None):
    """`cell_programs`, each compiled for one chip: (program's name, the
    engine settings, the `LMConfig`, the compiled program)."""
    device = None
    for key, e, cfg, build, fetch, rows in cell_programs(
            config, traffic, layers, only, root):
        # importable once `cell_programs` has put `root` on the path
        from benchmark import size_serve_pools
        from paddle_tpu.models import transformer as T
        device = device or one_chip()
        yield key, e, cfg, size_serve_pools.compiled_program(
            build, fetch, rows, device, T.kv_cache_names(cfg))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True)
    ap.add_argument('--layers', type=int, help='override the depth')
    ap.add_argument('--only', help='programs to compile, comma-separated')
    ap.add_argument('--root')
    ap.add_argument('--dump')
    args = ap.parse_args(argv)
    for key, e, cfg, compiled in compiled_programs(
            args.config, args.traffic, args.layers,
            args.only and args.only.split(','), args.root):
        from benchmark import size_serve
        from paddle_tpu.models import transformer as T
        shapes = T.kv_cache_shapes(cfg, e['num_blocks'], e['block_size'],
                                   e['slots'])
        pools = sorted(set(map(tuple, shapes.values())))
        text = compiled.as_text()
        if args.dump:
            with open('%s.%s.hlo' % (args.dump, key), 'w') as f:
                f.write(text)
        share, whole = scan(text, pools, e['block_size'])
        size = size_serve.report(compiled)
        print(json.dumps({
            'program': key, 'layers': cfg.n_layer,
            'pools': {n: list(s) for n, s in shapes.items()},
            'bytes_accessed_gb': round(size['bytes_accessed'] / 1e9, 3),
            'temp_gb': size['temp_gb'], 'flops': size['flops'],
            'mosaic_calls': size['mosaic_calls'],
            'layer_share_outputs': share, 'pool_shaped_outputs': whole,
            'largest_outputs': largest(text, pools)}),
            flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
