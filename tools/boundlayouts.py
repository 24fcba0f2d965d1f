"""Which weights of a serve cell its bound programs want held another way.

    JAX_PLATFORMS=cpu python3 tools/boundlayouts.py \
        --config benchmark/configs/nemotron-3-nano-30b-a3b-ep8-l20.json \
        --traffic benchmark/traffic/reason128-closed.json \
        [--layers n] [--only decode_step,prefill_b512,...]

`Executor.bind` compiles a program with every read-only leaf's layout left
to the compiler and stages each leaf, once, in the format the executable
asks for (`BoundProgram`, `StateCallable.lower_bound`). This compiles the
cell's decode step and every prefill bucket that way for one chip of the
device-less `v5e:2x2` topology (the programs of tools/poolscan.py; nothing
runs, so no time comes from here) and prints one JSON line a program: the
read-only leaves whose CHOSEN layout is not the chip's default for their
shape (`major_to_minor` both ways, shape, bytes), the `copy` instructions
left whose output has a weight's shape (a weight laid out anew on every
call), and the argument and temporary bytes.

The engine binds the decode step first and the prefills after it, on the
same scope: a leaf the step leaves at the default that a prefill would
have otherwise stays at the default. The last line, `"program": "cell"`,
says what the engine's scope ends up with (the step's choices, then each
bucket's for the leaves no earlier program reads) and which programs asked
for something else than they get. A new configuration gets this check
before any chip time: a leaf listed here is either bytes a step moves for
nothing today or a layout two programs disagree on.
"""
import argparse
import collections
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools import poolscan                                  # noqa: E402


def lower_bound(build, fetch, rows, device, pools):
    """`build()` (-> the program's vars) as `Executor.bind` compiles it,
    for `device` at `rows` rows a feed, `pools` read and written:
    (the `StateCallable`, the read-only leaves' shapes in its order, the
    compiled entry). Shapes in, nothing executed."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import unique_name
    from paddle_tpu.core.lowering import build_callable
    from paddle_tpu.framework import Program, program_guard
    main = Program()
    with program_guard(main, Program()):
        with unique_name.guard():
            v = build()
    block = main.global_block()
    one = SingleDeviceSharding(device)

    def sds(var, lead=1):
        shape = tuple(lead if s < 0 else s for s in var.shape)
        dt = jnp.dtype(str(var.dtype))
        return jax.ShapeDtypeStruct(
            shape, jnp.int32 if dt == jnp.int64 else dt, sharding=one)
    state = [x.name for x in block.vars.values() if x.persistable]
    fn, _, _ = build_callable(main, [v[fetch].name], state, list(pools))
    feeds = {n: x for n, x in block.vars.items()
             if n.startswith('gen_') and not x.persistable
             and any(n in names for op in block.ops
                     for names in op.inputs.values())
             and not any(n in names for op in block.ops
                         for names in op.outputs.values())}
    ro = tuple(sds(block.var(n)) for n in fn.ro_names)
    compiled = fn.lower_bound(
        {n: sds(x, lead=rows) for n, x in feeds.items()}, ro,
        tuple(sds(block.var(n)) for n in fn.rw_names),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one),
        [Format(Layout.AUTO, one)] * len(ro)).compile()
    return fn, ro, compiled


def default_layouts(leaves):
    """The layouts the chip gives entry parameters of `leaves`' shapes (and
    shardings) when nobody asks for another: those of a program that
    reads one element of each."""
    import jax
    compiled = jax.jit(lambda *xs: [x.ravel()[:1] for x in xs]).lower(
        *leaves).compile()
    return [f.layout for f in compiled.input_formats[0]]


def relaid(fn, leaves, compiled):
    """{name: {'shape', 'bytes', 'default', 'chosen'}} of the read-only
    leaves `compiled` (`lower_bound`) wants in another layout than the
    default."""
    out = {}
    chosen = [f.layout for f in compiled.input_formats[0][1]]
    for name, leaf, d, c in zip(fn.ro_names, leaves,
                                default_layouts(leaves), chosen):
        if c != d:
            out[name] = {
                'shape': list(leaf.shape),
                'bytes': leaf.dtype.itemsize * math.prod(leaf.shape),
                'default': list(d.major_to_minor),
                'chosen': list(c.major_to_minor)}
    return out


def weight_copies(text, leaves, least=1 << 20):
    """{'<dtype><dims>': count} of the `copy` instructions outside fusions'
    bodies in a compiled program's HLO `text` whose output has the shape
    of one of `leaves` (of `least` bytes or more): a weight laid out anew
    on every call — in the entry or, for the experts a chip holds a share
    of, in each branch of `grouped_ffn`'s conditional (one of the two
    runs a dispatch)."""
    shapes = {tuple(leaf.shape) for leaf in leaves
              if leaf.dtype.itemsize * math.prod(leaf.shape) >= least}
    found = collections.Counter()
    for _, kind, dtype, dims in poolscan._outputs(text):
        if kind.startswith('copy ') and dims in shapes:
            found['%s%s' % (dtype, list(dims))] += 1
    return dict(found)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True)
    ap.add_argument('--layers', type=int, help='override the depth')
    ap.add_argument('--only', help='programs to compile, comma-separated')
    args = ap.parse_args(argv)
    scope, disagree, device = {}, {}, None
    for key, _e, cfg, build, fetch, rows in poolscan.cell_programs(
            args.config, args.traffic, args.layers,
            args.only and args.only.split(',')):
        from paddle_tpu.models import transformer as T
        device = device or poolscan.one_chip()
        fn, leaves, compiled = lower_bound(build, fetch, rows, device,
                                           T.kv_cache_names(cfg))
        want = relaid(fn, leaves, compiled)
        ma = compiled.memory_analysis()
        print(json.dumps({
            'program': key, 'layers': cfg.n_layer,
            'read_only_leaves': len(leaves), 'relaid': want,
            'weight_copies': weight_copies(compiled.as_text(), leaves),
            'argument_gb': round(ma.argument_size_in_bytes / 1e9, 3),
            'temp_gb': round(ma.temp_size_in_bytes / 1e9, 3)}), flush=True)
        # the engine's order: who binds first chooses for the leaves it
        # reads, a later program takes them as they lie
        for name in fn.ro_names:
            asked = want.get(name, {}).get('chosen')
            got = scope.setdefault(name, asked)
            if asked != got:
                disagree.setdefault(key, {})[name] = {'asked': asked,
                                                      'gets': got}
    print(json.dumps({
        'program': 'cell',
        'relaid': {n: c for n, c in sorted(scope.items()) if c},
        'asked_otherwise': disagree}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
