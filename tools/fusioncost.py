"""XLA's own account of the GEMM fusions of an LM train step, on no chip.

    JAX_PLATFORMS=cpu python3 tools/fusioncost.py \
        [--layers 2] [--d-model 1024] [--heads 16] [--d-ff 4096] \
        [--vocab 50264] [--sequences 4] [--seq-len 2048] [--amp bf16|none] \
        [--hlo <file>]

Builds `build_lm` + `Adam(fuse=False)` (under `mp.decorate` with `--amp
bf16`: the recipe of benchmark/drivers/train.py; the defaults are
`fd355m-train-2k`'s widths at 2 layers), lowers the step the way
`Executor.run` does (`lowering.build_callable`, the read-written state
donated) for one chip of the device-less `v5e:2x2` topology, compiles it
with the real XLA:TPU and prints one line a fusion that holds a
`convolution`: its name, kind, output shapes, the convolution's
`dim_labels` and the `estimated_cycles` and window bounds XLA:TPU writes
into the fusion's `backend_config` — the compiler's own cost model and
tiling. A weight-gradient GEMM reads `fb_io->bf` (the contraction over the
tokens); a forward GEMM of the same three dimensions is its twin to compare
with (ISSUE 54's table: with Adam's three float32 streams as its epilogue a
dW GEMM was tiled at 1.5-3.9 x its twin's cycles).

Nothing runs: a cycle count is the compiler's estimate, never a time.
"""
import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

_COMPUTATION = re.compile(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$')
_FUSION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) fusion\(.*?kind=(\w+), '
    r'calls=%?([\w.\-]+)')
_SHAPE = re.compile(r'\b(?:pred|[su]\d+|bf16|f16|f32|f64)\[[\d,]*\]')
_WINDOW = re.compile(r'"(kernel|output|input)_window_bounds":\[([^\]]*)\]')


def conv_fusions(text):
    """Every fusion of one compiled program's HLO `text` whose body holds a
    `convolution`, in the program's order: dicts of `name`, `kind`,
    `outputs` (the shapes it writes), `dim_labels` (one a convolution),
    `estimated_cycles` and `windows` ({'kernel' | 'output' | 'input':
    bounds}; None / {} where the compiler printed none)."""
    labels, inside = {}, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            inside = head.group(1)
        elif ' convolution(' in line:
            found = re.search(r'dim_labels=([\w\->]+)', line)
            labels.setdefault(inside, []).append(found and found.group(1))
    rows = []
    for line in text.splitlines():
        m = _FUSION.match(line)
        if not m or m.group(4) not in labels:
            continue
        name, out, kind, body = m.groups()
        cycles = re.search(r'"estimated_cycles":"?(\d+)', line)
        rows.append({
            'name': name, 'kind': kind, 'outputs': _SHAPE.findall(out),
            'dim_labels': labels[body],
            'estimated_cycles': cycles and int(cycles.group(1)),
            'windows': {k: [int(s.strip('" ')) for s in v.split(',') if s]
                        for k, v in _WINDOW.findall(line)}})
    return rows


def lm_train_step_hlo(one_chip, lm, sequences, amp=True):
    """The compiled HLO text of one train step of `build_lm(lm)` under
    `Adam(fuse=False)` (`mp.decorate`d with `amp`), for the described chip
    `one_chip`: shapes in, nothing executed."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu.core import lowering
    from paddle_tpu.models.transformer import build_lm

    main_p, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard():
        with fluid.program_guard(main_p, startup):
            _t, _l, _logits, avg_loss = build_lm(lm)
            opt = fluid.optimizer.Adam(learning_rate=1e-4, fuse=False)
            (mp.decorate(opt) if amp else opt).minimize(avg_loss)
    feed = {k: jax.ShapeDtypeStruct((sequences, lm.seq_len), jnp.int32,
                                    sharding=one_chip)
            for k in ('tokens', 'labels')}
    read, written = lowering.analyze_state(main_p, [avg_loss.name])
    needed = fluid.Executor._read_before_write(main_p, read, written,
                                               set(feed), [avg_loss.name])
    fn, ro, rw = lowering.build_callable(main_p, [avg_loss.name], needed,
                                         written)
    block = main_p.global_block()

    def state(names):
        out = {}
        for n in names:
            v = block._find_var_recursive(n)
            out[n] = jax.ShapeDtypeStruct(tuple(v.shape),
                                          jnp.dtype(str(v.dtype)),
                                          sharding=one_chip)
        return out
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    return fn.lower(feed, state(ro), state(rw), key).compile().as_text()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--layers', type=int, default=2)
    ap.add_argument('--d-model', type=int, default=1024)
    ap.add_argument('--heads', type=int, default=16)
    ap.add_argument('--d-ff', type=int, default=4096)
    ap.add_argument('--vocab', type=int, default=50264)
    ap.add_argument('--sequences', type=int, default=4)
    ap.add_argument('--seq-len', type=int, default=2048)
    ap.add_argument('--amp', choices=('bf16', 'none'), default='bf16')
    ap.add_argument('--hlo', help='write the compiled HLO text here')
    args = ap.parse_args(argv)
    # the chip's tier: this process sees the CPU and would lower the
    # unfused compositions
    os.environ.setdefault('PADDLE_FUSED_TIER', 'pallas')
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.models.transformer import LMConfig
    from tools.poolscan import one_chip
    lm = LMConfig(vocab_size=args.vocab, seq_len=args.seq_len,
                  d_model=args.d_model, n_head=args.heads,
                  n_layer=args.layers, d_ff=args.d_ff, dropout=0.1,
                  attn_dropout=0.0, use_flash_attention=True)
    text = lm_train_step_hlo(SingleDeviceSharding(one_chip()), lm,
                             args.sequences, amp=args.amp == 'bf16')
    if args.hlo:
        with open(args.hlo, 'w') as f:
            f.write(text)
    for row in conv_fusions(text):
        print(json.dumps(row))
    return 0


if __name__ == '__main__':
    sys.exit(main())
