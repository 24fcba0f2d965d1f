"""The grouped expert matmul states its tiles (ISSUE 50): `grouped_ffn`'s
`lax.ragged_dot`s carry `ragged_dot_tiling`, chosen by
`grouped_matmul_tiling` from the call's rows, K, N and precision.

- the rule's invariants over a grid of shapes, and its answers at the
  expert cells' shapes (Qwen3-Next's since PR 55: 640 to 5 120 assignments
  of which an eighth are held);
- on the CPU the attribute is carried and ignored: `grouped_ffn` with and
  without it is the same bit for bit, forward and gradient, and the
  gradient's own grouped matmuls state nothing;
- the counter's labels after lowering a toy step;
- Mosaic, without a chip: `grouped_ffn` at the cells' widths compiled for a
  described v5e, every `%ragged-dot-none` carrying exactly the rule's
  tiling in both branches of the `cond` -- and XLA's own 256,128,128 at
  Nemotron's widths with nothing stated, the finding the rule answers. A
  libtpu that drops the attribute fails here, not in a cell's numbers.
"""
import contextlib
import itertools
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import monitor
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.moe_ops import (grouped_ffn, grouped_matmul_tiling,
                                    tiling_label as _label)

from benchmark.models import nemotron, olmoe

from test_paged_decode_attention import _serving_program

HERE = os.path.dirname(os.path.abspath(__file__))

# a decode step's (or a prefill bucket's) expert layer in each cell: rows,
# experts a row, experts held, experts routed over, d_model, expert width,
# gated, the precision its programs state
CELLS = {
    'olmoe-serve-chat16': (16, 8, 64, 64, 2048, 1024, True, None),
    'joyai-serve-longchat64': (64, 8, 64, 256, 2048, 768, True, None),
    'lfm2-serve-agent64': (64, 4, 32, 32, 2048, 1792, True, None),
    'kexaone-serve-mixed64': (64, 8, 8, 128, 6144, 2048, True, None),
    # the step's 128 slots and the b128 bucket give the experts the same rows
    'nemotron3-serve-reason128': (128, 6, 16, 128, 2688, 1856, False,
                                  'highest'),
    'nemotron3-serve-reason128-b256': (256, 6, 16, 128, 2688, 1856, False,
                                       'highest'),
    'nemotron3-serve-reason128-b512': (512, 6, 16, 128, 2688, 1856, False,
                                       'highest'),
    # 64 of 512 experts held, 10 a row: the step's 640 assignments (its cap
    # 128 rows) and the three prefill buckets' (5 120 at 512 rows, cap 1 024)
    'qwen3next-serve-longmix64': (64, 10, 64, 512, 2048, 512, True,
                                  'highest'),
    'qwen3next-serve-longmix64-b128': (128, 10, 64, 512, 2048, 512, True,
                                       'highest'),
    'qwen3next-serve-longmix64-b256': (256, 10, 64, 512, 2048, 512, True,
                                       'highest'),
    'qwen3next-serve-longmix64-b512': (512, 10, 64, 512, 2048, 512, True,
                                       'highest'),
}


def _precision(name):
    return jax.default_matmul_precision(name) if name \
        else contextlib.nullcontext()


def _rows_given(cell):
    """The rows `grouped_ffn` gives its grouped matmuls in a cell's layer:
    all n * k assignments, and before them the `cap` where a share of the
    experts is held and the cap is fewer (the conditional's two branches)."""
    n, k, held, routed = CELLS[cell][:4]
    cap = -(-3 * n * k * held // (2 * routed * 128)) * 128
    return [n * k] if held == routed or cap >= n * k else [cap, n * k]


# ---- 1. the rule ------------------------------------------------------------

_WIDTHS = (512, 768, 1024, 1792, 1856, 2048, 2688, 6144)


@pytest.mark.parametrize('passes', (1, 6))
@pytest.mark.parametrize('rows', (8, 24, 96, 128, 256, 384, 512, 640, 768,
                                  1024, 6144))
def test_a_stated_tiling_is_one_the_kernel_takes(rows, passes):
    for k, n in itertools.product(_WIDTHS, _WIDTHS):
        tiling = grouped_matmul_tiling(rows, k, n, passes)
        if tiling is None:
            continue
        tm, tk, tn = tiling
        assert tm % 8 == 0 and rows % tm == 0, (k, n, tiling)
        assert tk == k or (tk % 128 == 0 and k % tk == 0), (k, n, tiling)
        assert tn % 128 == 0 and tn < n + 128, (k, n, tiling)
        # the blocks in flight: rows and weights double-buffered, the
        # output block and its accumulator
        assert 4 * (2 * (tm * tk + tk * tn) + 2 * tm * tn) \
            <= moe_ops._TILE_VMEM_BYTES, (k, n, tiling)
        # a pair's MXU time under its bytes' time, or the smallest row tile
        assert tm == 8 or tm * 2 * passes / moe_ops._MXU_FLOPS \
            <= 4 / moe_ops._HBM_BYTES_PER_S, (k, n, tiling)


@pytest.mark.parametrize('rows,k,n', [(12, 2048, 1024), (128, 64, 32),
                                      (128, 2048, 100), (16, 100, 256)])
def test_shapes_the_kernel_has_no_whole_tiles_for_state_nothing(rows, k, n):
    assert grouped_matmul_tiling(rows, k, n, 1) is None


def test_the_rules_answers_at_the_cells_shapes():
    """Held here so that a change of a constant shows as a change of a
    cell's tiles (PERF.md, PR 50, has the sweep that set them)."""
    got = {}
    for cell, (_n, _k, _h, _r, d, width, _g, precision) in CELLS.items():
        rows = _rows_given(cell)[0]
        passes = 6 if precision else 1
        got[cell] = (_label(grouped_matmul_tiling(rows, d, width, passes)),
                     _label(grouped_matmul_tiling(rows, width, d, passes)))
    assert got == RULE_AT_THE_CELLS, got


# (up and gate, down) at the rows the decode step's `cap` gives
RULE_AT_THE_CELLS = {
    'olmoe-serve-chat16': ('xla', 'xla'),
    'joyai-serve-longchat64': ('256,1024,768', '256,768,1024'),
    'lfm2-serve-agent64': ('256,2048,256', '256,256,2048'),
    'kexaone-serve-mixed64': ('xla', 'xla'),
    'nemotron3-serve-reason128': ('64,2688,384', '64,1856,384'),
    'nemotron3-serve-reason128-b256': ('64,2688,384', '64,1856,384'),
    'nemotron3-serve-reason128-b512': ('80,2688,384', '80,1856,384'),
    'qwen3next-serve-longmix64': ('64,2048,512', '64,512,2048'),
    'qwen3next-serve-longmix64-b128': ('64,2048,512', '64,512,2048'),
    'qwen3next-serve-longmix64-b256': ('64,2048,512', '64,512,2048'),
    'qwen3next-serve-longmix64-b512': ('64,2048,512', '64,512,2048'),
}


def test_the_precision_in_force_says_the_passes():
    assert moe_ops.matmul_passes() == 1
    with jax.default_matmul_precision('highest'):
        assert moe_ops.matmul_passes() == 6
    with jax.default_matmul_precision('bfloat16'):
        assert moe_ops.matmul_passes() == 1


# ---- 2. on the CPU the attribute changes nothing ----------------------------

def _layer(rng, n, k, held, routed, d, width, gated):
    x = rng.randn(n, d).astype('float32')
    w = rng.rand(n, k).astype('float32')
    idx = np.stack([rng.permutation(routed)[:k] for _ in range(n)]) \
        .astype('int32')
    mats = [None if not gated else
            rng.randn(held, d, width).astype('float32') * d ** -0.5,
            rng.randn(held, d, width).astype('float32') * d ** -0.5,
            rng.randn(held, width, d).astype('float32') * width ** -0.5]
    first = None if held == routed else 2
    return (x, w, idx) + tuple(mats), first


@pytest.mark.parametrize('gated,held,routed,precision', [
    (True, 8, 8, None), (False, 4, 16, 'highest'), (True, 4, 16, None)])
def test_grouped_ffn_is_bit_for_bit_what_it_was(monkeypatch, gated, held,
                                                routed, precision):
    """Forward and gradient, with the rule's tilings stated and with the
    rule silenced: the same bits (the CPU's lowering ignores the
    attribute), through both shapes of the conditional."""
    args, first = _layer(np.random.RandomState(3), 64, 4, held, routed, 256,
                         128, gated)

    def loss(x, w, idx, gate_w, up_w, down_w):
        y = grouped_ffn(x, w, idx, gate_w, up_w, down_w, first, routed)
        return jnp.sum(y * y), y

    def both():
        with _precision(precision):
            argnums = (0, 1, 4, 5) + ((3,) if gated else ())
            (_, y), grads = jax.jit(jax.value_and_grad(
                loss, argnums=argnums, has_aux=True))(*args)
        return [np.asarray(y)] + [np.asarray(g) for g in grads]

    before = monitor.counters()
    stated = both()
    moved = monitor.counter_delta(before)
    assert any(k.startswith('moe_grouped_matmul_tiling_total{tiling=')
               and not k.endswith('=xla}') for k in moved), moved
    monkeypatch.setattr(moe_ops, 'grouped_matmul_tiling',
                        lambda *a: None)
    plain = both()
    for a, b in zip(stated, plain):
        np.testing.assert_array_equal(a, b)


def test_the_gradients_grouped_matmuls_state_nothing():
    """An equation differentiated under `set_xla_metadata` hands the
    attribute to its transpose: the backward's matmuls have other shapes
    ([rows, N] x [E, N, K]) and would be compiled with the forward's tiles
    (device-less: RESOURCE_EXHAUSTED at Nemotron's widths)."""
    args, first = _layer(np.random.RandomState(4), 64, 4, 8, 8, 256, 128,
                         False)

    def loss(x, up_w, down_w):
        return jnp.sum(jnp.square(grouped_ffn(
            x, args[1], args[2], None, up_w, down_w, first, 8)))
    x, up_w, down_w = args[0], args[4], args[5]

    def tagged(f):
        # the CPU spells a grouped matmul as masked dense products
        return len([line for line in jax.jit(f).lower(x, up_w, down_w)
                    .as_text().splitlines()
                    if 'dot_general' in line and 'ragged_dot_tiling' in line])
    assert tagged(loss) > 0
    assert tagged(jax.grad(loss, argnums=(0, 1, 2))) == tagged(loss)


# ---- 3. the counter ---------------------------------------------------------

def _toy(name, **over):
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-%s.json' % name)) as f:
        return dict(json.load(f), **over)


@pytest.mark.parametrize('family,toy,slots,want', [
    # MEM*E: two expert layers, ungated, 4 of 8 experts held, `highest`:
    # 56 slots x 3 = 168 assignments, the cap 128 of them -- two branches
    # of two matmuls a layer
    ('nemotron', dict(hidden_size=256, moe_intermediate_size=128), 56,
     {(128, 256, 128, 6): 2, (128, 128, 256, 6): 2,
      (168, 256, 128, 6): 2, (168, 128, 256, 6): 2}),
    # the toy's own widths (64, 32): narrower than a lane tile
    ('nemotron', {}, 56, {None: 8}),
    # 2 layers of 8 experts, all held, gated: three matmuls a layer
    ('olmoe', dict(hidden_size=256, intermediate_size=128), 8,
     {(16, 256, 128, 1): 4, (16, 128, 256, 1): 2}),
])
def test_a_lowered_step_counts_each_call_sites_tiling(family, toy, slots,
                                                      want):
    """`moe_grouped_matmul_tiling_total{tiling}`: + 1 a `ragged_dot` call
    site lowered, the label the rule's answer for the site's shapes."""
    builder = {'nemotron': nemotron, 'olmoe': olmoe}[family]
    cfg = builder.lm_config(_toy(family, **toy), 64, False)
    fn, args = _serving_program(
        lambda: T.build_lm_decode_step(cfg, slots, 64, block_size=8,
                                       num_blocks=2 * slots),
        'next_tokens', slots)
    before = monitor.counters()
    jax.eval_shape(fn, *args)
    moved = {k: n for k, n in monitor.counter_delta(before).items()
             if k.startswith('moe_grouped_matmul_tiling_total')}
    expect = {}
    for shape, sites in want.items():
        key = 'moe_grouped_matmul_tiling_total{tiling=%s}' % _label(
            shape and grouped_matmul_tiling(*shape))
        expect[key] = expect.get(key, 0) + sites
    assert moved == expect, moved
    if toy:
        assert any(not k.endswith('=xla}') for k in moved), moved


def test_the_sweep_that_set_the_constants_runs():
    """tools/kernbench.py's `grouped_matmul` case at its toy shapes: XLA's
    choice, the rule's and a given tiling a column, each against the
    float64 product."""
    from tools.kernbench import measure_grouped_matmul
    out = measure_grouped_matmul('small', 1, 2, tilings=['8,128,128'],
                                 shapes=['toy up'])
    (label, row), = out.items()
    assert label == 'toy up 16 x [4, 128, 256] highest'
    assert set(row) == {'xla', 'rule: 16,128,256', '8,128,128'}
    for col in row.values():
        assert col['ms'] > 0 and col['gb_per_s'] > 0
        assert col['max_err'] < 1e-5        # float32 rounding at `highest`


# ---------------------------------------------------------------------------
# Mosaic, without a chip: the TPU compiler against a described v5e. The
# topology is described inside a fixture, never at import (one process at a
# time may load libtpu: under xdist only this file's worker does).

@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for k, v in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
                 ('TPU_WORKER_HOSTNAMES', 'localhost'),
                 ('TPU_SKIP_MDS_QUERY', '1')):
        os.environ.setdefault(k, v)
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


_CALL = re.compile(r'^\s*%ragged-dot-none[.\d]* = f32\[(\d+),(\d+)\]')
_TILING = re.compile(r'ragged_dot_tiling="([0-9,]+)"')


def _compiled_tilings(one_chip, cell):
    """`grouped_ffn` at a cell's widths compiled for the described chip:
    [((rows, N), tiling)] of its `%ragged-dot-none` custom calls."""
    n, k, held, routed, d, width, gated, precision = CELLS[cell]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    first = None if held == routed else 0

    def ffn(x, w, idx, gate_w, up_w, down_w):
        return grouped_ffn(x, w, idx, gate_w, up_w, down_w, first, routed)
    with _precision(precision):
        text = jax.jit(ffn).lower(
            sds((n, d)), sds((n, k)), sds((n, k), jnp.int32),
            sds((held, d, width)) if gated else None,
            sds((held, d, width)), sds((held, width, d))) \
            .compile().as_text()
    calls = []
    for line in text.splitlines():
        m = _CALL.match(line)
        if m:
            found = _TILING.search(line)
            calls.append(((int(m.group(1)), int(m.group(2))),
                          found and found.group(1)))
    return calls


@pytest.mark.parametrize('cell', list(CELLS))
def test_xla_takes_the_rules_tiling_at_the_cells_shapes(one_chip, cell):
    """Every grouped matmul of the layer, in both branches of the
    conditional, is compiled with exactly the tiling the rule states for
    its rows, K and N (XLA prints the tiling it used on the custom call
    whether it was given or chosen), and Mosaic accepts each."""
    d, width, gated, precision = CELLS[cell][4:]
    calls = _compiled_tilings(one_chip, cell)
    branches = _rows_given(cell)
    assert len(calls) == len(branches) * (3 if gated else 2), calls
    passes = 6 if precision else 1
    for (rows, out), tiling in calls:
        assert rows in branches and out in (d, width), calls
        want = grouped_matmul_tiling(rows, width if out == d else d, out,
                                     passes)
        if want is None:
            # what the rule takes XLA's own choice to be, where it leaves
            # the choice to XLA: the largest of 512 / 256 / 128 that divides
            want = tuple(next(t for t in moe_ops._XLA_TILES if x % t == 0)
                         for x in (rows, width if out == d else d, out))
        assert tiling == _label(want), (calls, rows, out, want)


def test_xla_left_alone_tiles_nemotrons_experts_by_128(one_chip,
                                                       monkeypatch):
    """The finding: 2688 = 21 x 128 and 1856 = 14.5 x 128 leave XLA's
    choice among {512, 256, 128} at 128 x 128 -- 64 KB of weights a grid
    step, 5 040 steps a matmul. When this stops holding, the rule's case
    for Nemotron is to be measured again."""
    monkeypatch.setattr(moe_ops, 'grouped_matmul_tiling', lambda *a: None)
    calls = _compiled_tilings(one_chip, 'nemotron3-serve-reason128')
    assert sorted(calls) == [((256, 1856), '256,128,128'),
                             ((256, 2688), '256,128,128'),
                             ((768, 1856), '256,128,128'),
                             ((768, 2688), '256,128,128')], calls


# ---------------------------------------------------------------------------
# The other GEMM whose tiles were not its own (ISSUE 54): a weight-gradient
# GEMM with Adam's update as its epilogue. It lives in this file for the
# fixture: one file's worker loads libtpu.

def _lm(d_model, heads, d_ff, vocab, seq_len):
    return T.LMConfig(vocab_size=vocab, seq_len=seq_len, d_model=d_model,
                      n_head=heads, n_layer=1, d_ff=d_ff, dropout=0.1,
                      attn_dropout=0.0, use_flash_attention=True)


def test_no_weight_gradient_gemm_is_tiled_round_adams_update(one_chip,
                                                            monkeypatch):
    """The finding, not the implementation: a 1-layer LM train step at
    `fd355m-train-2k`'s widths (d_model 1024, d_ff 4096, 4 x 2048 tokens;
    a small vocabulary) under `mp.decorate(Adam(fuse=False))`, compiled
    for the described chip. With the update as their epilogue XLA:TPU
    tiled the QKV, FFN and head dW GEMMs round six float32 streams, at
    1.5-3.9 x their forward twins' cycles (PERF.md, PR 54). Now NO fusion
    holds a `convolution` and writes more than one float32 array of a
    parameter's shape (the update's parameter and two moments) — but the
    one whose epilogue is small enough to cost the GEMM nothing,
    `attn.proj.w`'s 1 M elements, which the compiler itself puts at its
    forward twin's cycles."""
    from tools.fusioncost import conv_fusions, lm_train_step_hlo
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')   # the chip's tier
    d, f, v = 1024, 4096, 2048
    rows = conv_fusions(lm_train_step_hlo(one_chip, _lm(d, 16, f, v, 2048),
                                          4))
    matrices = {'f32[%d,%d]' % s for s in
                ((d, 3 * d), (d, d), (d, f), (f, d), (d, v), (v, d))}

    def held(r):
        return [o for o in r['outputs'] if o in matrices]
    # qkv, ffn1, ffn2 and the head: each dW GEMM a fusion of its own, and
    # the compiler says what each costs
    alone = [r for r in rows if len(held(r)) == 1
             and r['dim_labels'][0].startswith('fb_')]
    assert {held(r)[0] for r in alone} >= {
        'f32[%d,%d]' % s for s in ((d, 3 * d), (d, f), (f, d), (d, v))}, rows
    assert all(r['estimated_cycles'] and r['windows'] for r in alone), alone
    with_update = [r for r in rows if len(held(r)) > 1]
    assert [set(held(r)) for r in with_update] == [{'f32[1024,1024]'}], \
        with_update
    twin, = [r for r in rows if r['outputs'] == ['f32[4,2048,1024]']
             and r['name'].startswith('convolution_add_fusion')]
    assert with_update[0]['estimated_cycles'] \
        <= 1.1 * twin['estimated_cycles'], (with_update, twin)


def test_no_transpose_round_the_flash_kernels_in_the_train_step(
        one_chip, monkeypatch):
    """ISSUE 57: the same 1-layer step at `fd355m-train-2k`'s widths. The
    three flash kernels read the fused QKV product `bf16[4,2048,3072]` as
    the projection's fusion leaves it and write `[4,2048,1024]` arrays as
    `attn.proj` and the dX / dW GEMMs read them: NO array of the compiled
    step is head-major (`[.., 16, 2048, 64]`, `[64, 2048, 64]`: the parent's
    step held 289 mentions of one at two layers), no `[4, 2048, ..]`
    activation is copied or transposed, and at most two fusions write an
    array of the product's shape -- the bias-and-cast behind the QKV GEMM
    and the ONE concatenate of dQ, dK and dV."""
    from tools.fusioncost import lm_train_step_hlo
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')   # the chip's tier
    text = lm_train_step_hlo(one_chip, _lm(1024, 16, 4096, 2048, 2048), 4)
    assert not re.findall(r'\[(?:\d+,)*16,2048,64\]|\[64,2048,64\]', text)
    entry = text[text.index('ENTRY '):].splitlines()
    shape_of = dict(m.groups() for m in (
        re.match(r'\s*(?:ROOT )?(%[\w.\-]+) = (\(?\w+\[[\d,]*\])', line)
        for line in entry) if m)
    kernels = [line for line in entry if 'tpu_custom_call' in line
               and 'flash_attention' in line]
    assert len(kernels) == 3, kernels
    for line in kernels:
        name, first = re.match(r'\s*(%[\w.\-]+) = .*? custom-call\((%[\w.\-]+)',
                               line).groups()
        assert shape_of[first] == 'bf16[4,2048,3072]', line
        assert shape_of[name].lstrip('(') == 'bf16[4,2048,1024]', line
    assert not [line for line in entry if re.search(
        r'= (?:bf16|f32)\[4,2048,\d+\]\S* (?:copy|transpose)\(', line)]
    wide = [line for line in entry if re.search(
        r'= bf16\[4,2048,3072\]\S* fusion\(', line)]
    assert len(wide) <= 2, wide


def test_fusioncost_prints_the_table_at_toy_width(one_chip, monkeypatch,
                                                  capsys):
    """tools/fusioncost.py end to end: one JSON line a fusion that holds a
    convolution, with XLA's own cycles and window bounds."""
    from tools import fusioncost
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'pallas')
    assert fusioncost.main(['--layers', '1', '--d-model', '128', '--heads',
                            '2', '--d-ff', '256', '--vocab', '512',
                            '--sequences', '2', '--seq-len', '128']) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith('{')]
    assert rows and all(set(r) == {'name', 'kind', 'outputs', 'dim_labels',
                                   'estimated_cycles', 'windows'}
                        for r in rows), rows
    assert any(r['estimated_cycles'] for r in rows), rows
    assert any('f32[128,256]' in r['outputs'] for r in rows), rows
