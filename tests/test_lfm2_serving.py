"""The LFM2-MoE block in the Program path (ISSUE 35): gated short
convolutions whose tails live in the block pool beside the K/V of the
attention layers, grouped-query attention, per-head q/k-norm, a tied head
and the 1e-6 router — the two new ops against a plain convolution, the
grouped-query kernel against the gather, prefill-then-decode through the
three pools against the plain reference's FULL forward pass (logits, not
tokens), chunked against unchunked, a suffix behind a prefix hit against
the prompt prefilled whole, an evicted block recomputed, the controls, the
counters, the parent's listings and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-lfm2.json): d 64,
8 query heads on 2 K/V heads of 8, 6 layers (conv conv attn conv attn
conv, 2 dense), 8 experts of width 32, top-2, 3 taps, seeded weights.
"""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import Scope, monitor, unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import lfm2
from benchmark.reference import lfm2_control, lfm2_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import LISTED, lower, serve_five, tap_logits
from test_paged_decode_attention import _attend, _pools, _serving_program

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-lfm2.json')) as _f:
    TOY = json.load(_f)

# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 2e-7 to 9e-7 over every comparison below); the controls move the
# logits by 4e-3 (bfloat16) to 1.2 (the head untied).
TOLERANCE = 1e-4


def _scope(m=TOY, seed=5):
    """Seeded weights; the experts four times larger, so that a wrong
    choice of expert or weight moves the logits (test_joyai_serving.py)."""
    scope = Scope()
    for name, value in lfm2.init_params(m, seed).items():
        big = '.moe.' in name and 'router' not in name
        scope.set(name, value * (4.0 if big else 1.0))
    return scope


def _drive(eng, prompts, n_new):
    """submit all, then admit / step by hand until every one has ended."""
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, n_new)]
    eng._admit()
    while any(r.finish_reason is None and r._error is None for r in reqs):
        eng._step()
        eng._admit()
    return [list(r.result(timeout=5)) for r in reqs]


def _engine(scope=None, buckets=(16, 32), **kw):
    kw.setdefault('block_size', 8)
    return GenerateEngine(GenerateConfig(
        model=lfm2.lm_config(TOY, 64, False), slots=4, max_len=64,
        prompt_buckets=list(buckets), eos_id=None, seed=3, **kw),
        scope=scope if scope is not None else _scope())


# ---- 1. the convolution's two ops -------------------------------------------

def _plain_conv(g, w):
    """c_t = sum_j w[:, j] g_{t - 2 + j}, g before 0 zero."""
    ext = np.concatenate([np.zeros((w.shape[1] - 1, g.shape[1]), g.dtype), g])
    return sum(ext[j:j + len(g)] * w[:, j] for j in range(w.shape[1]))


@pytest.mark.parametrize('chunks', [(21,), (16, 5), (8, 8, 5), (3, 8, 10)],
                         ids=['whole', 'block-edge', 'three', 'unaligned'])
def test_a_prefill_in_chunks_resumes_from_the_blocks_entry(chunks):
    """21 rows (block 8: two whole blocks and five rows) in one dispatch
    or several, each padded to its bucket: the same convolution, and the pool's
    entries are g of the last two rows written into each block; the pad
    rows' block goes to the trash."""
    rng = np.random.RandomState(0)
    d, bs, T = 16, 8, 24 if max(chunks) > 16 else 16
    g = rng.randn(21, d).astype('float32')
    w = rng.randn(d, 3).astype('float32')
    cache = rng.randn(6, 2, 2, d).astype('float32')     # stale everywhere
    table = np.array([[3, 1, 4, 0]], 'int32')
    want = _plain_conv(g, w)
    off, got = 0, []
    for n in chunks:
        x = np.zeros((1, T, d), 'float32')
        x[0, :n] = g[off:off + n]
        out = lower('short_conv_prefill_paged',
                    {'layer': 1, 'block_size': bs}, X=x, Weight=w,
                    Cache=cache, Positions=(off + np.arange(T))[None],
                    BlockTable=table, Length=np.array([[n]]))
        cache = out['CacheOut']
        got.append(out['Out'][0, :n])
        off += n
    np.testing.assert_allclose(np.concatenate(got), want, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(cache[3, 1], g[6:8])
    np.testing.assert_array_equal(cache[1, 1], g[14:16])
    np.testing.assert_array_equal(cache[4, 1], g[19:21])
    # layer 0 of every block, and the blocks the table does not name,
    # are as they were; block 0 is the trash
    assert np.abs(cache[[3, 1, 4], 0]).min() > 0


def test_a_decode_step_reads_the_block_of_the_row_before_and_writes_its_own():
    rng = np.random.RandomState(1)
    d, bs = 16, 8
    w = rng.randn(d, 3).astype('float32')
    g = rng.randn(12, d).astype('float32')
    cache = rng.randn(6, 2, 2, d).astype('float32')
    tables = np.array([[2, 5, 0], [0, 0, 0], [4, 0, 0]], 'int32')
    want = _plain_conv(g, w)
    for p in range(12):
        # slot 0 walks the sequence; slot 1 is idle; slot 2 sits at
        # position 0 of a block with stale entries: it reads zeros
        out = lower('short_conv_decode_paged',
                    {'layer': 0, 'block_size': bs},
                    X=np.stack([g[p], g[0], g[0]]), Weight=w, Cache=cache,
                    Positions=np.array([[p], [0], [0]]), BlockTables=tables)
        cache = out['CacheOut']
        np.testing.assert_allclose(out['Out'][0], want[p], rtol=1e-6,
                                   atol=1e-6)
        np.testing.assert_allclose(out['Out'][2], want[0], rtol=1e-6,
                                   atol=1e-6)
        blk = tables[0, p // bs]
        np.testing.assert_array_equal(
            cache[blk, 0], np.concatenate([np.zeros((2, d), 'float32'),
                                           g])[p + 1:p + 3])
    np.testing.assert_array_equal(cache[2, 0], g[6:8])   # final since p = 7


# ---- 2. grouped-query attention ---------------------------------------------

@pytest.mark.parametrize('H,Hkv', [(32, 8), (8, 8)], ids=['32on8', '8on8'])
def test_the_grouped_query_kernel_matches_the_gather(monkeypatch, H, Hkv):
    """The kernel (interpreted) against the gather formulation, and the
    gather against the K/V heads repeated in full: pages of 8 K/V heads
    serve 32 query heads, and at 8 on 8 it is the kernel it was."""
    S, bs, dh, MB, nb, layer = 6, 8, 64, 5, 24, 1
    assert pda.shapes_ok(H, dh, bs, Hkv)
    rng = np.random.RandomState(H)
    kc, vc = _pools(rng, nb, 2, bs, Hkv * dh)
    q = rng.randn(S, H, dh).astype('float32')
    tables = rng.randint(1, nb, size=(S, MB)).astype('int32')
    pos = np.array([0, bs - 1, bs, MB * bs - 1, 0, 2 * bs + 3], 'int32')
    tables[4] = 0
    tables[5, :2] = tables[3, :2]
    args = (q, kc, vc, tables, pos, layer, bs)
    off = _attend('off', monkeypatch, *args)
    np.testing.assert_allclose(_attend('interpret', monkeypatch, *args), off,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(_attend('xla', monkeypatch, *args), off,
                               rtol=2e-5, atol=2e-6)
    # query head h reads K/V head h // (H // Hkv): the pools widened to H
    # heads by repeating give the same through the one-to-one path
    G = H // Hkv
    wide = [np.repeat(c.reshape(nb, 2, bs, Hkv, dh), G, axis=3)
            .reshape(nb, 2, bs, H * dh) for c in (kc, vc)]
    np.testing.assert_allclose(
        _attend('off', monkeypatch, q, wide[0], wide[1], tables, pos, layer,
                bs), off, rtol=2e-5, atol=2e-6)


def _trace_decode_step(cfg, slots=4):
    """`build_lm_decode_step(cfg)` traced at its shapes (`jax.eval_shape`:
    every op's lowering runs and counts, nothing executes)."""
    import jax
    fn, args = _serving_program(
        lambda: T.build_lm_decode_step(cfg, slots, 64, block_size=8,
                                       num_blocks=16),
        'next_tokens', slots)
    jax.eval_shape(fn, *args)


@pytest.mark.parametrize('model,form,layers', [
    ('lfm2', 'mxu', 2), ('fairseq-dense', 'vpu', 3)])
def test_the_decode_program_counts_the_kernels_body_once_a_layer(
        monkeypatch, model, form, layers):
    """`paged_decode_attention_form_total{form}`: + 1 for each attention
    layer lowered to the kernel, `mxu` where the head counts make it take
    grouped queries (an LFM2-shaped block: 8 query heads on 2 K/V heads of
    64, conv conv attn conv attn conv) and `vpu` for the fairseq-dense
    block; the choice is the shapes', no field of the configuration names
    it. The toy widths above (heads of 8) fall to `xla` and count none."""
    monkeypatch.setenv('PADDLE_FUSED_TIER', 'interpret')
    common = dict(vocab_size=64, seq_len=64, dropout=0.0, attn_dropout=0.0)
    if model == 'lfm2':
        cfg = LMConfig(d_model=512, n_head=8, n_kv_head=2, n_layer=6,
                       layer_types=['conv', 'conv', 'attention', 'conv',
                                    'attention', 'conv'],
                       d_ff=64, norm='rms_norm', position='rope',
                       qk_norm='head', bias=False, tie_embeddings=True,
                       ffn='moe', n_dense_layers=2, n_experts=4,
                       experts_per_token=2, expert_width=32, **common)
    else:
        cfg = LMConfig(d_model=128, n_head=2, n_layer=3, d_ff=64,
                       use_flash_attention=False, **common)
    before = monitor.counters()
    _trace_decode_step(cfg)
    moved = monitor.counter_delta(before)
    assert {k: n for k, n in moved.items()
            if k.startswith('paged_decode_attention_form_total')} == \
        {'paged_decode_attention_form_total{form=%s}' % form: layers}, moved
    assert moved['fused_kernel_dispatch_total{impl=interpret,mesh=1,'
                 'op=kv_decode_attention_paged}'] == layers
    before = monitor.counters()
    _trace_decode_step(lfm2.lm_config(TOY, 64, False))
    moved = monitor.counter_delta(before)
    assert not any(k.startswith('paged_decode_attention_form_total')
                   for k in moved), moved


def test_a_prefix_attention_of_grouped_queries_repeats_no_key():
    rng = np.random.RandomState(2)
    H, Hkv, dh, bs, T, nb = 8, 2, 8, 8, 16, 7
    kc, vc = _pools(rng, nb, 2, bs, Hkv * dh)
    q = rng.randn(1, H, T, dh).astype('float32')
    table = np.array([[5, 2, 6, 0]], 'int32')
    pos = (8 + np.arange(T))[None]
    attrs = {'layer': 1, 'scale': dh ** -0.5, 'block_size': bs}
    got = lower('kv_prefix_attention', attrs, Q=q, KCache=kc, VCache=vc,
                Positions=pos, BlockTable=table)['Out']
    wide = [np.repeat(c.reshape(nb, 2, bs, Hkv, dh), H // Hkv, axis=3)
            .reshape(nb, 2, bs, H * dh) for c in (kc, vc)]
    want = lower('kv_prefix_attention', attrs, Q=q, KCache=wide[0],
                 VCache=wide[1], Positions=pos, BlockTable=table)['Out']
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


# ---- 3. through the engine, against the reference ---------------------------

@pytest.fixture(scope='module')
def served():
    """`serve_five` (test_olmoe_serving.py) on the toy LFM2 block."""
    eng = _engine()
    eng.warmup()
    return serve_five(eng, TOY['vocab_size'])


def _rows_by_request(served):
    by_first, rows = {}, {i: [] for i in range(5)}
    for kind, feed, lg in served['log']:
        if kind == 'prefill':
            n = int(feed['gen_len'][0, 0])
            i = [j for j, p in enumerate(served['prompts'])
                 if len(p) == n][0]
            by_first[i] = tuple(feed['gen_btab'][0][:1])
            rows[i].append(lg[0])
        else:
            for s in range(4):
                first = tuple(feed['gen_btab'][s][:1])
                if first != (0,):
                    i = [j for j, f in by_first.items() if f == first][-1]
                    rows[i].append(lg[s])
    return rows


def test_prefill_then_decode_through_the_pools_equals_the_full_forward(
        served):
    eng = served['eng']
    rows = _rows_by_request(served)
    for i, prompt in enumerate(served['prompts']):
        toks = served['tokens'][i]
        assert len(toks) == served['n_new'][i]
        got = np.stack(rows[i])[:len(toks)]
        np.testing.assert_array_equal(got.argmax(axis=1), toks)
        seq = np.concatenate([prompt, toks[:-1]])
        want = np.asarray(ref.logits(
            eng.scope, TOY, seq,
            positions=np.arange(len(prompt) - 1, len(seq))))
        assert logit_gap(got, want)[1] <= TOLERANCE, i
        assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0
    moved = served['moved']
    live = sum(len(p) for p in served['prompts']) \
        + sum(n - 1 for n in served['n_new'])
    # four expert layers a dispatch, two experts a live row
    assert moved['moe_assignments_total'] == 4 * 2 * live
    assert moved['prefill_prompt_tokens_total'] == \
        sum(len(p) for p in served['prompts'])
    # two attention layers: every step's live rows through both
    assert moved['kv_tokens_read_total'] % 2 == 0
    assert 'kv_latent_tokens_read_total' not in moved
    assert not any(k.startswith('compile_cache_miss') for k in moved)


def test_the_pools_hold_the_attention_layers_and_the_tails_alone(served):
    eng = served['eng']
    cfg = eng.config.model
    assert (cfg.n_attn_layers, cfg.n_conv_layers, cfg.kv_width) == (2, 4, 16)
    assert [cfg.layer_ordinal(i) for i in range(6)] == [0, 1, 0, 2, 1, 3]
    nb = eng.config.num_blocks
    assert T.kv_cache_names(cfg) == (T.KV_CACHE_K, T.KV_CACHE_V,
                                     T.CONV_CACHE)
    assert {n: tuple(eng.scope.get(n).shape)
            for n in T.kv_cache_names(cfg)} == {
        T.KV_CACHE_K: (nb, 2, 8, 16), T.KV_CACHE_V: (nb, 2, 8, 16),
        T.CONV_CACHE: (nb, 4, 2, 64)}
    assert not eng.scope.has('lm_head.w')                # the tied head
    v = eng._step_vars
    ops = [op.type for op in v['tokens'].block.ops]
    assert ops.count('short_conv_decode_paged') == 4
    assert ops.count('kv_decode_attention_paged') == 2
    layers_written = sorted(
        op.attr('layer') for op in v['tokens'].block.ops
        if op.type == 'kv_cache_update_paged')
    assert layers_written == [0, 0, 1, 1]                # K and V, ordinals


def _once_with_logits(eng, log, prompt, n):
    """generate_once and the logits of its n tokens (the last prefill
    dispatch's row, then slot 0's of each step)."""
    del log[:]
    toks = eng.generate_once(prompt, max_new_tokens=n)
    last_prefill = max(i for i, e in enumerate(log) if e[0] == 'prefill')
    return toks, np.stack([log[last_prefill][2][0]]
                          + [e[2][0] for e in log[last_prefill + 1:]])


@pytest.mark.parametrize('n_prompt', [40, 47, 33])
def test_a_chunked_prefill_equals_an_unchunked_one(n_prompt):
    """A prompt through a 16 bucket in three chunks (each resuming from
    the tail the last one left in the pool) and through a 48 bucket whole:
    the same tokens, logits, and pools."""
    scope_a, scope_b = _scope(), _scope()
    prompt = np.random.RandomState(n_prompt).randint(2, 96, size=n_prompt)
    outs = []
    for scope, buckets in ((scope_a, (16,)), (scope_b, (48,))):
        eng = _engine(scope, buckets, prefix_sharing=False)
        eng.warmup()
        log = tap_logits(eng)
        before = monitor.counters()
        toks, lg = _once_with_logits(eng, log, prompt, 9)
        outs.append((toks, lg, monitor.counter_delta(before).get(
            'conv_tail_resumes_total', 0)))
    (toks_a, lg_a, resumes_a), (toks_b, lg_b, resumes_b) = outs
    assert toks_a == toks_b
    assert logit_gap(lg_a, lg_b)[1] <= TOLERANCE
    assert (resumes_a, resumes_b) == ((n_prompt - 1) // 16, 0)
    want = np.asarray(ref.logits(
        scope_a, TOY, np.concatenate([prompt, toks_a[:-1]]),
        positions=np.arange(n_prompt - 1, n_prompt + 8)))
    assert logit_gap(lg_a, want)[1] <= TOLERANCE
    # the same blocks in the same order on both sides: the prompt's K, V
    # and tails are the same numbers
    for name in T.kv_cache_names(lfm2.lm_config(TOY, 64, False)):
        a, b = np.asarray(scope_a.get(name)), np.asarray(scope_b.get(name))
        np.testing.assert_allclose(a[1:1 + n_prompt // 8],
                                   b[1:1 + n_prompt // 8], rtol=1e-4,
                                   atol=1e-5)


@pytest.fixture(scope='module')
def shared():
    """Four requests through one engine with prefix sharing: A (24 tokens,
    three whole blocks) misses; B (A's first 16 + 7) hits at a block edge;
    A again lands WHOLE on shared blocks; C (A + 5) hits all three."""
    eng = _engine(prefix_sharing=True)
    eng.warmup()
    log = tap_logits(eng)
    rng = np.random.RandomState(11)
    a = rng.randint(2, 96, size=24).astype('int64')
    prompts = [a, np.concatenate([a[:16], rng.randint(2, 96, size=7)]),
               a.copy(), np.concatenate([a, rng.randint(2, 96, size=5)])]
    solo = [eng.generate_once(p, max_new_tokens=7) for p in prompts]
    del log[:]
    before = monitor.counters()
    tokens = _drive(eng, prompts, [7] * 4)
    return dict(eng=eng, log=list(log), prompts=prompts, solo=solo,
                tokens=tokens, moved=monitor.counter_delta(before))


@pytest.mark.parametrize('i,ctx', [(0, 0), (1, 16), (2, 16), (3, 24)],
                         ids=['miss', 'hit-at-a-block-edge',
                              'whole-prompt-shared', 'hit-and-a-suffix'])
def test_a_suffix_behind_a_prefix_hit_equals_the_prompt_prefilled_whole(
        shared, i, ctx):
    eng, prompt = shared['eng'], shared['prompts'][i]
    prefills = [e for e in shared['log'] if e[0] == 'prefill']
    kind, feed, lg = prefills[i]
    # the suffix alone was computed, from `ctx` on
    assert int(feed['gen_pos'][0, 0]) == ctx
    assert int(feed['gen_len'][0, 0]) == len(prompt) - ctx
    assert shared['tokens'][i] == shared['solo'][i]
    want = np.asarray(ref.logits(eng.scope, TOY, prompt,
                                 positions=[len(prompt) - 1]))
    assert logit_gap(lg[:1], want)[1] <= TOLERANCE
    assert ref.greedy_margins(eng.scope, TOY, prompt,
                              shared['tokens'][i]).max() == 0


def test_a_wholly_shared_prompt_recomputes_its_last_block_and_copies_none(
        shared):
    moved = shared['moved']
    assert moved['kv_prefix_hit_total{outcome=hit}'] == 3
    assert moved['kv_prefix_hit_total{outcome=miss}'] == 1
    assert moved['kv_prefix_tokens_saved_total'] == 16 + 16 + 24
    assert moved['prefill_prompt_tokens_total'] == 24 + 23 + 24 + 29
    assert moved['conv_tail_resumes_total'] == 3
    assert 'kv_block_cow_total' not in moved
    # A's second run shares A's first two blocks and owns a fresh third
    tables = [e[1]['gen_btab'][0] for e in shared['log']
              if e[0] == 'prefill']
    assert list(tables[2][:2]) == list(tables[0][:2])
    assert tables[2][2] != tables[0][2]
    # without a convolution layer the same plan copies (the rule is the
    # model's): the parent's behaviour, held in test_generate.py


def test_a_block_evicted_is_recomputed():
    """A pool of 9 blocks: A runs and ends (its three blocks stay in the
    prefix index), a 40-token prompt then needs them (evicted), and A
    again is prefilled from what is left: the same tokens."""
    eng = _engine(prefix_sharing=True, num_blocks=10)
    eng.warmup()
    rng = np.random.RandomState(12)
    a = rng.randint(2, 96, size=24).astype('int64')
    big = rng.randint(2, 96, size=40).astype('int64')
    first, = _drive(eng, [a], [6])
    assert len(eng._prefix) == 3
    _drive(eng, [big], [14])
    kept = eng._prefix.match(
        __import__('paddle_tpu.serving.kv_blocks', fromlist=['x'])
        .chain_hashes(a, 8))
    assert len(kept) < 3                                 # evicted
    before = monitor.counters()
    again, = _drive(eng, [a], [6])
    assert again == first
    assert ref.greedy_margins(eng.scope, TOY, a, again).max() == 0
    saved = monitor.counter_delta(before).get(
        'kv_prefix_tokens_saved_total', 0)
    assert saved == 8 * len(kept)


# ---- 4. the controls --------------------------------------------------------

@pytest.mark.parametrize('control', sorted(lfm2_control.controls(TOY, 16)))
def test_a_control_is_outside_the_tolerance(served, control):
    eng = served['eng']
    prompt, toks = served['prompts'][2], served['tokens'][2]
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(eng.scope, TOY, seq))
    kw = lfm2_control.controls(TOY, 16)[control]
    wrong = np.asarray(lfm2_control.control_logits(eng.scope, TOY, seq, kw))
    assert logit_gap(wrong, want)[1] > 40 * TOLERANCE, control


def test_the_zero_tail_control_is_what_a_resume_without_the_entry_gives():
    """The program with the pool's entry zeroed before a hit's suffix
    gives the reference's `zero_tail_at` logits, not the sound ones."""
    eng = _engine(prefix_sharing=True)
    eng.warmup()
    log = tap_logits(eng)
    rng = np.random.RandomState(13)
    a = rng.randint(2, 96, size=16).astype('int64')
    b = np.concatenate([a, rng.randint(2, 96, size=6)])
    _drive(eng, [a], [2])
    eng.scope.set(T.CONV_CACHE, jnp.zeros_like(eng.scope.get(T.CONV_CACHE)))
    del log[:]
    _drive(eng, [b], [2])
    kind, feed, lg = log[0]
    assert (kind, int(feed['gen_pos'][0, 0])) == ('prefill', 16)
    sound = np.asarray(ref.logits(eng.scope, TOY, b, positions=[21]))
    zeroed = np.asarray(ref.logits(eng.scope, TOY, b, positions=[21],
                                   zero_tail_at=16))
    assert logit_gap(lg[:1], zeroed)[1] <= TOLERANCE
    assert logit_gap(lg[:1], sound)[1] > 40 * TOLERANCE


def test_the_chip_comparison_runs_at_toy_width(served):
    """benchmark/reference/lfm2_control.py's session and compare, as its
    main() drives them on the chip."""
    eng = served['eng']
    engine = {'slots': 4, 'max_len': 64, 'block_size': 8, 'num_blocks': 33,
              'prompt_buckets': [16, 32]}
    prompt = served['prompts'][2]
    out = lfm2_control.compare(eng.config.model, engine, eng.scope, TOY,
                               prompt, new_tokens=6, shared_len=16)
    assert out['logits_vs_ref'][1] <= TOLERANCE
    assert out['resumed_logits_vs_ref'][1] <= TOLERANCE
    assert out['row_behind_prefix_vs_ref'][1] <= TOLERANCE
    assert out['row_behind_prefix_zero_tail_vs_ref'][1] > 40 * TOLERANCE
    assert out['greedy_margin_worst'] == 0.0
    for name, reading in out['controls'].items():
        assert reading['logits_vs_ref'][1] > 40 * TOLERANCE, name
    eng._ensure_cache()


# ---- 5. the programs the benchmark already had are the parent's -------------

@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
@pytest.mark.parametrize('config', sorted(LISTED))
def test_the_new_fields_at_their_defaults_build_the_parents_programs(
        config, program):
    """All-attention `layer_types` and `n_kv_head == n_head`, SAID: the
    listing of test_olmoe_serving.py's parent fixture, op for op."""
    from test_olmoe_serving import program_listing, parent_listing
    kw = dict(LISTED[config])
    cfg = LMConfig(layer_types=['attention'] * kw['n_layer'],
                   n_kv_head=kw['n_head'], tie_embeddings=False,
                   router_eps=1e-20, conv_kernel=3, **kw)
    assert program_listing(cfg, program) == parent_listing(config, program)


# ---- 6. the refusals --------------------------------------------------------

REFUSERS = {
    'build_lm': lambda cfg: T.build_lm(cfg, is_test=True),
    'build_lm_drafter': lambda cfg: T.build_lm_drafter(cfg, 2, 32, 2, 9, 8),
    'build_lm_verify': lambda cfg: T.build_lm_verify(cfg, 2, 3, 32, 9, 8),
}
FIELDS = {'layer_types': ['conv', 'attention'], 'n_kv_head': 2,
          'tie_embeddings': True, 'qk_norm': 'head'}


@pytest.mark.parametrize('field', sorted(FIELDS))
@pytest.mark.parametrize('builder', sorted(REFUSERS))
def test_the_other_builders_refuse_the_new_fields_by_name(builder, field):
    cfg = LMConfig(vocab_size=64, seq_len=32, d_model=64, n_head=4,
                   n_layer=2, d_ff=32, dropout=0.0, **{field: FIELDS[field]})
    with program_guard(Program(), Program()):
        with pytest.raises(ValueError, match=r'LMConfig\.%s=' % field):
            REFUSERS[builder](cfg)


def test_speculation_is_refused_for_a_model_with_a_convolution_layer():
    with pytest.raises(ValueError, match=r'speculative=True with '
                                         r'LMConfig\.layer_types'):
        _engine(speculative=True, spec_k=2)


def test_lmconfig_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match=r'LMConfig\.layer_types'):
        LMConfig(n_layer=2, layer_types=['conv'])
    with pytest.raises(ValueError, match=r'LMConfig\.layer_types'):
        LMConfig(n_layer=2, layer_types=['conv', 'window'])
    with pytest.raises(ValueError, match=r'LMConfig\.n_kv_head'):
        LMConfig(n_head=8, n_kv_head=3)
    with pytest.raises(ValueError, match=r'LMConfig\.qk_norm'):
        LMConfig(qk_norm='rows')
    with pytest.raises(ValueError, match='n_kv_head nor'):
        LMConfig(attention='mla', position='rope', q_lora_rank=8,
                 kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8,
                 n_head=4, n_kv_head=2)
