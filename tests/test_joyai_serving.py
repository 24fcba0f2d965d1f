"""The JoyAI-LLM-Flash block in the Program path (ISSUE 32): latent (MLA)
attention through ONE pool of latent rows, a dense SiLU-gated layer, and an
expert layer that holds its share of sigmoid-routed experts beside a shared
one — prefill-then-decode through the paged cache against the plain
reference's FULL forward pass (logits, not tokens), absorbed against
expanded, the shares adding up to the uncut layer, today's expert op bit
for bit, the controls, the counters and the refusals.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-joyai.json): d 64,
8 heads of 16 + 8 (values 16), latent 32, q bottleneck 24, 3 layers (1
dense), experts 4..7 of 16 held, top-4, one shared expert, seeded weights.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import Scope, monitor
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import GenerateConfig, GenerateEngine

from benchmark.models import joyai
from benchmark.reference import joyai_control, joyai_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import lower, serve_five

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                       'toy-joyai.json')) as _f:
    TOY = json.load(_f)


def _scope(m, seed=5):
    """Seeded weights; the experts four times larger, so that a wrong
    choice of expert, weight or share moves the logits (the startup's
    N(0, 0.02) experts add little to the residual stream at this
    width)."""
    scope = Scope()
    for name, value in joyai.init_params(m, seed).items():
        big = '.moe.' in name and 'router' not in name
        scope.set(name, value * (4.0 if big else 1.0))
    return scope


# ---- 1. the ops -----------------------------------------------------------

def test_interleaved_rope_rotates_the_pairs_where_they_lie():
    rng = np.random.RandomState(1)
    pos = np.array([0, 63, 17, 5, 40])
    x = rng.randn(5, 4, 16).astype('float32')
    inv = 10000.0 ** (-np.arange(0, 16, 2) / 16.0)
    ang = (pos[:, None] * inv)[:, None, :]                   # [5, 1, 8]
    want = np.empty_like(x)
    want[..., 0::2] = x[..., 0::2] * np.cos(ang) - x[..., 1::2] * np.sin(ang)
    want[..., 1::2] = x[..., 1::2] * np.cos(ang) + x[..., 0::2] * np.sin(ang)
    got = lower('rotary_embedding', {'theta': 10000.0, 'interleave': True},
                X=x, Positions=pos[:, None])['Out']
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ref.rope_interleaved(jnp.asarray(x), jnp.asarray(pos),
                                        10000.0)), want, rtol=1e-5,
        atol=1e-5)
    halves = lower('rotary_embedding', {'theta': 10000.0}, X=x,
                   Positions=pos[:, None])['Out']
    assert np.abs(halves - got).max() > 0.5       # not the same rotation
    # HF's way (pairs moved apart, halves rotated) permutes q and k alike:
    # the same dot products
    y = rng.randn(5, 4, 16).astype('float32')
    k_i = lower('rotary_embedding', {'theta': 10000.0, 'interleave': True},
                X=y, Positions=pos[:, None])['Out']
    apart = lambda a: np.concatenate([a[..., 0::2], a[..., 1::2]], -1)
    q_h = lower('rotary_embedding', {'theta': 10000.0}, X=apart(x),
                Positions=pos[:, None])['Out']
    k_h = lower('rotary_embedding', {'theta': 10000.0}, X=apart(y),
                Positions=pos[:, None])['Out']
    np.testing.assert_allclose((got * k_i).sum(-1), (q_h * k_h).sum(-1),
                               rtol=1e-4, atol=1e-4)


def _expert_layer(rng, n=11, d=64, E=16, w=32):
    x = rng.randn(n, d).astype('float32')
    router = (rng.randn(d, E) * 0.3).astype('float32')
    bias = (rng.randn(E) * 0.2).astype('float32')
    gate, up = (rng.randn(E, d, w).astype('float32') * 0.2 for _ in '12')
    down = rng.randn(E, w, d).astype('float32') * 0.2
    return x, router, bias, gate, up, down


SIGMOID = {'top_k': 4, 'norm_topk_prob': True, 'score': 'sigmoid',
           'routed_scale': 2.5}


@pytest.mark.parametrize('E,held,k', [(16, 4, 4), (32, 2, 8)],
                         ids=['joyai-4-shares-of-4', 'kexaone-16-shares-of-2'])
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer(
        E, held, k):
    """Each share computes its own experts' part for the rows routed to
    them; what every chip computes alike (the shared expert) is counted
    once; the sum is the uncut reference's expert layer. JoyAI's group of
    4, and K-EXAONE's of 16 (top 8; its reference's functions are
    JoyAI's, key for key)."""
    rng = np.random.RandomState(3)
    x, router, bias, gate, up, down = _expert_layer(rng, E=E)
    shared = [rng.randn(*s).astype('float32') * 0.2
              for s in ((64, 32), (64, 32), (32, 64))]
    scores = ref._scores(jnp.asarray(x), jnp.asarray(router))
    chosen = ref.chosen_mask(scores, jnp.asarray(bias), k)
    w = ref.expert_weights(scores, chosen, bias, True, 2.5)
    want = np.asarray(ref._experts(jnp.asarray(x), w, gate, up, down)
                      + ref._gated(jnp.asarray(x), *shared))
    total = np.asarray(ref._gated(jnp.asarray(x), *shared))
    elsewhere = 0
    for first in range(0, E, held):
        mine = slice(first, first + held)
        out = lower('moe_ffn', dict(SIGMOID, top_k=k, first_expert=first),
                    X=x, RouterW=router, SelectBias=bias, GateW=gate[mine],
                    UpW=up[mine], DownW=down[mine])
        total = total + out['Out']
        # the router scores all E and every row chooses k of them
        np.testing.assert_array_equal(
            np.sort(out['TopkIdx'], axis=1),
            np.sort(np.argsort(-np.asarray(scores + bias), axis=1)[:, :k],
                    axis=1))
        load = out['ExpertLoad']
        assert load.shape == (held + 1,) and load.sum() == 11 * k
        np.testing.assert_array_equal(
            load[:held], np.asarray(chosen)[:, mine].sum(axis=0))
        elsewhere += load[held]
        # the share alone is the reference's share
        np.testing.assert_allclose(
            out['Out'], np.asarray(ref._experts(
                jnp.asarray(x), w[:, mine], gate[mine], up[mine],
                down[mine])), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # each assignment is held once
    assert elsewhere == (E // held - 1) * 11 * k
    # the bias chooses (it decides some choices here) and is in no weight
    plain = ref.chosen_mask(scores, jnp.zeros(E), k)
    assert (np.asarray(plain) != np.asarray(chosen)).any()


@pytest.mark.parametrize('held_share', ['its-share', 'every-row'])
def test_a_share_gives_the_grouped_matmul_the_rows_it_needs(held_share):
    """96 rows x 4 = 384 assignments of which a quarter is expected here:
    the grouped matmuls take 256 rows (one and a half times the share, in
    tiles of 128) where no more are held, and all 384 in a step where the
    router sends more — the same sum either way, nothing dropped."""
    rng = np.random.RandomState(5)
    x, router, bias, gate, up, down = _expert_layer(rng, n=96)
    if held_share == 'every-row':
        x[:, 0] = 1.0
        router[0, 12:] = 50.0
        bias[12:] += 5.0                         # all four choices held
    out = lower('moe_ffn', dict(SIGMOID, first_expert=12), X=x,
                RouterW=router, SelectBias=bias, GateW=gate[12:],
                UpW=up[12:], DownW=down[12:])
    scores = ref._scores(jnp.asarray(x), jnp.asarray(router))
    chosen = ref.chosen_mask(scores, jnp.asarray(bias), 4)
    w = ref.expert_weights(scores, chosen, bias, True, 2.5)
    want = np.asarray(ref._experts(jnp.asarray(x), w[:, 12:], gate[12:],
                                   up[12:], down[12:]))
    np.testing.assert_allclose(out['Out'], want, rtol=2e-5, atol=2e-5)
    held = int(out['ExpertLoad'][:4].sum())
    assert held == (384 if held_share == 'every-row' else held) \
        and (held > 256) == (held_share == 'every-row')
    assert out['ExpertLoad'].sum() == 384


def test_a_share_leaves_the_other_experts_weights_unread():
    """NaN in place of a result that must not be used: an assignment to an
    expert held elsewhere is in no group of the grouped matmul, and what
    stands in its rows is dropped."""
    rng = np.random.RandomState(4)
    x, router, bias, gate, up, down = _expert_layer(rng)
    router[:, :12] -= 50.0 * np.sign(x.mean())    # nearly all go elsewhere
    out = lower('moe_ffn', dict(SIGMOID, first_expert=12), X=x,
                RouterW=router, SelectBias=bias, GateW=gate[12:],
                UpW=up[12:], DownW=down[12:])
    assert np.isfinite(out['Out']).all()


def _todays_moe_ffn(x, router_w, gate_w, up_w, down_w, top_k, norm):
    """ops/moe_ops.py `route` + `grouped_ffn` as they stood at the parent
    commit (44db736), word for word."""
    from jax import lax
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    w, idx = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    idx = idx.astype(jnp.int32)
    n, k = idx.shape
    n_experts = gate_w.shape[0]
    flat = idx.reshape(-1)
    order = jnp.argsort(flat)
    sizes = jnp.sum(flat[:, None] == jnp.arange(n_experts)[None, :],
                    axis=0, dtype=jnp.int32)
    xs = x[order // k]
    h = jax.nn.silu(lax.ragged_dot(xs, gate_w, sizes)) \
        * lax.ragged_dot(xs, up_w, sizes)
    y = lax.ragged_dot(h, down_w, sizes)
    y = y[jnp.argsort(order)].reshape(n, k, -1)
    return jnp.einsum('nk,nkd->nd', w.astype(y.dtype), y), idx


@pytest.mark.parametrize('norm', [False, True])
def test_every_expert_held_and_a_softmax_router_is_todays_op_bitwise(norm):
    rng = np.random.RandomState(6)
    x, router, _bias, gate, up, down = _expert_layer(rng, E=8)
    want, idx = _todays_moe_ffn(*(jnp.asarray(a) for a in
                                  (x, router, gate, up, down)), 2, norm)
    out = lower('moe_ffn', {'top_k': 2, 'norm_topk_prob': norm}, X=x,
                RouterW=router, GateW=gate, UpW=up, DownW=down)
    np.testing.assert_array_equal(out['Out'], np.asarray(want))
    np.testing.assert_array_equal(out['TopkIdx'], np.asarray(idx))
    assert out['ExpertLoad'].shape == (8,)
    # and the program says nothing new of that block
    cfg = LMConfig(vocab_size=97, seq_len=32, d_model=64, n_head=4,
                   n_layer=1, dropout=0.0, norm='rms_norm', position='rope',
                   head_dim=16, bias=False, ffn='moe', n_experts=8,
                   experts_per_token=2, expert_width=32,
                   experts_held=(0, 8))
    main = Program()
    with program_guard(main, Program()):
        T.build_lm_decode_step(cfg, 2, 32, block_size=8, num_blocks=9)
    op, = [o for o in main.global_block().ops if o.type == 'moe_ffn']
    assert sorted(op.attrs) == ['norm_topk_prob', 'top_k']
    assert 'SelectBias' not in op.inputs


# ---- 2. absorbed against expanded, on one pool ------------------------------

def _latent_case(rng, S=5, H=8, nope=16, rope=8, rank=128, v=16, bs=8,
                 MB=6, NB=40, Ln=2, width=256):
    """A pool of latent rows (zeros behind lane rank + rope), tables, and
    per slot one query at its own position."""
    pool = np.zeros((NB, Ln, bs, width), 'float32')
    pool[..., :rank + rope] = rng.randn(NB, Ln, bs, rank + rope)
    pos = np.array([0, 7, 8, 29, MB * bs - 1][:S], 'int64')   # ragged:
    # an idle slot (position 0, an all-zero table: the trash block), a
    # page's last row, a page's first, inside a partial page, the last
    tables = np.zeros((S, MB), 'int64')
    free = list(rng.permutation(np.arange(1, NB)))
    for s in range(1, S):
        for j in range(pos[s] // bs + 1):
            tables[s, j] = free.pop()
    q = rng.randn(S, H, nope + rope).astype('float32')
    w_uk = rng.randn(H, nope, rank).astype('float32') * 0.2
    w_uv = rng.randn(H, rank, v).astype('float32') * 0.2
    return dict(q=q, pool=pool, pos=pos, tables=tables, w_uk=w_uk,
                w_uv=w_uv)


def _absorbed(c, layer, tier, monkeypatch):
    monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
    return lower('mla_decode_attention_paged', {'layer': layer,
                                                'scale': 24 ** -0.5},
                 Q=c['q'], Cache=c['pool'], UpK=c['w_uk'], UpV=c['w_uv'],
                 Positions=c['pos'][:, None], BlockTables=c['tables'])['Out']


def test_the_absorbed_decode_is_the_expanded_attention(monkeypatch):
    """One result, two forms: each slot's absorbed decode output equals
    the LAST row of an expanded prefix attention whose queries end at the
    slot's position, over the same cached rows."""
    c = _latent_case(np.random.RandomState(8))
    got = _absorbed(c, 1, 'off', monkeypatch)
    assert got.shape == (5, 8, 16)
    for s in range(1, 5):
        n = int(c['pos'][s]) + 1
        t = min(n, 4)
        q = np.zeros((1, t, 8, 24), 'float32')
        q[0, -1] = c['q'][s]
        out = lower('mla_prefix_attention', {'layer': 1,
                                             'scale': 24 ** -0.5},
                    Q=q, Cache=c['pool'], UpK=c['w_uk'], UpV=c['w_uv'],
                    Positions=np.arange(n - t, n)[None],
                    BlockTable=c['tables'][s][None])['Out']
        np.testing.assert_allclose(got[s], out[0, -1], rtol=2e-5, atol=2e-5)


def test_the_expanded_prefill_takes_its_queries_in_chunks():
    """512 query rows go through `lax.map` in two chunks of 256, and are
    what one chunk of all of them gives."""
    from paddle_tpu.ops import mla_ops
    rng = np.random.RandomState(9)
    c = _latent_case(rng, MB=70, NB=80)
    q = rng.randn(1, 512, 8, 24).astype('float32')
    table = np.arange(1, 71)[None]
    args = dict(Q=q, Cache=c['pool'], UpK=c['w_uk'], UpV=c['w_uv'],
                Positions=np.arange(40, 552)[None], BlockTable=table)
    got = lower('mla_prefix_attention', {'layer': 0, 'scale': 0.2},
                **args)['Out']
    whole = mla_ops._QUERY_CHUNK
    mla_ops._QUERY_CHUNK = 512
    try:
        want = lower('mla_prefix_attention', {'layer': 0, 'scale': 0.2},
                     **args)['Out']
    finally:
        mla_ops._QUERY_CHUNK = whole
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ---- 3. the Pallas kernel, interpreted 

def test_the_kernel_matches_the_gather_formulation(monkeypatch):
    """Interpret mode against tier `off` over ragged positions, a last
    partial page, groups that end short of the ring's boundary (8 rows a
    page: 32 pages a group) and an idle slot; both layers of the pool."""
    c = _latent_case(np.random.RandomState(10), MB=40, NB=140)
    c['pos'] = np.array([0, 7, 130, 317, 255], 'int64')
    free = list(range(1, 140))
    for s in range(1, 5):
        c['tables'][s] = 0
        for j in range(c['pos'][s] // 8 + 1):
            c['tables'][s, j] = free.pop()
    for layer in (0, 1):
        want = _absorbed(c, layer, 'off', monkeypatch)
        got = _absorbed(c, layer, 'interpret', monkeypatch)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_a_slot_of_the_kernel_is_bitwise_independent_of_its_neighbours(
        monkeypatch):
    c = _latent_case(np.random.RandomState(11), MB=40, NB=140)
    c['pos'] = np.array([0, 7, 130, 317, 255], 'int64')
    free = list(range(1, 140))
    for s in range(1, 5):
        c['tables'][s] = 0
        for j in range(c['pos'][s] // 8 + 1):
            c['tables'][s, j] = free.pop()
    base = _absorbed(c, 1, 'interpret', monkeypatch)
    # other neighbours: slots 1 and 3 idle, slot 2's query and position
    # changed, and every block slot 4 does not own overwritten
    d = {k: np.array(v) for k, v in c.items()}
    d['pos'][[1, 3]] = 0
    d['tables'][[1, 3]] = 0
    d['pos'][2] = 77
    d['q'][2] += 1.0
    own = set(c['tables'][4][:c['pos'][4] // 8 + 1])
    for b in range(140):
        if b not in own:
            d['pool'][b, ..., :136] = 7.5
    moved = _absorbed(d, 1, 'interpret', monkeypatch)
    np.testing.assert_array_equal(moved[4], base[4])
    assert np.abs(moved[2] - base[2]).max() > 1e-3


def test_shapes_the_kernel_refuses_take_the_gather(monkeypatch):
    from paddle_tpu.ops import mla_paged_decode_attention as kern
    assert kern.shapes_ok(32, 640, 512, 16)
    assert not kern.shapes_ok(32, 576, 500, 16)     # values: whole tiles
    assert not kern.shapes_ok(4, 256, 128, 8)       # heads: whole sublanes
    assert not kern.shapes_ok(8, 256, 128, 12)      # pages: whole tiles
    c = _latent_case(np.random.RandomState(12), H=4)
    before = monitor.counters()
    _absorbed(c, 0, 'interpret', monkeypatch)
    moved = monitor.counter_delta(before)
    assert [k for k in moved if 'mla_decode_attention_paged' in k
            and 'impl=xla' in k]


# ---- 4. through the paged cache, against the full forward -------------------

@pytest.fixture(scope='module')
def served():
    """test_olmoe_serving's five requests through a 4-slot engine on the
    latent pool."""
    eng = GenerateEngine(GenerateConfig(
        model=joyai.lm_config(TOY, 64, False), slots=4, max_len=64,
        prompt_buckets=[16, 32], eos_id=None, seed=3, block_size=8),
        scope=_scope(TOY))
    eng.warmup()
    return serve_five(eng, 96)


# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU — the system through its latent
# cache, the absorbed decode, ragged_dot and the sorted assignments, the
# reference at `highest`, expanded, with a masked loop — so the routing is
# the same and what is left is summation order (a few 1e-7). 1e-4 is far
# above that and far under what the wrong computations below move the
# logits by.
TOLERANCE = 1e-4


def test_prefill_then_decode_through_the_latent_cache_equals_the_full_forward(
        served):
    eng, log = served['eng'], served['log']
    assert [len(t) for t in served['tokens']] == served['n_new']
    by_first = {}
    rows = {i: [] for i in range(5)}
    steps = latent_rows = 0
    for kind, feed, lg in log:
        if kind == 'prefill':
            n = int(feed['gen_len'][0, 0])
            i = [j for j, p in enumerate(served['prompts'])
                 if len(p) == n][0]
            by_first[i] = tuple(feed['gen_btab'][0][:1])
            rows[i].append(lg[0])
        else:
            steps += 1
            for s in range(4):
                first = tuple(feed['gen_btab'][s][:1])
                if first == (0,):
                    continue
                i = [j for j, f in by_first.items() if f == first][-1]
                rows[i].append(lg[s])
                latent_rows += int(feed['gen_pos'][s, 0]) + 1
    crossed = 0
    for i, prompt in enumerate(served['prompts']):
        toks = served['tokens'][i]
        got = np.stack(rows[i])[:len(toks)]
        np.testing.assert_array_equal(got.argmax(axis=1), toks)
        seq = np.concatenate([prompt, toks[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        want = np.asarray(ref.logits(eng.scope, TOY, seq, positions=pos))
        rms, worst = logit_gap(got, want)
        assert worst <= TOLERANCE, (i, rms, worst)
        assert ref.greedy_margins(eng.scope, TOY, prompt, toks).max() == 0
        crossed += len(seq) // 8 - len(prompt) // 8
    assert crossed >= 4                    # block boundaries crossed
    # the counters: two expert layers a dispatch (layer 0 is dense), four
    # experts a live row a layer of which those to experts 4..7 are held
    moved = served['moved']
    dispatches = len(log)
    assert moved['moe_layer_steps_total'] == 2 * dispatches
    live = sum(len(p) for p in served['prompts']) \
        + sum(n - 1 for n in served['n_new'])
    assert moved['moe_assignments_total'] == 2 * 4 * live
    assert 0 < moved['moe_held_assignments_total'] \
        < moved['moe_assignments_total']
    assert moved['moe_experts_touched_total'] <= 4 * 2 * dispatches
    assert moved['moe_max_expert_rows_total'] \
        <= moved['moe_held_assignments_total']
    assert moved['kv_latent_tokens_read_total'] == 3 * latent_rows
    assert not any(k.startswith('compile_cache_miss') for k in moved)


def test_there_is_one_pool_of_latent_rows_and_no_v_pool(served):
    eng = served['eng']
    cfg = eng.config.model
    assert T.kv_cache_names(cfg) == (T.KV_CACHE_K,)
    assert cfg.kv_width == 128 and cfg.attn_width == 8 * 16
    assert not eng.scope.has(T.KV_CACHE_V)
    assert tuple(eng.scope.get(T.KV_CACHE_K).shape) == (
        eng.config.num_blocks, 3, 8, 128)
    pool = np.asarray(eng.scope.get(T.KV_CACHE_K))
    assert np.abs(pool[..., :40]).max() > 0 and not pool[..., 40:].any()
    for prog in [eng._step_prog] + [p for p, _v in eng._prefill.values()]:
        block = prog.global_block()
        assert T.KV_CACHE_V not in block.vars
        assert not [op for op in block.ops
                    if op.type in ('kv_decode_attention_paged',
                                   'kv_prefix_attention')]
    assert eng._step_vars['v_cache'] is None
    # the published widths: 576 numbers a row in 640 lanes
    assert joyai.lm_config(json.load(open(os.path.join(
        HERE, os.pardir, 'benchmark', 'configs',
        'joyai-llm-flash-ep4.json'))), 2816, False).kv_width == 640


def test_the_share_served_is_the_references_share_and_not_the_whole(served):
    """The same weights under the uncut reading of the file (all 16
    experts held, the scope's four standing for experts 0..3) are another
    model: the comparison above would refuse it."""
    eng = served['eng']
    prompt, toks = served['prompts'][2], served['tokens'][2]
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(eng.scope, TOY, seq))
    moved = np.asarray(ref.logits(eng.scope, TOY, seq, held=(0, 4)))
    assert logit_gap(moved, want)[1] > 40 * TOLERANCE


@pytest.mark.parametrize('control', sorted(joyai_control.controls(TOY)))
def test_a_control_is_outside_the_tolerance(served, control):
    """The bfloat16 forward, one expert fewer, weights not renormalised,
    the scaling left out, `rotate_half` for the interleaved RoPE, the
    selection bias in the weights: each in the system's place differs from
    the reference by far more than the tolerance the system is held to.
    (`rotate-half` is the case that fails if the wrong rotation is
    BUILT: the system is inside the tolerance above.)"""
    eng = served['eng']
    kw = joyai_control.controls(TOY)[control]
    prompt, toks = served['prompts'][2], served['tokens'][2]
    seq = np.concatenate([prompt, toks[:-1]])
    want = np.asarray(ref.logits(eng.scope, TOY, seq))
    wrong = np.asarray(ref.logits(eng.scope, TOY, seq, **kw))
    rms, worst = logit_gap(wrong, want)
    assert worst > 40 * TOLERANCE, (control, rms, worst)


def test_the_chip_comparison_runs_at_toy_width(served):
    """benchmark/reference/joyai_control.py's Session and compare, as its
    main() drives them on the chip."""
    eng = served['eng']
    session = joyai_control.Session(
        eng.config.model,
        {'slots': 4, 'max_len': 64, 'block_size': 8, 'num_blocks': 33,
         'prompt_buckets': [16, 32]}, eng.scope)
    prompt = served['prompts'][2]
    toks, lg, chosen = session.generate(prompt, 10)
    assert len(toks) == 11 and lg.shape == (11, 96)
    assert [c.shape for c in chosen] == [(len(prompt) + 10, 4)] * 2
    out = joyai_control.compare(eng.scope, TOY, prompt, toks, lg, chosen)
    assert out['routing_rows_not_ref_top_k'] == 0.0
    assert out['logits_vs_ref_given_routing'][1] <= TOLERANCE
    assert out['logits_vs_ref_own_routing'][1] <= TOLERANCE
    assert out['greedy_margin_worst'] == 0.0
    assert sorted(out['controls']) == sorted(joyai_control.controls(TOY))
    for name, reading in out['controls'].items():
        assert reading['logits_vs_ref_own_routing'][1] > 40 * TOLERANCE, \
            name
    assert not eng.scope.has(T.KV_CACHE_V)
    eng._ensure_cache()


def test_engine_tokens_do_not_depend_on_the_tier(monkeypatch):
    """The kernels (interpreted) in the engine's decode step AND in its
    prefill serve the tokens the gather formulation and the plain
    composition serve."""
    from paddle_tpu.ops import prefix_attention as pfa
    prompts = [np.arange(2, 2 + n).astype('int64') for n in (5, 19)]
    served_by = {}
    # the decode kernel's values are whole lane tiles of a row: a latent
    # of 128; the prefill kernel's head is 128 lanes of its own beside 64
    # rotary ones (and takes a call of any size here: a matter of speed)
    wide = dict(TOY, kv_lora_rank=128, qk_nope_head_dim=128,
                qk_rope_head_dim=64, qk_head_dim=192, head_dim=64,
                v_head_dim=128)
    monkeypatch.setattr(pfa, '_MIN_SCORES_BYTES', 0)
    for tier in ('off', 'interpret'):
        monkeypatch.setenv('PADDLE_FUSED_TIER', tier)
        before = monitor.counters()
        eng = GenerateEngine(GenerateConfig(
            model=joyai.lm_config(wide, 64, False), slots=2, max_len=64,
            prompt_buckets=[32], eos_id=None, seed=3, block_size=8),
            scope=_scope(wide))
        served_by[tier] = [list(eng.generate_once(p, max_new_tokens=9))
                           for p in prompts]
        moved = monitor.counter_delta(before)
        for op in ('mla_decode_attention_paged', 'mla_prefix_attention'):
            # a dispatch a layer a program, and on no other tier
            assert {k: n for k, n in moved.items()
                    if k.startswith('fused_kernel_dispatch_total')
                    and 'op=%s}' % op in k} == {
                'fused_kernel_dispatch_total{impl=%s,mesh=1,op=%s}'
                % (tier, op): TOY['num_hidden_layers']}
    assert served_by['off'] == served_by['interpret']


# ---- 5. the refusals 

@pytest.mark.parametrize('builder', ['build_lm', 'build_lm_drafter',
                                     'build_lm_verify'])
def test_the_other_builders_refuse_latent_attention_by_name(builder):
    build = {
        'build_lm': lambda cfg: T.build_lm(cfg, is_test=True),
        'build_lm_drafter': lambda cfg: T.build_lm_drafter(cfg, 2, 32, 2, 9,
                                                           8),
        'build_lm_verify': lambda cfg: T.build_lm_verify(cfg, 2, 3, 32, 9,
                                                         8)}[builder]
    cfg = LMConfig(vocab_size=64, seq_len=32, d_model=64, n_head=4,
                   n_layer=1, d_ff=32, dropout=0.0, position='rope',
                   attention='mla', q_lora_rank=8, kv_lora_rank=16,
                   qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8)
    with program_guard(Program(), Program()):
        with pytest.raises(ValueError, match=r'LMConfig\.(position|'
                                             r'attention)='):
            build(cfg)


def test_lmconfig_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match='LMConfig.attention'):
        LMConfig(attention='gqa')
    with pytest.raises(ValueError, match="attention='mla' needs"):
        LMConfig(attention='mla')
    with pytest.raises(ValueError, match='LMConfig.moe_score'):
        LMConfig(moe_score='tanh')
    cfg = LMConfig(d_model=32, n_head=2, n_layer=1, ffn='moe', n_experts=8,
                   experts_per_token=2, expert_width=8,
                   experts_held=(6, 4))
    with program_guard(Program(), Program()):
        with pytest.raises(ValueError, match='is no share of 8'):
            T.build_lm_decode_step(cfg, 2, 32, block_size=8, num_blocks=9)
    with pytest.raises(ValueError, match='scoring_func'):
        joyai.lm_config(dict(TOY, scoring_func='softmax'), 64, False)
    with pytest.raises(ValueError, match='n_group'):
        joyai.lm_config(dict(TOY, n_group=8), 64, False)
