"""The Mellum 2 block in the Program path (ISSUE 51): window layers whose
window is LARGER than a prefill chunk beside a full layer, rotary
parameters by kind of layer (YaRN on the full layer alone), softmax experts
with no shared one -- and a shared prefix over the window layers: the
window pools' blocks come from an allocator of their own, the prefix cache
keeps a prefix's window blocks for the next tenant, and a request that
resumes at the prefix's edge is served what it would have been served cold.

Toy widths on the CPU (tests/benchmark_tests/configs/toy-mellum2.json): d
64, 8 query heads on 2 K/V heads of 8, 4 layers (window window window
full), a window of 40 keys (5 blocks of 8: a ring of 7), 8 experts of
width 32, top-2, YaRN of factor 4 past 32 positions, seeded weights.
"""
import json
import math
import os

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import Scope, monitor
from paddle_tpu.framework import Program, program_guard
from paddle_tpu import unique_name
from paddle_tpu.models import transformer as T
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops import paged_decode_attention as pda
from paddle_tpu.serving import GenerateConfig, GenerateEngine
from paddle_tpu.serving.kv_blocks import (BlockAllocator, PrefixCache,
                                          WindowRings, chain_hashes)

from benchmark.models import kexaone, mellum2
from benchmark.reference import mellum2_control, mellum2_reference as ref
from benchmark.reference.olmoe_control import logit_gap

from test_olmoe_serving import program_listing, tap_logits

HERE = os.path.dirname(os.path.abspath(__file__))


def _toy(name):
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-%s.json' % name)) as f:
        return json.load(f)


TOY = _toy('mellum2')
with open(os.path.join(os.path.dirname(HERE), 'benchmark', 'configs',
                       'mellum2-12b-a2.5b-l4.json')) as _f:
    PUBLISHED = json.load(_f)
# Largest difference of a logit, relative to its row's (max - mean). Both
# sides compute in float32 on the CPU, so what is left is summation order
# (read: 3e-7 to 3e-6 over every comparison below); the controls move the
# logits by 1e-3 (a window one key off) and more.
TOLERANCE = 1e-4
BS = 8


def _scope(m=TOY, seed=5):
    """Seeded weights; the experts four times larger, so that a wrong
    choice of expert or weight moves the logits (test_joyai_serving.py)."""
    scope = Scope()
    for name, value in mellum2.init_params(m, seed).items():
        big = '.moe.' in name and 'router' not in name
        scope.set(name, value * (4.0 if big else 1.0))
    return scope


def _engine(scope=None, buckets=(16, 32), max_len=256, slots=4, **kw):
    kw.setdefault('block_size', BS)
    kw.setdefault('prefix_sharing', True)
    kw.setdefault('num_blocks', 160)
    return GenerateEngine(GenerateConfig(
        model=mellum2.lm_config(TOY, max_len, False), slots=slots,
        max_len=max_len, prompt_buckets=list(buckets), eos_id=None, seed=3,
        **kw), scope=scope if scope is not None else _scope())


def _window(eng):
    return eng.stats()['blocks']['window']


def _drive(eng, *reqs):
    while any(r.finish_reason is None and r._error is None for r in reqs):
        eng._step()
        eng._admit()


def _serve(eng, log, prompt, n, before=()):
    """(tokens, logits [n, V], what the counters moved by) of one request
    through a tapped engine driven from here; `before`: requests resident
    meanwhile, which step along."""
    del log[:]
    moved = monitor.counters()
    req = eng.submit(np.asarray(prompt, 'int64'), max_new_tokens=n)
    eng._admit()
    slot, = [i for i, st in enumerate(eng._slots)
             if st is not None and st.req is req]
    mark = len(log)
    while req.finish_reason is None and req._error is None:
        eng._step()
    toks = list(req.result(timeout=5))
    got = [log[mark - 1][2][0]] + [lg[slot] for kind, _f, lg in log[mark:]
                                   if kind == 'step']
    return toks, np.stack(got)[:len(toks)], monitor.counter_delta(moved)


def _reference(eng, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]])
    return np.asarray(ref.logits(eng.scope, TOY, seq, positions=np.arange(
        len(prompt) - 1, len(seq))))


def _prompts(seed, shared, *own):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(2, 97, size=shared)
    return [np.concatenate([prefix, rng.randint(2, 97, size=n)])
            .astype('int64') for n in own]


# ---- 1. the rotation by kind of layer ---------------------------------------

def test_the_yarn_table_is_the_equations_at_the_published_numbers():
    """HF `_compute_yarn_parameters` at theta 500 000, factor 16, 8 192
    original positions, beta 32 / 1, heads of 128: pairs 0 .. 18 keep
    theta^(-2i/128), pairs 35 .. 63 have it divided by 16, a linear ramp
    between, and the factor on cos and sin is 0.1 ln 16 + 1."""
    full = PUBLISHED['rope_parameters']['full_attention']
    dim = lambda turns: 128 * math.log(8192 / (turns * 2 * math.pi)) \
        / (2 * math.log(500000))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35) \
        == ref.yarn_range(128, full)
    got = moe_ops.yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    i = np.arange(64)
    extra = 500000.0 ** (-2.0 * i / 128)
    np.testing.assert_allclose(got[:19], extra[:19], rtol=1e-12)
    np.testing.assert_allclose(got[35:], extra[35:] / 16, rtol=1e-12)
    ramp = (i[19:35] - 18) / 17.0
    np.testing.assert_allclose(
        got[19:35], extra[19:35] / 16 * ramp + extra[19:35] * (1 - ramp),
        rtol=1e-12)
    np.testing.assert_allclose(got, ref.inv_freq(128, full)[0], rtol=1e-12)
    assert full['attention_factor'] == pytest.approx(0.1 * math.log(16) + 1,
                                                     abs=1e-12)
    assert ref.inv_freq(128, full)[1] == full['attention_factor']


@pytest.mark.parametrize('kind', ['sliding_attention', 'full_attention'])
def test_the_rotary_op_rotates_by_its_kinds_table(kind):
    """`moe_ops.rotate` against the reference's `rope` at the published
    parameters and positions past the original 8 192: the full layer's
    rotation is YaRN's with its factor, the sliding layers' the plain one,
    and the two differ."""
    entry = PUBLISHED['rope_parameters'][kind]
    rng = np.random.RandomState(0)
    x = rng.randn(6, 4, 128).astype('float32')
    pos = np.array([0, 1, 1023, 8191, 8192, 10751])
    yarn = None if kind == 'sliding_attention' else (
        16.0, 8192, 32.0, 1.0, entry['attention_factor'])
    got = np.asarray(moe_ops.rotate(jnp.asarray(x), jnp.asarray(pos),
                                    500000.0, False, yarn))
    freq, factor = ref.inv_freq(128, entry)
    want = np.asarray(ref.rope(jnp.asarray(x), jnp.asarray(pos),
                               jnp.asarray(freq, jnp.float32), factor))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    other = np.asarray(moe_ops.rotate(
        jnp.asarray(x), jnp.asarray(pos), 500000.0, False,
        (16.0, 8192, 32.0, 1.0, 1.2772588722239782) if yarn is None
        else None))
    assert np.abs(got - other).max() > 0.1


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
def test_only_the_full_layers_rotation_carries_yarns_attributes(program):
    """Rotary parameters by kind of layer: q and k of the three sliding
    layers are rotated by theta alone -- the op's attributes are what
    every other program's are -- and the full layer's carry YaRN's five."""
    ops = [op for op in program_listing(
        mellum2.lm_config(TOY, 32, False), program, slots=4)['ops']
        if op[0] == 'rotary_embedding']
    assert len(ops) == 2 * 4
    plain, yarn = ops[:6], ops[6:]
    assert all(op[3] == {'theta': 10000.0} for op in plain)
    for op in yarn:
        assert op[3] == {
            'theta': 10000.0, 'yarn_factor': 4.0,
            'yarn_original_max_position': 32, 'yarn_beta_fast': 4.0,
            'yarn_beta_slow': 1.0,
            'yarn_attention_factor': 1.1386294361119891}


def test_lmconfig_refuses_a_rotation_parameter_it_does_not_know():
    with pytest.raises(ValueError, match=r'LMConfig\.attention_rope'):
        T.LMConfig(position='rope', attention_rope={'scale': 2.0})
    cfg = mellum2.lm_config(TOY, 64, False)
    assert cfg.rope(0) == {'theta': 10000.0}
    assert cfg.rope(3)['factor'] == 4 and cfg.rope(3)['theta'] == 10000.0


# ---- 2. prefill then decode through both pools ------------------------------

# (prompt, new tokens, buckets): inside one chunk; across chunks' edges with
# the window (40) larger than the chunk (16, 32) and past the ring's wrap
# (7 blocks of 8 = 56 positions); a prompt that ends on a block's last row
THROUGH = [(5, 4, (16, 32)), (70, 30, (16, 32)), (121, 70, (16,)),
           (64, 9, (32,)), (200, 20, (16, 32))]


@pytest.mark.parametrize('n_prompt,n_new,buckets', THROUGH)
def test_prefill_then_decode_through_both_pools_equals_the_full_forward(
        n_prompt, n_new, buckets):
    eng = _engine(buckets=buckets)
    eng.warmup()
    log = tap_logits(eng)
    prompt, = _prompts(n_prompt, 0, n_prompt)
    toks, got, moved = _serve(eng, log, prompt, n_new)
    assert len(toks) == n_new
    assert logit_gap(got, _reference(eng, prompt, toks))[1] <= TOLERANCE
    # nothing was shared, so nothing was copied: a tenant alone writes over
    # its own blocks where they lie
    assert 'kv_window_rows_copied_total' not in moved or eng._prefix
    ring = _window(eng)['ring']
    assert moved.get('kv_window_blocks_recycled_total', 0) == max(
        0, -(-(n_prompt + n_new - 1) // BS) - ring) + min(
        ring, -(-(n_prompt + n_new - 1) // BS))
    assert _window(eng)['in_use'] == 0


@pytest.mark.parametrize('sharing', [False, True], ids=['alone', 'sharing'])
def test_generate_once_opens_a_block_before_the_step_that_writes_it(sharing):
    """`generate_once` (the benchmark's check runs it first, on untouched
    pools) feeds a step its slot's table AFTER the books have opened the
    block the step writes: a column is the trash block until then."""
    eng = _engine(prefix_sharing=sharing)
    eng.warmup()
    prompt, = _prompts(9, 0, 13)
    once = list(eng.generate_once(prompt, max_new_tokens=60))
    assert _window(eng)['in_use'] == 0
    log = tap_logits(eng)
    toks, got, _moved = _serve(eng, log, prompt, 60)
    assert once == toks
    assert logit_gap(got, _reference(eng, prompt, toks))[1] <= TOLERANCE


# ---- 3. a shared prefix over the window layers ------------------------------

@pytest.fixture(scope='module')
def cold():
    """The second prompt of `_prompts(7, 96, 30, 45)` served alone by an
    engine that shares nothing: its tokens and logits."""
    eng = _engine(prefix_sharing=False)
    eng.warmup()
    log = tap_logits(eng)
    _first, second = _prompts(7, 96, 30, 45)
    toks, got, _moved = _serve(eng, log, second, 20)
    return toks, got


@pytest.mark.parametrize('first_tenant', ['resident', 'moved-on', 'released'])
def test_a_resumed_request_is_served_what_it_is_served_cold(cold,
                                                            first_tenant):
    """96 shared tokens (12 blocks), a window of 40: the second request
    resumes at 96 from the 5 window blocks before it, which the prefix
    cache kept -- with the first tenant still resident a few steps on,
    moved on by more than a whole ring (its columns hold later blocks by
    then), or released."""
    eng = _engine()
    eng.warmup()
    log = tap_logits(eng)
    first, second = _prompts(7, 96, 30, 45)
    n_first = {'resident': 200, 'moved-on': 200, 'released': 6}[first_tenant]
    one = eng.submit(first, max_new_tokens=n_first)
    eng._admit()
    for _ in range({'resident': 3, 'moved-on': 70,
                    'released': 20}[first_tenant]):
        eng._step()
    assert (one.finish_reason is not None) == (first_tenant == 'released')
    toks, got, moved = _serve(eng, log, second, 20)
    assert moved['kv_prefix_hit_total{outcome=hit}'] == 1
    assert moved['kv_prefix_tokens_saved_total'] == 96
    assert moved['kv_window_prefix_resumes_total'] == 1
    assert moved['kv_window_blocks_shared_total'] == 5
    # the chunks that wrote into columns of shared blocks they still read
    assert moved['kv_window_rows_copied_total'] % BS == 0
    assert toks == cold[0]
    assert logit_gap(got, cold[1])[1] <= TOLERANCE
    assert logit_gap(got, _reference(eng, second, toks))[1] <= TOLERANCE
    _drive(eng, one)
    w = _window(eng)
    assert w['in_use'] == 0 and w['cached'] > 0
    assert w['cached'] == eng._sides[0].blocks.in_use()


@pytest.mark.parametrize('lost', ['every-block', 'one-block-at-depth-10',
                                  'the-pools-own-pressure'])
def test_a_window_block_the_cache_lost_is_a_miss_never_a_stale_row(cold,
                                                                   lost):
    """The prefix's entries keep their global blocks and lose window
    blocks: all of them (a miss: the whole prompt is prefilled), the one at
    depth 10 (the request resumes at depth 10, the deepest whose five
    blocks before it are all held: 80 tokens saved), or as many as a
    crowd of other tenants needs (whatever is left, the logits are the
    cold request's)."""
    eng = _engine()
    eng.warmup()
    log = tap_logits(eng)
    first, second = _prompts(7, 96, 30, 45)
    _serve(eng, log, first, 4)
    cache, side = eng._prefix, eng._sides[0].blocks
    hashes = chain_hashes(second, BS)
    want = {'every-block': 0, 'one-block-at-depth-10': 80}.get(lost)
    if lost == 'every-block':
        held = _window(eng)['cached']
        assert cache.evict_side_for(10 ** 6) == held > 0
        assert _window(eng)['cached'] == 0
    elif lost == 'one-block-at-depth-10':
        entry = cache._entries[hashes[10]]
        side.deref(entry[3])
        entry[3] = None
    else:
        others = _prompts(11, 0, 150, 170, 160)
        crowd = [eng.submit(p, max_new_tokens=40) for p in others]
        eng._admit()
        _drive(eng, *crowd)
    toks, got, moved = _serve(eng, log, second, 20)
    if want is not None:
        assert moved.get('kv_prefix_tokens_saved_total', 0) == want
        assert moved.get('kv_window_prefix_resumes_total', 0) == (want > 0)
    assert toks == cold[0]
    assert logit_gap(got, cold[1])[1] <= TOLERANCE
    # ... and its own blocks went to the entries that had lost theirs
    again = _serve(eng, log, second, 3)[2]
    assert again['kv_prefix_tokens_saved_total'] == 136


def test_a_wholly_shared_prompt_resumes_at_its_last_blocks_edge():
    """A prompt that lands on shared blocks to its last row: no block is
    copied (the ring's pools do not copy), the last block is recomputed
    from its edge, as a model with convolution tails does."""
    eng = _engine()
    eng.warmup()
    assert not eng._cow_ok
    log = tap_logits(eng)
    prompt, = _prompts(3, 0, 64)
    toks, got, _moved = _serve(eng, log, prompt, 6)
    again, got2, moved = _serve(eng, log, prompt, 6)
    assert 'kv_block_cow_total' not in moved
    assert moved['kv_prefix_tokens_saved_total'] == 56
    assert again == toks and logit_gap(got2, got)[1] <= TOLERANCE


def test_a_thousand_moves_leave_every_window_block_accounted():
    """Random admissions (three prefixes, own tails, short and long
    outputs), steps and releases: after each move the window pools'
    blocks in the slots' rings, those the cache alone holds and the free
    ones are the capacity, no ring holds more than its columns, and what
    is served stays the sequential decode's."""
    eng = _engine(slots=3, num_blocks=120)
    eng.warmup()
    rng = np.random.RandomState(5)
    prefixes = [rng.randint(2, 97, size=n) for n in (48, 64, 96)]
    side = eng._sides[0]
    live, served = [], 0
    for move in range(1000):
        if rng.rand() < 0.12 and len(live) < 6:
            prompt = np.concatenate([prefixes[rng.randint(3)], rng.randint(
                2, 97, size=rng.randint(1, 60))])
            live.append(eng.submit(prompt, max_new_tokens=int(
                rng.choice([2, 9, 40, 75]))))
        eng._admit()
        eng._step()
        done = [r for r in live if r.finish_reason is not None
                or r._error is not None]
        for r in done:
            assert r._error is None and r.finish_reason == 'length'
            served += 1
            live.remove(r)
        w = _window(eng)
        assert w['in_use'] + w['cached'] == side.blocks.in_use()
        assert w['in_use'] + w['cached'] + side.blocks.available() \
            == w['capacity'] == 3 * w['ring'] + 3 * w['ring'] // 2
        assert w['in_use'] <= 3 * w['ring']
        held = [b for t in side._tables for b in t if b]
        assert all(side.blocks.refcount(b) >= 1 for b in held)
    assert served > 40
    _drive(eng, *live)
    eng._prefix.drop_all()
    assert side.blocks.in_use() == 0 == eng._alloc.in_use()
    stats = monitor.counters()
    assert stats['kv_window_prefix_resumes_total'] > 10


# ---- 4. the books, unit by unit ---------------------------------------------

def test_a_ring_lets_go_of_a_shared_block_and_writes_over_its_own():
    rings = WindowRings(slots=2, ring=4, block_size=8, reach=15, cached=2)
    cache = PrefixCache(BlockAllocator(9, 8), rings.blocks)
    rings.cache = cache
    assert rings.capacity == 2 * 4 + 2
    assert rings.advance(0, 32, 0) == 0 and rings.table(0) == [1, 2, 3, 4]
    # the cache takes logical block 1 and 2 of slot 0
    for depth in (1, 2):
        cache._alloc.alloc(1)
        cache.register(b'h%d' % depth, depth, depth,
                       (rings.held(0, depth), 0))
    # a chunk of positions 32 .. 55: block 4 over block 0 (its own: in
    # place), 5 over 1 and 6 over 2 (shared: fresh ones; block 2's rows
    # are within 15 of position 32, block 1's are not)
    assert rings.advance(0, 56, 32) == 3
    assert rings.table(0) == [1, 5, 6, 4]
    assert rings.moved() == [(3, 6)] and rings.moved() == []
    assert rings.held(0, 1) is None and rings.held(0, 5) == 5
    assert (rings.in_use(), rings.blocks.in_use()) == (4, 6)
    # a new tenant resumes at block 3 from blocks 1 and 2
    for b in (2, 3):
        rings.blocks.ref(b)
    rings.resume(1, 3, [2, 3])
    assert rings.table(1) == [0, 2, 3, 0] and rings.in_use() == 6
    # a step at position 24 opens block 3 in an empty column
    assert rings.advance(1, 25) == 0 and rings.table(1) == [0, 2, 3, 7]
    assert rings.release(0) == 4 and rings.release(1) == 3
    stats = {'blocks': {}}
    rings.report(stats)
    assert stats['blocks']['window'] == {'capacity': 10, 'ring': 4,
                                         'in_use': 0, 'cached': 2}


def test_the_cache_gives_up_its_shallowest_window_blocks_first():
    alloc, side = BlockAllocator(12, 8), BlockAllocator(7, 8)
    cache = PrefixCache(alloc, side)
    hashes = [b'h%d' % i for i in range(6)]
    for i, h in enumerate(hashes):
        cache.register(h, i, alloc.alloc(1)[0], (side.alloc(1)[0], 0))
    for b in range(1, 7):
        side.deref(b)               # the tenant that made them is gone
    assert cache.match(hashes) == [1, 2, 3, 4, 5, 6]
    assert cache.side_run(hashes, 6, reach=15) == (6, [5, 6])
    assert cache.side_run(hashes, 6, reach=17) == (6, [4, 5, 6])
    assert cache.evict_side_for(3) == 3 and side.available() == 3
    assert [e[3] for e in (cache._entries[h] for h in hashes)] == \
        [None, None, None, 4, 5, 6]
    assert cache.side_run(hashes, 4, reach=15) == (0, [])
    assert cache.side_run(hashes, 5, reach=8) == (5, [5])
    # an entry that lost its block takes a later tenant's
    new = side.alloc(1)[0]
    assert not cache.register(hashes[2], 2, 99, (new, 0))
    assert cache._entries[hashes[2]][3] == new and side.refcount(new) == 2
    # a block written from its fourth row on serves the one depth that
    # needs no earlier row of it
    late = side.alloc(1)[0]
    cache.register(hashes[1], 1, 99, (late, 4))
    assert cache.side_run(hashes, 3, reach=12) == (3, [late, new])
    assert cache.side_run(hashes, 3, reach=13) == (0, [])
    cache.drop_all()
    alloc.deref_many(range(1, 7))
    side.deref_many([new, late])
    assert side.in_use() == 0 and alloc.in_use() == 0


@pytest.mark.parametrize('program', ['decode_step', 'prefill_paged'])
def test_kexaones_programs_and_pools_are_what_they_were(program):
    """K-EXAONE's toy, whose cell shares no prefix: op for op with every
    attribute what it built at PR 46 (fixtures/lm_programs_parent_pr46.json)
    and its pools the shapes they had; only an engine that shares prefixes
    gives the window pools the cache's blocks."""
    cfg = kexaone.lm_config(_toy('kexaone'), 32, False)
    with open(os.path.join(HERE, 'fixtures',
                           'lm_programs_parent_pr46.json')) as f:
        assert program_listing(cfg, program, slots=4) == \
            json.load(f)['kexaone'][program]
    ring = T.window_ring(cfg, 8)
    shapes = T.kv_cache_shapes(cfg, 9, 8, 4)
    assert shapes[T.WINDOW_CACHE_K] == (4 * ring + 1, 4, 8, 16) \
        == shapes[T.WINDOW_CACHE_V]
    assert shapes[T.KV_CACHE_K] == (9, 1, 8, 16)
    shared = T.kv_cache_shapes(cfg, 9, 8, 4, shared=True)
    assert shared[T.WINDOW_CACHE_K][0] == 4 * ring + 1 + 2 * ring
    assert {k: v for k, v in shared.items() if 'window' not in k} == \
        {k: v for k, v in shapes.items() if 'window' not in k}


def test_speculation_stays_refused_over_the_rings():
    with pytest.raises(ValueError, match=r'speculative=True with '
                                         r'LMConfig\.layer_types'):
        _engine(speculative=True)
    ring = [p for p in _engine()._pools if p.index == 'ring'][0]
    assert (ring.rewinds, ring.copies, ring.reach) == (False, False, 39)
    assert ring.books['hit'] == 'kv_window_prefix_resumes_total'


# ---- 5. the controls --------------------------------------------------------

@pytest.fixture(scope='module')
def resumed_run():
    """A first tenant of 96 shared + 70 own tokens and 12 more, then a
    second of the same 96 + 33 and 12 more, resumed: the second's logits,
    and the reference's."""
    eng = _engine()
    eng.warmup()
    log = tap_logits(eng)
    first, second = _prompts(41, 96, 70, 33)
    toks1, _got, _moved = _serve(eng, log, first, 12)
    toks, got, moved = _serve(eng, log, second, 12)
    assert moved['kv_window_prefix_resumes_total'] == 1
    seq = np.concatenate([second, toks[:-1]])
    pos = np.arange(len(second) - 1, len(seq))
    wrong = dict(mellum2_control.controls(TOY),
                 **mellum2_control.ring_controls(
                     eng.scope, TOY, 96, np.concatenate([first, toks1[:-1]]),
                     BS, _window(eng)['ring']))
    return dict(eng=eng, seq=seq, pos=pos, got=got, wrong=wrong,
                want=np.asarray(ref.logits(eng.scope, TOY, seq,
                                           positions=pos)))


CONTROLS = sorted(list(mellum2_control.controls(TOY))
                  + ['ring-later', 'ring-zeros'])


@pytest.mark.parametrize('control', CONTROLS)
def test_a_control_is_outside_the_tolerance(resumed_run, control):
    kw = resumed_run['wrong'][control]
    hidden = ref.forward(resumed_run['eng'].scope, TOY, resumed_run['seq'],
                         **kw)[0]
    wrong = np.asarray(ref.head(resumed_run['eng'].scope, TOY, hidden,
                                resumed_run['pos'],
                                kw.get('norm_weights', True)))
    assert logit_gap(resumed_run['got'], resumed_run['want'])[1] <= TOLERANCE
    assert logit_gap(wrong, resumed_run['want'])[1] > 20 * TOLERANCE, control


def test_the_chip_comparison_runs_at_toy_width():
    """`mellum2_control.compare`, the comparison the chip run makes: one
    engine, the first request cold and the second resumed behind it, the
    sound system inside both limits' meaning and every control named."""
    scope = _scope()
    served = mellum2_control.Served(
        mellum2.lm_config(TOY, 256, False),
        {'slots': 2, 'max_len': 256, 'block_size': BS, 'num_blocks': 90,
         'prompt_buckets': [16, 32]}, scope)
    first, second = _prompts(43, 96, 90, 20)
    one, two = mellum2_control.compare(served, scope, TOY, first, second, 6)
    assert (one['resumed_at'], two['resumed_at']) == (0, 96)
    assert two['first_moved_on_blocks'] >= _window(served.eng)['ring']
    assert sorted(two['controls']) == CONTROLS
    assert sorted(one['controls']) == sorted(mellum2_control.controls(TOY))
    for reading in (one, two):
        assert reading['logits_vs_ref'][1] <= TOLERANCE
        assert reading['logits_vs_ref_given_routing'][1] <= TOLERANCE
        for name, c in reading['controls'].items():
            assert c['logits_vs_ref'][1] > 20 * TOLERANCE, name


# ---- 6. Mosaic, at the cell's shapes ----------------------------------------

@pytest.fixture(scope='module')
def one_chip():
    os.environ.setdefault('TPU_LOG_DIR', 'disabled')
    for k, v in (('TPU_ACCELERATOR_TYPE', 'v5litepod-4'),
                 ('TPU_WORKER_HOSTNAMES', 'localhost'),
                 ('TPU_SKIP_MDS_QUERY', '1')):
        os.environ.setdefault(k, v)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize('span', [1024, None], ids=['window', 'full'])
def test_mosaic_accepts_the_kernel_at_mellums_shapes(one_chip, span):
    """32 query heads on 4 K/V heads of 128 in pages of 32: a page of 512
    lanes. The window layers' call over the 3 265-block pools (64 rings of
    34, the trash block, 1 088 for the prefix cache) and their 34-column
    rings, 33 pages a slot where K-EXAONE reads 5; the full layer's over
    6 144 blocks and a table of 336."""
    import jax
    assert pda.shapes_ok(32, 128, 32, 4)
    cfg = mellum2.lm_config(PUBLISHED, 10752, False)
    assert T.window_ring(cfg, 32) == 34
    assert T.window_pool_blocks(cfg, 64, 32, shared=True) == 3265
    nb, ln, mb = (3265, 3, 34) if span else (6144, 1, 336)

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kw = {'attention_span': span} if span else {}
    compiled = jax.jit(lambda q, k, v, t, p, l: pda.paged_decode_attention(
        q, k, v, t, p, l, scale=128 ** -0.5, **kw)).lower(
        sds((64, 32, 128)), sds((nb, ln, 32, 512)),
        sds((nb, ln, 32, 512)), sds((64, mb), jnp.int32),
        sds((64,), jnp.int32), sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('tpu_custom_call') == 1
    assert ('paged_window_decode_attention' in text) == bool(span)


# (T, keys, window): the full layer's call over the cell's longest table at
# the widest and the narrowest bucket; the window layers' over the 1 023 ring
# rows before a chunk and the chunk itself -- a window WIDER than the chunk,
# where K-EXAONE's is a quarter of one
PREFIX_SHAPES = {'full-b512': (512, 10752, None),
                 'full-b128': (128, 10752, None),
                 'window-b512': (512, 1535, 1024),
                 'window-b128': (128, 1151, 1024)}


@pytest.mark.parametrize('shape', sorted(PREFIX_SHAPES))
def test_mosaic_accepts_the_prefix_kernel_at_mellums_shapes(one_chip, shape):
    """ops/prefix_attention.py at the cell's shape classes: one custom call
    under its kind's name and nothing beside it as long as the scores."""
    import functools
    import re
    import jax
    from paddle_tpu.ops import prefix_attention as pfa
    T_, keys, window = PREFIX_SHAPES[shape]

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        pfa.prefix_attention, scale=128 ** -0.5, window=window)).lower(
        sds((32, T_, 128)), sds((4, keys, 128)), sds((4, keys, 128)),
        sds((keys,), jnp.int32), sds((T_,), jnp.int32)).compile()
    text = compiled.as_text()
    name = 'kv_prefix_window_attention' if window else 'kv_prefix_attention'
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert re.search(r'%%?%s[.\d]* = ' % name, text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 32 * T_ * keys * 4 // 4
