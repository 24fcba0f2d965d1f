"""The KV block pool (serving/generate.py + serving/kv_blocks.py + the
ops/kv_cache_ops.py block-table ops): exact greedy parity vs a forward
that has NO cache, block-allocator admission/growth/exhaustion semantics,
prefix sharing with physical block reuse and copy-on-write isolation,
per-request sampling streams, and the zero-recompile contract under
mixed traffic.

Engines here share ONE tiny-LM shape family (test_generate.py's, at
block size 8), so the process-wide fingerprint compile cache
keeps per-test warmups at milliseconds after the first test pays the
XLA compiles. Several tests drive the engine INLINE (submit + _admit +
_step, loop thread never started) — that makes allocator state,
refcounts and block tables observable deterministically between token
boundaries. The heavy shared-prefix measurement is @slow
(tests/conftest.py asserts this file's marker split like
test_generate.py's).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import monitor
from paddle_tpu.models.transformer import LMConfig, build_lm
from paddle_tpu.serving import GenerateConfig, GenerateEngine
from paddle_tpu.serving.kv_blocks import (BlockAllocator, PrefixCache,
                                          chain_hashes)

BUCKETS = [8, 16]
MAX_LEN = 48
SLOTS = 4
BS = 8                        # block size
NUM_BLOCKS = SLOTS * MAX_LEN // BS          # 24 physical: MAX_LEN rows a slot
USABLE = NUM_BLOCKS - 1                     # block 0 is the trash block


def _model(seq_len=32):
    return LMConfig(vocab_size=64, seq_len=seq_len, d_model=32, n_head=2,
                    n_layer=2, d_ff=64, dropout=0.0, attn_dropout=0.0,
                    use_flash_attention=False)


def _paged_cfg(**kw):
    kw.setdefault('model', _model())
    kw.setdefault('slots', SLOTS)
    kw.setdefault('max_len', MAX_LEN)
    kw.setdefault('prompt_buckets', list(BUCKETS))
    kw.setdefault('eos_id', None)
    kw.setdefault('seed', 0)
    kw.setdefault('block_size', BS)
    return GenerateConfig(**kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 64, size=n) \
        .astype('int64')


def _drive(eng, *reqs):
    """Run the engine loop inline (deterministic, no thread) until every
    given request finishes."""
    eng._admit()
    while any(r.finish_reason is None and r._error is None
              for r in reqs):
        eng._step()
        eng._evict_expired()
        eng._admit()


# ---------------------------------------------------------------------------
# allocator + prefix cache (host-side, no programs)


def test_block_allocator_and_prefix_cache_unit():
    alloc = BlockAllocator(8, 4)            # blocks 1..7 usable
    assert alloc.capacity == 7 and alloc.available() == 7
    a = alloc.alloc(3)
    assert len(a) == 3 and 0 not in a and alloc.in_use() == 3
    assert alloc.alloc(5) is None           # all-or-nothing
    assert alloc.available() == 4
    alloc.ref(a[0])
    assert not alloc.deref(a[0])            # still referenced
    assert alloc.deref(a[0])                # now freed
    assert alloc.available() == 5
    with pytest.raises(ValueError):
        alloc.deref(a[0])                   # double free

    # prefix cache: register/match/evict with chain semantics
    toks = np.arange(12)
    h = chain_hashes(toks, 4)
    assert len(h) == 3                      # full blocks only
    assert chain_hashes(toks[:11], 4) == h[:2]
    assert chain_hashes(np.concatenate([toks[:4], [99] * 8]), 4)[0] == h[0]
    cache = PrefixCache(alloc)
    b = alloc.alloc(2)
    cache.register(h[0], 0, b[0])
    cache.register(h[1], 1, b[1])
    assert alloc.refcount(b[0]) == 2        # owner + cache
    assert cache.match(h) == [b[0], b[1]]   # longest run, chain order
    assert cache.match([h[1]]) == []        # chains start at depth 0
    for x in b:
        alloc.deref(x)                      # owner releases; cache holds
    assert alloc.available() == 3
    cache.evict_for(4)                      # pressure: deepest-first
    assert alloc.available() >= 4 and len(cache) <= 1


# ---------------------------------------------------------------------------
# parity + recompiles


def _cache_free_greedy(eng, prompt, n_new):
    """The reference with no cache in it: `build_lm(is_test=True)` — the
    training forward, none of the decode programs' ops — re-run on the
    growing sequence, argmax at its last real row. One program at
    MAX_LEN rows serves every length: the sequence is zero-padded behind
    and the causal mask keeps the padding out of every real row."""
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        with fluid.unique_name.guard():
            _t, _l, logits, _loss = build_lm(_model(MAX_LEN), is_test=True)
    ids = [int(t) for t in prompt]
    for _ in range(n_new):
        arr = np.zeros((1, MAX_LEN), 'int64')
        arr[0, :len(ids)] = ids
        out = eng.executor.run(main, feed={'tokens': arr, 'labels': arr},
                               fetch_list=[logits], scope=eng.scope)
        ids.append(int(np.asarray(out[0])[0, len(ids) - 1].argmax()))
    return ids[len(prompt):]


# (prompt length, new tokens): the prompt ends inside a block; on a block
# edge of both sizes; and is wider than the widest bucket (16), so its
# prefill runs in three chunks. Each generation crosses a block edge.
PARITY_CASES = {'mid_block': (5, 14), 'block_edge': (16, 18),
                'chunked': (37, 10)}


@pytest.mark.parametrize('block_size', [8, 16])
@pytest.mark.parametrize('case', sorted(PARITY_CASES))
def test_greedy_tokens_equal_the_cache_free_forward(case, block_size):
    """Prefill + block-table decode must give the tokens of a forward
    that keeps no cache EXACTLY — through generate_once and with
    neighbours in the other slots: the page gather/scatter, the
    trash-block masking and the chunked prefill are bit-transparent to
    the argmax."""
    eng = GenerateEngine(_paged_cfg(block_size=block_size))
    plen, n_new = PARITY_CASES[case]
    work = [(_prompt(7, 2), 14), (_prompt(plen, 70 + plen), n_new),
            (_prompt(12, 3), 6), (_prompt(9, 6), 13)]
    refs = [_cache_free_greedy(eng, p, n) for p, n in work]
    assert eng.generate_once(*work[1]) == refs[1]
    with eng:
        reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
        outs = [r.result(60) for r in reqs]
        live = eng.stats()['blocks']
        # finished requests returned their blocks; only the prefix
        # cache's references (every prompt's full blocks) remain until
        # stop() drops them
        assert live['in_use'] == live['prefix_entries'] == \
            sum(p.size // block_size for p, _ in work)
    assert outs == refs
    assert eng.stats()['active'] == 0
    assert eng.stats()['blocks']['in_use'] == 0     # stop() drops cache


def test_mixed_paged_traffic_zero_recompiles_after_warmup():
    """Any mix of prompt lengths, suffix buckets, prefix hits, COW
    copies and sampling params re-executes the warmed signature set:
    compile_cache_miss delta 0 — block tables, positions and sampling
    controls are ordinary feeds."""
    eng = GenerateEngine(_paged_cfg())
    warm = eng.warmup()
    assert warm['buckets'] == len(BUCKETS)
    shared = _prompt(16, seed=77)
    before = monitor.counters()
    with eng:
        reqs = [eng.submit(_prompt(3 + (i * 5) % 14, seed=i),
                           max_new_tokens=3 + i % 9)
                for i in range(8)]
        # repeated prompt: prefix hits + a COW (16 = 2 full blocks)
        reqs += [eng.submit(shared, max_new_tokens=4,
                            temperature=0.7 if i else 0.0,
                            sample_seed=i)
                 for i in range(3)]
        for r in reqs:
            r.result(60)
    delta = monitor.counter_delta(before)
    assert not any(k.startswith('compile_cache_miss') for k in delta), \
        delta
    assert delta.get('generate_request_total{outcome=ok}') == 11
    assert delta.get('kv_prefix_hit_total{outcome=hit}', 0) >= 2
    assert delta.get('kv_block_cow_total', 0) >= 2


# ---------------------------------------------------------------------------
# prefix sharing: physical block reuse + COW isolation


def test_prefix_sharing_reuses_physical_blocks():
    """Two requests with the same 20-token prompt (2 full blocks + a
    partial): the second maps its leading table entries onto the FIRST
    request's physical blocks (refcount proof), prefills only the
    4-token suffix (tokens-saved counter), and still decodes the exact
    greedy continuation."""
    # a wider ladder so the FIRST (no-hit) admission can prefill the
    # whole 20-token prompt; the second admission buckets its 4-token
    # suffix onto the smallest cell
    eng = GenerateEngine(_paged_cfg(prompt_buckets=[8, 16, 32]))
    eng.warmup()
    p = _prompt(20, seed=21)
    before = monitor.counters()
    a = eng.submit(p, max_new_tokens=3)
    _drive(eng, a)
    d1 = monitor.counter_delta(before)
    assert d1.get('kv_prefix_hit_total{outcome=miss}') == 1
    # A's full prompt blocks stayed registered after A finished
    assert eng.stats()['blocks']['prefix_entries'] == 2
    reg = [e[0] for e in sorted(eng._prefix._entries.values(),
                                key=lambda e: e[1])]

    b = eng.submit(p, max_new_tokens=3)
    eng._admit()
    st = next(s for s in eng._slots if s is not None)
    assert st.blocks[:2] == reg             # SAME physical blocks
    assert eng._alloc.refcount(reg[0]) == 2     # cache + B
    assert list(st.table[:3]) == st.blocks      # table mirrors, in order
    _drive(eng, b)
    d2 = monitor.counter_delta(before)
    assert d2.get('kv_prefix_hit_total{outcome=hit}') == 1
    assert d2.get('kv_prefix_tokens_saved_total') == 16
    assert d2.get('kv_block_cow_total', 0) == 0     # suffix != block edge
    assert b.result(5) == a.result(5)       # exact greedy continuation
    eng.stop()


def test_cow_isolation_between_divergent_sharers():
    """Two sampled requests forked off the SAME fully-shared prompt
    (length a block multiple, so the final prompt position lands on a
    shared block) each copy-on-write their last block and then diverge:
    each must reproduce its solo (unshared, fresh-block) run exactly —
    neither ever observes the other's writes, and the shared originals
    stay pristine for the next hit."""
    eng = GenerateEngine(_paged_cfg())
    eng.warmup()
    p = _prompt(16, seed=31)                # 2 full blocks, no partial
    # solo references run with NO sharing (generate_once bypasses the
    # prefix cache: fresh blocks, full prefill)
    ref_a = eng.generate_once(p, max_new_tokens=6, temperature=0.9,
                              top_k=8, sample_seed=1)
    ref_b = eng.generate_once(p, max_new_tokens=6, temperature=0.9,
                              top_k=8, sample_seed=2)
    assert ref_a != ref_b                   # streams genuinely diverge
    greedy = eng.generate_once(p, max_new_tokens=6)
    before = monitor.counters()
    with eng:
        g = eng.submit(p, max_new_tokens=6)             # registers blocks
        assert g.result(60) == greedy
        ra = eng.submit(p, max_new_tokens=6, temperature=0.9, top_k=8,
                        sample_seed=1)
        rb = eng.submit(p, max_new_tokens=6, temperature=0.9, top_k=8,
                        sample_seed=2)
        assert ra.result(60) == ref_a
        assert rb.result(60) == ref_b
    delta = monitor.counter_delta(before)
    assert delta.get('kv_block_cow_total', 0) >= 2
    assert delta.get('kv_prefix_hit_total{outcome=hit}', 0) >= 2


# ---------------------------------------------------------------------------
# allocator exhaustion + the >=2x concurrency contract


def test_allocator_exhaustion_cache_full_and_blocks_returned():
    """Four co-resident growers demand 4 * 6 = 24 blocks of a 23-block
    pool: exactly one starves at its final block-boundary crossing and
    finishes 'cache_full' early (with its tokens so far); the others
    decode on to the cache edge; every block returns to the free
    list."""
    eng = GenerateEngine(_paged_cfg(prefix_sharing=False))
    eng.warmup()
    assert eng._alloc.capacity == USABLE == 23
    reqs = [eng.submit(_prompt(16, seed=50 + i), max_new_tokens=40)
            for i in range(4)]
    _drive(eng, *reqs)
    outs = [r.result(5) for r in reqs]
    assert all(r.finish_reason == 'cache_full' for r in reqs)
    lens = sorted(len(o) for o in outs)
    # starved: 1 prefill token + steps up to the failed growth at
    # position 40; survivors: 1 + 32 steps to the max_len edge
    assert lens == [25, 33, 33, 33], lens
    assert eng._alloc.in_use() == 0
    assert eng._alloc.available() == USABLE
    eng.stop()


def test_paged_serves_2x_concurrent_sequences_at_same_hbm():
    """THE capacity contract: at the HBM budget of a MAX_LEN row-span
    for each of SLOTS sequences (NUM_BLOCKS * BS = SLOTS * MAX_LEN
    rows), the pool holds >= 2x that many sequences in flight
    simultaneously, because short sequences commit one block instead of
    a max_len row-span — with exact greedy parity throughout."""
    row_span_slots_at_budget = NUM_BLOCKS * BS // MAX_LEN   # = SLOTS
    assert row_span_slots_at_budget == SLOTS
    eng = GenerateEngine(_paged_cfg(slots=4 * SLOTS))
    eng.warmup()
    work = [(_prompt(3 + i % 3, seed=60 + i), 3) for i in range(16)]
    refs = [eng.generate_once(p, max_new_tokens=n) for p, n in work]
    reqs = [eng.submit(p, max_new_tokens=n) for p, n in work]
    eng._admit()                 # blocks-available admission, inline
    stats = eng.stats()
    assert stats['active'] == 16            # all co-resident: 16 blocks
    assert stats['blocks']['in_use'] <= USABLE
    _drive(eng, *reqs)
    assert [r.result(5) for r in reqs] == refs
    assert eng.stats()['peak_active'] >= 2 * row_span_slots_at_budget
    eng.stop()


def test_prefix_match_pinned_against_pressure_eviction():
    """Regression: under pool pressure, planning an admission must not
    evict the very blocks the prefix match just returned and recycle
    one as 'fresh' (a duplicate block id would make the suffix prefill
    clobber its own cached prefix). The matched blocks are pinned before
    the allocator runs: with the rest of the pool hoarded, the plan
    PARKS instead of cannibalizing its own match, and proceeds correctly
    once blocks free up."""
    eng = GenerateEngine(_paged_cfg())
    eng.warmup()
    p = _prompt(16, seed=91)                # 2 full blocks
    a = eng.submit(p, max_new_tokens=3)
    _drive(eng, a)                          # registers both blocks
    reg = sorted(e[0] for e in eng._prefix._entries.values())
    hoard = eng._alloc.alloc(eng._alloc.available())    # free list: 0
    b = eng.submit(p, max_new_tokens=3)
    eng._admit()
    # the only refcount-1 blocks are the matched ones; an unpinned plan
    # would evict + recycle them — the pinned plan parks instead
    assert eng._pending_admit is b
    assert sorted(e[0] for e in eng._prefix._entries.values()) == reg
    eng._deref_blocks(hoard)
    _drive(eng, b)
    assert b.result(5) == a.result(5)
    assert eng._alloc.in_use() == len(eng._prefix._entries)
    eng.stop()


# ---------------------------------------------------------------------------
# sampling: per-seed determinism + per-slot stream independence


def test_sampling_determinism_and_stream_independence():
    """A pinned sample_seed replays the identical token stream; two
    sampled requests co-resident with different seeds each match their
    SOLO runs exactly (per-slot PRNG streams never cross-pollinate),
    and temperature 0 stays bitwise greedy next to them."""
    eng = GenerateEngine(_paged_cfg())
    p1, p2 = _prompt(6, seed=71), _prompt(9, seed=72)
    kw = dict(max_new_tokens=8, temperature=0.8, top_k=8, top_p=0.9)
    solo1 = eng.generate_once(p1, sample_seed=11, **kw)
    assert eng.generate_once(p1, sample_seed=11, **kw) == solo1
    solo2 = eng.generate_once(p2, sample_seed=12, **kw)
    assert solo2 != eng.generate_once(p2, sample_seed=13, **kw)
    greedy = eng.generate_once(p1, max_new_tokens=8)
    with eng:
        r1 = eng.submit(p1, sample_seed=11, **kw)
        r2 = eng.submit(p2, sample_seed=12, **kw)
        rg = eng.submit(p1, max_new_tokens=8)
        assert r1.result(60) == solo1
        assert r2.result(60) == solo2
        assert rg.result(60) == greedy


# ---------------------------------------------------------------------------
# shared-prefix workload (heavy: @slow, tier-1 skips)


@pytest.mark.slow
def test_shared_prefix_workload_reduces_prefill():
    """End-to-end shared-prefix win (the servebench --shared-prefix
    workload): N clients, one system prompt — prefix blocks physically
    shared (refcount over the shared blocks reaches cache + all
    sharers), every post-first admission hits, and total prefill wall
    time drops measurably vs sharing off, at identical greedy
    output."""
    from tools.servebench import measure_shared_prefix
    row = measure_shared_prefix(clients=6)
    assert row['greedy_parity_on_vs_off'] is True
    assert row['prefix_hits'] == 5
    assert row['prefill_tokens_saved'] >= 5 * row['system_len'] - 5
    assert row['peak_refcount_on_shared_blocks'] >= 3
    assert row['peak_blocks']['sharing_on'] < \
        row['peak_blocks']['sharing_off']
    assert row['prefill_speedup'] >= 1.2, row
