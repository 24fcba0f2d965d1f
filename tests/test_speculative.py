"""Speculative decoding + chunked prefill on the paged KV engine
(serving/generate.py speculative mode, models/transformer.py
build_lm_drafter / build_lm_verify, ops/kv_cache_ops.py span-write +
verify-attention ops).

The load-bearing contracts:

- BITWISE greedy parity spec-vs-plain across the accept, reject and
  rollback paths — speculation changes how many tokens land per
  dispatch, never which tokens.
- chunked prefill admits prompts past the widest bucket and its
  continuation is bit-exact vs a single-shot prefill through a wider
  bucket.
- paged-block refcount conservation after speculative rollback: tail
  blocks a rejected window briefly held all return to their pools.
- the fixed-signature contract survives: zero recompiles after warmup
  under mixed speculative traffic including chunked prompts.

Engines reuse test_paged_generate.py's tiny-LM shape family, so the
process-wide fingerprint cache amortizes warmups across both files.
The throughput measurement is @slow (tests/conftest.py asserts this
file's marker split like test_generate.py's).
"""
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.executor import Scope
from paddle_tpu.models.transformer import (KV_CACHE_K, KV_CACHE_V,
                                           LMConfig)
from paddle_tpu.serving import GenerateConfig, GenerateEngine

BUCKETS = [8, 16]
MAX_LEN = 48
SLOTS = 4
BS = 8
K = 2                           # spec_k for every engine in this file
                                # (compile cost scales with the unroll;
                                # K=2 already exercises multi-draft
                                # windows + the bonus-token path)


def _model(**kw):
    d = dict(vocab_size=64, seq_len=32, d_model=32, n_head=2,
             n_layer=2, d_ff=64, dropout=0.0, attn_dropout=0.0,
             use_flash_attention=False)
    d.update(kw)
    return LMConfig(**d)


def _cfg(**kw):
    kw.setdefault('model', _model())
    kw.setdefault('slots', SLOTS)
    kw.setdefault('max_len', MAX_LEN)
    kw.setdefault('prompt_buckets', list(BUCKETS))
    kw.setdefault('eos_id', None)
    kw.setdefault('seed', 0)
    kw.setdefault('block_size', BS)
    return GenerateConfig(**kw)


def _spec_cfg(**kw):
    kw.setdefault('speculative', True)
    kw.setdefault('spec_k', K)
    return _cfg(**kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(2, 64, size=n) \
        .astype('int64')


WORK = [(_prompt(4, 1), 9), (_prompt(7, 2), 14), (_prompt(12, 3), 6),
        (_prompt(16, 4), 11)]


def _drive(eng, *reqs):
    """Run the engine loop inline (deterministic, no thread) until
    every given request finishes."""
    eng._admit()
    while any(r.finish_reason is None and r._error is None
              for r in reqs):
        eng._step()
        eng._evict_expired()
        eng._admit()


def test_config_validation():
    with pytest.raises(ValueError):
        _spec_cfg(spec_k=0)
    with pytest.raises(ValueError):
        _spec_cfg(draft_model=_model(vocab_size=128))


def test_spec_greedy_parity_accept_path_bitwise():
    """Draft == target (aliased weights): every draft is accepted
    (accept_rate exactly 1.0 — the window advances spec_k + 1 tokens
    per round), outputs are BIT-IDENTICAL to the plain paged engine,
    and both pools drain to conservation when the requests finish."""
    plain = GenerateEngine(_cfg())
    refs = [plain.generate_once(p, max_new_tokens=n) for p, n in WORK]
    spec = GenerateEngine(_spec_cfg())
    spec.warmup()
    with spec:
        reqs = [spec.submit(p, max_new_tokens=n) for p, n in WORK]
        outs = [list(r.result(60)) for r in reqs]
    assert outs == refs
    st = spec.stats()
    assert st['spec']['accept_rate'] == 1.0, st['spec']
    assert st['spec']['rounds'] > 0
    # speculation actually batched the decode: far fewer rounds than
    # tokens (the longest request alone needs ceil(13 / (K+1)) rounds)
    assert st['decode_steps'] < st['decode_tokens'] / 2
    # conservation: draft pool fully drained; target pool holds only
    # the prefix cache's references (dropped at stop())
    assert st['spec']['draft_blocks_in_use'] == 0
    assert st['blocks']['in_use'] == st['blocks']['prefix_entries']
    # per-request accept-rate rides the timing breakdown
    t = reqs[0].timing
    assert t['spec_accept_rate'] == 1.0 and t['spec_proposed'] > 0
    assert 'draft_s' in t and 'verify_s' in t


def test_spec_greedy_parity_reject_rollback_bitwise():
    """A DIVERGENT draft (fresh 1-layer model — its proposals rarely or
    never match) forces the reject + rollback path every round: output
    must STILL be bit-identical to plain decode (every emitted token is
    the target's own argmax), and every speculative tail block returns
    to its pool."""
    plain = GenerateEngine(_cfg())
    refs = [plain.generate_once(p, max_new_tokens=n) for p, n in WORK]
    spec = GenerateEngine(_spec_cfg(draft_model=_model(n_layer=1)))
    spec.warmup()
    with spec:
        reqs = [spec.submit(p, max_new_tokens=n) for p, n in WORK]
        outs = [list(r.result(60)) for r in reqs]
    assert outs == refs
    st = spec.stats()
    assert st['spec']['accept_rate'] < 1.0
    assert st['spec']['draft_blocks_in_use'] == 0
    assert st['blocks']['in_use'] == st['blocks']['prefix_entries']


def test_spec_partial_accept_layer_skip_draft():
    """Layer-skip draft (the target's own first layer via an aliased
    draft_scope — the self-speculative idiom): agreement is partial, so
    accept/reject MIX within windows; parity must hold regardless, and
    the round-by-round inline drive checks the block-table truncation
    invariant after every round."""
    plain = GenerateEngine(_cfg())
    refs = [plain.generate_once(p, max_new_tokens=n) for p, n in WORK]
    tgt = GenerateEngine(_cfg())    # donor scope for the aliased draft
    ds = Scope()
    for name in tgt.scope.names():
        if name not in (KV_CACHE_K, KV_CACHE_V):
            ds.set(name, tgt.scope.get(name))
    spec = GenerateEngine(_spec_cfg(draft_model=_model(n_layer=1)),
                          scope=tgt.scope, draft_scope=ds)
    spec.warmup()
    reqs = [spec.submit(p, max_new_tokens=n) for p, n in WORK]
    spec._admit()
    while any(r.finish_reason is None and r._error is None
              for r in reqs):
        spec._step()
        for st in spec._slots:
            if st is None:
                continue
            # truncation invariant: after every round a slot holds
            # exactly the blocks covering its accepted positions PLUS
            # the block its next token writes into (never released —
            # a competing slot grabbing it would turn the next growth
            # into a premature cache_full)
            keep = min(MAX_LEN // BS, st.pos // BS + 1)
            assert len(st.blocks) == keep
            assert len(st.dblocks) == keep
        spec._evict_expired()
        spec._admit()
    assert [list(r.result(5)) for r in reqs] == refs
    assert spec._draft_alloc.in_use() == 0
    spec.stop()


def test_spec_eos_inside_window():
    """An eos landing MID-window must cut emission exactly where plain
    decode would have stopped — tokens after the eos row are discarded
    even when the draft got them 'right'."""
    probe = GenerateEngine(_cfg())
    ref0 = probe.generate_once(WORK[1][0], max_new_tokens=14)
    eos = ref0[len(ref0) // 2]      # a token greedy decode really emits
    plain = GenerateEngine(_cfg(eos_id=int(eos)), scope=probe.scope)
    refs = [plain.generate_once(p, max_new_tokens=n) for p, n in WORK]
    assert any(r[-1] == eos and len(r) < n for r, (_, n) in
               zip(refs, WORK)), "probe token never terminates a ref"
    spec = GenerateEngine(_spec_cfg(eos_id=int(eos)), scope=probe.scope)
    spec.warmup()
    with spec:
        outs = [list(spec.submit(p, max_new_tokens=n).result(60))
                for p, n in WORK]
    assert outs == refs


def test_chunked_prefill_bitexact_vs_single_shot():
    """A prompt longer than the widest bucket is admitted via chunked
    prefill and its continuation matches the single-shot (wide-bucket)
    reference bit-exactly, through generate_once AND the engine loop."""
    p = _prompt(40, 9)              # widest chunked bucket is 16
    wide = GenerateEngine(_cfg(prompt_buckets=[40]))
    ref = wide.generate_once(p, max_new_tokens=8)
    chunk = GenerateEngine(_cfg())
    assert chunk.generate_once(p, max_new_tokens=8) == ref
    with chunk:
        r = chunk.submit(p, max_new_tokens=8)
        assert list(r.result(60)) == ref
    # the admission bound is max_len - 1
    with pytest.raises(ValueError):
        chunk.submit(_prompt(MAX_LEN, 10))


def test_chunked_prefill_composes_with_speculation_and_sharing():
    """Long prompt + prefix sharing + speculative decode in one flow:
    two requests sharing a 40-token prompt — the second's prefill hits
    the prefix cache, both decode speculatively, outputs bit-match the
    plain reference."""
    p = _prompt(40, 21)
    wide = GenerateEngine(_cfg(prompt_buckets=[40]))
    ref = wide.generate_once(p, max_new_tokens=8)
    spec = GenerateEngine(_spec_cfg())
    spec.warmup()
    before = monitor.counters()
    with spec:
        a = spec.submit(p, max_new_tokens=8)
        assert list(a.result(60)) == ref
        b = spec.submit(p, max_new_tokens=8)
        assert list(b.result(60)) == ref
    delta = monitor.counter_delta(before)
    assert delta.get('kv_prefix_hit_total{outcome=hit}', 0) >= 1
    assert spec.stats()['spec']['accept_rate'] == 1.0


def test_spec_zero_recompiles_after_warmup():
    """Mixed speculative traffic — varying prompt/output lengths,
    chunked prompts, prefix hits — re-executes the warmed signature
    set: compile_cache_miss delta 0 (drafter, verify and the block
    copies are all fixed signatures; every control is a feed)."""
    eng = GenerateEngine(_spec_cfg())
    eng.warmup()
    before = monitor.counters()
    with eng:
        reqs = [eng.submit(_prompt(3 + (i * 7) % 30, seed=i),
                           max_new_tokens=3 + i % 9)
                for i in range(8)]
        for r in reqs:
            r.result(60)
    delta = monitor.counter_delta(before)
    assert not any(k.startswith('compile_cache_miss') for k in delta), \
        delta
    assert delta.get('spec_propose_total', 0) > 0
    assert delta.get('spec_accept_total', 0) > 0


def test_spec_mixed_sampled_traffic_falls_back():
    """A sampled resident pins rounds on the plain step path
    (spec_fallback_total advances); greedy and sampled outputs both
    match their solo references."""
    eng = GenerateEngine(_spec_cfg())
    ref_g = eng.generate_once(_prompt(6, 31), max_new_tokens=8)
    ref_s = eng.generate_once(_prompt(9, 32), max_new_tokens=8,
                              temperature=0.8, top_k=8, sample_seed=11)
    with eng:
        rg = eng.submit(_prompt(6, 31), max_new_tokens=8)
        rs = eng.submit(_prompt(9, 32), max_new_tokens=8,
                        temperature=0.8, top_k=8, sample_seed=11)
        assert list(rg.result(60)) == ref_g
        assert list(rs.result(60)) == ref_s
    assert eng.stats()['spec']['fallback_rounds'] > 0


def test_draft_cache_resync_after_fallback_burst():
    """ISSUE 14 satellite (open from PR 13): plain fallback rounds (a
    sampled co-rider) deposit K/V into the TARGET cache only, so greedy
    speculation used to resume against a STALE draft cache — correct
    but accept-degraded until the next admission. The engine now counts
    the resume (spec_stale_draft_rounds_total) and, on the
    draft==target path, resyncs via the existing _draft_cache_sync
    block copy BEFORE drafting — so the accept rate recovers to exactly
    1.0 after the burst (without the resync the drafter reads zero rows
    for every fallback-era position and acceptance collapses)."""
    spec = GenerateEngine(_spec_cfg())
    pg = _prompt(6, 41)
    ref_g = spec.generate_once(pg, max_new_tokens=18)
    spec.warmup()
    before = monitor.counters()
    g = spec.submit(pg, max_new_tokens=18)
    s = spec.submit(_prompt(9, 42), max_new_tokens=5, temperature=0.8,
                    top_k=8, sample_seed=7)
    _drive(spec, s)     # sampled rider resident -> every round falls back
    assert spec._spec_fallbacks > 0
    assert g.finish_reason is None      # greedy rider still mid-flight
    st_g = next(st for st in spec._slots
                if st is not None and st.req is g)
    pos_before = st_g.pos               # fallback-era write head
    spec._step()        # the RESUMED speculative round (resync fires)
    # mechanical pin: after the resync, every draft-cache row covering
    # a position written BEFORE the resumed round bitwise-equals the
    # target cache's row (the block copy moves target truth across
    # pools) — without it the fallback-era positions are still the
    # zero holes the plain steps never filled. Rows the resumed round
    # itself wrote are excluded: drafter and verify deposit them from
    # differently-shaped programs, so they agree only to float
    # reduction order, not bitwise.
    kt = np.asarray(spec.scope.get(KV_CACHE_K))
    kd = np.asarray(spec._draft_scope.get(KV_CACHE_K))
    for p in range(pos_before):
        tb, db = st_g.blocks[p // BS], st_g.dblocks[p // BS]
        np.testing.assert_array_equal(
            kt[tb, :, p % BS, :], kd[db, :, p % BS, :],
            err_msg='draft cache stale at position %d' % p)
    _drive(spec, g)     # speculation continues on the synced cache
    delta = monitor.counter_delta(before)
    assert delta.get('spec_stale_draft_rounds_total', 0) >= 1
    st = spec.stats()['spec']
    assert st['stale_draft_rounds'] >= 1
    assert st['fallback_rounds'] > 0
    assert st['rounds'] > 0
    # accept-rate RECOVERY: every post-resync proposal is target-equal
    # again — 1.0 overall because no round before the burst speculated
    assert st['accept_rate'] == 1.0, st
    # the resync is a warmed fixed signature: no recompiles appeared
    assert not any(k.startswith('compile_cache_miss')
                   for k in delta), delta
    assert list(g.result(5)) == ref_g   # bitwise parity held throughout
    spec.stop()
    # engine-scoped goodput block rode along (bound decode dispatches)
    gp = spec.stats()['goodput']
    assert gp['dispatches'] > 0 and gp['by_kind']['bound']['flops'] > 0


@pytest.mark.slow
def test_speculative_throughput_and_chunked_workload():
    """The servebench speculative row end to end: >= 1.2x engine
    tokens/sec over the plain paged engine at a target-equal draft
    (the bench contract is 1.5x on a quiet box; this bound absorbs
    loaded-box noise), accept rate 1.0, zero recompiles, greedy parity,
    and the long-prompt workload admits via chunked prefill with
    bit-exact continuations."""
    from tools.servebench import measure_speculative
    row = measure_speculative(rounds=3)
    assert row['speculative']['accept_rate'] == 1.0
    assert row['speculative']['greedy_parity'] is True
    assert row['speculative']['recompiles_after_warmup'] == 0
    assert row['speculative']['vs_plain_tokens_per_sec'] >= 1.2, row
    assert row['chunked_prefill']['admitted'] is True
    assert row['chunked_prefill']['bitexact_vs_single_shot'] is True
