"""The table of a model's pools (ISSUE 46, models/transformer.py
`cache_pools`): for the toy `LMConfig` of each of the benchmark's
configurations (Nemotron's since PR 48, Qwen3-Next's since PR 55,
Olmo-Hybrid's since PR 58, Ouro's since PR 63: the K and V row's layers a
PASS of each layer), every
pool's name, what indexes it, whether a rejected
draft rewinds from it and whether a shared block's entry of it copies;
`kv_cache_names` and `kv_cache_shapes` are views of it; and what an engine
refuses, which bookkeepers it keeps and what it books follow from the table
and from nothing else."""
import copy
import json
import os

import pytest

from paddle_tpu.models import transformer as T
from paddle_tpu.models.transformer import LMConfig
from paddle_tpu.serving import GenerateConfig, GenerateEngine
from paddle_tpu.serving import kv_blocks

from benchmark.models import (jamba, joyai, kexaone, lfm2, lm, nemotron,
                              olmoe, olmohybrid, ouro, qwen3next)

from test_olmoe_serving import LISTED

HERE = os.path.dirname(os.path.abspath(__file__))


def _toy(module, name):
    with open(os.path.join(HERE, 'benchmark_tests', 'configs',
                           'toy-%s.json' % name)) as f:
        return module.lm_config(json.load(f), 64, False)


# the toys the serving suites build, under their configurations' names
CONFIGS = {
    'fairseq-dense-355m': lambda: LMConfig(**LISTED['fairseq-dense']),
    'fairseq-dense-1.3b': lambda: _toy(lm, 'lm'),
    'olmoe-1b-7b-0125-l6': lambda: _toy(olmoe, 'olmoe'),
    'joyai-llm-flash-ep4': lambda: _toy(joyai, 'joyai'),
    'lfm2-8b-a1b-l8': lambda: _toy(lfm2, 'lfm2'),
    'k-exaone-236b-a23b-ep16-l5': lambda: _toy(kexaone, 'kexaone'),
    'ai21-jamba2-3b': lambda: _toy(jamba, 'jamba'),
    'nemotron-3-nano-30b-a3b-ep8-l20': lambda: _toy(nemotron, 'nemotron'),
    'qwen3-next-80b-a3b-ep8-l8': lambda: _toy(qwen3next, 'qwen3next'),
    'olmo-hybrid-7b-l8': lambda: _toy(olmohybrid, 'olmohybrid'),
    'ouro-2.6b-l8': lambda: _toy(ouro, 'ouro'),
}
KV = [(T.KV_CACHE_K, 'block', True, True), (T.KV_CACHE_V, 'block', True, True)]
# (name, index, rewinds, copies) of every pool, in the order of the state
POOLS = {
    'fairseq-dense-355m': KV,
    'fairseq-dense-1.3b': KV,
    'olmoe-1b-7b-0125-l6': KV,
    'joyai-llm-flash-ep4': KV[:1],      # latent rows: ONE pool
    'lfm2-8b-a1b-l8': KV + [(T.CONV_CACHE, 'block', False, False)],
    'k-exaone-236b-a23b-ep16-l5': KV + [
        (T.WINDOW_CACHE_K, 'ring', False, False),
        (T.WINDOW_CACHE_V, 'ring', False, False)],
    'ai21-jamba2-3b': KV + [(T.SSM_STATE, 'row', False, False),
                            (T.SSM_TAIL, 'row', False, False)],
    'nemotron-3-nano-30b-a3b-ep8-l20': KV + [
        (T.SSD_STATE, 'row', False, False), (T.SSD_TAIL, 'row', False, False)],
    'qwen3-next-80b-a3b-ep8-l8': KV + [
        (T.GDN_STATE, 'row', False, False), (T.GDN_TAIL, 'row', False, False)],
    'olmo-hybrid-7b-l8': KV + [
        (T.GDN_STATE, 'row', False, False), (T.GDN_TAIL, 'row', False, False)],
    'ouro-2.6b-l8': KV,
}
# the series a decode step books its reads under: (series, rows a slot at
# most, of which field of the config a layer count -- the K and V row's
# once a pass, `_layers`)
STEP_READS = {
    'joyai-llm-flash-ep4': [('kv_latent_tokens_read_total', None, 'n_layer')],
    'k-exaone-236b-a23b-ep16-l5': [
        ('kv_tokens_read_total', None, 'n_attn_layers'),
        ('kv_window_tokens_read_total', 12, 'n_window_layers')],
    'ai21-jamba2-3b': [('kv_tokens_read_total', None, 'n_attn_layers'),
                       ('ssm_state_rows_updated_total', 1, 'n_ssm_layers')],
    'nemotron-3-nano-30b-a3b-ep8-l20': [
        ('kv_tokens_read_total', None, 'n_attn_layers'),
        ('ssd_state_rows_updated_total', 1, 'n_ssd_layers')],
    'qwen3-next-80b-a3b-ep8-l8': [
        ('kv_tokens_read_total', None, 'n_attn_layers'),
        ('gdn_state_rows_updated_total', 1, 'n_gdn_layers')],
    'olmo-hybrid-7b-l8': [
        ('kv_tokens_read_total', None, 'n_attn_layers'),
        ('gdn_state_rows_updated_total', 1, 'n_gdn_layers')],
}
SLOTS, BLOCKS, BLOCK_SIZE = 4, 19, 8


def _layers(cfg, field):
    """The layers of the pool that `field` counts: the global attention
    layers' K and V hold a cache layer a PASS of each."""
    return getattr(cfg, field) * (cfg.passes if field == 'n_attn_layers'
                                  else 1)


@pytest.mark.parametrize('config', sorted(CONFIGS))
def test_the_table_holds_every_pool_and_the_views_are_its(config):
    cfg = CONFIGS[config]()
    pools = T.cache_pools(cfg, BLOCKS, BLOCK_SIZE, SLOTS)
    assert [(p.name, p.index, p.rewinds, p.copies) for p in pools] == \
        POOLS[config]
    assert T.kv_cache_names(cfg) == tuple(p.name for p in pools)
    assert T.kv_cache_shapes(cfg, BLOCKS, BLOCK_SIZE, SLOTS) == \
        {p.name: p.shape for p in pools}
    # what the allocator indexes is as long as its pool, what the slots
    # size is as long as they make it, and layers come second everywhere
    ring = T.window_ring(cfg, BLOCK_SIZE)
    entries = {'block': BLOCKS, 'ring': SLOTS * ring + 1, 'row': SLOTS + 1}
    for p in pools:
        assert p.shape[0] == entries[p.index] and len(p.shape) == 4
        assert p.shape[1] in (cfg.passes * cfg.n_attn_layers,
                              cfg.n_conv_layers, cfg.n_window_layers,
                              cfg.n_ssm_layers, cfg.n_ssd_layers,
                              cfg.n_gdn_layers)
        # a pool an option is refused over says why; the others need not
        assert (p.why is None) == (p.rewinds and p.index == 'block')
        assert T.INDEX_FEEDS[p.index].startswith('gen_')
    # without the slots a pool they size has no shape, and the view says so
    sized = [p.index != 'block' for p in pools]
    assert [p.shape is None for p in T.cache_pools(cfg, BLOCKS, BLOCK_SIZE)] \
        == sized
    if any(sized):
        with pytest.raises(ValueError, match='sized by the slots'):
            T.kv_cache_shapes(cfg, BLOCKS, BLOCK_SIZE)
    want = STEP_READS.get(
        config, [('kv_tokens_read_total', None, 'n_attn_layers')])
    assert [p.books['step'] + (p.shape[1],) for p in pools
            if 'step' in p.books] == \
        [(series, most, _layers(cfg, field)) for series, most, field in want]


@pytest.mark.parametrize('config', sorted(CONFIGS))
def test_the_kv_rows_layers_are_a_pass_of_every_attention_layer(config):
    """The ONE row of the K and V pools sizes their second dimension
    ``passes x n_attn_layers``: with one pass today's shapes, with more the
    K and V pools alone grow (which models may loop is `LMConfig`'s to
    say: tests/test_ouro_serving.py)."""
    cfg = CONFIGS[config]()
    assert cfg.passes == (4 if config == 'ouro-2.6b-l8' else 1)
    one, three = copy.copy(cfg), copy.copy(cfg)
    one.passes, three.passes = 1, 3
    shapes = [T.kv_cache_shapes(c, BLOCKS, BLOCK_SIZE, SLOTS)
              for c in (one, cfg, three)]
    width = (BLOCKS, cfg.n_attn_layers, BLOCK_SIZE, cfg.kv_width)
    for c, got in zip((one, cfg, three), shapes):
        for name, shape in got.items():
            if name in (T.KV_CACHE_K, T.KV_CACHE_V):
                assert shape == width[:1] + (c.passes * width[1],) + width[2:]
            else:
                assert shape == shapes[0][name]
        assert [c.cache_ordinal(i, t) for t in range(c.passes)
                for i in range(c.n_layer)
                if c.layer_types[i] == 'attention'] == \
            list(range(c.passes * c.n_attn_layers))


def _engine(monkeypatch, cfg, **options):
    """An engine of `cfg` with its programs built and no state made: the
    weights and the pools' arrays are not what is asked here."""
    monkeypatch.setattr(GenerateEngine, '_init_state', lambda self: None)
    options.setdefault('prefix_sharing', False)
    return GenerateEngine(GenerateConfig(
        model=cfg, slots=SLOTS, max_len=64, prompt_buckets=[16],
        eos_id=None, seed=0, block_size=BLOCK_SIZE, **options))


@pytest.mark.parametrize('option', ['speculative', 'prefix_sharing'])
@pytest.mark.parametrize('config', sorted(CONFIGS))
def test_an_engine_refuses_what_the_table_says_and_keeps_its_books(
        config, option, monkeypatch):
    cfg = CONFIGS[config]()
    # the table refuses speculation over a pool that cannot be rewound;
    # a prefix is shared over every pool (no field says otherwise)
    unfit = [p for p in T.cache_pools(cfg, BLOCKS, BLOCK_SIZE, SLOTS)
             if option == 'speculative' and not p.rewinds]
    assert bool(unfit) == ((option, config) in {
        ('speculative', 'lfm2-8b-a1b-l8'),
        ('speculative', 'k-exaone-236b-a23b-ep16-l5'),
        ('speculative', 'ai21-jamba2-3b'),
        ('speculative', 'nemotron-3-nano-30b-a3b-ep8-l20'),
        ('speculative', 'qwen3-next-80b-a3b-ep8-l8'),
        ('speculative', 'olmo-hybrid-7b-l8')})
    if unfit:
        with pytest.raises(ValueError) as refusal:
            _engine(monkeypatch, cfg, **{option: True})
        said = str(refusal.value)
        assert said.startswith('%s=True with LMConfig.layer_types=%r'
                               % (option, cfg.layer_types))
        assert repr(unfit[0].name) in said and unfit[0].why in said
    elif option == 'prefix_sharing' or config.startswith('fairseq-dense'):
        # since PR 51 a prefix is shared over the slots' rings too, since
        # PR 58 over the slots' rows: their bookkeeper is the one whose
        # blocks (whose snapshot rows) the prefix cache holds beside the
        # allocator's, and the pools have room for those
        shared = _engine(monkeypatch, cfg, **{option: True})
        assert len(shared._books) <= 1
        assert list(shared._sides) == \
            list(shared._books) * (option == 'prefix_sharing')
        for b in shared._sides:
            assert b.cache is shared._prefix
            if isinstance(b, kv_blocks.WindowRings):
                assert b.capacity == SLOTS * b.ring + SLOTS * b.ring // 2
            else:
                assert (b.capacity, b.blocks.capacity, b.reach) == \
                    (SLOTS, SLOTS, 1)
    else:
        # the table lets it pass; the drafter refuses the block by field
        with pytest.raises(ValueError, match=r'build_lm_drafter .*LMConfig\.'):
            _engine(monkeypatch, cfg, **{option: True})
    eng = _engine(monkeypatch, cfg)
    # one bookkeeper a kind of index that is not the allocator's, in the
    # table's order, each with the feed the programs declare for its kind
    kinds = []
    for p in eng._pools:
        if p.index != 'block' and p.index not in kinds:
            kinds.append(p.index)
    books = {'ring': kv_blocks.WindowRings, 'row': kv_blocks.SlotRows}
    assert [type(b) for b in eng._books] == [books[k] for k in kinds]
    assert [b.feed for b in eng._books] == [T.INDEX_FEEDS[k] for k in kinds]
    assert sorted(eng._tables_feed([[0] * 8])) == \
        sorted(['gen_btab'] + [b.feed for b in eng._books])
    # only a tail is recomputed where a wholly shared prompt would copy;
    # since PR 58 the rows share too, and a row is no block to copy: a
    # wholly shared prompt resumes at the deepest edge before its last
    # block that has a snapshot row
    assert eng._cow_ok == (config not in (
        'lfm2-8b-a1b-l8', 'k-exaone-236b-a23b-ep16-l5', 'ai21-jamba2-3b',
        'nemotron-3-nano-30b-a3b-ep8-l20', 'qwen3-next-80b-a3b-ep8-l8',
        'olmo-hybrid-7b-l8'))
    assert eng._sides == () and eng._prefix is None
    stats = {'blocks': {}}
    for b in eng._books:
        b.report(stats)
    assert sorted(stats) == ['blocks'] + ['state'] * ('row' in kinds)
    assert sorted(stats['blocks']) == ['window'] * ('ring' in kinds)
