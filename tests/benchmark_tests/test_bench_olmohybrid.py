"""The Olmo-Hybrid-7B configuration's benchmark files (ISSUE 58): a toy cell
with the new builder through run.py end to end on the CPU (its own toy
manifest; two documents shared, a hit resumes from a snapshot row), the
manifest's entries and the published file against the catalog's row, the
cell's traffic letter for letter, flops_olmohybrid's formulae against a
count of param_shapes and against the issue's arithmetic, the three new
readers and the accepted gdn_* readers (read through a listed copy of the
manifest: the cell reports no itl_p95_ms) on made-up facts of this
configuration, and the comparison script's main() at toy
width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_olmohybrid, flops_qwen3next
from benchmark.models import olmohybrid

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures',
                            'BENCHMARK.toy.olmohybrid.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-olmohybrid.json')
TOY_TRAFFIC = os.path.join(HERE, 'traffic', 'toy-serve-olmohybrid.json')
NAME = 'olmo-hybrid-7b-l8'
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', NAME + '.json')
CELL = 'olmohybrid-serve-docqa32'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('state_snapshot_resume_share', 'state_snapshot_copy_share',
       'attention_kv_step_share')
GDN = ('gdn_decode_state_roofline', 'gdn_state_step_share',
       'gdn_prefill_chunk_roofline')
ROW = (30 * 96 * 192 + 3 * 11520) * 4   # one layer's state and tail, a slot


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

# (a window of 3 s: under the six workers of a whole run a miss of four
# chunks and its followers take over half a second, and a window in which no
# request was sent and ended is not `correct`)

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-olmohybrid', '--seed',
                          '3000000001', '--seconds', '3.0', '--trace', '0'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True and out['failed'] == 0
    assert out['attempted'] > 0
    assert set(out['metrics']) == {'serve_tokens_per_s', 'itl_p95_ms',
                                   'setup_s'}
    assert all(v['value'] > 0 for v in out['metrics'].values())
    check = [ln for ln in lines if 'check: prompt of' in ln]
    assert len(check) == 2 and all('generate_once: True' in ln
                                   for ln in check)


def test_traced_line(run_on_cpu, capsys):                  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-olmohybrid', '--seed',
                          '7', '--seconds', '3.0', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the two rooflines and the peak
    # return nothing, the copies' share reads 0
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'gdn_state_step_share', 'prefix_hit_token_share',
        'state_snapshot_resume_share', 'state_snapshot_copy_share',
        'attention_kv_step_share'}
    assert 0 < out['metrics']['gdn_state_step_share']['value'] < 100
    assert 0 < out['metrics']['attention_kv_step_share']['value'] < 100
    assert out['metrics']['state_snapshot_copy_share']['value'] == 0
    # two documents of 32 tokens, six requests each: their first readers
    # miss, the others resume at 32
    assert out['metrics']['state_snapshot_resume_share']['value'] > 50
    assert out['metrics']['prefix_hit_token_share']['value'] > 30
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_olmohybrid_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], NAME)
    check_config_entry(conf, manifest)
    assert conf['reduced'] == ['num_hidden_layers', 'layer_types']
    assert conf['source'] == 'https://huggingface.co/allenai/' \
        'Olmo-Hybrid-7B/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=conf['name'],
                        traffic='docqa32-closed', chips=1)
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # `serve_tokens_per_s` and `setup_s`, the accepted metrics that move
    # them and whose readers ask nothing of the configuration, and its own
    # three
    assert listed >= {
        'serve_tokens_per_s', 'ttft_p95_unbounded_ms',
        'ttft_mean_unbounded_ms', 'device_idle_share.serve',
        'peak_hbm_gb.serve', 'decode_overlapped_step_share',
        'setup_program_s', 'setup_program_s.import', 'setup_program_s.build',
        'setup_program_s.trace', 'setup_program_s.lower',
        'setup_program_s.compile', 'setup_program_s.cache_load',
        'setup_program_s.place', 'setup_program_s.first_run'} | set(NEW)
    # NOT `itl_p95_ms`: at the issue's outputs an admission sits in ~5 % of
    # the token gaps, and the 95th percentile read 24.6 ms in one seed of
    # six and 44.2-44.6 in five (PERF.md section 6: spread 11.7 % where
    # 2.5 % admits a cell). A per-layer metric lists only cells that report
    # what it moves, so none that moves `itl_p95_ms` lists the cell, the
    # three accepted `gdn_*` readers among them (PERF.md section 5 reads
    # them through a listed copy of the manifest)
    moved = {x['name']: x.get('moves') for x in manifest['per_layer']}
    assert 'itl_p95_ms' not in listed
    assert not {n for n in listed if moved.get(n) == 'itl_p95_ms'}
    assert not listed & set(GDN)
    # NOT `decode_hbm_share`: the driver's `decode_bytes_per_step` counts a
    # shared block once (11.46 GB where the steps read ~16: PERF.md section
    # 7); not under another family's readers, and not under the two lists
    # that test_bench_lfm2.py holds with `==` (M7)
    assert not {n for n in listed
                if n.startswith(('ssm_', 'ssd_', 'mla_', 'kv_', 'moe_'))}
    assert not listed & {'decode_hbm_share',
                         'paged_decode_attention_roofline',
                         'prefix_hit_token_share',
                         'window_decode_attention_roofline',
                         'window_prefix_resume_share',
                         'qwen3next_moe_ffn_roofline', 'ttft_p95_ms'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert CELL in by[name]['workloads']
        assert by[name]['unit'] == '%'
    assert {by[n]['moves'] for n in NEW} == {'serve_tokens_per_s'}
    assert [by[n]['layer'] for n in NEW] == ['server', 'model step',
                                             'model step']
    assert [by[n]['source'] for n in NEW] == [
        'program_counter', 'device_trace', 'program_counter']


def test_config_entry_admits_the_new_entry():
    check_olmohybrid_entry(MANIFEST)


def test_the_cells_traffic_is_the_issues():
    tr = _json(os.path.join(ROOT, 'benchmark', 'traffic',
                            'docqa32-closed.json'))
    assert tr['kind'] == 'serve' and tr['sampling'] == 'greedy'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 32,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'uniform', 'min': 3104, 'max': 3328}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 512,
                                'sigma': 0.5, 'min': 128, 'max': 1024}
    assert (tr['pool_size'], tr['shared_prefix_len'], tr['group_size']) \
        == (256, 3072, 64)
    assert tr['engine'] == {'paged': True, 'slots': 32, 'block_size': 32,
                            'max_len': 4352,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 1536}
    assert (tr['check_new_tokens'], tr['trace_seconds']) == (8, 8.0)
    e = tr['engine']
    # four documents; each a whole number of the widest bucket's chunks, so
    # its last chunk's edge IS the document's end and takes a snapshot row;
    # a hit leaves one bucket of the two smaller to prefill
    assert tr['pool_size'] // tr['group_size'] == 4
    assert tr['shared_prefix_len'] == 6 * e['prompt_buckets'][-1]
    assert tr['shared_prefix_len'] % e['block_size'] == 0
    own = [tr['prompt_len'][k] - tr['shared_prefix_len']
           for k in ('min', 'max')]
    assert own == [32, 256] and own[1] <= e['prompt_buckets'][1]
    assert tr['prompt_len']['max'] + tr['output_len']['max'] == e['max_len']
    # the documents' blocks and every slot at its worst fit the pool
    doc = tr['shared_prefix_len'] // e['block_size']
    worst = -(-(e['max_len'] - tr['shared_prefix_len']) // e['block_size'])
    assert (doc, worst) == (96, 40)
    assert 4 * doc + e['slots'] * worst + 1 <= e['num_blocks'] + 129
    assert doc + e['slots'] * worst + 1 <= e['num_blocks']
    # a snapshot row a slot holds every edge of the four documents
    from paddle_tpu.models.transformer import snapshot_rows
    assert snapshot_rows(e['slots'], True) == 32 >= 4 * 6


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'Olmo-Hybrid-7B']
    m = _json(CONFIG)
    changed = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    # `layer_types` is shortened WITH the depth it is a list of, and is
    # named for it
    assert changed == {'num_hidden_layers', 'layer_types'} \
        == set(m['reduced']) == set(m['reduced_from'])
    assert m['reduced'] == ['num_hidden_layers', 'layer_types']
    assert m['reduced_from'] == {k: row['config'][k] for k in m['reduced']}
    assert m['reduced_from']['num_hidden_layers'] == 32 \
        == len(m['reduced_from']['layer_types'])
    assert m['layer_types'] == row['config']['layer_types'][:8]
    assert m['layer_types'] == (['linear_attention'] * 3
                                + ['full_attention']) * 2
    assert m['source'].startswith(row['source_url'])
    assert m['builder'] == 'olmohybrid'
    # every width of the row
    assert (m['hidden_size'], m['intermediate_size'],
            m['num_attention_heads'], m['num_key_value_heads'],
            m['vocab_size'], m['linear_num_key_heads'],
            m['linear_num_value_heads'], m['linear_key_head_dim'],
            m['linear_value_head_dim'], m['linear_conv_kernel_dim'],
            m['linear_allow_neg_eigval'], m['rms_norm_eps'],
            m['tie_word_embeddings'], m['attention_bias'],
            m['rope_parameters'], m['max_position_embeddings']) == \
        (3840, 11008, 30, 30, 100352, 30, 30, 96, 192, 4, True, 1e-6, False,
         False, {'rope_theta': None}, 65536)
    assert 'FIRST 8 OF THE 32 LAYERS' in m['deployment']
    assert set(m['assumed']) == {
        'reordered_norm', 'qk_norm', 'no_rotary', 'output_gate',
        'short_conv', 'l2norm_eps', 'A_log_dt_bias_shapes',
        'allow_neg_eigval', 'head_dim', 'ffn'}
    assert 'float32' in m['changed']['serving_dtype']
    assert "'highest'" in m['changed']['matmul_precision']
    assert m['matmul_precision'] == 'highest'
    assert '4 352' in m['changed']['context']
    # derived, and listed as such: the accepted gdn_* readers ask for it
    assert m['full_attention_interval'] == 4
    assert 'DERIVED' in m['changed']['full_attention_interval']
    assert [flops_qwen3next.is_full(m, i) for i in range(8)] == \
        [flops_olmohybrid.is_full(m, i) for i in range(8)]


# ---- flops_olmohybrid against a count of the parameters ---------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG], ids=[NAME, 'toy'])
def test_flops_olmohybrid_counts_what_param_shapes_lists(path):
    m = _json(path)
    f = flops_olmohybrid
    shapes = olmohybrid.param_shapes(m)
    assert f.param_count(m) == _count(shapes)
    for i in range(m['num_hidden_layers']):
        assert f.layer_param_count(m, i) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    cfg = olmohybrid.lm_config(m, 32, False)
    assert f.kv_bytes_per_token(m) == 2 * cfg.n_attn_layers * cfg.kv_width * 4
    assert (f.n_gdn_layers(m), f.n_full_layers(m)) == (
        cfg.n_gdn_layers, cfg.n_attn_layers) == (
        flops_qwen3next.n_gdn_layers(m), flops_qwen3next.n_full_layers(m))
    from paddle_tpu.models import transformer as T
    pools = T.kv_cache_shapes(cfg, 4, 8, 1)
    assert pools[T.GDN_TAIL][2] == 8
    assert f.state_bytes_per_slot(m) == 4 * (
        int(np.prod(pools[T.GDN_STATE][1:]))
        + int(np.prod(pools[T.GDN_TAIL][1:]))
        * (m['linear_conv_kernel_dim'] - 1) // 8) \
        == flops_qwen3next.state_bytes_per_slot(m)
    # one row: every weight but the table, the table's one row, the state
    one = f.decode_bytes_per_step(m, 0, 1)
    assert one == 4 * (_count(shapes) - m['vocab_size'] * m['hidden_size']
                       + m['hidden_size']) + 2 * f.state_bytes_per_slot(m)
    assert f.decode_bytes_per_step(m, 100, 1) - one == \
        100 * f.kv_bytes_per_token(m)


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_olmohybrid
    assert f.mixer_param_count(m, False) == pytest.approx(88.7e6, rel=2e-3)
    assert f.mixer_param_count(m, True) == pytest.approx(59.0e6, rel=2e-3)
    assert f.ffn_param_count(m) == 3 * 3840 * 11008
    assert f.layer_param_count(m, 0) == pytest.approx(215.6e6, rel=1e-3)
    assert f.layer_param_count(m, 3) == pytest.approx(185.8e6, rel=1e-3)
    assert (f.n_gdn_layers(m), f.n_full_layers(m)) == (6, 2)
    assert 4 * f.param_count(m) == pytest.approx(9.74e9, rel=1e-3)
    whole = dict(m, num_hidden_layers=32, layer_types=m['layer_types'] * 4)
    assert f.param_count(whole) == pytest.approx(7.43e9, rel=1e-3)
    # the state: 14.1 MB a slot that count, 15.48 MB a row as the pools lie
    assert f.state_row_bytes(m) == ROW == 2350080
    assert f.state_bytes_per_slot(m) == 6 * ROW
    assert 6 * (30 * 96 * 192 + 8 * 11520) * 4 == 15482880
    assert f.kv_bytes_per_token(m) == 61440
    assert 32 * f.kv_bytes_per_token(m) == 1966080
    # the step the issue reckoned: ~8.2 GB of weights, ~6.9 GB of K/V at
    # ~3.5 k live tokens a slot, ~0.9 GB of state
    step = f.decode_bytes_per_step(m, 32 * 3500, 32)
    assert step == pytest.approx(16.0e9, rel=0.01)
    assert 4 * (f.param_count(m) - 100352 * 3840) == pytest.approx(
        8.2e9, rel=0.01)
    assert 32 * 3500 * 61440 == pytest.approx(6.9e9, rel=0.01)
    assert 2 * 32 * f.state_bytes_per_slot(m) == pytest.approx(0.9e9,
                                                               rel=0.01)
    cfg = olmohybrid.lm_config(m, 4352, False)
    from paddle_tpu.models import transformer as T
    assert T.kv_cache_shapes(cfg, 1536, 32, 32, shared=True) == {
        'gen_kv_k': (1536, 2, 32, 3840), 'gen_kv_v': (1536, 2, 32, 3840),
        'gen_gdn_state': (65, 6, 96, 5760),
        'gen_gdn_tail': (65, 6, 8, 11520)}
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width) == (30, 30, 128, 3840, 3840)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.ssm_conv, cfg.gdn_chunk, cfg.gdn_inner,
            cfg.gdn_conv_width) == (30, 30, 96, 192, 4, 64, 5760, 11520)
    assert cfg.layer_types == ('gdn', 'gdn', 'gdn', 'attention') * 2
    assert (cfg.matmul_precision, cfg.position, cfg.ffn, cfg.norm,
            cfg.rms_eps, cfg.bias, cfg.tie_embeddings, cfg.qk_norm,
            cfg.norm_placement, cfg.gdn_allow_neg_eigval, cfg.d_ff) == \
        ('highest', 'none', 'gated', 'rms_norm', 1e-6, False, False, True,
         'post', True, 11008)
    from paddle_tpu.ops import gdn_ops
    assert gdn_ops.shapes_ok(96, 192, 30, 30, 512, 64)


def test_init_params_is_seeded_and_takes_the_familys_initialisation():
    m = _json(TOY_CONFIG)
    a = olmohybrid.init_params(m, 3000000001)
    b = olmohybrid.init_params(m, 3000000001)
    c = olmohybrid.init_params(m, 5)
    assert sorted(a) == sorted(olmohybrid.param_shapes(m))
    for name, shape in olmohybrid.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    # every norm lies round 1
    for name in ('layer_0.ln1.w', 'layer_3.attn.q_norm.w', 'final_ln.w',
                 'layer_0.gdn.norm.w'):
        w = np.asarray(a[name])
        assert abs(w.mean() - 1.0) < 0.1 and 0.03 < w.std() < 0.2
    assert 0.2 < np.asarray(a['layer_0.gdn.conv.w']).std() < 0.4
    a_log = np.concatenate([np.asarray(a['layer_%d.gdn.A_log' % i], 'f8')
                            for i in range(3)])
    assert 0 < np.exp(a_log).min() and np.exp(a_log).max() <= 16.0


def test_the_builder_refuses_by_name_what_it_does_not_build():
    m = _json(TOY_CONFIG)
    for key, value in (('hidden_act', 'gelu'), ('attention_bias', True),
                       ('tie_word_embeddings', True),
                       ('rope_parameters', {'rope_theta': 500000})):
        with pytest.raises(ValueError, match='builds %s=' % key):
            olmohybrid.lm_config(dict(m, **{key: value}), 32, False)
    with pytest.raises(ValueError, match='builds num_hidden_layers'):
        olmohybrid.lm_config(dict(m, num_hidden_layers=6), 32, False)
    with pytest.raises(ValueError, match='builds num_hidden_layers'):
        olmohybrid.lm_config(dict(m, layer_types=['sliding_attention'] * 4),
                             32, False)
    with pytest.raises(ValueError, match='served only'):
        olmohybrid.lm_config(m, 32, True)
    with pytest.raises(ValueError, match='beyond the published context'):
        olmohybrid.lm_config(m, 1024, False)


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 1 900 decode steps of 6 DeltaNet layers at 31 active rows;
# 110 admissions of which 104 resumed at 3 072 from a snapshot row and 6
# missed in seven chunks
COUNTERS = {'gdn_state_rows_updated_total': 1900 * 31 * 6,
            'gdn_prefill_rows_total': (104 * 150 + 6 * 3200) * 6,
            'gdn_state_resumes_total': 104 + 6 * 6,
            'generate_admit_total': 110,
            'state_snapshot_resumes_total': 104,
            'state_snapshot_tokens_resumed_total': 104 * 3072,
            'state_snapshot_rows_written_total': 6 * 6 + 3,
            'kv_tokens_read_total': 1900 * 31 * 3500 * 2}
STATS = {'state': {'capacity': 32, 'in_use': 31,
                   'snapshots': {'rows': 32, 'in_use': 27}}}


def _traced(**ops):
    live = 31 * 3500
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'engine_stats': STATS, 'window_s': 50.0, 'decode_steps': 1900,
            'active_slots_mean': 31.0, 'block_size': 32,
            # the allocator's blocks in use: the documents' 96 once
            'live_blocks_mean': (live - 27 * 3072) / 32.0,
            'decode_bytes_per_step': flops_olmohybrid.decode_bytes_per_step(
                M, live - 27 * 3072, 31),
            'trace': {'window_s': 8.0, 'busy_s': 7.9, 'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (no snapshot rows in its `stats()`),
    another configuration (Qwen3-Next's, Jamba2's and Mellum 2's among
    them: none shares over state), an untraced or a CPU run: nothing to
    read, nothing raised."""
    read = _reader(name).read
    parent = dict(STATS, state={'capacity': 32, 'in_use': 31})
    copy = {'mosaic:state_snapshot_copy': 0.01}
    for facts in [{}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None}]:
        assert read(facts) is None
    if name == 'attention_kv_step_share':
        others = [_json(os.path.join(ROOT, 'benchmark', 'configs',
                                     n + '.json'))
                  for n in ('qwen3-next-80b-a3b-ep8-l8', 'ai21-jamba2-3b',
                            'mellum2-12b-a2.5b-l4', 'lfm2-8b-a1b-l8')]
        for facts in [dict(_traced(), decode_steps=0),
                      dict(_traced(), counters={}),
                      dict(_traced(), active_slots_mean=0.0)] + [
                dict(_traced(), config=m) for m in others]:
            assert read(facts) is None
        return
    for facts in [dict(_traced(**copy), engine_stats=parent),
                  dict(_traced(**copy), engine_stats={'blocks': {}})]:
        assert read(facts) is None
    if name == 'state_snapshot_copy_share':
        assert read(dict(_traced(**copy), trace=None)) is None
        # the rows are there and no copy ran in the trace: 0, not nothing
        assert read(_traced(fusion=0.5)) == 0.0
    else:
        assert read(dict(_traced(), counters={})) is None
        assert read(dict(_traced(), counters={
            'generate_admit_total': 5})) == 0.0


def test_state_snapshot_resume_share_on_made_up_facts():
    read = _reader('state_snapshot_resume_share').read
    assert read(_traced()) == pytest.approx(100.0 * 104 / 110)
    assert read(_traced()) > 90


def test_state_snapshot_copy_share_on_made_up_facts():
    read = _reader('state_snapshot_copy_share').read
    facts = _traced(**{'mosaic:state_snapshot_copy': 0.004,
                       'mosaic:state_snapshot_copy.1': 0.002,
                       'mosaic:gdn_decode_update': 0.5, 'fusion': 3.0})
    assert read(facts) == pytest.approx(100.0 * 0.006 / 7.9)
    assert read(facts) < 1
    # a stall of the host inside the trace moves nothing
    stalled = dict(facts, trace=dict(facts['trace'], window_s=11.0))
    assert read(stalled) == read(facts)


def test_attention_kv_step_share_on_made_up_facts():
    read = _reader('attention_kv_step_share').read
    need = flops_olmohybrid.decode_bytes_per_step(M, 31 * 3500, 31)
    assert read(_traced()) == pytest.approx(
        100.0 * 31 * 3500 * 61440 / need)
    # the issue's step: ~43 % K/V, and with the state's share and the
    # weights' the whole of it
    assert read(_traced()) == pytest.approx(43.0, abs=1.5)
    # it does not read the driver's `decode_bytes_per_step`, which counts a
    # shared block once: with 27 of 31 slots on one document's 3 072 tokens
    # the allocator's view is a fifth of what the step reads
    assert read(dict(_traced(), decode_bytes_per_step=None)) == \
        read(_traced())
    state = 100.0 * 2 * 31 * 6 * ROW / need
    weights = 100.0 * 4 * (flops_olmohybrid.param_count(M) - 100352 * 3840
                           + 31 * 3840) / need
    assert read(_traced()) > state
    assert read(_traced()) + state + weights == pytest.approx(100.0)
    # the accepted reader of the state's share divides by the driver's
    # bytes and over-reads by the same under-read (PERF.md section 7)
    assert _reader('gdn_state_step_share').read(_traced()) > state


def test_the_accepted_gdn_readers_read_this_configuration():
    """The three accepted readers of the delta rule's kernels read this
    configuration's keys as they stand (`full_attention_interval` beside
    `layer_types`), at its own widths."""
    facts = _traced(**{'mosaic:gdn_decode_update': 0.30,
                       'mosaic:ssm_decode_conv': 0.05,
                       'mosaic:gdn_prefill_chunk': 0.2, 'fusion': 5.0})
    need = 2 * 1900 * 31 * 6 * ROW
    assert _reader('gdn_decode_state_roofline').read(facts) == pytest.approx(
        100.0 * (need / 50.0 / 819e9) / (0.35 / 7.9))
    per_row = 2 * (2 * 64 * 96 + 64 * 288 + 3 * 96 * 192 + 64 * 192)
    assert flops_qwen3next.gdn_prefill_chunk_flops(M, 1) == 30 * per_row
    flops = flops_qwen3next.gdn_prefill_chunk_flops(
        M, COUNTERS['gdn_prefill_rows_total'])
    got = _reader('gdn_prefill_chunk_roofline').read(facts)
    assert got == pytest.approx(
        100.0 * (flops / 50.0 / 197e12) / (0.2 / 7.9))
    assert 0 < got < 100.0 / 6
    assert _reader('decode_step_ms').read(dict(
        facts, histograms={'decode_step_seconds': (1900, 49.4)})) == \
        pytest.approx(26.0)
    # the other state families' readers, keyed to their own keys, and the
    # window's resume share read nothing here
    for name in ('ssm_decode_state_roofline', 'ssm_state_step_share',
                 'ssd_decode_state_roofline', 'ssd_state_step_share',
                 'kv_window_read_share', 'window_prefix_resume_share',
                 'qwen3next_moe_ffn_roofline'):
        assert _reader(name).read(facts) is None


# ---- the comparison script, as the chip runs it -----------------------------

def test_olmohybrid_control_main_at_toy_width(capsys):
    from benchmark.reference import olmohybrid_control
    rc = olmohybrid_control.main([TOY_CONFIG, TOY_TRAFFIC, '5',
                                  '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    always = {'bfloat16', 'bfloat16-state', 'beta-in-0-1', 'pre-norm',
              'rope'}
    hit = {'another-prefix-snapshot', 'kv-shared-state-zero',
           'tail-not-restored'}
    # a seed's first request misses (its document: two chunks of 16), its
    # second resumes at the document's end
    assert [out['resumed_at'] for out in lines] == [0, 32, 0, 32]
    for out in lines:
        assert out['rows'] == min(25, 72 - out['prompt_len'] + 1)
        assert out['logits_vs_ref'][0] < olmohybrid_control.LOGITS_RMS_LIMIT
        assert out['refused_by'] == []
        assert out['greedy_margin_worst'] == 0.0
        # on the CPU the default precision IS float32: the programs built
        # without the configuration's read what the served ones do
        lower = out['controls'].pop('default-matmul-precision')
        assert lower['refused_by'] == []
        assert set(out['controls']) == always | (
            hit if out['resumed_at'] else {'chunk-edge'})
        for name, reading in out['controls'].items():
            assert 'logits' in reading['refused_by'], name
            assert reading['greedy_margin_check_rows'] <= \
                reading['greedy_margin_worst']
