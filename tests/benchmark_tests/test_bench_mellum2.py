"""The Mellum2-12B-A2.5B-Instruct configuration's benchmark files (ISSUE
51): a toy cell with the new builder through run.py end to end on the CPU
(its own toy manifest; every request starts with one shared prefix, so the
check's second request and every admission of the window resume over the
window layers' shared blocks), the manifest's entries and the published
file against the catalog's row, the traffic letter for letter,
flops_mellum2's formulae against a count of param_shapes and against the
issue's table, the two new readers and the accepted readers the cell is
listed under on made-up facts of this configuration (no roofline over 100
on a trace that spends its whole window in the kernel at peak), and the
comparison script's main() at toy width."""
import json
import os

import numpy as np
import pytest

from benchmark import flops_mellum2, traffic_gen
from benchmark.models import mellum2

from test_bench_olmoe import _last_json, _load, run_on_cpu   # noqa: F401
from test_bench_run import MANIFEST, by_name, check_config_entry

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY_MANIFEST = os.path.join(HERE, 'fixtures', 'BENCHMARK.toy.mellum2.json')
TOY_CONFIG = os.path.join(HERE, 'configs', 'toy-mellum2.json')
TOY_TRAFFIC = os.path.join(HERE, 'traffic', 'toy-serve-mellum2.json')
NAME = 'mellum2-12b-a2.5b-l4'
CONFIG = os.path.join(ROOT, 'benchmark', 'configs', NAME + '.json')
TRAFFIC = os.path.join(ROOT, 'benchmark', 'traffic', 'code64-closed.json')
CELL = 'mellum2-serve-code64'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = ('window_prefix_resume_share', 'mellum2_moe_ffn_roofline')
REDUCED = ['num_hidden_layers', 'layer_types', 'mlp_layer_types']


def _json(path):
    with open(path) as f:
        return json.load(f)


# ---- the toy cell through run.py --------------------------------------------

def test_end_to_end_line(run_on_cpu, capsys):              # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-mellum2', '--seed',
                          '3000000001', '--seconds', '0.5', '--trace', '0'],
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
    # the builder's notes beside the two comparisons: by the second the
    # check's requests had resumed over the window layers' shared blocks
    notes = [ln for ln in lines if ln.startswith('[mellum2] check')]
    assert len(notes) == 2
    resumes = [int(ln.split('kv_window_prefix_resumes_total ')[1]
                   .split(',')[0]) for ln in notes]
    assert resumes[1] >= resumes[0] > 0


def test_traced_line(run_on_cpu, capsys):                  # noqa: F811
    rc = run_on_cpu.main(['--workload', 'toy-serve-mellum2', '--seed', '7',
                          '--seconds', '0.7', '--trace', '1'],
                         manifest_path=TOY_MANIFEST)
    out, lines = _last_json(capsys)
    assert rc == 0 and out['correct'] is True
    # on the CPU no operation of the trace is a Mosaic kernel and
    # peak_hbm_gb.serve has nothing to read: the two rooflines and the
    # peak return nothing
    assert set(out['metrics']) == {
        'decode_step_ms', 'decode_hbm_share', 'device_idle_share.serve',
        'moe_experts_touched_share', 'moe_load_max_over_mean',
        'prefix_hit_token_share', 'kv_window_read_share',
        'window_prefix_resume_share'}
    # every request starts with the 48 shared tokens and resumes there
    assert out['metrics']['window_prefix_resume_share']['value'] == 100.0
    # 48 of 58 .. 100 prompt tokens
    assert 45.0 < out['metrics']['prefix_hit_token_share']['value'] < 85.0
    # one full layer in four reads every live key (58 to 106), three read
    # 40 at most
    assert 25.0 < out['metrics']['kv_window_read_share']['value'] < 100.0
    assert 1.0 <= out['metrics']['moe_load_max_over_mean']['value'] <= 8.0
    assert 0 < out['metrics']['decode_hbm_share']['value'] < 100
    facts = json.loads([ln for ln in lines
                        if ln.startswith('facts: ')][-1][len('facts: '):])
    assert facts['decode_bytes_per_step'] > 0


# ---- the manifest and the published file ------------------------------------

def check_mellum2_entry(manifest):
    """The configuration, its cell and the metrics that list the cell,
    each found by name: where they stand in their lists is
    test_bench_manifest.py's to hold (appended, never put in)."""
    conf = by_name(manifest['configs'], NAME)
    check_config_entry(conf, manifest)
    assert conf['reduced'] == REDUCED
    assert conf['source'] == 'https://huggingface.co/JetBrains/' \
        'Mellum2-12B-A2.5B-Instruct/blob/main/config.json'
    cell = by_name(manifest['workloads'], CELL)
    assert cell == dict(cell, name=CELL, config=NAME,
                        traffic='code64-closed', chips=1)
    # what the issue asks the `why` to say
    assert "whole batch" in cell['why'] and "host share" in cell['why']
    listed = {x['name'] for x in manifest['end_to_end']
              + manifest['per_layer'] if CELL in x.get('workloads', ())}
    # `itl_p95_ms`, every per-layer metric that moves it and whose reader
    # finds something to read in this configuration, and its own two. At
    # least: a later PR may append
    assert listed >= {
        'itl_p95_ms', 'decode_step_ms', 'decode_host_gap_ms',
        'decode_host_gap_ms.admit', 'decode_host_gap_ms.feed',
        'decode_host_gap_ms.dispatch', 'decode_host_gap_ms.deliver',
        'server_loop_unaccounted_share', 'decode_sampled_step_share',
        'admission_ms', 'admission_ms.dispatch', 'admission_ms.drain',
        'admission_ms.fetch', 'token_gap_ms.admission', 'token_gap_ms.plain',
        'token_gap_admission_share', 'moe_load_max_over_mean',
        'window_decode_attention_roofline', 'kv_window_read_share'} \
        | set(NEW)
    # NOT `serve_tokens_per_s`, nor a metric that moves it (a per-layer
    # metric lists the cells that report what it moves): two sets of six
    # seeds spread 1.5 and 2.1 % where 2.5 % admits a cell, one run of the
    # twelve 5 % under the others at their `itl_p95_ms` -- the machine's
    # kind of stall, and a second such run in a set passes the limit
    # (PERF.md section 6 has the readings)
    moved = {x['name']: x.get('moves') for x in manifest['per_layer']}
    assert 'serve_tokens_per_s' not in listed
    assert not {n for n in listed if moved.get(n) == 'serve_tokens_per_s'}
    # NOT under the two lists that test_bench_lfm2.py holds with `==`
    # (PERF.md section 7: the next benchmark PR appends this cell), nor
    # under another family's kernel
    assert not listed & {'prefix_hit_token_share',
                         'paged_decode_attention_roofline',
                         'kexaone_moe_ffn_roofline', 'lfm2_moe_ffn_roofline',
                         'nemotron_moe_ffn_roofline', 'moe_ffn_hbm_share',
                         'moe_held_assignment_share', 'ttft_p95_ms'}
    by = {x['name']: x for x in manifest['per_layer']}
    for name in NEW:
        assert by[name]['workloads'] == [CELL] or CELL in by[name][
            'workloads']
    assert {by[n]['moves'] for n in NEW} == {'itl_p95_ms'}
    assert (by[NEW[0]]['layer'], by[NEW[0]]['source']) == \
        ('server', 'program_counter')
    assert (by[NEW[1]]['layer'], by[NEW[1]]['source']) == \
        ('kernels', 'device_trace')


def test_config_entry_admits_the_new_entry():
    check_mellum2_entry(MANIFEST)


def test_the_published_file_keeps_every_number_of_the_catalogs_row():
    if not os.path.isfile(CATALOG):
        pytest.skip('no catalog on this machine')
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f)
                if r['name'] == 'Mellum2-12B-A2.5B-Instruct']
    m = _json(CONFIG)
    differs = {k for k, v in row['config'].items() if m.get(k, '?') != v}
    assert differs == set(m['reduced']) == set(m['reduced_from']) \
        == set(REDUCED)
    assert all(m['reduced_from'][k] == row['config'][k] for k in differs)
    assert m['source'].startswith(row['source_url'])
    # one whole period: sliding, sliding, sliding, full; every FFN sparse
    for key in ('layer_types', 'mlp_layer_types'):
        assert m[key] == row['config'][key][:4]
    assert m['layer_types'] == ['sliding_attention'] * 3 + ['full_attention']
    assert m['num_hidden_layers'] == 4
    # every width of the row, every expert, the whole vocabulary
    assert (m['hidden_size'], m['num_attention_heads'],
            m['num_key_value_heads'], m['head_dim'], m['intermediate_size'],
            m['moe_intermediate_size'], m['num_experts'],
            m['num_experts_per_tok'], m['norm_topk_prob'],
            m['sliding_window'], m['vocab_size'], m['rms_norm_eps'],
            m['tie_word_embeddings'], m['max_position_embeddings']) == \
        (2304, 32, 4, 128, 7168, 896, 64, 8, True, 1024, 98304, 1e-6, False,
         131072)
    # the two rotary sections as published
    assert m['rope_parameters'] == row['config']['rope_parameters'] == {
        'full_attention': {
            'rope_type': 'yarn', 'rope_theta': 500000, 'factor': 16,
            'original_max_position_embeddings': 8192, 'beta_fast': 32,
            'beta_slow': 1, 'attention_factor': 1.2772588722239782},
        'sliding_attention': {'rope_type': 'default', 'rope_theta': 500000}}
    assert m['builder'] == 'mellum2'
    assert m['deployment'].strip() and 'seven' in m['deployment']
    assert set(m['changed']) >= {'weights', 'serving_dtype',
                                 'matmul_precision', 'context', 'qkv_layout',
                                 'multi_token_prediction'}
    assert 'float32' in m['changed']['serving_dtype']
    assert '10752' in m['changed']['context']
    assert set(m['assumed']) == {'qk_norm', 'norm_placement'}


def test_the_traffic_is_the_issues_letter_for_letter():
    tr = _json(TRAFFIC)
    assert tr['kind'] == 'serve'
    assert tr['arrival'] == {'kind': 'closed', 'clients': 64,
                             'stagger_s': 0.1}
    assert tr['prompt_len'] == {'dist': 'uniform', 'min': 8448, 'max': 10240}
    assert tr['output_len'] == {'dist': 'lognormal', 'median': 192,
                                'sigma': 0.6, 'min': 32, 'max': 512}
    assert tr['engine'] == {'paged': True, 'slots': 64, 'block_size': 32,
                            'max_len': 10752,
                            'prompt_buckets': [128, 256, 512],
                            'num_blocks': 6144}
    assert (tr['pool_size'], tr['sampling'], tr['shared_prefix_len'],
            tr['group_size'], tr['check_new_tokens'], tr['trace_seconds']) \
        == (1024, 'greedy', 8192, 1024, 8, 8.0)
    assert tr['users'].strip()
    # ONE prefix for the whole pool, of whole blocks, past YaRN's original
    # length from its edge on; the longest request fits the table
    m = _json(CONFIG)
    assert tr['shared_prefix_len'] % tr['engine']['block_size'] == 0
    assert tr['shared_prefix_len'] >= m['rope_parameters'][
        'full_attention']['original_max_position_embeddings']
    assert tr['prompt_len']['max'] + tr['output_len']['max'] \
        == tr['engine']['max_len']
    # the full layer's pool: the prefix once, every slot's worst case of
    # its own, and slack for the cache's own entries
    own = (tr['engine']['max_len'] - tr['shared_prefix_len']) // 32
    assert 256 + 64 * own + 768 == tr['engine']['num_blocks']
    requests = traffic_gen.make_requests(tr, m['vocab_size'], 3000000001)
    first = requests[0]['prompt'][:8192]
    assert all(np.array_equal(r['prompt'][:8192], first)
               for r in requests[:64])
    assert len({int(r['prompt'][8192]) for r in requests[:64]}) > 32


# ---- flops_mellum2 against a count of the parameters ------------------------

def _count(shapes, pick=lambda name: True):
    return sum(int(np.prod(s)) for n, s in shapes.items() if pick(n))


@pytest.mark.parametrize('path', [CONFIG, TOY_CONFIG],
                         ids=[NAME, 'toy-mellum2'])
def test_flops_mellum2_counts_what_param_shapes_lists(path):
    m = _json(path)
    f = flops_mellum2
    shapes = mellum2.param_shapes(m)
    n = m['num_hidden_layers']
    assert f.param_count(m) == _count(shapes)
    for i in range(n):
        assert f.layer_param_count(m) == _count(
            shapes, lambda k: k.startswith('layer_%d.' % i))
    routed = lambda k: '.moe.' in k and 'router' not in k     # noqa: E731
    assert f.expert_param_count(m) * m['num_experts'] * n == _count(
        shapes, routed)
    assert f.attention_param_count(m) == _count(
        shapes, lambda k: k.startswith('layer_0.attn.'))
    cfg = mellum2.lm_config(m, 32, False)
    assert f.kv_bytes_per_token(m) == \
        2 * cfg.n_attn_layers * cfg.kv_width * 4
    assert f.window_bytes_per_slot(m) == \
        2 * cfg.n_window_layers * cfg.sliding_window * cfg.kv_width * 4
    # one live row reads what it touches of the experts; very many rows
    # every weight but the table, of which a row a slot
    one = f.decode_bytes_per_step(m, 0, 1)
    rest = _count(shapes, lambda k: not routed(k) and k != 'tok_emb.w')
    assert one == pytest.approx(4 * (
        rest + m['hidden_size'] + n * m['num_experts_per_tok']
        * f.expert_param_count(m)) + f.window_bytes_per_slot(m))
    assert f.decode_bytes_per_step(m, 100, 1) - one == \
        100 * f.kv_bytes_per_token(m)
    d, w = m['hidden_size'], m['moe_intermediate_size']
    assert f.grouped_matmul_bytes(m, 3, 10) == 4 * (
        3 * 3 * d * w + 10 * (2 * d + 3 * w))
    assert f.grouped_matmul_flops(m, 10) == 2.0 * 10 * 3 * d * w


def test_the_published_configuration_is_what_the_issue_sized():
    m = _json(CONFIG)
    f = flops_mellum2
    assert f.attention_param_count(m) == pytest.approx(21.23e6, rel=1e-3)
    assert 2304 * 64 == pytest.approx(0.147e6, rel=5e-3)
    assert f.expert_param_count(m) == pytest.approx(6.193e6, rel=1e-3)
    assert 64 * f.expert_param_count(m) == pytest.approx(396.36e6, rel=1e-3)
    assert f.layer_param_count(m) == pytest.approx(417.75e6, rel=1e-3)
    assert 4 * f.layer_param_count(m) == pytest.approx(1.671e9, rel=1e-3)
    assert 4 * 2 * 98304 * 2304 == pytest.approx(1.812e9, rel=1e-3)
    assert 4 * f.param_count(m) == pytest.approx(8.50e9, rel=2e-3)
    # two periods leave no cache
    eight = dict(m, num_hidden_layers=8, layer_types=m['layer_types'] * 2)
    assert 4 * f.param_count(eight) == pytest.approx(15.2e9, rel=5e-3)
    # the whole model, as published: 12.15 B parameters
    whole = dict(m, **m['reduced_from'])
    assert f.param_count(whole) == pytest.approx(12.15e9, rel=2e-3)
    # the cache: 4096 B a token a layer; the full layer's pool, and the
    # three window layers' with what the prefix cache may hold
    assert f.kv_row_bytes(m) == f.kv_bytes_per_token(m) == 4096
    assert 6144 * 32 * f.kv_bytes_per_token(m) == pytest.approx(0.805e9,
                                                                rel=2e-3)
    cfg = mellum2.lm_config(m, 10752, False)
    from paddle_tpu.models import transformer as T
    assert T.window_ring(cfg, 32) == 34
    assert T.kv_cache_shapes(cfg, 6144, 32, 64, shared=True) == {
        'gen_kv_k': (6144, 1, 32, 512), 'gen_kv_v': (6144, 1, 32, 512),
        'gen_kv_window_k': (3265, 3, 32, 512),
        'gen_kv_window_v': (3265, 3, 32, 512)}
    assert T.kv_cache_shapes(cfg, 6144, 32, 64)['gen_kv_window_k'][0] == 2177
    assert 2 * 3265 * 3 * 32 * 512 * 4 == pytest.approx(1.28e9, rel=5e-3)
    # 64 rows x 8 of 64: nearly every expert touched a layer-step
    assert f.expected_experts_touched(m, 64) == pytest.approx(63.99,
                                                              abs=0.01)
    # a step: ~7.6 GB of weights, ~2.5 GB of the full layer's keys at
    # ~9.4 k positions a slot, 0.8 GB of window keys
    step = f.decode_bytes_per_step(m, 64 * 9400, 64)
    weights = f.decode_bytes_per_step(m, 0, 64) \
        - 64 * f.window_bytes_per_slot(m)
    assert weights == pytest.approx(7.59e9, rel=5e-3)
    assert 64 * 9400 * 4096 == pytest.approx(2.46e9, rel=5e-3)
    assert 64 * f.window_bytes_per_slot(m) == pytest.approx(0.805e9,
                                                            rel=2e-3)
    assert step == pytest.approx(10.86e9, rel=5e-3)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.kv_width,
            cfg.attn_width) == (32, 4, 128, 512, 4096)
    assert cfg.layer_types == ('window',) * 3 + ('attention',)
    assert (cfg.n_attn_layers, cfg.n_window_layers, cfg.sliding_window,
            cfg.global_rope) == (1, 3, 1024, True)
    assert all(cfg.rotates(i) for i in range(4))
    assert [cfg.rope(i) for i in range(3)] == [{'theta': 500000.0}] * 3
    assert cfg.rope(3) == {
        'theta': 500000.0, 'factor': 16, 'original_max_position': 8192,
        'beta_fast': 32, 'beta_slow': 1,
        'attention_factor': 1.2772588722239782}
    assert (cfg.n_experts, cfg.experts_per_token, cfg.expert_width,
            cfg.n_shared_experts, cfg.n_dense_layers, cfg.n_moe_layers) == \
        (64, 8, 896, 0, 0, 4)
    assert (cfg.moe_score, cfg.norm_topk_prob, cfg.qk_norm,
            cfg.tie_embeddings, cfg.norm, cfg.rms_eps, cfg.bias) == \
        ('softmax', True, 'head', False, 'rms_norm', 1e-6, False)
    assert cfg.matmul_precision == m.get('matmul_precision')
    with pytest.raises(ValueError):
        mellum2.lm_config(m, 10752, True)               # served only
    with pytest.raises(ValueError, match='published context'):
        mellum2.lm_config(m, 131073, False)
    plain = dict(m['rope_parameters'],
                 full_attention=m['rope_parameters']['sliding_attention'])
    for key, other in (('hidden_act', 'gelu'), ('norm_topk_prob', False),
                       ('tie_word_embeddings', True),
                       ('attention_bias', True),
                       ('use_sliding_window', False),
                       ('mlp_layer_types', ['dense'] + ['sparse'] * 3),
                       ('rope_parameters', plain),
                       ('layer_types', ['linear_attention'] * 4)):
        with pytest.raises(ValueError, match=key):
            mellum2.lm_config(dict(m, **{key: other}), 10752, False)


def test_init_params_is_seeded_and_gives_the_norms_a_spread():
    m = _json(TOY_CONFIG)
    a = mellum2.init_params(m, 3000000001)
    b = mellum2.init_params(m, 3000000001)
    c = mellum2.init_params(m, 5)
    assert sorted(a) == sorted(mellum2.param_shapes(m))
    for name, shape in mellum2.param_shapes(m).items():
        assert tuple(a[name].shape) == tuple(shape)
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]))
    assert np.abs(np.asarray(a['tok_emb.w'])
                  - np.asarray(c['tok_emb.w'])).max() > 0
    for name in ('layer_2.ln1.w', 'layer_3.ln2.w', 'final_ln.w'):
        ln = np.asarray(a[name])
        assert abs(ln.mean() - 1.0) < 0.06 and 0.05 < ln.std() < 0.2
    assert abs(np.asarray(a['layer_0.attn.q_norm.w']).mean() - 1.0) < 0.15
    assert np.asarray(a['layer_0.attn.qkv.w']).std() == pytest.approx(
        0.02, rel=0.2)
    assert a['layer_1.moe.gate.w'].shape == (8, 64, 32)
    assert a['layer_1.moe.router.w'].shape == (64, 8)


# ---- the readers ------------------------------------------------------------

def _reader(name):
    return _load(os.path.join(ROOT, 'benchmark', 'layer_metrics',
                              name + '.py'), 'reader_' + name.replace(
                                  '.', '_'))


M = _json(CONFIG)
PEAKS = {'hbm_bytes_per_s': 819e9, 'bf16_flops_per_s': 197e12}
# a window of 100 decode steps of 64 rows and 12 admissions of 3 chunks,
# each dispatch touching every expert of the 4 layers; the prompts' own
# ~1 100 tokens prefilled, the 8 192 shared ones saved; 64 x 9 400 live
# positions x 1 full layer and 64 x 1 024 x 3 window layers a step
DISPATCHES = 100 + 36
ROWS = 100 * 64 + 12 * 1100
COUNTERS = {'moe_layer_steps_total': 4 * DISPATCHES,
            'moe_assignments_total': ROWS * 4 * 8,
            'moe_experts_touched_total': DISPATCHES * 4 * 64,
            'moe_max_expert_rows_total': int(ROWS * 4 * 8 / 64 * 1.4),
            'kv_tokens_read_total': 100 * 64 * 9400,
            'kv_window_tokens_read_total': 100 * 64 * 1024 * 3,
            'generate_admit_total': 12,
            'kv_window_prefix_resumes_total': 12,
            'kv_window_blocks_shared_total': 12 * 32,
            'prefill_prompt_tokens_total': 12 * 9292,
            'kv_prefix_tokens_saved_total': 12 * 8192}
STATS = {'blocks': {'window': {'capacity': 3264, 'ring': 34, 'in_use': 2100,
                               'cached': 300}}}
ROOFLINES = {'mellum2_moe_ffn_roofline': 'mosaic:ragged-dot',
             'window_decode_attention_roofline':
             'mosaic:paged_window_decode_attention'}


def _traced(**ops):
    return {'counters': COUNTERS, 'config': M, 'peaks': PEAKS,
            'engine_stats': STATS, 'window_s': 4.0,
            'trace': {'window_s': 2.0, 'busy_s': 1.8, 'op_seconds': ops}}


@pytest.mark.parametrize('name', NEW)
def test_a_new_reader_reads_nothing_where_there_is_nothing(name):
    """The parent commit's program (its `stats()` counts no window block
    the cache holds, it books no resume), another configuration, an
    untraced or a CPU run: nothing to read, nothing raised."""
    read = _reader(name).read
    others = [_json(os.path.join(ROOT, 'benchmark', 'configs', n + '.json'))
              for n in ('k-exaone-236b-a23b-ep16-l5', 'lfm2-8b-a1b-l8',
                        'olmoe-1b-7b-0125-l6')]
    every = {op: 0.2 for op in ROOFLINES.values()}
    for facts in ({}, {'counters': {}, 'config': M},
                  {'counters': {}, 'config': {'d_model': 8}, 'trace': None},
                  dict(_traced(**every), counters={}),
                  dict(_traced(**every), counters={
                      'kv_tokens_read_total': 5})):
        assert read(facts) is None
    if name == 'mellum2_moe_ffn_roofline':
        for m in others + [{'hidden_size': 8}]:
            assert read(dict(_traced(**every), config=m)) is None
        assert read(_traced(fusion=0.5)) is None    # no such operation
        assert read(dict(_traced(**every), trace=None)) is None
        assert read(_traced(**{
            'mosaic:paged_window_decode_attention': 0.2})) is None
    else:
        # the parent's engine on K-EXAONE's cell: rings, no cache over them
        parent = {'blocks': {'window': {'capacity': 384, 'ring': 6,
                                        'in_use': 380}}}
        assert read(dict(_traced(), engine_stats=parent)) is None
        assert read(dict(_traced(), engine_stats={'blocks': {}})) is None
        # an engine that does share and resumed nowhere reads 0, not nothing
        lost = {k: v for k, v in COUNTERS.items()
                if k != 'kv_window_prefix_resumes_total'}
        assert read(dict(_traced(), counters=lost)) == 0.0


def test_window_prefix_resume_share_on_made_up_facts():
    read = _reader('window_prefix_resume_share').read
    assert read(_traced()) == 100.0
    some = dict(COUNTERS, kv_window_prefix_resumes_total=9)
    assert read(dict(_traced(), counters=some)) == pytest.approx(75.0)
    # it reads counters and stats alone: an untraced run's facts do
    assert read({'counters': COUNTERS, 'engine_stats': STATS}) == 100.0


def test_mellum2_moe_ffn_roofline_on_made_up_facts():
    read = _reader('mellum2_moe_ffn_roofline').read
    need = flops_mellum2.grouped_matmul_bytes(
        M, COUNTERS['moe_experts_touched_total'],
        COUNTERS['moe_assignments_total'])
    # 34 816 touched experts x 24.8 MB is what counts; activations ~2 %
    assert need == pytest.approx(
        COUNTERS['moe_experts_touched_total'] * 3 * 2304 * 896 * 4, rel=0.03)
    facts = _traced(**{'mosaic:ragged-dot': 0.9, 'mosaic:ragged-dot.1': 0.4,
                       'mosaic:paged_window_decode_attention': 0.2,
                       'fusion': 0.3})
    assert read(facts) == pytest.approx(
        100.0 * (need / 4.0 / 819e9) / (1.3 / 2.0))
    assert 0 < read(facts) < 100.0
    # the readers the cell is NOT listed under, on this configuration's
    # keys: K-EXAONE's and Nemotron's ask for their own family's keys and
    # read nothing; OLMoE's counts an expert at the dense layer's width,
    # eight times the bytes
    for other in ('kexaone_moe_ffn_roofline', 'nemotron_moe_ffn_roofline'):
        assert _reader(other).read(facts) is None
    from benchmark import flops_moe
    assert flops_moe.grouped_matmul_bytes(M, 100, 0) == pytest.approx(
        8 * flops_mellum2.grouped_matmul_bytes(M, 100, 0))


def test_the_accepted_readers_the_cell_lists_read_this_configuration():
    """`window_decode_attention_roofline` and `kv_window_read_share` read
    `sliding_window`, `layer_types`, `num_key_value_heads` and `head_dim`
    alone: the same kernel at 33 pages a slot; `moe_load_max_over_mean`
    divides by the file's `num_experts`, all 64 held."""
    facts = _traced(**{'mosaic:paged_window_decode_attention': 0.1,
                       'mosaic:paged_decode_attention': 0.5,
                       'mosaic:ragged-dot': 1.0})
    need = 100 * 64 * 1024 * 3 * 4096
    assert _reader('window_decode_attention_roofline').read(facts) == \
        pytest.approx(100.0 * (need / 4.0 / 819e9) / (0.1 / 2.0))
    assert 0 < _reader('window_decode_attention_roofline').read(facts) < 100
    # one layer reads 9 400 keys a slot, three read 1 024: of 4 x 9 400
    assert _reader('kv_window_read_share').read(facts) == pytest.approx(
        100.0 * (9400 + 3 * 1024) / (4 * 9400))
    assert _reader('moe_load_max_over_mean').read(facts) == pytest.approx(
        1.4, rel=1e-3)
    # the two that wait for a benchmark PR (PERF.md section 7) read this
    # configuration too: the saved share of the prompts' tokens, and the
    # full layer's kernel over its own rows at this row width
    assert _reader('prefix_hit_token_share').read(facts) == pytest.approx(
        100.0 * 8192 / 9292)
    assert _reader('paged_decode_attention_roofline').read(facts) == \
        pytest.approx(100.0 * (100 * 64 * 9400 * 4096 / 4.0 / 819e9)
                      / (0.5 / 2.0))


@pytest.mark.parametrize('name,op,counters', [
    ('window_decode_attention_roofline',
     'mosaic:paged_window_decode_attention',
     lambda s: {'kv_window_tokens_read_total': int(819e9 * s / 4096)}),
    ('mellum2_moe_ffn_roofline', 'mosaic:ragged-dot',
     lambda s: {'moe_experts_touched_total':
                int(819e9 * s / (3 * 2304 * 896 * 4)),
                'moe_assignments_total': 0})])
def test_a_roofline_never_passes_100_at_peak(name, op, counters):
    """A made-up trace that spends its WHOLE window in the kernel, moving
    bytes at the chip's peak all the while: exactly the roofline, and
    less for any time beside it."""
    read = _reader(name).read
    facts = {'counters': counters(4.0), 'config': M, 'peaks': PEAKS,
             'window_s': 4.0, 'trace': {'window_s': 2.0, 'busy_s': 2.0,
                                        'op_seconds': {op: 2.0}}}
    assert 99.9 < read(facts) <= 100.0
    facts['trace']['op_seconds'] = {op: 1.0}       # twice peak: impossible
    assert read(facts) > 105.0                     # and it shows, unclipped


# ---- the comparison script, as the chip runs it -----------------------------

def test_mellum2_control_main_at_toy_width(capsys):
    from benchmark.reference import mellum2_control
    rc = mellum2_control.main([TOY_CONFIG, TOY_TRAFFIC, '5', '3000000009'])
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith('{')]
    assert rc == 0 and len(lines) == 4
    names = set(mellum2_control.controls(_json(TOY_CONFIG)))
    for i, out in enumerate(lines):
        # a seed's longest prompt cold, then its shortest resumed at the
        # shared prefix's edge behind it
        assert out['resumed_at'] == (0 if i % 2 == 0 else 48)
        assert out['rows'] == mellum2_control.DECODE_STEPS
        assert out['logits_vs_ref'][1] < 1e-4
        assert out['logits_vs_ref_given_routing'][1] < 1e-4
        assert out['refused_by_logits_rms'] is False
        assert out['greedy_margin_worst'] == 0.0
        assert set(out['controls']) == names | (
            {'ring-zeros', 'ring-later'} if i % 2 else set())
        for name, reading in out['controls'].items():
            assert reading['logits_vs_ref'][1] > 5e-4, name
    assert lines[1]['first_moved_on_blocks'] >= 1
